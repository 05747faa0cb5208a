"""Keys the device sorted per key a client sent over the window: (real +
padded keys) / real keys, from the service's ``ServiceStats`` counters.
Row padding to a pow2 width and batch padding to a pow2 batch both count."""


def read(run):
    return run.counters.get("pad_ratio")

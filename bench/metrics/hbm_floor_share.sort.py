"""The least time one sort call could take on a chip, over the device busy
time of one call: every key of the chip's share read once and written once
at the chip's HBM bandwidth (``bench/peaks.json``). It counts the same bytes
whatever implements the sort."""


def read(run):
    t = run.trace
    calls = run.counters.get("calls")
    if t is None or not calls:
        return None
    floor_s = 2 * run.counters["keys_per_device"] * run.counters["key_bytes"] / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * floor_s / (t.mean_busy_s() / calls)

"""Share of device busy time under the program's ``repro.partition`` scope,
averaged over the cell's chips: model D's partition (the partitioner, the
destination argsort, the slot arithmetic and the send-slab scatter). Nothing
to read where no operation of the window ran under a ``repro`` scope (a
program without them)."""

import scopes


def read(run):
    t = scopes.of(run)
    return None if t is None else t.share("repro.partition")

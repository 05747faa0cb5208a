"""Share of device busy time under the program's ``repro.compact`` scope,
averaged over the cell's chips: the compaction of the result slabs to the
dense result (``compact_slabs``). Nothing to read where no operation of the
window ran under a ``repro`` scope (a program without them)."""

import scopes


def read(run):
    t = scopes.of(run)
    return None if t is None else t.share("repro.compact")

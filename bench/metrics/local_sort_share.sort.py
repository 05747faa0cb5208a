"""Share of device busy time under the program's ``repro.local_sort`` scope,
averaged over the cell's chips: model D's local sort of the received slab.
Nothing to read where no operation of the window ran under a ``repro`` scope
(a program without them)."""

import scopes


def read(run):
    t = scopes.of(run)
    return None if t is None else t.share("repro.local_sort")

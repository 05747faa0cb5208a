"""Share of device busy time under the program's ``repro.merge`` scope,
averaged over the cell's chips: model B's merge rounds (``merge_adjacent``,
one stable sort per run pair). Nothing to read where no operation of the
window ran under a ``repro`` scope (a program without them)."""

import scopes


def read(run):
    t = scopes.of(run)
    return None if t is None else t.share("repro.merge")

"""Share of device busy time under the program's ``repro.kv_order`` scope:
the stable argsort of the keys in ``sort_kv``'s one-chip program. Nothing to
read where no operation of the window ran under a ``repro`` scope (a program
without them)."""

import scopes


def read(run):
    t = scopes.of(run)
    return None if t is None else t.share("repro.kv_order")

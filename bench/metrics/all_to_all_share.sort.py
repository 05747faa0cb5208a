"""Share of device 0's busy time in all-to-all operations: the model-D
exchange. XLA names the instruction ``all_to_all`` or ``all-to-all``. Nothing
to read where no all-to-all ran (one chip)."""

import re

ALL_TO_ALL = re.compile(r"^all[-_]to[-_]all\b")


def read(run):
    t = run.trace
    if t is None:
        return None
    dev = t.devices[0]
    a2a = t.op_s(dev, lambda kind: bool(ALL_TO_ALL.match(kind)))
    if a2a == 0:
        return None
    return 100.0 * a2a / t.busy_s(dev)

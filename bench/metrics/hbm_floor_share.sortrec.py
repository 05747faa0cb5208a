"""The least time one Sort Benchmark record sort could take on a chip, over
the device busy time of one call: every record of the chip's share, all 100
bytes (``record_bytes``), read once and written once at the chip's HBM
bandwidth. It counts the record's bytes, not what the program carries beside
them (``carried_bytes``), so it reads the same work whatever implements the
sort. ``hbm_floor_share.sortkv``'s reading of the loop's record counters."""

import harness

_sortkv = harness.module("metrics", "hbm_floor_share.sortkv")
floor_bytes = _sortkv.floor_bytes


def read(run):
    return _sortkv.read(run)

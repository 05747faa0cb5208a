"""The least time one record sort could take on a chip, over the device busy
time of one call: every record of the chip's share, its key and its payload
(``record_bytes``), read once and written once at the chip's HBM bandwidth
(``bench/peaks.json``). It counts the same bytes whatever implements the
sort. Nothing to read without the loop's record counters."""


def floor_bytes(records: int, record_bytes: int) -> int:
    """Bytes one call must move at least: each record read and written once."""
    return 2 * records * record_bytes


def read(run):
    t = run.trace
    c = run.counters
    if t is None or not c.get("calls") or "record_bytes" not in c:
        return None
    floor_s = floor_bytes(c["records_per_device"], c["record_bytes"]) / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * floor_s / (t.mean_busy_s() / c["calls"])

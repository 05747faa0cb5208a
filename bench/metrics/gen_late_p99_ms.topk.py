"""99th percentile of how late the load generator sent a request (send time
minus due time, host clock). A starved generator shows here, not as a fast
server."""


def read(run):
    return run.counters.get("gen_late_p99_ms")

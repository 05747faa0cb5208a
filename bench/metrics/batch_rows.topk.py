"""Mean requests per batch the frontend dispatched in the window, from the
service's ``ServiceStats`` counters."""


def read(run):
    return run.counters.get("batch_rows")

"""Largest peak / mean bucket load of the model-D exchanges in the window,
as the program's ``ExchangeTelemetry`` reports them (1.0 is a balanced
partition)."""


def read(run):
    return run.counters.get("peak_mean_ratio")

"""Share of the window in which no operation ran on the device, averaged
over the cell's chips: 1 - busy / window, where busy is the union of the
device's operation intervals in the profiler trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.mean_busy_s() / run.trace.window_s)

"""Device idle share of the window, in %: 1 - busy / window, where busy is
the union of the intervals in which an op ran on the device (profiler
trace)."""


def read(run):
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])

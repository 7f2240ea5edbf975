"""Problems per served flush in the window: the scheduler's coalescing,
from the service's own counters (ServeMetrics problems and flushes)."""


def read(run):
    flushes = run.window.counters.get("flushes")
    if not flushes:
        return None
    return run.window.counters["flushed_problems"] / flushes

"""Device busy ms per served flush: the union of op intervals in the
traced window over the flushes the service made in it (ServeMetrics)."""


def read(run):
    flushes = run.window.counters.get("flushes")
    if run.trace is None or not flushes:
        return None
    return run.trace["busy_s"] * 1e3 / flushes

"""Device idle ms per served flush named by the certify/refine polish's
host spans (``repro.polish.*``), from the profiler trace (``host_spans``,
bench/spans.py), over the flushes the service made in the window
(ServeMetrics)."""

from bench import spans


def read(run):
    return spans.idle_ms(run, ("repro.polish.",),
                         run.window.counters.get("flushes"))

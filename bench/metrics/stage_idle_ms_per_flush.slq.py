"""Device idle ms per served flush named by the engine's staging (the
``repro.serve.stage`` spans: host packing and padding), from the
profiler trace (``host_spans``, bench/spans.py), over the flushes the
service made in the window (ServeMetrics)."""

from bench import spans


def read(run):
    return spans.idle_ms(run, ("repro.serve.stage",),
                         run.window.counters.get("flushes"))

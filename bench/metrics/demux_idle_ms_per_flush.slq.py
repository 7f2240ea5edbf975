"""Device idle ms per served flush named by the engine's demux (the
``repro.serve.demux`` spans: the transfer and each member's finalizer)
and any escalation it runs (``repro.request.ladder``), from the profiler
trace (``host_spans``, bench/spans.py), over the flushes the service
made in the window (ServeMetrics)."""

from bench import spans


def read(run):
    return spans.idle_ms(run, ("repro.serve.demux", "repro.request.ladder"),
                         run.window.counters.get("flushes"))

"""Device idle ms per solve named by the certify/refine polish's host
spans (``repro.polish.certify``, ``.compact``, ``.sweep``, ``.scatter``:
core/bisect.py's host loop between its launches), from the profiler
trace (``host_spans``, bench/spans.py)."""

from bench import spans


def read(run):
    return spans.idle_ms(run, ("repro.polish.",), run.window.problems)

"""Useful lanes per launched lane of the polish's refine launches, in %:
the live lanes over the launched width (stats ``live`` and ``width`` of
the ``repro.polish.compact`` spans, summed over the window), from the
profiler trace (``host_spans``, bench/spans.py)."""

from bench import spans


def read(run):
    rows = spans.spans_of(run, ("repro.polish.compact",))
    if rows is None:
        return None
    width = sum(r["stats"].get("width", 0) for r in rows)
    if not width:
        return None
    return 100.0 * sum(r["stats"].get("live", 0) for r in rows) / width

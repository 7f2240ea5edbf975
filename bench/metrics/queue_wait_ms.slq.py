"""Mean queueing of a served flush, in ms: the time from its oldest
member's submit to the flush's assembly (stat ``queue_wait_us`` of the
``repro.serve.stage`` spans), from the profiler trace (``host_spans``,
bench/spans.py)."""

from bench import spans


def read(run):
    rows = spans.spans_of(run, ("repro.serve.stage",))
    if rows is None:
        return None
    count = sum(r["count"] for r in rows)
    if not count:
        return None
    return sum(r["stats"].get("queue_wait_us", 0) for r in rows) / count / 1e3

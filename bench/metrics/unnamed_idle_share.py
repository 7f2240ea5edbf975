"""Share of the window's device idle, in %, that no program span
(``repro.*``) names: idle under the benchmark's own spans or between
them, from the profiler trace (``host_spans``, bench/spans.py).  The
idle is the window less the busy union of the cell's one device."""

from bench import spans


def read(run):
    rows = spans.spans_of(run, (spans.PROGRAM_PREFIX,))
    if rows is None:
        return None
    idle = run.trace["window_s"] - run.trace["busy_s"]
    if idle <= 0:
        return None
    named = sum(r["idle_s"] for r in rows)
    return 100.0 * max(idle - named, 0.0) / idle

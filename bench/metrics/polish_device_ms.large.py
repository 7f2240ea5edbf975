"""Device ms per solve in the certify/refine executables of
core/bisect.py (``jit__certify_executor``, ``jit__refine_executor``,
``jit__sturm_count_flat``), from the profiler trace of the window."""


def read(run):
    if run.trace is None or "polish" not in run.trace["layers"]:
        return None
    return run.trace["layers"]["polish"]["seconds"] * 1e3 / run.window.problems

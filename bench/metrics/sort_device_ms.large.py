"""Device ms per solve in XLA sort ops (the merge head's sorts in
core/merge.py, and the final eigenvalue sort), from the profiler trace."""


def read(run):
    if run.trace is None or "sorts" not in run.trace["layers"]:
        return None
    return run.trace["layers"]["sorts"]["seconds"] * 1e3 / run.window.problems

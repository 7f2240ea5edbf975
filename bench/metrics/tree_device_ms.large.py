"""Device ms per solve in the D&C tree's executable (XLA module
``jit__executor``: core/plan.py, core/br_dc.py, core/merge.py, kernels/),
from the profiler trace of the window."""


def read(run):
    if run.trace is None or "tree" not in run.trace["layers"]:
        return None
    return run.trace["layers"]["tree"]["seconds"] * 1e3 / run.window.problems

"""Launches per solve of the certify/refine executables (core/bisect.py's
host-driven polish loop), counted from the profiler trace's module runs."""


def read(run):
    if run.trace is None or "polish" not in run.trace["layers"]:
        return None
    return run.trace["layers"]["polish"]["launches"] / run.window.problems

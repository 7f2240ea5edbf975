"""Seconds per solve: window wall time over solves completed (host clock)."""


def read(run):
    w = run.window
    return w.seconds / w.problems if w.problems else None

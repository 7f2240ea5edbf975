"""Problems completed over the window's wall time (host clock)."""


def read(run):
    w = run.window
    return w.problems / w.seconds if w.problems else None

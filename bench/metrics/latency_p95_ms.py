"""95th percentile (nearest rank) of every problem's client-side
issue -> result time in the window, in ms (host clock)."""

import math


def read(run):
    lat = sorted(run.window.latencies_s)
    if not lat:
        return None
    rank = math.ceil(0.95 * len(lat))
    return lat[rank - 1] * 1e3

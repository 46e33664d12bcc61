"""Window and latency arithmetic on the host clock.

An epoch commits at its fence, so the measured window runs from one
commit fence to another: it starts at the fence that closes set-up and
ends at the first fence at least ``seconds`` later.  A window cut between
fences would count part of an epoch's time and none of its commits.
"""
from __future__ import annotations

import numpy as np


def window_end(fences, start: int, seconds: float):
    """Index of the first fence at least ``seconds`` after fence ``start``,
    or None while no such fence has come."""
    t0 = fences[start]
    for k in range(start + 1, len(fences)):
        if fences[k] - t0 >= seconds:
            return k
    return None


def percentile_ms(latencies_s, q: float) -> float:
    """The q-th percentile in ms (linear interpolation between samples)."""
    lat = np.asarray(latencies_s, np.float64)
    if lat.size == 0:
        return float("nan")
    return float(np.percentile(lat, q)) * 1e3


def rate(n: int, t0: float, t1: float) -> float:
    """Events per second between two host-clock readings."""
    return n / (t1 - t0)

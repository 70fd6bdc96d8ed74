"""Percentiles and the window's edges."""
from __future__ import annotations

import numpy as np


def percentile(xs, q: float):
    """Linear-interpolation percentile (numpy's default, as the program's
    ``serving.scheduler.percentiles`` takes it); None when empty."""
    if not len(xs):
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


def in_window(t: float, w0: float, w1: float) -> bool:
    """The window holds [w0, w1): a token stamped at w1 is the next
    window's."""
    return w0 <= t < w1

"""Percentile and rate arithmetic of the end-to-end metrics (copied from
the program's ``serving/engine/metrics.py`` convention: numpy's linear
interpolation)."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear interpolation."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def rate(count: float, seconds: float) -> float:
    return float(count) / float(seconds)


"""Percentiles for the end-to-end metrics.

A copy of the program's ``repro.obs.metrics.percentile`` arithmetic
(linear interpolation on the sorted sample, numpy's default), kept here
so that the yardstick cannot move with the program.  It takes the tail
over every sample it is given; callers pass all of them.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile(xs: Sequence[float], q: float) -> float | None:
    """q-th percentile (0..100) of ``xs``; None on the empty input (a
    metric with no sample is left out, never reported as 0)."""
    if not len(xs):
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))

"""Percentiles and spreads.

``percentile`` is ``repro.launch.metrics.ServeMetrics.percentile`` (numpy's
linear interpolation), copied so that the yardstick does not move with the
program.  ``spread`` is the distance between the first and third quartile
over the median, as ``statistics.quantiles(values, n=4)`` gives them.
"""
from __future__ import annotations

import statistics
from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def spread(values: Sequence[float]) -> float:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med

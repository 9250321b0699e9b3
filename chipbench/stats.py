"""The benchmark's own arithmetic on samples, kept here so that no change to
the program can move the yardstick."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def nearest_rank(xs: Sequence[float], q: float) -> float:
    """Nearest-rank quantile: the ceil(q * n)-th order statistic, so the
    0.95 quantile of 240 samples is the 228th smallest."""
    if not xs:
        raise ValueError("quantile of no samples")
    s = sorted(xs)
    return float(s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))])


def spread(xs: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles``' default method)."""
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)

"""Nearest-rank percentile: ``x[ceil(q * n) - 1]`` of the sorted series
(the convention of the program's ``repro.core.stats``, copied so that the
yardstick cannot move with the program)."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    if not xs:
        raise ValueError("percentile of an empty series")
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"percentile q={q} outside [0, 1]")
    ys = sorted(xs)
    return ys[min(len(ys) - 1, max(0, math.ceil(q * len(ys)) - 1))]

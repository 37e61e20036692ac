"""Metric arithmetic, kept with the benchmark."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (no interpolation): copied from
    ``scaling_tpu/obs/report.py`` ``percentile``."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    return s[max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))]

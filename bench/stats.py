"""Percentile arithmetic of the benchmark.

``percentile`` is the exact-rank (nearest-rank) rule of
``repro.utils.percentiles``, copied so that no change to the program can
change how the benchmark reads a tail: the p-th percentile of n samples is
``sorted(xs)[ceil(p / 100 * n) - 1]``, always a sample that was seen.
"""
from __future__ import annotations

import math
from typing import Iterable


def percentile(samples: Iterable[float], p: float) -> float:
    xs = sorted(float(s) for s in samples)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile must be in [0, 100], got {p}")
    return xs[max(math.ceil(p / 100.0 * len(xs)), 1) - 1]


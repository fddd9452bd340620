"""Percentiles, and which percentile a sample supports."""

from __future__ import annotations

import math
from typing import Sequence

#: the percentiles the benchmark reports, lowest first
LADDER = (50.0, 75.0, 90.0, 95.0, 99.0, 99.9)
#: a percentile is supported when at least this many samples lie beyond it
BEYOND = 10


def percentile(values: Sequence[float], p: float) -> float:
    """The p-th percentile (0..100), linear between the two closest ranks."""
    if not values:
        raise ValueError("percentile of an empty sample")
    v = sorted(values)
    k = (len(v) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return float(v[lo] + (v[hi] - v[lo]) * (k - lo))


def supported_percentile(n: int) -> float:
    """The highest percentile of LADDER with at least BEYOND of ``n`` samples
    beyond it (a p95 needs 200 samples); the median when none is."""
    best = LADDER[0]
    for p in LADDER:
        if round(n * (100.0 - p), 6) >= BEYOND * 100.0:
            best = p
    return best


def tail_note(name: str, values: Sequence[float], wanted: float) -> str:
    """One ``bench:`` line for a tail metric: the count, the median, and,
    where the sample is too small for ``wanted``, the percentile it does
    support with its value."""
    n = len(values)
    note = f"{name}: n={n} p50={percentile(values, 50.0):.3f}"
    top = supported_percentile(n)
    if top < wanted:
        note += (
            f"; {n} samples do not support p{wanted:g} (needs "
            f"{math.ceil(BEYOND * 100.0 / (100.0 - wanted))}): the highest "
            f"supported is p{top:g}={percentile(values, top):.3f}"
        )
    return note

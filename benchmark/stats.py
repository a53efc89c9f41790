"""Percentile and spread arithmetic of the benchmark (no jax, no numpy)."""
from __future__ import annotations

import math
import statistics

#: tails the harness will name, highest first
TAILS = (99.0, 95.0, 90.0, 75.0, 50.0)


def percentile(values, q):
    """Nearest-rank percentile: the smallest sample with at least ``q`` % of
    the samples at or below it. No interpolation, so a tail is always a
    latency some request really had."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[min(rank, len(vals)) - 1]


def samples_beyond(n, q):
    """How many of ``n`` samples lie strictly beyond the nearest-rank ``q``."""
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


def supported_tail(n, beyond=10):
    """The highest of :data:`TAILS` that still has ``beyond`` samples past
    it among ``n`` (50 when none has)."""
    for q in TAILS:
        if samples_beyond(n, q) >= beyond:
            return q
    return 50.0


def median(values):
    return statistics.median(values)


def iqr_share(values):
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them —
    the spread the bounds are set from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def in_window(t, start, seconds):
    """Whether the instant ``t`` falls inside ``[start, start + seconds)``."""
    return start <= t < start + seconds

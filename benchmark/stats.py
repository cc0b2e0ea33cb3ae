"""Order statistics of the benchmark: its own copy, so that a later change to
the program's loadgen cannot move the yardstick (original:
sparse_tpu/loadgen/_run.py `_percentile`, listed in PERF.md for deletion)."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` of the
    sample at or below it. ``q`` in (0, 1]. Raises on an empty sample."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    rank = max(int(math.ceil(q * len(vals))), 1)
    return vals[rank - 1]


def median(values) -> float:
    return statistics.median(values)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, the way the driver reads a set of runs."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

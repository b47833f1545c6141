"""Order statistics of a run's samples."""
from __future__ import annotations

import math
import statistics
from typing import Iterable, Optional


def percentile(values: Iterable[float], q: float,
               n_missing: int = 0) -> Optional[float]:
    """The nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``
    with ``n_missing`` further samples that count as beyond every value (a
    request that failed or got nothing within its limit). Inf where the
    rank falls among the missing; None where there is no sample."""
    vals = sorted(values)
    n = len(vals) + n_missing
    if n == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))
    return vals[rank - 1] if rank <= len(vals) else math.inf


def spread(values) -> float:
    """Interquartile distance as a share of the median (Python's
    ``statistics.quantiles`` quartiles)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2

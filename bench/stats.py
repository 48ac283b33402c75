"""Order statistics of latencies."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it. None for no values."""
    if not values:
        return None
    xs = sorted(values)
    k = max(math.ceil(q / 100.0 * len(xs)), 1)
    return xs[k - 1]


def latencies_ms(ops) -> list:
    """Submit-to-acknowledgement times of ``ops`` in milliseconds."""
    return [(op.done_t - op.t_sub) * 1e3 for op in ops]

"""Order statistics the benchmark reports, computed from raw samples."""
from __future__ import annotations

import math
from typing import Iterable, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of every sample.

    A miss is passed in as ``math.inf`` and stays in the ranking, so a tail
    with misses in it reads infinite instead of hiding them. Returns None
    for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    rank = max(math.ceil(q / 100.0 * len(xs)), 1)
    return xs[rank - 1]


"""Order statistics the metric readers share."""
from __future__ import annotations

import math

import numpy as np


def percentile(x, q: float, cap: float = math.inf) -> float:
    """Nearest-rank ``q``-th percentile of every value; a value that is
    not finite (an op never answered, refused or answered wrongly) counts
    as ``cap``."""
    x = np.asarray(x, np.float64)
    x = np.sort(np.where(np.isfinite(x), x, cap))
    return float(x[min(max(int(math.ceil(q / 100 * len(x))) - 1, 0),
                       len(x) - 1)])

"""Order statistics the harness and its readers share."""
from __future__ import annotations

import math
from typing import Iterable, Optional


def quantile(values: Iterable[float], q: float) -> Optional[float]:
    """Linear-interpolated ``q``-quantile (0 <= q <= 1) over every value, the
    way numpy's default does it; None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = q * (len(xs) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or xs[lo] == xs[hi]:
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)

"""Small statistics shared by the metric readers."""
from __future__ import annotations

from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile of the values, linear between the closest ranks
    (numpy's default); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latencies_ms(outcomes) -> List[float]:
    """Latency of every answered request of an open-loop window, from its
    scheduled send to its full response; failed and refused requests have
    none (they count in `failed`)."""
    return [1e3 * (o.done - o.scheduled) for o in outcomes
            if o.error is None and o.done is not None]


def mean(values: Sequence[float]) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) if values else None

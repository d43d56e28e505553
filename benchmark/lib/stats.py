"""Percentiles and spreads, one definition for the whole benchmark."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float | None:
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics; None on an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def compared(name: str, value: float, limit: float, note: str = "",
             at_most: bool = True) -> dict:
    """Print one number beside its limit and return the row. `value` must
    be <= `limit` (at_most) or >= it; NaN fails."""
    ok = bool(value <= limit if at_most else value >= limit)
    print(f"compare {name} value={value!r} limit={'<=' if at_most else '>='}"
          f"{limit!r} {'ok' if ok else 'FAIL'}  {note}", flush=True)
    return {"name": name, "value": value, "limit": limit, "ok": ok}


def spread(values) -> float | None:
    """Distance between the first and third quartile as a share of the
    median, by `statistics.quantiles(values, n=4)` as the driver takes it."""
    xs = list(values)
    if len(xs) < 2:
        return None
    q1, _, q3 = statistics.quantiles(xs, n=4)
    med = statistics.median(xs)
    return (q3 - q1) / abs(med) if med else None

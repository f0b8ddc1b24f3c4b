"""Metric arithmetic over the client's records: percentiles over every
request (a failed or unfinished one counts as missing the limit, +inf),
and rates over the whole measured window."""
from __future__ import annotations

import math
from typing import Iterable, List, Sequence


def percentile(xs: Iterable[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``%
    of the samples at or below it. ``inf`` entries take part as the
    largest values. Empty input has no percentile."""
    v = sorted(float(x) for x in xs)
    if not v:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(v)))
    return v[rank - 1]


def ttft_samples(records, w0: float, w1: float) -> List[float]:
    """Seconds from when each request due in ``[w0, w1)`` was due to
    when its first token line arrived; ``inf`` where none arrived or
    the request did not end ``done``."""
    out = []
    for r in records:
        if not w0 <= r.due < w1:
            continue
        if r.token_times and r.state == "done":
            out.append(r.token_times[0] - r.due)
        else:
            out.append(math.inf)
    return out


def itl_samples(records, w0: float, w1: float) -> List[float]:
    """Every gap between two consecutive token lines of every request due
    in ``[w0, w1)``."""
    out = []
    for r in records:
        if w0 <= r.due < w1:
            t = r.token_times
            out.extend(b - a for a, b in zip(t, t[1:]))
    return out


def tokens_in(records, t0: float, t1: float) -> int:
    """Token lines received in ``[t0, t1)``, from any request."""
    return sum(1 for r in records for t in r.token_times if t0 <= t < t1)


def first_tokens_in(records, t0: float, t1: float) -> int:
    return sum(1 for r in records
               if r.token_times and t0 <= r.token_times[0] < t1)


def rate(count: float, seconds: float) -> float:
    return count / seconds


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median (``statistics.quantiles``
    with n=4), the spread the benchmark's bounds are set from."""
    import statistics
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

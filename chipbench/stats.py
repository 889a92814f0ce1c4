"""The arithmetic between raw timings and reported numbers. No JAX."""

from __future__ import annotations

import math
import statistics


def percentile(values, p: float) -> float:
    """p-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default). Raises on an empty sample: a metric that
    has nothing under it is left out, never reported as 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    k = (len(xs) - 1) * (p / 100.0)
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def pooled_gaps_ms(chunk_times_by_request) -> list[float]:
    """Gaps between consecutive streamed chunks of one request, pooled over
    all requests, in ms."""
    out = []
    for ts in chunk_times_by_request:
        out.extend((b - a) * 1000.0 for a, b in zip(ts, ts[1:]))
    return out


def tpot_ms(t_first: float, t_last: float, tokens: int):
    """(last token - first token) / (tokens - 1) in ms; None for a
    one-token answer, which has no pace."""
    if tokens < 2:
        return None
    return (t_last - t_first) * 1000.0 / (tokens - 1)


def iqr_share(values) -> float:
    """The spread the contract's bounds are set from: distance between the
    first and third quartile (statistics.quantiles, n=4) over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def knee(rows) -> float | None:
    """The highest sustained rate of a sweep. `rows`: dicts with `rate`,
    `failed` and `ttft_ms.p50_late` (median time to first token of the
    requests due in the second half of that rate's window). Under the knee
    that time is the service time (a prefill behind the decode call that is
    running) whatever the rate; above it the queue, and with it that time,
    grows all through the window. A rate is sustained when no request failed
    and its late median is at most twice that of the ladder's lowest rate;
    the knee is the highest rate such that it and every lower rate of the
    ladder are sustained. (The backlog of unfinished requests, tried first,
    wobbles with the order of long and short answers and flagged rates that
    every other reading showed sustained.)"""
    rows = sorted(rows, key=lambda r: r["rate"])
    best = None
    for r in rows:
        if r["failed"] or r["ttft_ms.p50_late"] > 2 * rows[0]["ttft_ms.p50_late"]:
            break
        best = r["rate"]
    return best

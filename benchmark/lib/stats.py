"""The yardstick's arithmetic on samples: percentiles, time per output
token, and the spread the bounds are set from.  Plain Python."""

import statistics


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between order
    statistics (numpy's default).  None on an empty list."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def tpot_ms(first_s, last_s, n_tokens, min_tokens=8):
    """Time per output token of one request, in ms: (last token time -
    first token time) / (tokens - 1).  None for a request with fewer than
    ``min_tokens`` tokens: a burst of a few tokens measures the sync
    window, not the decode rate."""
    if n_tokens < max(min_tokens, 2) or first_s is None or last_s is None:
        return None
    return (last_s - first_s) * 1e3 / (n_tokens - 1)


def summary(values):
    """count, mean and percentiles of a sample, for the lines before the
    result (the end-to-end metrics are the named ones; the rest are there to
    be read beside them)."""
    xs = [v for v in values if v is not None]
    xs.sort()
    mid = xs[len(xs) // 10: len(xs) - len(xs) // 10]    # without the tenths
    return {"count": len(xs), "mean": sum(xs) / len(xs) if xs else None,
            "midmean": sum(mid) / len(mid) if mid else None,
            "p25": percentile(xs, 25), "p50": percentile(xs, 50),
            "p75": percentile(xs, 75), "p90": percentile(xs, 90),
            "p95": percentile(xs, 95), "p99": percentile(xs, 99),
            "max": max(xs) if xs else None}


def iqr_share(values):
    """The spread the contract sets bounds from: the distance between the
    first and third quartile (``statistics.quantiles(values, n=4)``) as a
    share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

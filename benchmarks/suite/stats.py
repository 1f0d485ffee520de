"""Percentiles as the benchmark reports them."""

import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default), on plain Python lists."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n, q):
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0 + 1e-9))


def tail_supported(n, q, need=10):
    """A tail percentile is reported only with ``need`` samples beyond
    it (choosing-metrics, section 1): with fewer it is a maximum."""
    return samples_beyond(n, q) >= need


def summary(values, q):
    """``{"n", "min", "median", "p<q>", "supported"}`` for an earlier
    output line: the median and sample count go beside every tail."""
    return {"n": len(values),
            "min": min(values) if values else None,
            "median": percentile(values, 50) if values else None,
            f"p{q:g}": percentile(values, q) if values else None,
            "supported": tail_supported(len(values), q)}

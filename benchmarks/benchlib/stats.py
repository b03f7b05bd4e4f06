"""Percentiles and spreads (the percentile is a copy of
`paddle_tpu/serving/metrics.py:percentile`, the linear interpolation
numpy defaults to; copied so that the program cannot move it)."""
import statistics


def percentile(values, q):
    """Linear-interpolation percentile, q in [0, 100]; None when empty."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    return float(xs[lo] * (1.0 - frac) + xs[hi] * frac)


def iqr_spread(values):
    """Distance between the first and third quartile as a share of the
    median, by `statistics.quantiles(values, n=4)` — the driver's spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

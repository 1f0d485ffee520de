"""A statistic of one host series (the durations of a harness span, or a
series a driver filled in). Arguments: ``series``; ``stat`` (``median``,
``mean`` or ``p<q>``); ``scale`` (1000 turns seconds into ms, 100 a
share into %)."""

from benchmarks.suite import stats


def read(ctx, result, series, stat, scale=1.0):
    values = ctx.recorder.series.get(series)
    if not values:
        return None
    if stat == "mean":
        value = sum(values) / len(values)
    elif stat == "median":
        value = stats.percentile(values, 50)
    elif stat.startswith("p"):
        value = stats.percentile(values, float(stat[1:]))
    else:
        raise ValueError(f"unknown stat {stat!r}")
    return scale * value

"""``span_stat``'s ratio of two counters (``attr``) for counters that
only some models' spans carry (a recurrent state's): the same reading,
under a reader of its own so that a cell whose program has no such
counters is not expected to report it. ``None`` there, as ever."""

from benchmarks.suite.readers import span_stat


def read(ctx, result, path, stat, attr, scale=1.0):
    return span_stat.read(ctx, result, path=path, stat=stat, attr=attr,
                          scale=scale)

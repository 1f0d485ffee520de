"""Device time of one kernel found by its name in the trace
(``op_time``'s ``pattern`` and ``per``), in ms; ``None`` where no op of
the trace matches, so that a program that does not give its kernels
these names (an older one) reports nothing, not a time of 0."""

from benchmarks.suite.readers import op_time


def read(ctx, result, pattern, per="window"):
    return op_time.read(ctx, result, pattern=pattern, per=per) or None

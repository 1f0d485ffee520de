"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-op intervals) / window, averaged over chips."""


def read(ctx, result):
    trace = result.trace
    if trace is None:
        return None
    w0, w1 = trace.window()
    return 100.0 * (1.0 - trace.busy_seconds() / (w1 - w0))

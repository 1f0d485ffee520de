"""Device time in the ops whose name matches ``pattern``, from the trace,
in ms, averaged over chips. ``per``: ``step`` divides by the training
steps profiled, ``span:<name>`` by how many harness spans of that name
the trace holds (a decode step, a prefill), ``window`` by nothing.
``line``: ``flight`` takes the time during which a matching op is under
way, hidden or not (an asynchronous collective from start to done on the
``Async XLA Ops`` line, a synchronous one on the core's line), instead of
the time the core itself spends in matching ops (a ``-done`` lasts as
long as the core waits: the exposed part)."""


def read(ctx, result, pattern, per="window", line="ops"):
    trace = result.trace
    if trace is None:
        return None
    if line == "flight":
        seconds = trace.flight_seconds(pattern)
    else:
        seconds = trace.op_seconds(pattern)
    if per == "step":
        count = result.facts.get("profiled_steps")
    elif per.startswith("span:"):
        count = sum(1 for n, _, _ in trace.spans if n == per[5:])
    else:
        count = 1
    if not count:
        return None
    return 1e3 * seconds / count

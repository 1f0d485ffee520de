"""``scope_time`` for a run that holds more than one compiled program:
device time, in ms, of the ops of ONE program that belong to named
phases (``jax.named_scope``).

A trace names an op by its HLO instruction, and two programs both have
a ``fusion.12``. So the driver hands over one map a program
(``result.facts["program_scopes"][program]``: instruction ->
``op_name``, from that program's compiled text), and an op belongs to
``program`` if it ran while a harness span of that name was open on the
host: ``engine.prefill`` and ``engine.decode`` return only once their
device work is done, so what the device runs inside a ``prefill`` span
is the prefill program and nothing else. ``per`` as in ``op_time``
(``span:<name>`` divides by the trace's harness spans of that name).
``None`` without a trace, without the map (a program that names no
phases, or an untraced run), or where nothing matches."""

import bisect

from benchmarks.suite import xplane


def read(ctx, result, program, scopes, per="window"):
    trace = result.trace
    known = (result.facts.get("program_scopes") or {}).get(program)
    if trace is None or not known:
        return None
    spans = sorted((s, e) for n, s, e in trace.spans if n == program)
    if not spans:
        return None
    starts = [s for s, _ in spans]

    def inside(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < spans[i][1]

    per_chip = []
    for events in trace.devices.values():
        mine = [ev for ev in events if inside(0.5 * (ev[1] + ev[2]))]
        per_chip.append(sum(
            seconds for name, seconds in xplane.self_times(mine)
            if any(s in known.get(name.partition(" ")[0], "")
                   for s in scopes)))
    seconds = sum(per_chip) / len(per_chip)
    if per == "step":
        count = result.facts.get("profiled_steps")
    elif per.startswith("span:"):
        count = sum(1 for n, _, _ in trace.spans if n == per[5:])
    else:
        count = 1
    if not seconds or not count:
        return None
    return 1e3 * seconds / count

"""``op_time`` for a run that holds more than one compiled program whose
kernels share a name: device time, in ms, of the ops whose name in the
trace matches ``pattern`` **and** that ran while a harness span named
``program`` was open on the host (``engine.prefill`` and
``engine.decode`` return only once their device work is done, so what
the device runs inside a ``decode`` span is the decode program:
``program_scope_time`` says the same of scopes). ``per`` as in
``op_time``. ``None`` without a trace, without such spans, or where
nothing matches."""

import bisect
import re

from benchmarks.suite import xplane


def read(ctx, result, program, pattern, per="window"):
    trace = result.trace
    if trace is None:
        return None
    spans = sorted((s, e) for n, s, e in trace.spans if n == program)
    if not spans:
        return None
    starts = [s for s, _ in spans]
    rx = re.compile(pattern)

    def inside(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < spans[i][1]

    per_chip = []
    for events in trace.devices.values():
        mine = [ev for ev in events if inside(0.5 * (ev[1] + ev[2]))]
        per_chip.append(sum(seconds for name, seconds
                            in xplane.self_times(mine) if rx.search(name)))
    seconds = sum(per_chip) / len(per_chip)
    if per.startswith("span:"):
        count = sum(1 for n, _, _ in trace.spans if n == per[5:])
    else:
        count = 1
    if not seconds or not count:
        return None
    return 1e3 * seconds / count

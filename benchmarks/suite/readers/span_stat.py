"""A statistic over the program's own spans of one ``path`` (the
program's ring, see ``program_ring.py``) that closed in the measured
window.

Without ``attr``: of each span's duration, less the time of the spans
named in ``minus`` (full paths) that lie inside it; the profiled
segment's spans are left out, as the harness leaves out its own (a
traced host is slower). ``over`` = ``run`` also takes the spans of the
ramp before the window: the harness keeps its own span series
(``decode``, ``prefill``) from the start of the ramp to the profiler's
start, so the twin of such a series covers the same calls and the two
can be held against each other. With ``attr = [a, b]``: of ``attrs[a] /
attrs[b]``, a ratio of two counters the span carries, over the whole
window (tracing does not move a count). ``stat`` is ``median``,
``mean`` or ``p<q>``; ``scale`` 1000 turns seconds into ms, 100 a share
into %. ``None`` where the ring holds nothing of the window or no such
span."""

import bisect
import dataclasses

from benchmarks.suite import program_ring


def read(ctx, result, path, stat, minus=(), attr=None, scale=1.0,
         over="window"):
    v = program_ring.view(ctx, result)
    if v is None:
        return None
    if over == "run":
        v = dataclasses.replace(
            v, w0=v.w0 - ctx.workload["traffic"]["ramp_s"])
    elif over != "window":
        raise ValueError(f"unknown over {over!r}")
    if attr is not None:
        top, bottom = attr
        values = [r[3][top] / r[3][bottom]
                  for r in v.spans(path, untraced=False)
                  if r[3] and r[3].get(top) is not None and r[3].get(bottom)]
    else:
        inner = sorted((r[1], r[2]) for r in v.ring if r[0] in minus)
        values = []
        for _, t0, t1, _ in v.spans(path):
            i = bisect.bisect_left(inner, (t0, t0))
            held = 0.0
            while i < len(inner) and inner[i][0] < t1:
                held += min(inner[i][1], t1) - inner[i][0]
                i += 1
            values.append((t1 - t0) - held)
    if not values:
        return None
    return scale * program_ring.statistic(values, stat)

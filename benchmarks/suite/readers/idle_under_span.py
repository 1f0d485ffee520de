"""The first chip's idle time, laid to what the *program* was doing:
each gap between device ops of the traced segment (``xplane.gaps``, as
``Trace.top_gaps`` takes them) is cut where a program span opens or
closes on the host (the program's ring, see ``program_ring.py``), and
each piece goes to the innermost span open in it, ``no_span`` where
none is. (``top_gaps`` gives a whole gap to the span at its middle; the
5 ms between two decode programs cross half a dozen spans.) The pieces
whose path matches the regular expression ``match`` are summed; ``per``
= ``decode_step`` divides by the program's decode spans in the segment,
``window`` by nothing. ms.

The ring and the trace run on clocks a constant apart;
``program_ring.trace_offset`` finds the constant and checks it (every
harness ``decode`` span must contain one program ``serve/step/decode``
span to within 0.2 ms). ``None`` without a trace, without a ring, or
where that check fails. The whole split goes to the progress log."""

import bisect
import re

from benchmarks.suite import program_ring, xplane


def split(v, trace, offset):
    """``({path: idle seconds}, decode spans in the segment)``."""
    w0, w1 = trace.window()
    spans = program_ring.nested(
        r for r in v.ring if r[0].startswith(program_ring.STEP)
        and r[2] + offset > w0 and r[1] + offset < w1)
    edges = sorted({t for s in spans for t in s[:2]})
    evs = trace.devices[min(trace.devices)]
    acc = {}
    for g0, g1 in xplane.gaps([(s, e) for _, s, e in evs], (w0, w1)):
        g0, g1 = g0 - offset, g1 - offset       # on the ring's clock
        cuts = [g0, *edges[bisect.bisect_right(edges, g0):
                           bisect.bisect_left(edges, g1)], g1]
        for a, b in zip(cuts, cuts[1:]):
            path = program_ring.innermost(spans, 0.5 * (a + b))
            acc[path] = acc.get(path, 0.0) + (b - a)
    return acc, sum(1 for s in spans if s[2] == program_ring.DECODE)


def read(ctx, result, match, per="decode_step"):
    trace = result.trace
    if trace is None:
        return None
    v = program_ring.view(ctx, result)
    if v is None:
        return None
    fit = program_ring.trace_offset(v, trace)
    if fit is None:
        ctx.log("idle_under_span: the program's decode spans do not fit "
                "the harness's in the trace; nothing attributed")
        return None
    offset, worst = fit
    acc, decodes = split(v, trace, offset)
    ctx.log(f"idle by program span, ms per decode step over {decodes} "
            f"steps (clock fit: worst decode span {1e6 * worst:+.1f} us "
            f"outside its harness span): " + ", ".join(
                f"{p} {1e3 * t / max(decodes, 1):.3f}" for p, t in
                sorted(acc.items(), key=lambda kv: -kv[1])))
    count = decodes if per == "decode_step" else 1
    if not count:
        return None
    rx = re.compile(match)
    return 1e3 * sum(t for p, t in acc.items() if rx.search(p)) / count

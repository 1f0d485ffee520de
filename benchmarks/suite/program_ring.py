"""What the readers of the program's own spans and stamps share.

The program keeps every closed span, and one ``serve/request`` record
per finished request, in one in-memory ring
(``deepspeed_tpu.telemetry.spans``: ``(path, t0, t1, attrs)`` on
``time.perf_counter``, which is also ``harness.clock``). A reader runs
after the run, in the same process, and sees neither the engine nor the
scheduler: it reads that ring. The ring outlives a run (and a test), so
everything here selects by the run's window, never "all of the ring".

A program that has no ring (an older one) gives ``view() -> None``, and
every reader built on it returns ``None``.

**The two clocks.** The profiler's timeline (``xplane.Trace``) counts
Unix-epoch nanoseconds less the profile's own start
(``profile_start_time`` in the trace file's ``Task Environment`` plane,
which ``xplane.load`` does not keep), so a ring time and a trace time
differ by a constant that this file has to find: ``trace_offset`` fits
it on the pairs (harness ``decode`` span in the trace, program
``serve/step/decode`` span in the ring) and then checks that with that
one constant every harness span contains its program span to within
``CLOCK_TOLERANCE_S``. Both clocks count the same nanoseconds, so the
fit absorbs the constant and the check guards rate, pairing and jitter.
"""

import bisect
import dataclasses
import importlib

from benchmarks.suite import stats

REQUEST = "serve/request"
STEP = "serve/step"
DECODE = "serve/step/decode"
MIN_AFTER_WRAP = 100        # samples a wrapped ring must still hold
CLOCK_TOLERANCE_S = 0.2e-3


@dataclasses.dataclass
class View:
    """One run as the ring shows it. Clock readings, seconds."""
    ring: list          # every record the ring holds, oldest first
    w0: float           # the measured window [w0, w1)
    w1: float
    seg0: float         # the profiled segment [seg0, w1); w1 if none

    def spans(self, path, untraced=True):
        """Records of ``path`` that closed in the window; by default
        not those of the profiled segment (a traced host is slower)."""
        end = self.seg0 if untraced else self.w1
        return [r for r in self.ring
                if r[0] == path and self.w0 <= r[2] < end]


def ring_records():
    """``(records, dropped)`` of the program's ring, or ``(None, 0)``
    where the program keeps none."""
    try:
        from deepspeed_tpu.telemetry import spans
    except ImportError:
        return None, 0
    ring = getattr(spans, "ring", None)
    if ring is None:
        return None, 0
    return ring.recent(), ring.dropped


def view(ctx, result):
    """The run's ``View``; ``None`` where the ring holds nothing of the
    window, or has wrapped past its start with fewer than
    ``MIN_AFTER_WRAP`` records of it left."""
    records, dropped = ring_records()
    if not records:
        return None
    w0 = ctx.t_process + result.setup_s
    w1 = w0 + ctx.seconds
    inside = sum(1 for r in records if w0 <= r[2] < w1)
    if not inside:
        return None
    if dropped and records[0][2] > w0 and inside < MIN_AFTER_WRAP:
        return None
    profile_s = (ctx.workload.get("trace", {}).get("profile_s")
                 if ctx.trace else None)
    return View(ring=records, w0=w0, w1=w1,
                seg0=w1 - profile_s if profile_s else w1)


def statistic(values, stat):
    """``median``, ``mean`` or ``p<q>`` of a non-empty list."""
    if stat == "mean":
        return sum(values) / len(values)
    if stat == "median":
        return stats.percentile(values, 50)
    if stat.startswith("p"):
        return stats.percentile(values, float(stat[1:]))
    raise ValueError(f"unknown stat {stat!r}")


def due_times(ctx, v):
    """``{rid: clock reading at which the request was due}``, from the
    cell's own generator: the driver submits a request when it is due
    and hands the program no arrival time, so the program's
    ``arrival_t`` is the submit time."""
    traffic = ctx.workload["traffic"]
    gen = importlib.import_module(
        "benchmarks.suite.traffic." + traffic["generator"])
    arrivals = gen.make(traffic, ctx.seed, seconds=ctx.seconds,
                        vocab_size=ctx.config["vocab_size"])
    t0 = v.w0 - traffic["ramp_s"]
    return {a.rid: t0 + a.due_s for a in arrivals}


def requests(ctx, v):
    """``{rid: attrs}`` of the run's ``serve/request`` records: those
    that arrived between the start of the ramp and the end of the
    drain (the ring may hold other runs' requests of the same ids)."""
    traffic = ctx.workload["traffic"]
    lo = v.w0 - traffic["ramp_s"]
    hi = v.w1 + traffic["drain_s"]
    return {r[3]["rid"]: r[3] for r in v.ring
            if r[0] == REQUEST and r[1] is not None and lo <= r[1] < hi}


def trace_offset(v, trace):
    """``(offset, worst)``: seconds to add to a ring time to get the
    trace's time, and by how much the worst-placed program ``decode``
    span sticks out of its harness span (negative: it lies inside);
    ``None`` where the clocks cannot be shown to agree. The harness's
    ``decode`` spans in the trace are consecutive calls of
    ``engine.decode``, each of which opened one program span, so some
    run of consecutive program spans must fit them one to one with a
    single constant, each inside its harness span to within
    ``CLOCK_TOLERANCE_S``, and only one run may."""
    harness = sorted((s, e) for n, s, e in trace.spans if n == "decode")
    program = sorted((r[1], r[2]) for r in v.ring
                     if r[0] == DECODE and v.seg0 - 1.0 <= r[2]
                     and r[1] <= v.w1 + 1.0)
    n = len(harness)
    if not n or len(program) < n:
        return None
    fits = []
    for k in range(len(program) - n + 1):
        pairs = list(zip(harness, program[k:k + n]))
        c = stats.percentile([0.5 * ((hs + he) - (ps + pe))
                              for (hs, he), (ps, pe) in pairs], 50)
        worst = max(max(hs - (ps + c), (pe + c) - he)
                    for (hs, he), (ps, pe) in pairs)
        if worst <= CLOCK_TOLERANCE_S:
            fits.append((c, worst))
    return fits[0] if len(fits) == 1 else None


def nested(records):
    """``(t0, t1, path)`` of the records, a span before those it holds:
    the order ``innermost`` needs."""
    return sorted(((r[1], r[2], r[0]) for r in records),
                  key=lambda s: (s[0], -s[1]))


def innermost(spans, t):
    """Path of the innermost of ``spans`` (``nested``, of one thread, so
    they nest) that is open at ``t``; ``no_span`` if none is."""
    i = bisect.bisect_right(spans, t, key=lambda s: s[0]) - 1
    while i >= 0:
        t0, t1, path = spans[i]
        if t < t1:
            return path
        i -= 1
    return "no_span"

"""What the readers of the program's *kept* records share (PR 36).

Besides its steps' spans the program's ring (``program_ring.py``) keeps,
past any number of steps: an engine's construction (``setup/engine``
and its children), a record for every trace, lowering and backend
compile of a jitted function (``<open span's path>/jax/trace``,
``/jax/lower``, ``/jax/backend_compile``, attrs ``fun``, ``step``,
``cache``) and one for every collector pause worth a record
(``<path>/gc``, attrs ``generation``, ``collected``), and every span
such a record fell in. Its step spans (``serve/step``, ``train/step``)
carry ``gc_s`` and, every 50 ms or more, ``cpu_s`` / ``cpu_wall_s``.
All on the one clock, so a record lies inside the span it fell in.

Here: which of those a record is (``kind``), every record's *self*
time, the time no record inside it covers (``self_times``), and a run's
instants on that clock (``Run``). A program without such records (an
older one) makes every reader built on this return ``None``.
"""

import dataclasses

from benchmarks.suite import program_ring

LEDGER = {"jax/trace": "trace", "jax/lower": "lower",
          "jax/backend_compile": "compile", "gc": "gc"}
SETUP = "setup/engine"
STEPS = ("serve/step", "train/step")
# the ring outlives a run (and a test): a record is this run's if it
# closed between the process's start and this long after the window
RUN_TAIL_S = 600.0


def kind(path):
    """``trace``, ``lower``, ``compile`` or ``gc`` for a record of the
    compile ledger or the collector, whatever span it fell in; ``None``
    for a span."""
    for leaf, name in LEDGER.items():
        if path == leaf or path.endswith("/" + leaf):
            return name
    return None


@dataclasses.dataclass
class Run:
    """One run's instants on the ring's clock, seconds."""
    records: list       # the ring's records of this run, oldest close first
    t_process: float
    ramp0: float        # where set-up proper ends: the ramp's start
    w0: float           # the measured window [w0, w1)
    quiet1: float       # [w0, quiet1): the window with the profiler off
    w1: float

    def closed_in(self, lo, hi, under=None):
        return [r for r in self.records if lo <= r[2] < hi
                and (under is None or r[0].startswith(under))]


def per_step_from():
    """Close of the oldest per-step record the program's ring still
    holds, where it has dropped any (the kept records lie apart and do
    not wrap with them); ``None`` where it has dropped none."""
    from deepspeed_tpu.telemetry import spans
    ring = spans.ring
    if not ring.dropped or not ring.records:
        return None
    return min(r[2] for r in list(ring.records))


def run_of(ctx, result, per_step=False):
    """The run's ``Run``; ``None`` where the program keeps no ring or
    its ring holds no ledger record of this process: every process
    compiles something before its window. A reader of the per-step
    records (``per_step``) also gets ``None`` where they have wrapped
    past the window's start with fewer than
    ``program_ring.MIN_AFTER_WRAP`` of the window left: the guard of
    ``program_ring.view``, which looks at the ring's first record, and
    that is a kept one since the program keeps any."""
    records, _ = program_ring.ring_records()
    if not records:
        return None
    t_process = ctx.t_process
    w0 = t_process + result.setup_s
    w1 = w0 + ctx.seconds
    records = [r for r in records if t_process <= r[2] < w1 + RUN_TAIL_S]
    if not any(kind(r[0]) == "compile" for r in records):
        return None
    tr = ctx.workload.get("trace", {}) if ctx.trace else {}
    if "reserve_s" in tr:       # a training cell: the window is shorter
        quiet1 = w0 + max(1.0, ctx.seconds - tr["reserve_s"])
    else:                       # a serving cell: its last seconds traced
        quiet1 = w1 - tr.get("profile_s", 0.0)
    if per_step:
        held_from = per_step_from()
        if held_from is not None and held_from > w0 and sum(
                1 for r in records if w0 <= r[2] < w1
                and not kind(r[0])) < program_ring.MIN_AFTER_WRAP:
            return None
    ramp_s = ctx.workload["traffic"].get("ramp_s", 0.0)
    return Run(records=records, t_process=t_process, ramp0=w0 - ramp_s,
               w0=w0, quiet1=quiet1, w1=w1)


def self_times(records, lo, hi):
    """``[(record, self seconds)]`` for the records that overlap
    ``[lo, hi)``, each cut to it: a record's time less what the records
    directly inside it cover (``serve/request`` records are stays, not
    spans, and are left out). Records of one thread nest; one that
    sticks out of its parent (two clocks' readings a microsecond apart)
    is cut to the parent."""
    cut = sorted(((max(r[1], lo), min(r[2], hi), r) for r in records
                  if r[0] != program_ring.REQUEST    # a stay, not a span
                  and r[1] < hi and r[2] > lo),
                 key=lambda c: (c[0], -c[1]))
    out, stack = [], []         # stack: [t1, index into out]
    for t0, t1, rec in cut:
        while stack and stack[-1][0] <= t0:
            stack.pop()
        if stack:
            t1 = min(t1, stack[-1][0])
            out[stack[-1][1]][1] -= t1 - t0
        out.append([rec, t1 - t0])
        stack.append((t1, len(out) - 1))
    return [(rec, own) for rec, own in out]


def overlapping(records, t0, t1):
    """The ledger's and the collector's records that overlap
    ``[t0, t1]``, as one string for a log line."""
    hits = [r for r in records if kind(r[0]) and r[1] < t1 and r[2] > t0]
    if not hits:
        return "no gc or jax record overlaps it"
    return "; ".join(
        f"{r[0]} {1e3 * (r[2] - r[1]):.1f} ms {r[3]}" for r in hits[:8])

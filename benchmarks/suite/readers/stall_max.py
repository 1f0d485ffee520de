"""The longest stall of the window, by the program's own spans (PR 36;
``program_records.py``), and a log line that says where it fell.

``step`` = ``serve/step``: every span under ``serve/step`` but the
prompt's prefill (``admit/prefill``, whose length is its prompt's) has
a *self* time, its own less the spans inside it: the leaves ``expire``,
``pages``, ``sample``, ``grow``, ``inputs``, ``upload``, ``dispatch``,
``wait_tokens``, ``logits_d2h``, ``book`` whole, and the un-spanned
rest of ``serve/step``, ``admit`` and ``decode``. The metric is the
largest excess of one over its path's median in the window. A gc pause
or a compile inside a span is that span's time: the log lists every
``gc`` / ``jax/*`` record that overlaps the worst one, beside its
path, its step, the wall and the thread's CPU seconds over the stretch
that holds it (``cpu_s`` of ``cpu_wall_s``, which the program stamps on
a step every 50 ms or more: wall without CPU is a blocked or descheduled
thread; wall with CPU is Python or a native call burning it).

Most runs on the benchmark's machines hold one pause of 0.1-0.16 s in
whichever span the thread is in (the thread off the CPU), so the log
also counts the excesses of ``LONG_S`` or more: those are the stalls
somebody can mend.

``step`` = ``train/step``: the largest interval between consecutive
``train/step`` closes less the window's median interval (with steps in
flight a step's own span is the host's dispatch alone; the interval is
what the tokens a second feel). The log lays it to ``dispatch``, the
rest of ``train/step`` or the time outside the engine (the harness
waiting for a loss), whichever exceeds its own median most.

The window's part with the profiler off, as far back as the ring still
holds it (the per-step records wrap; the log says from where). ms.
``None`` where the steps carry no ``gc_s`` (an older program) or the
ring holds too few."""

import bisect

from benchmarks.suite import program_records, program_ring

PREFILL = program_ring.STEP + "/admit/prefill"
# the machine deschedules a thread for 0.1-0.16 s in most runs: a stall
# that is somebody's to mend is longer, or one of many
LONG_S = 0.2


def median(values):
    return program_ring.statistic(values, "median")


def cpu_over(steps, t0):
    """What the first of ``steps`` (records, in time's order) to close
    after ``t0`` with a CPU mark says, for a log line."""
    for _, _, t1, attrs in steps:
        if t1 >= t0 and "cpu_s" in (attrs or {}):
            return (f"the thread's CPU {1e3 * attrs['cpu_s']:.0f} ms of the "
                    f"{1e3 * attrs['cpu_wall_s']:.0f} ms up to the close "
                    f"of step {attrs.get('step')}")
    return "no CPU mark after it"


def serve(ctx, run):
    spans = [r for r in run.closed_in(run.w0, run.quiet1, program_ring.STEP)
             if not program_records.kind(r[0])
             and r[0] != program_ring.REQUEST]
    steps = sorted((r[1], r) for r in spans if r[0] == program_ring.STEP)
    if len(steps) < 2 or not any("gc_s" in (r[3] or {}) for _, r in steps):
        return None
    by_path = {}
    for rec, own in program_records.self_times(spans, run.w0 - 3600.0,
                                               run.quiet1):
        if not rec[0].startswith(PREFILL):
            by_path.setdefault(rec[0], []).append((own, rec))
    worst, n_long = None, 0
    for path, owns in by_path.items():
        mid = median([o for o, _ in owns])
        n_long += sum(o - mid >= LONG_S for o, _ in owns)
        own, rec = max(owns, key=lambda o: o[0])
        if worst is None or own - mid > worst[0]:
            worst = (own - mid, own, mid, rec)
    excess, own, mid, (path, t0, t1, _) = worst
    i = bisect.bisect_right(steps, t0, key=lambda s: s[0]) - 1
    step = steps[max(i, 0)][1]
    attrs = step[3] or {}
    ctx.log(f"longest stall of the window: {path} {1e3 * own:.2f} ms of "
            f"its own (span {1e3 * (t1 - t0):.2f} ms; the path's median "
            f"{1e3 * mid:.3f} ms over {len(by_path[path])}), step "
            f"{attrs.get('step')}, {t0 - run.w0:.2f} s into the window "
            f"({n_long} of {LONG_S:g} s or more in it); that step: wall {1e3 * (step[2] - step[1]):.2f} ms, gc_s "
            f"{1e3 * attrs.get('gc_s', float('nan')):.2f} ms; "
            f"{cpu_over([r for _, r in steps], t1)}; "
            + program_records.overlapping(run.records, t0, t1)
            + f"; the ring holds the window from "
            f"{max(0.0, min(r[1] for r in spans) - run.w0):.1f} s on")
    return 1e3 * excess


def train(ctx, run):
    path = "train/step"
    steps = [r for r in run.closed_in(run.w0, run.quiet1)
             if r[0] == path and "gc_s" in (r[3] or {})]
    if len(steps) < 3:
        return None
    inside = sorted((r[1], r[2] - r[1]) for r in run.closed_in(
        run.w0, run.quiet1) if r[0] == path + "/dispatch")
    parts = []      # per interval: whole, dispatch, rest of step, outside
    for before, (_, t0, t1, _) in zip(steps, steps[1:]):
        i = bisect.bisect_left(inside, (t0, 0.0))
        held = inside[i][1] if i < len(inside) and inside[i][0] < t1 \
            else 0.0
        parts.append((t1 - before[2], held, (t1 - t0) - held,
                      t0 - before[2]))
    mids = [median([p[k] for p in parts]) for k in range(4)]
    n = max(range(len(parts)), key=lambda j: parts[j][0])
    whole, *laid = parts[n]
    names = ("train/step/dispatch", "the rest of train/step",
             "outside the engine")
    k = max(range(3), key=lambda j: laid[j] - mids[j + 1])
    _, t0, t1, attrs = steps[n + 1]
    ctx.log(f"longest interval between train/step closes: "
            f"{1e3 * whole:.2f} ms (median {1e3 * mids[0]:.2f} over "
            f"{len(parts)}), step {attrs.get('step')}, "
            f"{steps[n][2] - run.w0:.2f} s into the window "
            f"({sum(p[0] - mids[0] >= LONG_S for p in parts)} of "
            f"{LONG_S:g} s or more over the median in it), laid to "
            f"{names[k]} ({1e3 * laid[k]:.2f} ms, median "
            f"{1e3 * mids[k + 1]:.2f}); that step: wall "
            f"{1e3 * (t1 - t0):.2f} ms, gc_s {1e3 * attrs['gc_s']:.2f} ms; "
            f"{cpu_over(steps, t1)}; "
            + program_records.overlapping(run.records, steps[n][2], t1))
    return 1e3 * (whole - mids[0])


def read(ctx, result, step):
    run = program_records.run_of(ctx, result, per_step=True)
    if run is None:
        return None
    if step == program_ring.STEP:
        return serve(ctx, run)
    if step == "train/step":
        return train(ctx, run)
    raise ValueError(f"unknown step {step!r}")

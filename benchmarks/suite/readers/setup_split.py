"""One part of ``setup_s``, by the program's own records (PR 36; see
``program_records.py``). Every instant of set-up proper, from the
process's start to the start of the ramp (a serving cell's
``traffic.ramp_s`` is the harness's constant and is taken out first; a
training cell has none), goes to the innermost record open in it:

- ``trace``, ``lower``, ``compile``, ``gc``: the compile ledger's
  ``*/jax/trace``, ``*/jax/lower``, ``*/jax/backend_compile`` (cold the
  compile, warm the cache's read) and the collector's ``*/gc``, wherever
  they fell (a trace inside a trace counts once, as the inner one);
- ``engine``: ``setup/engine`` (an engine's, a scheduler's
  construction) less those inside it;
- ``warmup``: the ``serve/step`` / ``train/step`` spans before the
  ramp, less those inside them: the warm-up's execution;
- ``rest``: what no record covers: imports, the weights' execution, the
  reference's check before a training window, the harness's own code.

The seven sum to ``setup_s`` less the ramp. Seconds. The whole split,
the functions that cost most and the collector's tallies go to the
progress log with the first part. ``None`` on a program without the
ledger."""

from benchmarks.suite import program_records

PARTS = ("trace", "lower", "compile", "gc", "engine", "warmup", "rest")


def part_of(path):
    kind = program_records.kind(path)
    if kind:
        return kind
    if path.startswith(program_records.SETUP):
        return "engine"
    if path.startswith(program_records.STEPS):
        return "warmup"
    return "rest"


def split(run):
    """``({part: seconds}, {part: {fun: seconds}})`` of
    ``[t_process, ramp0)``."""
    parts = dict.fromkeys(PARTS, 0.0)
    funs = {"trace": {}, "lower": {}, "compile": {}}
    for rec, own in program_records.self_times(
            run.records, run.t_process, run.ramp0):
        part = part_of(rec[0])
        parts[part] += own
        if part in funs:
            fun = (rec[3] or {}).get("fun")
            funs[part][fun] = funs[part].get(fun, 0.0) + own
    # what no record covers, and the spans that are nobody's part
    parts["rest"] = (run.ramp0 - run.t_process) - sum(
        v for k, v in parts.items() if k != "rest")
    return parts, funs


def collector_tallies():
    """The collector's own totals (whole process), for the log."""
    try:
        from deepspeed_tpu.telemetry import spans
        tallies = spans.collector.by_generation
    except (ImportError, AttributeError):
        return "no collector"
    return ", ".join(      # generation 0 is counted, not timed
        f"generation {g}: {n}" + (f" in {s:.3f} s" if s else "")
        for g, (n, s) in enumerate(tallies))


def read(ctx, result, part):
    if part not in PARTS:
        raise ValueError(f"unknown part {part!r}")
    run = program_records.run_of(ctx, result)
    if run is None:
        return None
    parts, funs = split(run)
    if part == PARTS[0]:        # the log goes with the first part
        recorded = sum(r[2] - r[1] for r in run.records
                       if program_records.kind(r[0]) == "gc")
        top = "; ".join(
            f"{p}: " + ", ".join(
                f"{fun} {s:.2f}" for fun, s in sorted(
                    funs[p].items(), key=lambda kv: -kv[1])[:4])
            for p in funs)
        ctx.log(
            f"set-up by the program's records, s (setup_s "
            f"{result.setup_s:.3f} less a ramp of "
            f"{run.w0 - run.ramp0:g}): " + ", ".join(
                f"{p} {parts[p]:.3f}" for p in PARTS)
            + f" | most by function: {top} | collector, whole process: "
            f"{collector_tallies()}; {recorded:.3f} s of it in records, "
            f"the rest too short for one")
    return parts[part]

"""Mean seconds of Python's collector a step, by the ``gc_s`` the
program's step spans carry (PR 36; ``program_records.py``): over the
window's ``serve/step`` spans that decoded a live row (``step`` =
``serve/step``; an idle tick of the open loop is no step anyone waits
for), or over its ``train/step`` spans (``step`` = ``train/step``).
Every collection of generation 1 or 2 counts, the many too short for a
record of their own too (generation 0 the program counts and does not
time). ms. ``None`` where no step of the window carries ``gc_s``."""

from benchmarks.suite import program_records


def read(ctx, result, step):
    run = program_records.run_of(ctx, result, per_step=True)
    if run is None:
        return None
    steps = [r[3] for r in run.closed_in(run.w0, run.quiet1)
             if r[0] == step and r[3] and "gc_s" in r[3]]
    if step == "serve/step":
        steps = [a for a in steps if a.get("batch")]
    if not steps:
        return None
    return 1e3 * sum(a["gc_s"] for a in steps) / len(steps)

"""How many backend compiles (reads of the persistent cache included)
closed inside the measured window, by the program's compile ledger
(PR 36; ``program_records.py``): the ``*/jax/backend_compile`` records
that closed in ``[w0, w1)`` of a serving cell (``step`` =
``serve/step``), or from ``w0`` to the close of the run's last step of
a training cell (``step`` = ``train/step``: its blocking and profiled
steps follow the window and count, as the harness's own counter has
them). Any is a shape that was not warmed up, and the log names each:
function, the span it fell in, the step, the seconds. The twin is the
harness's ``compiles_in_window`` / ``compiles_in_run``, a number with
no name. ``None`` on a program without the ledger."""

from benchmarks.suite import program_records


def read(ctx, result, step):
    run = program_records.run_of(ctx, result)
    if run is None:
        return None
    end = run.w1
    if step == "train/step":
        closes = [r[2] for r in run.records
                  if r[0] == step and r[2] >= run.w0]
        end = max(closes) + 1e-9 if closes else run.w0
    elif step != "serve/step":
        raise ValueError(f"unknown step {step!r}")
    found = [r for r in run.closed_in(run.w0, end)
             if program_records.kind(r[0]) == "compile"]
    for path, t0, t1, attrs in found:
        ctx.log(f"a compile inside the window: {attrs.get('fun')} under "
                f"{path}, step {attrs.get('step')}, {t1 - t0:.3f} s "
                f"(cache {attrs.get('cache')}), {t1 - run.w0:.2f} s "
                f"into the window")
    return len(found)

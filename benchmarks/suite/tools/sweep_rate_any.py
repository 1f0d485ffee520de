#!/usr/bin/env python3
"""``sweep_rate.py`` for a serving cell of any driver: the cell's traffic
at several offered rates, one after the other on one warm engine, through
the ``build``, ``warm_up`` and ``measure`` of the driver the workload file
names. Per rate, one JSON line on stdout and in
``chiprun_out/sweep_<cell>.jsonl``. "Sustains" = no request failed and
the queue is empty at the end of the window. Give ``--seconds`` the
benchmark's ``run_seconds``: the window's length is part of the trace.
``--seeds`` gives each window a seed of its own (as many as rates, or
one for all): the trace's tokens redrawn (and, where the driver leaves
the order to the seed, its sizes reordered) on the weights of
``--seed``, which is how a tail's spread over seeds is read without a
process a seed. ``--prefill-chunk`` overrides the file's.

    python3 benchmarks/suite/tools/sweep_rate_any.py --workload <cell> \
        --seed 1 --seconds 51 --rates 3,4,5
"""

import argparse
import copy
import importlib
import json
import os
import sys

SUITE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(SUITE))
sys.path.insert(0, ROOT)

KEEP = ("finished_measured", "steps_in_window", "mean_occupancy",
        "mean_pool_fill",
        "occupancy_halves", "queue_depth_first_last", "max_queue_depth",
        "generator_late_ms", "ttft_ms", "itl_ms")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default=None)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    seeds = [int(s) for s in (args.seeds or str(args.seed)).split(",")]
    if len(seeds) == 1:
        seeds *= len(rates)
    if len(seeds) != len(rates):
        ap.error("--seeds takes one seed, or one a rate")

    from benchmarks.suite import run
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)

    code, ctx, _ = run.prepare(args.workload, args.seed, args.seconds, 0)
    if code:
        return code
    driver = importlib.import_module(
        "benchmarks.suite.drivers." + ctx.workload["driver"])
    if args.prefill_chunk:
        ctx.workload["inference"]["prefill_chunk"] = args.prefill_chunk
    engine, sched = driver.build(ctx)
    driver.warm_up(ctx, engine, sched)
    base = ctx.workload
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"sweep_{args.workload}.jsonl"),
              "w") as f:
        for rate, seed in zip(rates, seeds):
            ctx.workload = copy.deepcopy(base)
            ctx.workload["traffic"]["rate_per_s"] = rate
            ctx.seed = seed
            # stale cache contents do no harm: a page is written, and a
            # slot's state started from zero, by prefill before it is
            # read; a new scheduler frees them all
            sched = ContinuousBatchingScheduler(engine)
            res = driver.measure(ctx, engine, sched)
            line = {"rate_per_s": rate, "seed": seed,
                    "prefill_chunk": engine.prefill_chunk,
                    "correct": res.correct,
                    "attempted": res.attempted, "failed": res.failed,
                    **res.end_to_end,
                    **{k: res.detail[k] for k in KEEP},
                    "checks": res.detail["checks"]}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

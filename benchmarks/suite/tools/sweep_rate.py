#!/usr/bin/env python3
"""Find the knee of a serving cell once, on the chip: the cell's traffic
at several offered rates, one after the other on one warm engine. Per
rate, one JSON line on stdout and in ``chiprun_out/sweep_<cell>.jsonl``.
"Sustains" = no request failed and the queue is empty at the end of the
window. Give ``--seconds`` the benchmark's ``run_seconds``: the window's
length is part of the cell's trace.

    python3 benchmarks/suite/tools/sweep_rate.py --workload <cell> \
        --seed 1 --seconds 51 --rates 2.0,2.4
"""

import argparse
import copy
import json
import os
import sys

SUITE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(SUITE))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from benchmarks.suite import run
    from benchmarks.suite.drivers import serve
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)

    code, ctx, _ = run.prepare(args.workload, args.seed, args.seconds, 0)
    if code:
        return code
    engine, sched = serve.build(ctx)
    serve.warm_up(ctx, engine, sched)
    base = ctx.workload
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"sweep_{args.workload}.jsonl"),
              "w") as f:
        for rate in (float(r) for r in args.rates.split(",")):
            ctx.workload = copy.deepcopy(base)
            ctx.workload["traffic"]["rate_per_s"] = rate
            # stale cache contents do no harm: a page is written by
            # prefill before it is read; a new scheduler frees them all
            sched = ContinuousBatchingScheduler(engine)
            res = serve.measure(ctx, engine, sched)
            d = res.detail
            line = {"rate_per_s": rate,
                    "correct": res.correct, "attempted": res.attempted,
                    "failed": res.failed, **res.end_to_end,
                    **{k: d[k] for k in (
                        "finished_measured", "mean_occupancy",
                        "mean_pool_fill",
                        "occupancy_halves", "queue_depth_first_last",
                        "max_queue_depth", "generator_late_ms",
                        "ttft_ms", "itl_ms")}}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

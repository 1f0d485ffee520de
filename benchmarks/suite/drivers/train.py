"""Driver of training cells: ``deepspeed_tpu.initialize`` ->
``engine.train_batch`` on seeded batches, as a training job runs it.

Workload file keys: ``traffic`` (``generator`` and its parameters),
``engine`` (``ds_config``, ``mesh``, ``remat``), ``in_flight`` (how many
steps the host may run ahead), ``correctness`` and ``trace``.
"""

import collections
import importlib
import math

import numpy as np

from benchmarks.suite import flops, harness
from benchmarks.suite.harness import clock
from benchmarks.suite.reference import gpt2_ref


def build_engine(ctx):
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import make_gpt2_loss_fn
    from deepspeed_tpu.parallel.mesh import build_mesh

    eng = ctx.workload["engine"]
    model = harness.gpt2_model(ctx.config, "train",
                               remat=bool(eng.get("remat", False)))
    mesh = build_mesh(eng.get("mesh") or {}, devices=ctx.devices)
    params = harness.seeded_params(model, ctx.seed, mesh)
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=dict(eng["ds_config"]), loss_fn=make_gpt2_loss_fn(model),
        params=params, mesh=mesh)
    return engine


def check_against_reference(ctx, engine, batch):
    """Before the first update: the engine's loss on two sequences of the
    first batch against the plain float32 reference on the same weights.
    ``eval_batch`` wants the cell's global batch, so the two sequences
    are repeated to fill it (every row has the same number of labels, so
    the mean is the two sequences' mean)."""
    import jax

    ids = batch["input_ids"]
    two = ids[:2]
    tiled = np.tile(two, (ids.shape[0] // 2, 1))
    got = float(engine.eval_batch({"input_ids": tiled}))
    ref_fn = jax.jit(lambda p, x: gpt2_ref.loss(
        p, x, ctx.config["n_head"], ctx.config["layer_norm_epsilon"]))
    want = float(ref_fn(engine.params, two))
    tol = ctx.workload["correctness"]["loss_rtol"] * abs(want)
    return {"engine_loss": got, "reference_loss": want,
            "abs_diff": abs(got - want), "tolerance": tol,
            "ok": bool(math.isfinite(got) and abs(got - want) <= tol)}


def pipelined_steps(ctx, engine, batches, in_flight, until=None, steps=None):
    """``train_batch`` on fresh batches with at most ``in_flight`` steps
    ahead of the device (the loss of step i - in_flight is read before
    step i is dispatched), for ``until`` seconds or ``steps`` steps. The
    clock stops when the last step's loss is ready. Returns (steps,
    seconds, losses)."""
    rec = ctx.recorder
    pending, losses, n = collections.deque(), [], 0
    t0 = clock()
    while (steps is None or n < steps) and \
            (until is None or clock() - t0 < until):
        if len(pending) == in_flight:
            with rec.span("wait_loss"):
                losses.append(float(pending.popleft()))
        with rec.span("host_batch"):
            batch = batches.next()
        with rec.span("train_batch"):
            pending.append(engine.train_batch(batch))
        n += 1
    with rec.span("wait_loss"):
        losses.extend(float(x) for x in pending)
    return n, clock() - t0, losses


def run(ctx):
    from deepspeed_tpu.analysis import compiled_cache_size

    wl, rec = ctx.workload, ctx.recorder
    chips = len(ctx.devices)
    gen = importlib.import_module(
        "benchmarks.suite.traffic." + wl["traffic"]["generator"])
    batches = gen.make(wl["traffic"], ctx.seed,
                       vocab_size=ctx.config["vocab_size"])
    in_flight = int(wl["in_flight"])

    ctx.log("building the engine")
    engine = build_engine(ctx)
    first = batches.next()
    ctx.log("correctness against the reference (before any update)")
    ref = check_against_reference(ctx, engine, first)
    ctx.log(f"reference: {ref}")
    ctx.log("warm-up")
    warm = [float(engine.train_batch(first))]
    warm += pipelined_steps(ctx, engine, batches, in_flight,
                            steps=int(wl["warmup_steps"]))[2]
    entries = compiled_cache_size(engine)

    tr = wl["trace"]
    window_s = ctx.seconds
    if ctx.trace:
        # the blocking and the profiled steps come out of the window
        window_s = max(1.0, ctx.seconds - tr["reserve_s"])
    compiles_before = ctx.compiles.n
    setup_s = clock() - ctx.t_process
    ctx.log(f"window of {window_s:.1f} s (set-up took {setup_s:.1f} s)")
    n, secs, losses = pipelined_steps(ctx, engine, batches, in_flight,
                                      until=window_s)
    rate = n * batches.tokens_per_batch / secs / chips

    trace = None
    if ctx.trace:
        rec.collect = True
        for _ in range(int(tr["blocking_steps"])):
            batch = batches.next()
            with rec.span("train_step"):
                losses.append(float(engine.train_batch(batch)))
        prof = harness.Profiler(ctx)
        prof.start()
        try:
            _, _, more = pipelined_steps(ctx, engine, batches, in_flight,
                                         steps=int(tr["profiled_steps"]))
        finally:
            trace = prof.stop()
        losses += more
    compiled_in_window = ctx.compiles.n - compiles_before
    finite = [bool(np.isfinite(x)) for x in losses]
    checks = {
        "reference": ref,
        "losses_finite": all(finite),
        "compiles_in_window": compiled_in_window,
        "train_step_jit_entries": [entries, compiled_cache_size(engine)],
    }
    correct = bool(ref["ok"] and all(finite) and compiled_in_window == 0
                   and compiled_cache_size(engine) == entries == 1)
    flops_per_token = flops.train_flops_per_token(ctx.config,
                                                  batches.seq)
    return harness.Result(
        correct=correct, attempted=len(losses),
        failed=finite.count(False), setup_s=setup_s,
        end_to_end={"train_tokens_per_s_per_chip": rate},
        facts={"tokens_per_s_per_chip": rate,
               "flops_per_token": flops_per_token,
               "profiled_steps": int(tr["profiled_steps"]) if ctx.trace
               else 0},
        detail={"steps_in_window": n, "window_seconds": secs,
                "tokens_per_step": batches.tokens_per_batch,
                "warmup_losses": warm[:3], "last_loss": losses[-1],
                "checks": checks},
        trace=trace)

"""Driver of the OLMoE training cells: ``deepspeed_tpu.initialize`` ->
``engine.train_batch`` on seeded batches, as ``drivers/train.py`` runs
the GPT-2 cells: build, check against the reference, warm up, window,
optional traced steps.

Workload file keys: ``traffic``, ``engine`` (``ds_config``),
``in_flight``, ``warmup_steps``, ``correctness`` (tolerances with their
reasons) and ``trace`` (``scope_marker``: the prefix of the program's
``jax.named_scope`` names that a traced run maps ops to).
"""

import dataclasses
import importlib
import math

import numpy as np

from benchmarks.suite import flops_olmoe, harness
from benchmarks.suite.drivers.train import pipelined_steps
from benchmarks.suite.harness import clock
from benchmarks.suite.readers import scope_time
from benchmarks.suite.reference import olmoe_ref


def olmoe_model(config):
    """The program's model for a configuration file's ``train`` group:
    its ``OlmoeConfig`` holds the published keys under their published
    names, so they pass as they are; the two loss coefficients and the
    initialiser's range come from ``assumed``."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.olmoe import OlmoeConfig, OlmoeLM

    g, assumed = config["train"], config["assumed"]
    fields = {f.name for f in dataclasses.fields(OlmoeConfig)}
    published = {k: v for k, v in config.items() if k in fields}
    published.update({k: v for k, v in assumed.items() if k in fields})
    published.update(
        num_hidden_layers=config["n_layer"],        # the layers as run
        rope_theta=float(config["rope_theta"]),
        dtype=getattr(jnp, g["compute_dtype"]),
        param_dtype=getattr(jnp, g["param_dtype"]),
        use_flash_attention=bool(g.get("use_flash_attention", False)))
    return OlmoeLM(OlmoeConfig(**published))


def build_engine(ctx, model):
    import deepspeed_tpu
    from deepspeed_tpu.models.olmoe import make_olmoe_loss_fn
    from deepspeed_tpu.parallel.mesh import build_mesh

    eng = ctx.workload["engine"]
    mesh = build_mesh(eng.get("mesh") or {}, devices=ctx.devices)
    params = harness.seeded_params(model, ctx.seed, mesh)
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=dict(eng["ds_config"]), loss_fn=make_olmoe_loss_fn(model),
        params=params, mesh=mesh)
    return engine


def compare(got, want, tol):
    """The checks behind ``correct``. ``got``: the program's ``loss``
    (the engine's ``eval_batch``), ``ce``, ``lb``, ``z``,
    ``logit_max_diff`` (over the tokens whose experts are the
    reference's, ``tokens_with_same_experts`` of all),
    ``choice_differs`` (share of token-expert pairs whose expert the
    reference did not choose) and, from the feed-forward part held to
    the reference on its own input, ``router_prob_diff``,
    ``own_choice_differs``, ``experts_out_diff`` and
    ``tokens_with_same_experts_own`` (`program_and_reference`);
    ``want``: the reference's ``loss``, ``ce``, ``lb``, ``z`` and
    ``logit_scale`` (its largest |logit|); ``tol``: the workload file's
    ``correctness`` block. Each term within its ``*_rtol`` of the
    reference's, the logits within ``logit_rtol`` of the scale, the
    share under ``choice_differs_max``, the router's probabilities
    within ``router_prob_rtol`` of the largest and the experts' output
    within ``experts_out_rtol`` of its largest."""
    out = {}
    for key in ("loss", "ce", "lb", "z"):
        allowed = tol[key + "_rtol"] * abs(want[key])
        diff = abs(got[key] - want[key])
        out[key] = {"program": got[key], "reference": want[key],
                    "abs_diff": diff, "tolerance": allowed,
                    "ok": bool(math.isfinite(got[key]) and diff <= allowed)}
    allowed = tol["logit_rtol"] * want["logit_scale"]
    out["logits"] = {"max_abs_diff": got["logit_max_diff"],
                     "on_share_of_tokens": got["tokens_with_same_experts"],
                     "tolerance": allowed,
                     "ok": bool(got["logit_max_diff"] <= allowed)}
    out["expert_choice"] = {
        "share_differs": got["choice_differs"],
        "bound": tol["choice_differs_max"],
        "ok": bool(got["choice_differs"] <= tol["choice_differs_max"])}
    out["router"] = {
        "max_prob_diff_over_max_prob": got["router_prob_diff"],
        "own_choice_differs": got["own_choice_differs"],
        "tolerance": tol["router_prob_rtol"],
        "ok": bool(got["router_prob_diff"] <= tol["router_prob_rtol"])}
    out["experts"] = {
        "max_abs_diff_over_max_abs": got["experts_out_diff"],
        "on_share_of_tokens": got["tokens_with_same_experts_own"],
        "tolerance": tol["experts_out_rtol"],
        "ok": bool(got["experts_out_diff"] <= tol["experts_out_rtol"])}
    out["ok"] = all(v["ok"] for v in out.values())
    return out


def own_input_checks(seen, stats, params, cast, top_k):
    """Every layer's feed-forward part against the reference's **on the
    program's own router input** (``seen``: the intermediates flax
    captured: each layer's ``post_attn_norm`` output and ``experts``
    output), so that nothing upstream is in the difference and each is
    read at the layer's own scale: the chosen probabilities against a
    float32 router's (over the largest probability), and the experts'
    output before the residual on the tokens whose experts agree (over
    its largest entry). The reference takes the float32 ``params``,
    but the router's weights as the program has them (``cast``, the
    step's copy in the compute dtype): what the router check holds to
    float32 is the product and the softmax. The worst layer of each."""
    import jax.numpy as jnp

    layers = []
    for i in range(stats["chosen"].shape[0]):
        layer = seen[f"layers_{i}"]
        n = layer["post_attn_norm"]["__call__"][0]
        y = layer["experts"]["__call__"][0][0]
        n, y = (a.reshape(-1, a.shape[-1]) for a in (n, y))
        ref_y, ref_p, ref_mask = olmoe_ref.experts(n, dict(
            params[f"layers_{i}"]["experts"],
            router=cast[f"layers_{i}"]["experts"]["router"]), top_k)
        chosen = stats["chosen"][i]
        p_diff = jnp.abs(stats["weights"][i] - jnp.take_along_axis(
            ref_p, chosen, axis=-1)).max() / ref_p.max()
        hit = jnp.take_along_axis(ref_mask, chosen, axis=-1) > 0
        same = hit.all(-1)
        y_diff = jnp.where(same[:, None], jnp.abs(
            y.astype(jnp.float32) - ref_y), 0.0).max() / jnp.abs(ref_y).max()
        layers.append((p_diff, 1.0 - hit.mean(), y_diff, same.mean()))
    p_diff, differs, y_diff, same = (jnp.stack(a) for a in zip(*layers))
    return {"router_prob_diff": p_diff.max(),
            "own_choice_differs": differs.max(),
            "experts_out_diff": y_diff.max(),
            "tokens_with_same_experts_own": same.min()}


def program_and_reference(model, params, ids, config):
    """One ``[1, T]`` sequence through both: the program's loss
    function and model on ``params`` in the compute dtype, and the plain
    float32 reference on the same ``params``, whole and (each layer's
    feed-forward part) on the program's own input. Returns ``(got,
    want)`` as ``compare`` takes them, all floats."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.olmoe import make_olmoe_loss_fn

    assumed = config["assumed"]
    loss_fn = make_olmoe_loss_fn(model)
    dtype = model.config.dtype

    @jax.jit
    def both(params, x):
        cast = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
        loss, scalars = loss_fn(cast, {"input_ids": x})
        (logits, stats), state = model.apply(
            {"params": cast}, x, capture_intermediates=lambda mdl, _:
            mdl.name in ("post_attn_norm", "experts"))
        ref = olmoe_ref.loss_terms(
            params, x, config, assumed["router_aux_loss_coef"],
            assumed["router_z_loss_coef"])
        ref_logits, ref_mask = ref["logits"], ref["mask"]
        # [layers, N, top_k]: is each chosen expert among the reference's?
        hit = jnp.take_along_axis(ref_mask, stats["chosen"], axis=-1)
        # a token that went to another expert than the reference's has
        # other logits by right: the logits are held to the reference
        # on the tokens whose experts all agree
        same = (hit > 0).all(axis=(0, 2)).reshape(x.shape)[..., None]
        diff = jnp.abs(logits.astype(jnp.float32) - ref_logits)
        got = {"loss": loss, "ce": scalars["moe_ce_loss"],
               "lb": scalars["moe_lb_loss"], "z": scalars["moe_z_loss"],
               "logit_max_diff": jnp.where(same, diff, 0.0).max(),
               "choice_differs": 1.0 - hit.mean(),
               "tokens_with_same_experts": same.mean()}
        got.update(own_input_checks(
            state["intermediates"], stats, params, cast,
            config["num_experts_per_tok"]))
        want = {k: ref[k] for k in ("loss", "ce", "lb", "z")}
        want["logit_scale"] = jnp.abs(ref_logits).max()
        return got, want

    return jax.tree_util.tree_map(float, both(params, jnp.asarray(ids)))


def check_against_reference(ctx, engine, model, batch):
    """Before the first update, on one sequence of the first batch. The
    total is the engine's ``eval_batch`` (which wants the cell's global
    batch, so the sequence is repeated to fill it: every row is the
    same, so every mean is the one sequence's); everything else is
    ``program_and_reference``'s."""
    ids = batch["input_ids"]
    got, want = program_and_reference(model, engine.params, ids[:1],
                                      ctx.config)
    got["loss"] = float(engine.eval_batch(
        {"input_ids": np.tile(ids[:1], (ids.shape[0], 1))}))
    return compare(got, want, ctx.workload["correctness"])


def load_counters(engine, mean_load):
    """The last step's counters, from the program's own
    ``step_metrics``: (largest expert load of any layer over
    ``mean_load``, the scalars as floats). (None, {}) from a program
    that hands none out."""
    scalars = getattr(engine, "step_metrics", {}).get("loss_scalars")
    if not scalars:
        return None, {}
    scalars = {k: float(v) for k, v in scalars.items()}
    return scalars["moe_tokens_per_expert_max"] / mean_load, scalars


def run(ctx):
    from deepspeed_tpu.analysis import audit_engine, compiled_cache_size

    wl, rec = ctx.workload, ctx.recorder
    chips = len(ctx.devices)
    gen = importlib.import_module(
        "benchmarks.suite.traffic." + wl["traffic"]["generator"])
    batches = gen.make(wl["traffic"], ctx.seed,
                       vocab_size=ctx.config["vocab_size"])
    in_flight = int(wl["in_flight"])

    ctx.log("building the engine")
    model = olmoe_model(ctx.config)
    engine = build_engine(ctx, model)
    first = batches.next()
    ctx.log("correctness against the reference (before any update)")
    ref = check_against_reference(ctx, engine, model, first)
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

    trace, op_scopes = None, None
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
    entries_after = compiled_cache_size(engine)
    load, counters = load_counters(
        engine, batches.tokens_per_batch *
        ctx.config["num_experts_per_tok"] / ctx.config["num_experts"])
    if ctx.trace:
        # which phase each op of the compiled step belongs to: the
        # program's own audit lowers the live step again (after every
        # count above; it runs no step)
        ctx.log("mapping the step's ops to the program's named scopes")
        op_scopes = scope_time.scopes_of(
            audit_engine(engine, first, rules=()).hlo_text,
            tr["scope_marker"])
    finite = [bool(np.isfinite(x)) for x in losses]
    dropped = counters.get("moe_dropped_tokens")
    checks = {
        "reference": ref,
        "losses_finite": all(finite),
        "compiles_in_window": compiled_in_window,
        "train_step_jit_entries": [entries, entries_after],
        "dropped_tokens": dropped,
    }
    correct = bool(ref["ok"] and all(finite) and compiled_in_window == 0
                   and entries_after == entries == 1
                   and dropped in (None, 0.0))
    flops_per_token = flops_olmoe.train_flops_per_token(ctx.config,
                                                        batches.seq)
    return harness.Result(
        correct=correct, attempted=len(losses),
        failed=finite.count(False), setup_s=setup_s,
        end_to_end={"train_tokens_per_s_per_chip": rate},
        facts={"tokens_per_s_per_chip": rate,
               "flops_per_token": flops_per_token,
               "profiled_steps": int(tr["profiled_steps"]) if ctx.trace
               else 0,
               "op_scopes": op_scopes,
               "moe_load_max_over_mean": load},
        detail={"steps_in_window": n, "window_seconds": secs,
                "tokens_per_step": batches.tokens_per_batch,
                "warmup_losses": warm[:3], "last_loss": losses[-1],
                "last_step_counters": counters,
                "ops_in_named_scopes": len(op_scopes or {}),
                "checks": checks},
        trace=trace)

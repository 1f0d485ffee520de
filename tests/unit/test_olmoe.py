"""OLMoE (`models/olmoe.py`, `moe/dropless.py`) against its plain
reference (`benchmarks/suite/reference/olmoe_ref.py`) on seeded
weights, at a toy size on the CPU."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.suite.drivers import train_olmoe
from benchmarks.suite.reference import olmoe_ref
from deepspeed_tpu.models import olmoe
from deepspeed_tpu.moe import dropless

LB_COEF, Z_COEF = 0.01, 0.001


def ref_cfg(cfg):
    return {"rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "num_attention_heads": cfg.num_attention_heads,
            "num_experts_per_tok": cfg.num_experts_per_tok}


def build(dtype, seed=0, **kw):
    cfg = olmoe.olmoe_tiny(dtype=dtype, **kw)
    model = olmoe.OlmoeLM(cfg)
    params = olmoe.init_olmoe_params(model, jax.random.PRNGKey(seed))
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 32), 0,
                             cfg.vocab_size)
    return cfg, model, params, ids


def test_tiny_preset_has_two_layers_eight_experts_top_two():
    cfg, _, params, _ = build(jnp.float32)
    assert (cfg.num_hidden_layers, cfg.num_experts,
            cfg.num_experts_per_tok) == (2, 8, 2)
    assert params["layers_1"]["experts"]["w_gate"].shape == (8, 64, 32)
    assert params["lm_head"].shape == (64, 256)      # untied
    full = olmoe.olmoe_1b_7b(n_layer=1)
    assert (full.hidden_size, full.num_experts, full.intermediate_size,
            full.num_hidden_layers) == (2048, 64, 1024, 1)


# float32: the two compute the same mathematics in another order (the
# program sorts pairs by expert and sums 2 expert rows a token; the
# reference applies all 8 experts to every token), so they differ by
# float32 rounding alone. bf16: every product's inputs are rounded to
# 2^-9; 3e-2 of the largest |logit| / |gradient| is ten times that
# rounding, amplified by the few layers between a weight and the loss,
# and a wrong formula is off by order one (`test_check_*` below).
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_logits_loss_terms_and_every_gradient_against_reference(dtype, tol):
    cfg, model, params, ids = build(dtype)
    loss_fn = olmoe.make_olmoe_loss_fn(model)

    def cast_loss(p):
        return loss_fn(jax.tree_util.tree_map(
            lambda x: x.astype(dtype), p), {"input_ids": ids})

    # each side one program: op by op, the two backward passes are some
    # hundreds of compiles
    (loss, scalars), grads = jax.jit(jax.value_and_grad(
        cast_loss, has_aux=True))(params)
    want = jax.jit(lambda p: olmoe_ref.loss_terms(
        p, ids, ref_cfg(cfg), LB_COEF, Z_COEF))(params)
    want_grads = jax.jit(jax.grad(lambda p: olmoe_ref.loss(
        p, ids, ref_cfg(cfg), LB_COEF, Z_COEF)))(params)
    logits, _ = jax.jit(lambda p: model.apply({"params": p}, ids))(
        jax.tree_util.tree_map(lambda x: x.astype(dtype), params))
    want_logits, _, _ = jax.jit(lambda p: olmoe_ref.forward(
        p, ids, ref_cfg(cfg)))(params)

    scale = float(jnp.abs(want_logits).max())
    assert float(jnp.abs(logits - want_logits).max()) <= tol * scale
    # the terms are means over 64 tokens and more, which average the
    # rounding: a tenth of the elementwise tolerance
    for got, key in ((loss, "loss"), (scalars["moe_ce_loss"], "ce"),
                     (scalars["moe_lb_loss"], "lb"),
                     (scalars["moe_z_loss"], "z")):
        assert abs(float(got) - float(want[key])) <= \
            0.1 * tol * abs(float(want[key])), key
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == len(flat_want) == 27
    for (path, got), ref in zip(flat, flat_want):
        err = float(jnp.abs(got - ref).max())
        assert err <= tol * float(jnp.abs(ref).max()), \
            (jax.tree_util.keystr(path), err)


def per_token_loop(x, router, w_gate, w_up, w_down, top_k):
    """The routed feed-forward one token at a time, in numpy float64."""
    x, router, w_gate, w_up, w_down = (
        np.asarray(a, np.float64) for a in (x, router, w_gate, w_up, w_down))
    y = np.zeros_like(x)
    counts = np.zeros(router.shape[1], int)
    for t in range(x.shape[0]):
        logits = x[t] @ router
        p = np.exp(logits - logits.max())
        p /= p.sum()
        for e in np.argsort(-p, kind="stable")[:top_k]:
            g = x[t] @ w_gate[e]
            h = g / (1.0 + np.exp(-g)) * (x[t] @ w_up[e])
            y[t] += p[e] * (h @ w_down[e])
            counts[e] += 1
    return y, counts


def test_dropless_routing_keeps_every_token_under_a_skewed_router():
    """One expert takes nearly every token, one takes none: a capacity
    of 1.25 x the mean would drop most pairs; here none is dropped and
    every token's result equals the per-token loop's."""
    n, m, i, e, k = 96, 32, 16, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (n, m), jnp.float32)
    router = 0.3 * jax.random.normal(ks[1], (m, e), jnp.float32)
    # expert 0 is everybody's first choice, expert 7 nobody's choice:
    # x's first feature is made large and positive
    x = x.at[:, 0].set(4.0)
    router = router.at[0, 0].set(5.0).at[0, 7].set(-5.0)
    w_gate = 0.3 * jax.random.normal(ks[2], (e, m, i), jnp.float32)
    w_up = 0.3 * jax.random.normal(ks[3], (e, m, i), jnp.float32)
    w_down = 0.3 * jax.random.normal(ks[4], (e, i, m), jnp.float32)
    y, stats = jax.jit(dropless.dropless_moe, static_argnums=5)(
        x, router, w_gate, w_up, w_down, k)
    want, counts = per_token_loop(x, router, w_gate, w_up, w_down, k)
    assert counts[0] == n and counts[7] == 0
    np.testing.assert_array_equal(np.asarray(stats["tokens_per_expert"]),
                                  counts)
    assert int(stats["dropped"]) == 0
    assert int(stats["tokens_per_expert"].sum()) == n * k
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-5, atol=2e-6)


def test_dropless_gradients_match_plain_autodiff():
    """The hand-written cotangents of the two permutations (gathers by
    the inverse permutation instead of scatter-adds) against jax's own
    through plain indexing."""
    n, m, i, e, k = 40, 16, 8, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    args = [jax.random.normal(ks[0], (n, m)),
            jax.random.normal(ks[1], (m, e)),
            0.3 * jax.random.normal(ks[2], (e, m, i)),
            0.3 * jax.random.normal(ks[3], (e, m, i)),
            0.3 * jax.random.normal(ks[4], (e, i, m))]

    def loss(*a):
        y, stats = dropless.dropless_moe(*a, k)
        return (y ** 2).sum() + stats["prob_sum"].var() + stats["z_sum"]

    got = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dropless, "_gather_tokens",
                   lambda x, order, inverse, top_k: x[order // top_k])
        mp.setattr(dropless, "_gather_pairs",
                   lambda rows, order, inverse: rows[inverse])
        want = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


def test_train_batch_through_initialize_loss_falls_counters_present():
    cfg, model, params, ids = build(jnp.bfloat16)
    ids = np.asarray(jnp.tile(ids, (4, 1)))            # 8 rows: data = 8
    engine, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": 8, "bf16": {"enabled": True},
                "zero_optimization": {"stage": 0},
                "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
                "gradient_clipping": 1.0,
                "steps_per_print": 10 ** 9},
        loss_fn=olmoe.make_olmoe_loss_fn(model), params=params)
    before = float(engine.eval_batch({"input_ids": ids}))
    losses = [float(engine.train_batch({"input_ids": ids}))
              for _ in range(20)]
    assert abs(losses[0] - before) < 1e-3      # eval: the loss alone
    assert losses[-1] < losses[0] - 0.5
    scalars = engine.step_metrics["loss_scalars"]
    assert set(scalars) == {
        "moe_ce_loss", "moe_lb_loss", "moe_z_loss", "moe_dropped_tokens",
        "moe_tokens_per_expert_max", "moe_tokens_per_expert_min"}
    assert int(scalars["moe_dropped_tokens"]) == 0
    pairs = 2 * 8 * 32 * cfg.num_experts_per_tok    # layers x tokens x k
    assert int(scalars["moe_tokens_per_expert_min"]) <= \
        pairs / (2 * cfg.num_experts) <= \
        int(scalars["moe_tokens_per_expert_max"])
    total = float(scalars["moe_ce_loss"]) + \
        LB_COEF * float(scalars["moe_lb_loss"]) + \
        Z_COEF * float(scalars["moe_z_loss"])
    assert abs(total - losses[-1]) < 1e-4
    assert {"loss", "grad_norm", "lr"} <= set(engine.step_metrics)


def test_loss_scalars_reach_the_step_event_and_average_over_microbatches():
    cfg, model, params, ids = build(jnp.float32)
    ids = np.asarray(jnp.tile(ids, (8, 1)))            # 16 rows
    engine, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": 16,
                "train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "telemetry": {"enabled": True},
                "steps_per_print": 10 ** 9},
        loss_fn=olmoe.make_olmoe_loss_fn(model), params=params)
    engine.train_batch({"input_ids": ids})
    event = engine.metrics_history[-1]
    assert event["moe_dropped_tokens"] == 0.0
    assert event["moe_lb_loss"] > 0 and event["grad_norm"] > 0
    # both microbatches hold the same two sequences: the mean of the
    # two is either's
    want = olmoe_ref.loss_terms(params, jnp.asarray(ids[:2]), ref_cfg(cfg),
                                LB_COEF, Z_COEF)
    assert abs(event["moe_ce_loss"] - float(want["ce"])) < 1e-4
    engine.telemetry.close()


def test_dropless_under_a_data_mesh_routes_each_chips_tokens_alone():
    """Traced under `placed_on_mesh` (as the engine traces its loss
    over more than one device) the routing runs inside a `shard_map`
    over the rows axis: each chip sorts and multiplies its own tokens,
    the counters and the losses' sums are added up over the chips, and
    values and every gradient are the single-device ones."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.ops.pallas.flash_attention import placed_on_mesh
    from deepspeed_tpu.parallel.mesh import build_mesh

    n, m, i, e, k = 64, 16, 8, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    args = [jax.random.normal(ks[0], (n, m)),
            jax.random.normal(ks[1], (m, e)),
            0.3 * jax.random.normal(ks[2], (e, m, i)),
            0.3 * jax.random.normal(ks[3], (e, m, i)),
            0.3 * jax.random.normal(ks[4], (e, i, m))]

    def loss(*a):
        y, stats = dropless.dropless_moe(*a, k)
        counts = stats["tokens_per_expert"].astype(jnp.float32)
        return ((y ** 2).sum() + (counts * stats["prob_sum"]).sum() +
                0.1 * stats["z_sum"]), stats

    mesh = build_mesh({"data": 4}, devices=jax.devices()[:4])

    def placed(*a):
        with placed_on_mesh(mesh, rows="data", heads="model"):
            return loss(*a)

    grad = lambda f: jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2, 3, 4), has_aux=True))
    (want, want_stats), want_grads = grad(loss)(*args)
    on_mesh = [jax.device_put(a, NamedSharding(mesh, spec))
               for a, spec in zip(args, [P("data")] + [P()] * 4)]
    (got, stats), grads = grad(placed)(*on_mesh)
    assert "shard_map" in str(jax.make_jaxpr(placed)(*args))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for key in ("chosen", "tokens_per_expert", "dropped"):
        np.testing.assert_array_equal(np.asarray(stats[key]),
                                      np.asarray(want_stats[key]))
    assert int(stats["tokens_per_expert"].sum()) == n * k
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="do not divide"):
        placed(args[0][:-1], *args[1:])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_grouped_matmul_against_a_loop_over_groups(dtype):
    """`grouped_matmul` (`megablox.gmm`, interpret mode here) over
    groups that include an empty one, one of several row tiles and
    ones that end inside a tile, against plain per-group products:
    the result and both gradients."""
    sizes, k, n = [20, 0, 37, 7], 32, 48
    group_sizes = jnp.asarray(sizes, jnp.int32)
    row_group = np.repeat(np.arange(4), sizes)
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    rows = jax.random.normal(ks[0], (sum(sizes), k)).astype(dtype)
    bank = jax.random.normal(ks[1], (4, k, n)).astype(dtype)
    cot = jax.random.normal(ks[2], (sum(sizes), n))

    def kernel_loss(rows, bank):
        out = dropless.grouped_matmul(rows, bank, group_sizes)
        return (out.astype(jnp.float32) * cot).sum(), out

    def loop_loss(rows, bank):
        out = jnp.einsum("rk,rkn->rn", rows.astype(jnp.float32),
                         bank.astype(jnp.float32)[row_group])
        return (out * cot).sum(), out

    (_, out), (d_rows, d_bank) = jax.value_and_grad(
        kernel_loss, argnums=(0, 1), has_aux=True)(rows, bank)
    (_, want), (w_rows, w_bank) = jax.value_and_grad(
        loop_loss, argnums=(0, 1), has_aux=True)(rows, bank)
    assert out.dtype == d_rows.dtype == d_bank.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for got, ref in ((out, want), (d_rows, w_rows), (d_bank, w_bank)):
        got, ref = (np.asarray(a, np.float32) for a in (got, ref))
        assert np.isfinite(got).all()
        assert np.abs(got - ref).max() <= tol * np.abs(ref).max()
    # the empty group's block of the bank's gradient is written: zeros
    assert not np.asarray(d_bank, np.float32)[1].any()


# --- the benchmark's check (`benchmarks/suite/drivers/train_olmoe.py`)
# --- must fail a program with a fault in it -----------------------------

def _fault_bf16_router(mp):
    real = dropless.router_logits
    # the router's product kept in bf16 (reduce_precision: XLA may not
    # simplify the rounding away, as it may a convert pair)
    mp.setattr(dropless, "router_logits", lambda x, r:
               jax.lax.reduce_precision(real(x, r), 8, 7))


def _fault_renormalised_top_k(mp):
    real = jax.lax.top_k

    def renormalised(probs, k):
        weights, experts = real(probs, k)
        return weights / weights.sum(-1, keepdims=True), experts
    mp.setattr(jax.lax, "top_k", renormalised)


def _fault_dropped_tokens(mp):
    real = dropless.grouped_matmul

    def with_capacity(rows, bank, group_sizes):
        # GShard's capacity of 1.25 x the mean load: an expert's rows
        # past it come back as zeros
        start = jnp.cumsum(group_sizes) - group_sizes
        group = jnp.searchsorted(jnp.cumsum(group_sizes),
                                 jnp.arange(rows.shape[0]), side="right")
        keep = jnp.arange(rows.shape[0]) - start[group] < \
            int(1.25 * rows.shape[0] / bank.shape[0])
        return jnp.where(keep[:, None], real(rows, bank, group_sizes), 0)
    mp.setattr(dropless, "grouped_matmul", with_capacity)


def _fault_8bit_experts(mp):
    real = dropless.grouped_matmul
    # both operands of the experts' products rounded to 3 bits of
    # mantissa (an fp8 product without its scaling)
    mp.setattr(dropless, "grouped_matmul", lambda rows, bank, sizes: real(
        jax.lax.reduce_precision(rows, 8, 3),
        jax.lax.reduce_precision(bank, 8, 3), sizes))


def _fault_no_qk_norm(mp):
    real = olmoe.RMSNorm.__call__
    mp.setattr(olmoe.RMSNorm, "__call__", lambda self, x:
               x if self.name in ("q_norm", "k_norm") else real(self, x))


# float32 compute: what the float32 parity test above allows, and no
# pair elsewhere (512 tokens x 2: one flipped pair is 1e-3)
FLOAT32_TOLERANCES = {"loss_rtol": 2e-6, "ce_rtol": 2e-6, "lb_rtol": 2e-6,
                      "z_rtol": 2e-6, "logit_rtol": 2e-5,
                      "choice_differs_max": 5e-4,
                      "router_prob_rtol": 2e-5, "experts_out_rtol": 2e-5}


@pytest.mark.parametrize("fault,dtype,caught_by", [
    (_fault_renormalised_top_k, jnp.bfloat16, "logits"),
    (_fault_dropped_tokens, jnp.bfloat16, "logits"),
    (_fault_no_qk_norm, jnp.bfloat16, "logits"),
    # against the whole reference a bf16 router hides in bf16 compute:
    # the activations that reach a float32 router are rounded as
    # coarsely as a bf16 router rounds its result (on the chip 0.63-0.65
    # % of pairs moved against 0.58-0.60 %). Held to a float32 router
    # on its own input it stands out by two orders of magnitude
    (_fault_bf16_router, jnp.bfloat16, "router"),
    (_fault_bf16_router, jnp.float32, "expert_choice"),
    (_fault_8bit_experts, jnp.bfloat16, "experts"),
], ids=["renormalised-top-k", "dropped-tokens", "no-qk-norm",
        "bf16-router", "bf16-router-float32-compute", "8-bit-experts"])
def test_check_against_reference_fails_a_faulty_program(fault, dtype,
                                                        caught_by):
    """The cell's own check, with the committed tolerances for bf16
    compute: passes the program, fails it with each fault. One layer as
    in the cell; the initialiser's range is 0.11 so that at hidden 64 a
    weight matrix scales its input as at hidden 2048 (range x
    sqrt(fan-in) ~ 0.9) and the experts are the share of the residual
    stream that they are there."""
    suite = os.path.dirname(os.path.dirname(train_olmoe.__file__))
    with open(os.path.join(suite, "workloads",
                           "train-olmoe-1b-7b-seq4096.json")) as f:
        tol = json.load(f)["correctness"]
    if dtype == jnp.float32:
        tol = FLOAT32_TOLERANCES
    cfg = olmoe.olmoe_tiny(dtype=dtype, num_hidden_layers=1,
                           max_position_embeddings=512,
                           initializer_range=0.11)
    model = olmoe.OlmoeLM(cfg)
    params = olmoe.init_olmoe_params(model, jax.random.PRNGKey(0))
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, 512), 0,
                                        cfg.vocab_size))
    config = dict(ref_cfg(cfg), assumed={"router_aux_loss_coef": LB_COEF,
                                         "router_z_loss_coef": Z_COEF})

    def check():
        got, want = train_olmoe.program_and_reference(model, params, ids,
                                                      config)
        return train_olmoe.compare(got, want, tol)

    sound = check()
    assert sound["ok"], sound
    with pytest.MonkeyPatch.context() as mp:
        fault(mp)
        faulty = check()
    assert not faulty["ok"]
    assert not faulty[caught_by]["ok"], faulty

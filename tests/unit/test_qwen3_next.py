"""Qwen3-Next (Gated DeltaNet mixers whose state is a matrix a head
under a delta rule, a gated attention with partly rotated, normed keys
in a per-head pool every fourth block, renormalised softmax routing
over a share of the experts and a gated shared expert) through the
serving engine against the plain reference
(`benchmarks/suite/reference/qwen3_next_ref.py`) at the tiny preset on
the CPU: logits, states, windows and the page pool after ragged chunked
prefills into used slots and decoded tokens beside dead rows, float32
and bfloat16; the chunked delta rule and the step against the
token-by-token recurrence (ragged, across calls, dead rows); the
state's leaves from admit to release; partial rotary against a direct
formula; the router against a direct top-k; the share test; and that
this model's two tiny programs lower to the text they lowered to."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.reference import qwen3_next_ref as ref
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.scheduler import (
    ContinuousBatchingScheduler, Request)
from deepspeed_tpu.models import qwen3_next as qn
from deepspeed_tpu.moe.dropless import (dropless_moe, softmax_top_k,
                                        softmax_top_k_renorm)
from deepspeed_tpu.ops import gated_delta
from tests.unit.test_gated_delta_kernel import recurrence as _recurrence

CHUNK, PAGE, SEQ, ROWS = 16, 8, 64, 3
INF = {"max_batch": ROWS, "seq_buckets": (SEQ,), "prefill_chunk": CHUNK,
       "page_size": PAGE, "attention_block_k": PAGE}


def ref_cfg(cfg, **extra):
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    out.update(n_layer=cfg.num_hidden_layers,
               assumed={"experts_held": list(cfg.experts_held)}, **extra)
    return out


@pytest.fixture(scope="module")
def tiny():
    cfg = qn.qwen3_next_tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    model = qn.Qwen3NextLM(cfg)
    return model, qn.init_qwen3_next_params(model, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["dense", "flash"])
def engine(request, tiny):
    model, params = tiny
    return InferenceEngine(model, params, config=dict(
        INF, attention_impl=request.param))


def table(row):
    """The row's pages, in descending order: none where the allocator
    would have put it."""
    per = SEQ // PAGE
    return np.arange((row + 1) * per, row * per, -1, dtype=np.int32)


def leaves_of(eng, slot):
    """``{layer: (S, window)}`` as the engine's leaves hold them."""
    return {k: (np.asarray(v["gdn"][slot]), np.asarray(v["conv"][:, slot]))
            for k, v in eng.cache.items() if "gdn" in v}


def pool_of(eng, row, n):
    """``{layer: (k, v)}`` ``[n, heads, head_dim]`` of the row's first
    ``n`` positions as the engine's pool holds them."""
    out = {}
    for name, leaves in eng.cache.items():
        if "k" in leaves:
            pages = table(row)[:-(-n // PAGE)]
            out[name] = tuple(
                np.moveaxis(np.asarray(leaves[x])[pages], -1, 1).reshape(
                    (-1,) + leaves[x].shape[1:3])[:n] for x in "kv")
    return out


def decode_one(eng, slot, token, position):
    tokens = np.zeros(ROWS, np.int32)
    positions = np.zeros(ROWS, np.int32)
    tables = np.zeros((ROWS, SEQ // PAGE), np.int32)
    tokens[slot], positions[slot], tables[slot] = token, position, \
        table(slot)
    return np.asarray(eng.decode(tokens, positions, tables)[1][slot])


def test_presets():
    cfg = qn.qwen3_next_80b_share()
    assert cfg.layer_types == (qn.DELTA,) * 3 + (qn.ATTENTION,) + \
        (qn.DELTA,) * 3 + (qn.ATTENTION,)
    assert [len(cfg.names(k)) for k in (qn.DELTA, qn.ATTENTION)] == [6, 2]
    assert (cfg.key_dim, cfg.value_dim, cfg.conv_dim) == (2048, 4096, 8192)
    assert cfg.rotary_dim == 64 and cfg.experts_held == (0, 128)
    spec = cfg.cache_spec(128, 9216, page_size=128, n_pages=4097)
    assert (spec.n_layer, spec.n_head, spec.head_dim) == (2, 2, 256)
    # 12.9 MB a slot: six float32 states and six windows
    assert spec.state_bytes_per_slot == \
        6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2) == 12_877_824
    whole = qn.Qwen3NextConfig()
    assert whole.layer_types.count(qn.ATTENTION) == 12 and \
        len(whole.layer_types) == 48
    with pytest.raises(ValueError, match="experts_held"):
        qn.qwen3_next_tiny(experts_held=(6, 4))
    with pytest.raises(ValueError, match="expert layer"):
        qn.qwen3_next_tiny(mlp_only_layers=(0,))
    with pytest.raises(ValueError, match="even"):
        qn.qwen3_next_tiny(head_dim=10, partial_rotary_factor=0.5)


# every raggedness of the last chunk, and prompts of 1 to 3 chunks
@pytest.mark.parametrize("n", [1, 7, 16, 17, 33, 41])
def test_engine_against_reference(engine, tiny, n):
    """Prefill in chunks, then decode through the cache, teacher-forced:
    logits, every mixer's state and window and every attention layer's
    pool against the reference's full forward. The slot was some other
    prompt's before (the fixture is shared), its pages too, and the
    other rows of a decode step hold no request."""
    model, params = tiny
    cfg = ref_cfg(model.config)
    toks = np.random.default_rng(n).integers(0, 256, n + 4).tolist()
    slot = n % ROWS
    want, at_end, kv = ref.forward(params, toks, cfg)
    last = engine.prefill(slot, toks[:n], table(slot))
    np.testing.assert_allclose(last, want[n - 1], atol=1e-4)
    _, at_prompt, _ = ref.forward(params, toks, cfg, state_at=n - 1)
    for name, (S, window) in leaves_of(engine, slot).items():
        np.testing.assert_allclose(S, at_prompt[name][0], atol=5e-5)
        np.testing.assert_allclose(window, at_prompt[name][1], atol=5e-5)
    for j in range(4):
        lg = decode_one(engine, slot, toks[n + j], n + j)
        np.testing.assert_allclose(lg, want[n + j], atol=1e-4)
    for name, (S, window) in leaves_of(engine, slot).items():
        np.testing.assert_allclose(S, at_end[name][0], atol=5e-5)
        np.testing.assert_allclose(window, at_end[name][1], atol=5e-5)
    pool = pool_of(engine, slot, n + 4)
    assert set(pool) == set(kv) == set(model.config.names(qn.ATTENTION))
    for name, (k, v) in pool.items():
        np.testing.assert_allclose(k, kv[name][0], atol=5e-5)
        np.testing.assert_allclose(v, kv[name][1], atol=5e-5)
    assert engine.compile_counts() == {"prefill": 1, "decode": 1}


def test_engine_in_bfloat16(tiny):
    """The served precision: weights, activations, pool and window
    bfloat16, the state float32. At the tiny widths a whole model's
    logits say little in bfloat16 (8 experts, top 3: a near-tie flips),
    so the layers are held to the reference on their own input."""
    cfg = qn.qwen3_next_tiny()
    model = qn.Qwen3NextLM(cfg)
    params = qn.init_qwen3_next_params(model, jax.random.PRNGKey(1))
    assert params["layers_0"]["mixer"]["out_proj"].dtype == jnp.bfloat16
    eng = InferenceEngine(model, params, config=dict(
        INF, attention_impl="flash"))
    assert eng.cache["layers_0"]["gdn"].dtype == jnp.float32
    assert eng.cache["layers_0"]["conv"].dtype == jnp.bfloat16
    assert eng.cache["layers_3"]["k"].dtype == jnp.bfloat16
    toks = np.random.default_rng(5).integers(0, 256, 41).tolist()
    assert np.isfinite(eng.prefill(1, toks[:37], table(1))).all()
    assert np.isfinite(decode_one(eng, 1, toks[37], 37)).all()
    rc = ref_cfg(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 64)).astype(
        jnp.bfloat16)
    # the mixer: a ragged chunk from a zero state, then a step
    p = params["layers_0"]["mixer"]
    leaves = {"gdn": jnp.zeros((1, 4, 16, 8), jnp.float32),
              "conv": jnp.zeros((3, 1, 96), jnp.bfloat16)}
    slot, n = jnp.zeros((1,), jnp.int32), 29
    mixer = qn.GatedDeltaNet(cfg)
    y, leaves = mixer.apply(
        {"params": p}, x.at[:, n:].set(0), leaves,
        jnp.arange(32, dtype=jnp.int32)[None], slot,
        jnp.full((1,), n, jnp.int32))
    y1, leaves = mixer.apply(
        {"params": p}, x[:, n:n + 1], leaves, jnp.full((1, 1), n, jnp.int32),
        slot, jnp.ones((1,), jnp.int32))
    want, (S, _) = ref.delta_net(x[0, :n + 1].astype(jnp.float32), p, rc)
    got = np.concatenate([np.asarray(y[0, :n], np.float32),
                          np.asarray(y1[0], np.float32)])
    assert np.abs(got - want).max() < 0.03 * np.abs(want).max()
    assert np.abs(leaves["gdn"][0] - S).max() < 0.03 * np.abs(S).max()
    # the attention layer: the dense chunk
    from deepspeed_tpu.inference.cache import init_kv_cache
    p = params["layers_3"]["attn"]
    pool = init_kv_cache(cfg.cache_spec(1, 32, page_size=8))["layers_3"]
    y, _ = qn.GatedAttention(cfg).apply(
        {"params": p}, x, pool, jnp.arange(32, dtype=jnp.int32)[None],
        jnp.arange(1, 5, dtype=jnp.int32)[None], {"impl": "dense"})
    want = ref.attention(x[0].astype(jnp.float32), p, rc)
    assert np.abs(np.asarray(y[0], np.float32) - want).max() < \
        0.03 * np.abs(want).max()


def test_decode_counters_and_dead_rows(engine, tiny):
    """A step's span carries the expert layers' counters and the state
    update's rows; a dead row keeps its leaves and routes nothing."""
    model, _ = tiny
    cfg = model.config
    toks = list(range(3, 12))
    engine.prefill(0, toks, table(0))
    engine.prefill(2, toks[::-1], table(2))
    before = leaves_of(engine, 2)
    from deepspeed_tpu.telemetry import spans
    t0 = spans.clock()
    decode_one(engine, 0, 7, len(toks))
    for name, got in leaves_of(engine, 2).items():
        for a, b in zip(got, before[name]):
            np.testing.assert_array_equal(a, b)
    rec = [r for r in spans.recent(t0) if r[0].endswith("decode")
           and r[3] and "moe_pairs_routed" in r[3]][-1][3]
    layers = cfg.num_hidden_layers
    assert rec["moe_pairs_routed"] == cfg.num_experts_per_tok * layers
    assert rec["moe_experts_held"] == cfg.experts_held[1] * layers
    assert 0 <= rec["moe_pairs_held"] <= rec["moe_pairs_routed"]
    assert rec["moe_experts_touched"] <= rec["moe_pairs_held"]
    assert rec["moe_pairs_max"] == (1 if rec["moe_pairs_held"] else 0)
    # the step's kernel visits the live row and no other (ISSUE 50)
    assert rec["gdn_rows_live"] == 1 and rec["gdn_rows_touched"] == 1


def test_a_decode_step_over_two_of_four_rows_against_the_plain_step(
        tiny, monkeypatch):
    """Rows 1 and 3 of four decode, rows 0 and 2 hold what earlier
    tenants left: the live rows' logits are those of the same step with
    the plain masked pass (`gated_delta_step_plain`) in the kernel's
    place, the dead rows' states are the bytes they were, and the step
    counts the rows it visited."""
    from deepspeed_tpu.telemetry import spans
    model, params = tiny
    rows = 4
    prompts = [np.random.default_rng(20 + r).integers(0, 256, 9 + 3 * r)
               .tolist() for r in range(rows)]
    tokens = np.zeros(rows, np.int32)
    positions = np.zeros(rows, np.int32)
    tables = np.zeros((rows, SEQ // PAGE), np.int32)
    for r in (1, 3):
        tokens[r], positions[r], tables[r] = 5 + r, len(prompts[r]), table(r)

    def step(eng):
        for r in range(rows):
            eng.prefill(r, prompts[r], table(r))
        before = {r: leaves_of(eng, r) for r in (0, 2)}
        t0 = spans.clock()
        logits = np.asarray(eng.decode(tokens, positions, tables)[1])
        rec = [r for r in spans.recent(t0) if r[0].endswith("decode")
               and r[3] and "gdn_rows_live" in r[3]][-1][3]
        return logits, before, {r: leaves_of(eng, r) for r in range(rows)}, \
            rec

    config = dict(INF, max_batch=rows, attention_impl="flash")
    got, before, after, rec = step(InferenceEngine(model, params,
                                                   config=config))
    assert rec["gdn_rows_live"] == rec["gdn_rows_touched"] == 2
    for r in (0, 2):
        for name, (S, _) in after[r].items():
            np.testing.assert_array_equal(S, before[r][name][0])
    monkeypatch.setattr(gated_delta, "gated_delta_step",
                        gated_delta.gated_delta_step_plain)
    want, _, plain, _ = step(InferenceEngine(model, params, config=config))
    for r in (1, 3):
        np.testing.assert_allclose(got[r], want[r], atol=1e-4)
        for name, (S, _) in after[r].items():
            np.testing.assert_allclose(S, plain[r][name][0], atol=5e-5)


def test_state_leaves_from_admit_to_release_and_a_reused_slot(tiny):
    """Through the scheduler: a request takes a slot and its leaves, it
    finishes and gives them back, and the slot's next tenant starts from
    zeros: its tokens are what a fresh engine gives it."""
    model, params = tiny
    rng = np.random.default_rng(7)
    first = Request("a", rng.integers(0, 256, 37).tolist(), 6)
    second = Request("b", rng.integers(0, 256, 21).tolist(), 6)
    used = InferenceEngine(model, params, config=dict(INF, max_batch=1))
    sched = ContinuousBatchingScheduler(used)
    sched.submit(first)
    sched.step()
    assert sched.paging.state_rows_live == 1
    assert sched.paging.facts()["state_bytes_live"] == \
        used.spec.state_bytes_per_slot == 6 * (4 * 16 * 8 * 4 + 3 * 96 * 4)
    sched.run()
    assert sched.paging.state_rows_live == 0
    sched.run([second])
    fresh = ContinuousBatchingScheduler(
        InferenceEngine(model, params, config=dict(INF, max_batch=1)))
    fresh.run([dataclasses.replace(second, submit_t=None, arrival_t=None)])
    assert sched.completions[-1].slot == 0 == fresh.completions[-1].slot
    assert sched.completions[-1].tokens == fresh.completions[-1].tokens
    assert used.compile_counts() == {"prefill": 1, "decode": 1}


def test_rows_in_any_order_do_not_disturb_each_other(tiny):
    """Six requests of mixed lengths over three slots, admitted as slots
    free up and finishing out of order, against each alone."""
    model, params = tiny
    rng = np.random.default_rng(11)
    sizes = [(37, 3), (5, 9), (18, 5), (41, 2), (9, 7), (26, 4)]
    reqs = [Request(f"r{i}", rng.integers(0, 256, n).tolist(), new)
            for i, (n, new) in enumerate(sizes)]
    eng = InferenceEngine(model, params, config=INF)
    sched = ContinuousBatchingScheduler(eng)
    sched.run(reqs)
    together = {c.rid: c.tokens for c in sched.completions}
    assert [c.rid for c in sched.completions] != [r.rid for r in reqs]
    for r in reqs:
        alone = ContinuousBatchingScheduler(eng)
        done = alone.run([Request(r.rid, r.prompt, r.max_new_tokens)])
        assert done[0].tokens == together[r.rid], r.rid
    assert eng.compile_counts() == {"prefill": 1, "decode": 1}


# --- the delta rule ----------------------------------------------------------

def _delta_case(seed, T=32, H=4, K=8, V=6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (T, H, K)))
    v = jax.random.normal(ks[2], (T, H, V))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (H, K, V))


# the entry point the mixer calls (one kernel call since PR 44; Pallas
# interpret mode here) and the same algebra in plain XLA
FORMS = {"entry": lambda *a: gated_delta.gated_delta_chunked(*a),
         "plain": lambda *a: gated_delta.gated_delta_chunked_plain(*a)}


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_chunked_delta_rule_against_the_recurrence(chunk, form):
    q, k, v, g, beta, s0 = _delta_case(chunk)
    want_o, want_s = _recurrence(q, k, v, g, beta, s0)
    o, s1 = FORMS[form](q, k, v, g, beta, s0, chunk)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(s1, want_s, atol=2e-5)


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("n", [1, 5, 8, 13, 31])
def test_chunked_delta_rule_ragged_and_across_calls(n, form):
    """``n`` real tokens of 32, the tail's ``g`` and ``beta`` zeroed:
    the state is the recurrence's after ``n``; a second call that starts
    from it gives what one call over both gives."""
    q, k, v, g, beta, s0 = _delta_case(7)
    real = (jnp.arange(32) < n)[:, None]
    chunked = FORMS[form]
    o, s1 = chunked(
        q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0), s0, 8)
    want_o, want_s = _recurrence(q[:n], k[:n], v[:n], g[:n], beta[:n], s0)
    np.testing.assert_allclose(o[:n], want_o, atol=2e-5)
    np.testing.assert_allclose(s1, want_s, atol=2e-5)
    q2, k2, v2, g2, beta2, _ = _delta_case(8)
    o2, s2 = chunked(q2, k2, v2, g2, beta2, s1, 8)
    cat = lambda a, b: np.concatenate([np.asarray(a)[:n], np.asarray(b)])
    both_o, both_s = _recurrence(cat(q, q2), cat(k, k2), cat(v, v2),
                                 cat(g, g2), cat(beta, beta2), s0)
    np.testing.assert_allclose(o2, both_o[n:], atol=5e-5)
    np.testing.assert_allclose(s2, both_s, atol=5e-5)


def test_delta_step_against_the_recurrence_with_a_dead_row():
    q, k, v, g, beta, s0 = _delta_case(3)
    rows = np.array([3, 9, 17])
    live = jnp.array([True, False, True])
    state = jnp.stack([s0, 2 * s0, -s0])
    o, new = gated_delta.gated_delta_step(
        q[rows], k[rows], v[rows], g[rows], beta[rows], state, live)
    for i, t in enumerate(rows):
        wo, ws = _recurrence(q[t:t + 1], k[t:t + 1], v[t:t + 1],
                             g[t:t + 1], beta[t:t + 1], state[i])
        if live[i]:
            np.testing.assert_allclose(o[i], wo[0], atol=1e-5)
            np.testing.assert_allclose(new[i], ws, atol=1e-5)
        else:
            # not visited: its token is thrown away, its state stays
            assert not np.asarray(o[i]).any()
            np.testing.assert_array_equal(np.asarray(new[i]),
                                          np.asarray(state[i]))


@pytest.mark.parametrize("form", sorted(FORMS))
def test_beta_and_the_decay_are_seen(form):
    """``beta`` 1 everywhere or ``g`` 0 everywhere is another result."""
    q, k, v, g, beta, s0 = _delta_case(11)
    o, _ = FORMS[form](q, k, v, g, beta, s0, 8)
    for g_, b_ in ((g, jnp.ones_like(beta)), (jnp.zeros_like(g), beta)):
        other, _ = FORMS[form](q, k, v, g_, b_, s0, 8)
        assert np.abs(other - o).max() > 0.05 * np.abs(o).max()


# --- attention's parts --------------------------------------------------------

def test_partial_rotary_against_a_direct_formula():
    cfg = qn.qwen3_next_tiny(head_dim=16, partial_rotary_factor=0.25,
                             rope_theta=100.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    positions = jnp.array([[0, 1, 2, 3, 4], [130, 131, 132, 133, 134]])
    got = np.asarray(qn.partial_rotary(x, positions, cfg))
    r = 4
    want = np.array(x)
    for b in range(2):
        for t in range(5):
            for i in range(r // 2):
                ang = float(positions[b, t]) * 100.0 ** (-2.0 * i / r)
                a, c = np.asarray(x[b, t, :, i]), \
                    np.asarray(x[b, t, :, i + r // 2])
                want[b, t, :, i] = a * np.cos(ang) - c * np.sin(ang)
                want[b, t, :, i + r // 2] = c * np.cos(ang) + a * np.sin(ang)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(got[..., r:], np.asarray(x)[..., r:])
    # and the reference's, which is written apart from the program's
    ref_c = {"head_dim": 16, "partial_rotary_factor": 0.25,
             "rope_theta": 100.0}
    np.testing.assert_allclose(
        ref.rotary(x[1], positions[1], ref_c), want[1], atol=1e-5)


def test_zero_centred_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    want = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6) \
        * (1 + np.asarray(w))
    np.testing.assert_allclose(qn.zero_centred_norm(x, w, 1e-6), want,
                               atol=1e-6)
    np.testing.assert_allclose(ref.norm(x, w, 1e-6), want, atol=1e-6)


# --- the expert layer ---------------------------------------------------------

def test_renormalised_router_against_a_direct_top_k():
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    w, chosen, _ = softmax_top_k_renorm(x, router, 4)
    p = np.asarray(jax.nn.softmax(
        np.asarray(x, np.float64) @ np.asarray(router, np.float64), -1))
    order = np.argsort(-p, -1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(order, -1))
    top = np.take_along_axis(p, np.asarray(chosen), -1)
    np.testing.assert_allclose(w, top / top.sum(-1, keepdims=True),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    # OLMoE's, beside it, is not renormalised: the same experts, the
    # probabilities as they are
    w0, chosen0, _ = softmax_top_k(x, router, 4)
    np.testing.assert_array_equal(np.asarray(chosen0), np.asarray(chosen))
    np.testing.assert_allclose(w0, top, atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The four shares' routed parts plus the gated shared expert
    counted once equal the uncut reference's expert layer: the program
    on each share, the reference whole. The renormalised weights sum to
    1 over a token's chosen experts wherever they are held."""
    model, _ = tiny
    whole = dataclasses.replace(model.config, experts_held=(0, 8))
    layer = qn.SparseExperts(whole)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64), jnp.float32)
    mask = jnp.ones((1, 24), bool)
    p = layer.init(jax.random.PRNGKey(4), x, mask)["params"]
    want = np.asarray(ref.experts(x[0], p, ref_cfg(whole)))
    total, pairs = 0.0, 0
    for first in range(0, 8, 2):
        share = dataclasses.replace(whole, experts_held=(first, 2))
        ps = dict(p, **{b: p[b][first:first + 2]
                        for b in ("w_gate", "w_up", "w_down")})
        y, counters = qn.SparseExperts(share).apply({"params": ps}, x, mask)
        total = total + np.asarray(y[0])
        pairs += int(counters.pairs_held)
    shared = np.asarray(ref.shared(x[0], p))
    np.testing.assert_allclose(total - 3 * shared, want, atol=2e-5)
    assert pairs == 24 * 3          # every pair fell on exactly one share
    # a share's own reference: the same partial result
    one = dataclasses.replace(whole, experts_held=(2, 4))
    ps = dict(p, **{b: p[b][2:6] for b in ("w_gate", "w_up", "w_down")})
    y, _ = qn.SparseExperts(one).apply({"params": ps}, x, mask)
    np.testing.assert_allclose(
        y[0], ref.experts(x[0], ps, ref_cfg(one), first_expert=2),
        atol=2e-5)


def test_expert_layer_with_dead_tokens_against_a_loop():
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    N, M, I, E, held, first, top_k = 24, 32, 24, 8, 4, 2, 3
    x = jax.random.normal(k[0], (N, M))
    router = jax.random.normal(k[1], (M, E))
    wg, wu = (0.3 * jax.random.normal(k[i], (held, M, I)) for i in (2, 3))
    wd = 0.3 * jax.random.normal(k[4], (held, I, M))
    mask = jnp.arange(N) % 5 != 4
    y, stats = dropless_moe(x, router, wg, wu, wd, top_k,
                            route=softmax_top_k_renorm, first_expert=first,
                            token_mask=mask)
    w, chosen, _ = softmax_top_k_renorm(x, router, top_k)
    want = jnp.zeros_like(x)
    for e in range(held):
        mine = jnp.where(chosen == e + first, w, 0.0).sum(-1) * mask
        want = want + mine[:, None] * (
            (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    np.testing.assert_allclose(y, want, atol=1e-4)
    assert not np.asarray(y)[~np.asarray(mask)].any()
    assert int(stats["tokens_per_expert"].sum()) + int(stats["dropped"]) \
        == N * top_k


# --- the programs' text --------------------------------------------------------

# sha1 of the lowered (StableHLO) text of this model's two tiny
# programs as PR 43 left them, the prefill program's as PR 44 did (its
# delta rule became one kernel call, in interpret mode here), both as
# PR 45 did (the held experts became `moe/dropless.py:_held_moe`, loops
# over the live row tiles, and a counter more rides home), both as
# PR 50 did (the decode step's delta rule became one kernel call over
# the live rows, `gdn_rows_touched` the rows it visits in both programs,
# and the mixer no longer repeats its key heads by one); the twin
# of `tests/unit/test_nemotron_h.py::
# test_accepted_tiny_programs_lower_to_the_text_they_lowered_to`, whose
# digests pin granite's, Kimi's and OLMoE's. A later PR that changes
# one on purpose takes the new hash from this test's message.
LOWERED = {
    "qwen3_next.prefill": "331d8944e8342f7c7f6e44ede8df9a1941394c80",
    # PR 59: the decode step's counters are int32 scalars by name
    # (`models/blocks.py:expert_counters`, `summed_counters`) where they
    # were a stacked vector taken apart by position; every line that
    # holds a float type is the parent's, in the parent's order
    "qwen3_next.decode": "2380fa3d8d0c94bc4833f944962b99d0aaa1e128",
}


@pytest.mark.parametrize("which", sorted(LOWERED))
def test_tiny_programs_lower_to_the_text_they_lowered_to(which):
    m = qn.Qwen3NextLM(qn.qwen3_next_tiny())
    eng = InferenceEngine(
        m, qn.init_qwen3_next_params(m, jax.random.PRNGKey(0)),
        config=dict(max_batch=4, seq_buckets=(64,), prefill_chunk=16,
                    page_size=8, attention_impl="dense"))
    text = {
        "prefill": lambda: eng._prefill.lower(
            *eng.prefill_lowering_args()).as_text(),
        "decode": lambda: eng._decode.lower(
            *eng.decode_lowering_args()).as_text()}[which.split(".")[1]]()
    got = hashlib.sha1(text.encode()).hexdigest()
    assert got == LOWERED[which], (which, got)

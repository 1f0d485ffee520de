"""LFM2 with experts (`deepspeed_tpu/models/lfm2_moe.py`: short
convolutions between two gates, grouped-query attention with normed,
rotated heads in the layers an irregular ``layer_types`` names, a
leading dense layer, sigmoid routing with a choice bias over a share)
through the serving engine against the plain reference
(`benchmarks/suite/reference/lfm2_moe_ref.py`) at toy size: the full
forward; prefill in ragged chunks then decode through the cache (logits,
every window, the pooled keys and values); both forms of the mixer
against the explicit sum; the four shares adding up to the uncut layer;
the routing's ``eps``; a spec whose recurrent leaves are a window alone,
and what refuses it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.reference import lfm2_moe_ref as ref
from deepspeed_tpu.inference import cache as kvcache
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import lfm2_moe as lf
from deepspeed_tpu.moe.dropless import sigmoid_top_k

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
    cfg = lf.lfm2_moe_tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    model = lf.Lfm2MoeLM(cfg)
    return model, lf.init_lfm2_moe_params(model, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["dense", "flash"])
def engine(request, tiny):
    model, params = tiny
    return InferenceEngine(model, params, config=dict(
        INF, attention_impl=request.param))


def table(row):
    per = SEQ // PAGE
    return np.arange((row + 1) * per, row * per, -1, dtype=np.int32)


def windows_of(eng, slot):
    return {k: np.asarray(v["conv"][:, slot])
            for k, v in eng.cache.items() if "conv" in v}


def pool_of(eng, row, n):
    """``{layer: (keys, values) [n, heads, head_dim]}`` of the row's
    first ``n`` positions as the engine's pool holds them."""
    out = {}
    for name, leaves in eng.cache.items():
        if "k" in leaves:
            pages = table(row)[:-(-n // PAGE)]
            out[name] = tuple(np.moveaxis(
                np.asarray(leaves[x])[pages], -1, 1).reshape(
                    (-1,) + leaves[x].shape[1:3])[:n] for x in "kv")
    return out


def decode_one(eng, slot, token, position):
    tokens = np.zeros(ROWS, np.int32)
    positions = np.zeros(ROWS, np.int32)
    tables = np.zeros((ROWS, SEQ // PAGE), np.int32)
    tokens[slot], positions[slot], tables[slot] = token, position, \
        table(slot)
    return np.asarray(eng.decode(tokens, positions, tables)[1][slot])


def test_presets_and_refusals():
    cfg = lf.lfm2_8b_a1b_share()
    assert len(cfg.layer_types) == 24
    assert [i for i, t in enumerate(cfg.layer_types)
            if t == lf.ATTENTION] == [2, 6, 10, 14, 18, 21]
    assert [cfg.is_dense(i) for i in range(3)] == [True, True, False]
    assert (cfg.head_dim, cfg.experts_held) == (64, (0, 8))
    spec = cfg.cache_spec(192, 9216, page_size=128, n_pages=3841)
    assert (spec.n_layer, spec.n_head, spec.head_dim, spec.latent_v_dim) \
        == (6, 8, 64, 0)
    assert spec.layers == cfg.names(lf.ATTENTION)
    assert len(spec.recurrent_layers) == 18
    # 147 KB a slot: eighteen windows of two bfloat16 rows, nothing else
    assert spec.state_bytes_per_slot == 18 * 2 * 2048 * 2 == 147_456
    for kw, said in [
            ({"experts_held": (6, 4)}, "experts_held"),
            ({"layer_types": (lf.CONV,) * 5}, "layer_types"),
            ({"layer_types": (lf.CONV,) * 5 + ("mamba",)}, "layer_types"),
            ({"conv_bias": True}, "no bias"),
            ({"use_expert_bias": False}, "choice"),
            ({"num_key_value_heads": 3}, "divide")]:
        with pytest.raises(ValueError, match=said):
            lf.lfm2_moe_tiny(**kw)


# every raggedness of the last chunk, and prompts of 1 to 3 chunks
@pytest.mark.parametrize("n", [1, 16, 17, 41])
def test_engine_against_reference(engine, tiny, n):
    """Prefill in chunks, then decode through the cache, teacher-forced:
    logits, every convolution layer's window and the pooled keys and
    values against the reference's full forward. The slot was some other
    prompt's before (the fixture is shared), its pages too, and the
    other rows of a decode step hold no request."""
    model, params = tiny
    cfg = ref_cfg(model.config)
    toks = np.random.default_rng(n).integers(0, 256, n + 4).tolist()
    slot = n % ROWS
    want, at_end, pooled = ref.forward(params, toks, cfg)
    last = engine.prefill(slot, toks[:n], table(slot))
    np.testing.assert_allclose(last, want[n - 1], atol=1e-4)
    _, at_prompt, _ = ref.forward(params, toks, cfg, state_at=n - 1)
    for name, window in windows_of(engine, slot).items():
        np.testing.assert_allclose(window, at_prompt[name], atol=5e-5)
    for j in range(4):
        lg = decode_one(engine, slot, toks[n + j], n + j)
        np.testing.assert_allclose(lg, want[n + j], atol=1e-4)
    for name, window in windows_of(engine, slot).items():
        np.testing.assert_allclose(window, at_end[name], atol=5e-5)
    pool = pool_of(engine, slot, n + 4)
    assert set(pool) == set(pooled) == set(model.config.names(lf.ATTENTION))
    for name, (k, v) in pool.items():
        np.testing.assert_allclose(k, pooled[name][0], atol=5e-5)
        np.testing.assert_allclose(v, pooled[name][1], atol=5e-5)
    assert engine.compile_counts() == {"prefill": 1, "decode": 1}


def test_decode_counters_and_dead_rows(engine, tiny):
    """A step over two of three rows: the counters on the decode span's
    attributes, and the dead row's windows to the bit."""
    model, _ = tiny
    cfg = model.config
    for slot in (0, 1, 2):
        engine.prefill(slot, [3 + slot, 5, 7], table(slot))
    before = windows_of(engine, 1)
    assert all(np.abs(w).max() > 0 for w in before.values())
    tokens = np.asarray([1, 0, 2], np.int32)
    positions = np.asarray([3, 0, 3], np.int32)
    tables = np.stack([table(0), np.zeros(SEQ // PAGE, np.int32), table(2)])
    from deepspeed_tpu.telemetry import spans
    engine.decode(tokens, positions, tables)
    for name, window in windows_of(engine, 1).items():
        np.testing.assert_array_equal(window, before[name])
    attrs = [r[3] for r in spans.recent(0) if r[0].endswith("decode")
             and r[3] and "sconv_rows_live" in r[3]][-1]
    layers = cfg.num_hidden_layers - cfg.num_dense_layers
    assert (attrs["sconv_rows_live"], attrs["sconv_rows_touched"]) == \
        (2, ROWS)
    assert attrs["moe_pairs_routed"] == 2 * layers * cfg.num_experts_per_tok
    assert attrs["moe_experts_held"] == cfg.experts_held[1] * layers
    assert attrs["moe_pairs_held"] <= attrs["moe_pairs_routed"]
    assert attrs["moe_pairs_max"] <= 2 and attrs["moe_rows_visited"] >= 0


def test_engine_in_bfloat16(tiny):
    """The cell's dtype through both programs: the engine's logits lie
    near the float32 reference's on the same bfloat16 weights."""
    model, params = tiny
    cfg = dataclasses.replace(model.config, dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16)
    bf = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 and
        a.ndim > 1 else a, params)
    eng = InferenceEngine(lf.Lfm2MoeLM(cfg), bf, config=dict(
        INF, attention_impl="flash"))
    toks = np.random.default_rng(0).integers(0, 256, 21).tolist()
    want = np.asarray(ref.forward(bf, toks, ref_cfg(cfg))[0])
    last = eng.prefill(0, toks[:20], table(0))
    assert np.abs(last - want[19]).max() < 0.08 * np.abs(want).max()
    lg = decode_one(eng, 0, toks[20], 20)
    assert np.abs(lg - want[20]).max() < 0.08 * np.abs(want).max()
    assert eng.cache["layers_0"]["conv"].dtype == jnp.bfloat16


# --- the mixer's two forms against the explicit sum --------------------------------

@pytest.fixture(scope="module")
def mixer(tiny):
    model, params = tiny
    cfg = model.config
    name = cfg.names(lf.CONV)[0]
    return cfg, lf.ShortConv(cfg), params[name]["mixer"]


def test_mixer_prefill_carries_its_window_and_keeps_the_tail_out(mixer):
    """Two ragged calls into slot 1 of 3 (the first starts the prompt
    over a stale window, the second continues it) against the explicit
    sum over the whole sequence; the window after each call is the last
    two REAL tokens' ``b * x``, whatever the padded tail held; the other
    slots' windows are untouched to the bit."""
    cfg, layer, p = mixer
    C, T, n1, n2 = cfg.hidden_size, CHUNK, 11, 7
    x = jax.random.normal(jax.random.PRNGKey(1), (n1 + n2, C), jnp.float32)
    junk = 9.0 + jax.random.normal(jax.random.PRNGKey(2), (T, C))
    stale = jax.random.normal(jax.random.PRNGKey(3), (2, ROWS, C))
    want, want_window = ref.short_conv(x, p, ref_cfg(cfg))
    _, mid_window = ref.short_conv(x, p, ref_cfg(cfg), state_at=n1 - 1)

    def call(leaves, rows, start):
        padded = junk.at[:len(rows)].set(rows)[None]
        return layer.apply(
            {"params": p}, padded, leaves,
            (start + jnp.arange(T, dtype=jnp.int32))[None],
            jnp.asarray([1], jnp.int32),
            jnp.asarray([len(rows)], jnp.int32))

    y1, leaves = call({"conv": stale}, x[:n1], 0)
    np.testing.assert_allclose(leaves["conv"][:, 1], mid_window, atol=1e-5)
    y2, leaves = call(leaves, x[n1:], n1)
    got = np.concatenate([y1[0, :n1], y2[0, :n2]])
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(leaves["conv"][:, 1], want_window, atol=1e-5)
    for other in (0, 2):
        np.testing.assert_array_equal(leaves["conv"][:, other],
                                      stale[:, other])
    # the window holds b * x, not x: the explicit products
    u, _ = ref.gated_input(x, p)
    np.testing.assert_allclose(leaves["conv"][:, 1], u[-2:], atol=1e-5)


def test_mixer_decode_steps_against_the_explicit_sum(mixer):
    """Token by token through the decode form from an empty window: row
    0 live every step, row 1 never (its window stays as it was, to the
    bit), row 2 live every other step (it sees every other token)."""
    cfg, layer, p = mixer
    C, n = cfg.hidden_size, 9
    x = jax.random.normal(jax.random.PRNGKey(5), (n, C), jnp.float32)
    stale = jax.random.normal(jax.random.PRNGKey(6), (2, C))
    leaves = {"conv": jnp.zeros((2, ROWS, C)).at[:, 1].set(stale)}
    step = jax.jit(lambda leaves, row, live: layer.apply(
        {"params": p}, jnp.broadcast_to(row, (ROWS, 1, C)), leaves,
        jnp.zeros((ROWS, 1), jnp.int32), jnp.arange(ROWS, dtype=jnp.int32),
        live))
    got, every_other = [], []
    for t in range(n):
        live = jnp.asarray([1, 0, t % 2 == 0], jnp.int32)
        y, leaves = step(leaves, x[t], live)
        got.append(y[0, 0])
        if t % 2 == 0:
            every_other.append(y[2, 0])
    want, window = ref.short_conv(x, p, ref_cfg(cfg))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-5)
    np.testing.assert_allclose(leaves["conv"][:, 0], window, atol=1e-5)
    np.testing.assert_array_equal(leaves["conv"][:, 1], stale)
    want2, window2 = ref.short_conv(x[::2], p, ref_cfg(cfg))
    np.testing.assert_allclose(np.stack(every_other), want2, atol=2e-5)
    np.testing.assert_allclose(leaves["conv"][:, 2], window2, atol=1e-5)


# --- experts ---------------------------------------------------------------------

def test_sigmoid_routing_eps_against_a_loop_and_its_default():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    N, M, E, top = 40, 32, 8, 3
    x = jax.random.normal(k[0], (N, M))
    router = jax.random.normal(k[1], (M, E))
    bias = 0.3 * jax.random.normal(k[2], (E,))
    w, chosen, _ = sigmoid_top_k(bias, 1.0, eps=1e-6)(x, router, top)
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @
                        np.asarray(router, np.float64)))
    c = s + np.asarray(bias, np.float64)
    for t in range(N):
        best = sorted(range(E), key=lambda e: -c[t, e])[:top]
        assert sorted(np.asarray(chosen[t])) == sorted(best)
        np.testing.assert_allclose(
            np.sort(np.asarray(w[t])),
            np.sort(s[t, best] / (s[t, best].sum() + 1e-6)), rtol=1e-5)
    # the bias moved some token's choice, or it checks nothing
    plain = sigmoid_top_k(jnp.zeros_like(bias), 1.0)(x, router, top)[1]
    assert (np.sort(plain, -1) != np.sort(chosen, -1)).any()
    # the reference's plain top-k chooses and weighs the same
    rw, rchosen = ref.route(x, {"router": router, "expert_bias": bias},
                            {"num_experts_per_tok": top,
                             "routed_scaling_factor": 1.0})
    np.testing.assert_array_equal(np.sort(rchosen, -1), np.sort(chosen, -1))
    np.testing.assert_allclose(np.sort(rw, -1), np.sort(w, -1), rtol=1e-6)
    # the keyword's default is what the function divided by before it
    # had one, to the bit
    default = sigmoid_top_k(bias, 2.5)(x, router, top)[0]
    scores = jnp.take_along_axis(
        jax.nn.sigmoid(jnp.dot(x, router, precision="highest")), chosen, -1)
    before = scores / (scores.sum(-1, keepdims=True) + 1e-20) * 2.5
    np.testing.assert_array_equal(np.asarray(default), np.asarray(before))
    assert float(jnp.abs(sigmoid_top_k(bias, 2.5, eps=1e-6)(
        x, router, top)[0] - default).max()) > 0


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The four shares' routed parts (no shared expert to count once)
    equal the uncut reference's expert layer: the program on each share
    of two experts, the reference whole. A token's weights sum to 1
    less the renormaliser's 1e-6 wherever its experts are held."""
    model, _ = tiny
    whole = dataclasses.replace(model.config, experts_held=(0, 8))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64), jnp.float32)
    mask = jnp.ones((1, 24), bool)
    p = lf.HeldExperts(whole).init(jax.random.PRNGKey(4), x, mask)["params"]
    cfg = ref_cfg(whole)
    want = np.asarray(ref.experts(x[0], p, cfg))
    total, pairs = 0.0, 0
    for first in range(0, 8, 2):
        share = dataclasses.replace(whole, experts_held=(first, 2))
        ps = dict(p, **{b: p[b][first:first + 2]
                        for b in ("w_gate", "w_up", "w_down")})
        y, counters = lf.HeldExperts(share).apply({"params": ps}, x, mask)
        total = total + np.asarray(y[0])
        pairs += int(counters.pairs_held)
        assert int(counters.pairs_routed) == 24 * 2
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert pairs == 24 * 2          # every pair fell on exactly one share
    w, _ = ref.route(x[0], p, cfg)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=2e-6)


# --- a spec whose recurrent leaves are a window alone ------------------------------

def test_the_spec_holds_pages_and_window_only_leaves(tiny):
    model, _ = tiny
    spec = model.cache_spec(ROWS, SEQ, page_size=PAGE)
    assert spec.layers == ("layers_1", "layers_4")
    assert spec.recurrent_layers == ("layers_0", "layers_2", "layers_3",
                                     "layers_5")
    assert [name for name, _, _ in spec.recurrent_leaves] == ["conv"]
    tree = kvcache.init_kv_cache(spec)
    assert set(tree["layers_1"]) == {"k", "v"}
    assert tree["layers_1"]["k"].shape == (spec.n_pages, 2, 16, PAGE)
    assert set(tree["layers_0"]) == {"conv"}
    assert tree["layers_0"]["conv"].shape == (2, ROWS, 64)
    assert spec.state_bytes_per_slot == 4 * 2 * 64 * 4


def build(tiny, **kw):
    model, params = tiny
    cfg = dict(INF)
    cfg.update(kw.pop("config", {}))
    return InferenceEngine(model, params, config=cfg, **kw)


@pytest.mark.parametrize("feature", [
    "prefix_cache", "tier", "model_axis", "speculative", "page_moves",
    "resume"])
def test_each_refusing_feature_refuses_the_spec(tiny, feature):
    """What refuses a recurrent state refuses a window-only leaf too,
    before anything is traced: none is lifted for it."""
    refused = pytest.raises(kvcache.RecurrentStateUnsupported)
    if feature == "prefix_cache":
        with refused:
            build(tiny, config={"prefix_cache": True})
    elif feature == "tier":
        with refused:
            build(tiny, config={"tier": "prefill"})
    elif feature == "model_axis":
        from deepspeed_tpu.parallel.mesh import build_mesh
        with refused:
            build(tiny, mesh=build_mesh({"model": 2, "data": 4}))
    elif feature == "speculative":
        from deepspeed_tpu.inference.speculative import build_speculative
        eng = build(tiny)
        with refused:
            build_speculative(eng, {"speculative": {"k": 2}})
    elif feature == "page_moves":
        with refused:
            build(tiny).gather_pages([1])
    else:
        with refused:
            build(tiny).prefill(0, list(range(40)), table(0), start=CHUNK)

"""Ling-3.0 (`deepspeed_tpu/models/ling_hybrid.py`: KDA mixers whose
delta-rule state forgets a channel at a time, a gated latent attention
every ``layer_group_size``-th layer, leading dense layers, sigmoid
routing through groups of experts over a share) through the serving
engine against the plain reference
(`benchmarks/suite/reference/ling_hybrid_ref.py`) at toy size: the full
forward; prefill in chunks then decode through the cache (logits, every
KDA layer's state and window, the latent pool); the four shares adding
up to the uncut layer; the group routing against a loop; a token whose
kept groups are all elsewhere; the refusals of the configuration and of
a spec that is both latent and recurrent."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.reference import ling_hybrid_ref as ref
from deepspeed_tpu.inference import cache as kvcache
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import ling_hybrid as lh
from deepspeed_tpu.moe.dropless import sigmoid_group_top_k

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
    cfg = lh.ling_hybrid_tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    model = lh.LingHybridLM(cfg)
    return model, lh.init_ling_hybrid_params(model, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["dense", "flash"])
def engine(request, tiny):
    model, params = tiny
    return InferenceEngine(model, params, config=dict(
        INF, attention_impl=request.param))


def table(row):
    per = SEQ // PAGE
    return np.arange((row + 1) * per, row * per, -1, dtype=np.int32)


def leaves_of(eng, slot):
    return {k: (np.asarray(v["kda"][slot]), np.asarray(v["conv"][:, slot]))
            for k, v in eng.cache.items() if "kda" in v}


def pool_of(eng, row, n):
    """``{layer: latents [n, width]}`` of the row's first ``n``
    positions as the engine's pool holds them."""
    out = {}
    for name, leaves in eng.cache.items():
        if "k" in leaves:
            pages = table(row)[:-(-n // PAGE)]
            out[name] = np.moveaxis(
                np.asarray(leaves["k"])[pages], -1, 1).reshape(
                    -1, leaves["k"].shape[2])[:n]
    return out


def decode_one(eng, slot, token, position):
    tokens = np.zeros(ROWS, np.int32)
    positions = np.zeros(ROWS, np.int32)
    tables = np.zeros((ROWS, SEQ // PAGE), np.int32)
    tokens[slot], positions[slot], tables[slot] = token, position, \
        table(slot)
    return np.asarray(eng.decode(tokens, positions, tables)[1][slot])


def test_presets_and_refusals():
    cfg = lh.ling_3_flash_share()
    assert cfg.layer_types == (lh.KDA,) * 5 + (lh.MLA,) + (lh.KDA,) * 2
    assert [cfg.is_dense(i) for i in range(3)] == [True, True, False]
    assert (cfg.latent_dim, cfg.qk_head_dim, cfg.key_dim) == (576, 192, 4096)
    spec = cfg.cache_spec(64, 34816, page_size=128, n_pages=4097)
    assert (spec.n_layer, spec.n_head, spec.head_dim, spec.latent_v_dim) \
        == (1, 1, 576, 512)
    assert spec.layers == ("layers_5",) and len(spec.recurrent_layers) == 7
    # 15.2 MB a slot: seven float32 states and seven windows
    assert spec.state_bytes_per_slot == \
        7 * (32 * 128 * 128 * 4 + 3 * 12288 * 2) == 15_196_160
    whole = lh.LingHybridConfig()
    assert whole.layer_types.count(lh.MLA) == 7 and \
        len(whole.layer_types) == 42
    published = (0,) * 35 + (4,) * 7
    assert lh.ling_3_flash_share(expert_swiglu_limit_list=published)
    for kw, said in [
            ({"experts_held": (14, 4)}, "experts_held"),
            ({"n_group": 3}, "n_group"), ({"topk_group": 5}, "n_group"),
            ({"kda_safe_gate": False}, "bounded gate"),
            ({"use_kda_lora": True}, "full-rank"),
            ({"q_lora_rank": 24}, "query latent"),
            ({"rope_scaling": (("type", "yarn"),)}, "plain"),
            ({"expert_swiglu_limit_list": (0, 0, 4, 0)}, "clamp"),
            ({"share_expert_swiglu_limit_list": (0, 5)}, "clamp")]:
        with pytest.raises(ValueError, match=said):
            lh.ling_hybrid_tiny(**kw)
    with pytest.raises(ValueError, match="clamp"):
        lh.LingHybridConfig(expert_swiglu_limit_list=published)


# every raggedness of the last chunk, and prompts of 1 to 3 chunks
@pytest.mark.parametrize("n", [1, 16, 17, 41])
def test_engine_against_reference(engine, tiny, n):
    """Prefill in chunks, then decode through the cache, teacher-forced:
    logits, every KDA layer's state and window and the latent pool
    against the reference's full forward. The slot was some other
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
    assert set(pool) == set(pooled) == set(model.config.names(lh.MLA))
    for name, latent in pool.items():
        np.testing.assert_allclose(latent, pooled[name], atol=5e-5)
    assert engine.compile_counts() == {"prefill": 1, "decode": 1}


def test_decode_counters_and_dead_rows(engine, tiny):
    """A step over two of three rows: the counters on the decode span's
    attributes, and the dead row's state to the bit."""
    model, _ = tiny
    cfg = model.config
    for slot in (0, 2):
        engine.prefill(slot, [3 + slot, 5, 7], table(slot))
    before = leaves_of(engine, 1)
    tokens = np.asarray([1, 0, 2], np.int32)
    positions = np.asarray([3, 0, 3], np.int32)
    tables = np.stack([table(0), np.zeros(SEQ // PAGE, np.int32), table(2)])
    from deepspeed_tpu.telemetry import spans
    engine.decode(tokens, positions, tables)
    for name, (S, window) in leaves_of(engine, 1).items():
        np.testing.assert_array_equal(S, before[name][0])
        np.testing.assert_array_equal(window, before[name][1])
    attrs = [r[3] for r in spans.recent(0) if r[0].endswith("decode")
             and r[3] and "kda_rows_live" in r[3]][-1]
    layers = sum(not cfg.is_dense(i) for i in range(cfg.num_hidden_layers))
    assert attrs["kda_rows_live"] == attrs["kda_rows_touched"] == 2
    assert attrs["moe_tokens_routed"] == 2 * layers
    assert attrs["moe_pairs_routed"] == 2 * layers * cfg.num_experts_per_tok
    assert attrs["moe_experts_held"] == cfg.experts_held[1] * layers
    assert 0 <= attrs["moe_tokens_held_group"] <= attrs["moe_tokens_routed"]
    assert attrs["moe_pairs_held"] <= attrs["moe_pairs_routed"]


def test_engine_in_bfloat16(tiny):
    """The cell's dtype through both programs: the engine's greedy
    tokens' logits lie near the float32 reference's largest."""
    model, params = tiny
    cfg = dataclasses.replace(model.config, dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16)
    bf = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 and
        a.ndim > 1 else a, params)
    eng = InferenceEngine(lh.LingHybridLM(cfg), bf, config=dict(
        INF, attention_impl="flash"))
    toks = np.random.default_rng(0).integers(0, 256, 21).tolist()
    want = np.asarray(ref.forward(bf, toks, ref_cfg(cfg))[0])
    last = eng.prefill(0, toks[:20], table(0))
    assert np.abs(last - want[19]).max() < 0.08 * np.abs(want).max()
    lg = decode_one(eng, 0, toks[20], 20)
    assert np.abs(lg - want[20]).max() < 0.08 * np.abs(want).max()


# --- experts ---------------------------------------------------------------------

def test_group_routing_against_a_loop():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    N, M, E, G, keep, top = 40, 32, 16, 4, 2, 3
    x = jax.random.normal(k[0], (N, M))
    router = jax.random.normal(k[1], (M, E))
    bias = 0.3 * jax.random.normal(k[2], (E,))
    w, chosen, aux = sigmoid_group_top_k(bias, 2.5, G, keep)(x, router, top)
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @
                        np.asarray(router, np.float64)))
    c = s + np.asarray(bias, np.float64)
    for t in range(N):
        score = [np.sort(c[t, g * 4:(g + 1) * 4])[-2:].sum()
                 for g in range(G)]
        kept = sorted(np.argsort(score)[-keep:])
        assert sorted(np.asarray(aux["kept_groups"][t])) == kept
        allowed = [e for e in range(E) if e // 4 in kept]
        best = sorted(allowed, key=lambda e: -c[t, e])[:top]
        assert sorted(np.asarray(chosen[t])) == sorted(best)
        np.testing.assert_allclose(
            np.sort(np.asarray(w[t])),
            np.sort(s[t, best] / s[t, best].sum() * 2.5), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    # the reference's loop over groups chooses the same
    p = {"router": router, "expert_bias": bias}
    cfg = {"n_group": G, "topk_group": keep, "num_experts_per_tok": top,
           "routed_scaling_factor": 2.5}
    rw, rchosen, rkept = ref.route(x, p, cfg)
    np.testing.assert_array_equal(np.sort(rchosen, -1), np.sort(chosen, -1))
    np.testing.assert_allclose(np.sort(rw, -1), np.sort(w, -1), rtol=1e-5)
    with pytest.raises(ValueError, match="groups"):
        sigmoid_group_top_k(bias, 2.5, 3, 2)(x, router, top)


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The four shares' routed parts plus the shared expert counted once
    equal the uncut reference's expert layer: the program on each share
    (a group of four experts each), the reference whole. A token's
    weights sum to ``routed_scaling_factor`` wherever its experts are
    held, and a share none of whose groups a token kept adds nothing
    for it."""
    model, _ = tiny
    whole = dataclasses.replace(model.config, experts_held=(0, 16))
    layer = lh.GroupedExperts(whole)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64), jnp.float32)
    mask = jnp.ones((1, 24), bool)
    p = layer.init(jax.random.PRNGKey(4), x, mask)["params"]
    cfg = ref_cfg(whole)
    want = np.asarray(ref.experts(x[0], p, cfg))
    shared = np.asarray(ref._blocks(
        lambda r: ref.swiglu(r, p["shared"]), x[0]))
    _, _, kept = ref.route(x[0], p, cfg)
    total, pairs, tokens_here = 0.0, 0, 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(whole, experts_held=(first, 4))
        ps = dict(p, **{b: p[b][first:first + 4]
                        for b in ("w_gate", "w_up", "w_down")})
        y, counters = lh.GroupedExperts(share).apply({"params": ps}, x, mask)
        routed_part = np.asarray(y[0]) - shared
        total = total + routed_part
        pairs += int(counters.pairs_held)
        tokens_here += int(counters.tokens_held_group)
        # a token whose kept groups are all elsewhere adds nothing here
        elsewhere = ~np.asarray((kept == first // 4).any(-1))
        assert elsewhere.any()
        assert np.abs(routed_part[elsewhere]).max() < 1e-6
        assert int(counters.tokens_held_group) == int((~elsewhere).sum())
        assert int(counters.tokens_routed) == 24
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    assert pairs == 24 * 3          # every pair fell on exactly one share
    assert tokens_here == 24 * 2    # every token kept two of four groups
    w, _, _ = ref.route(x[0], p, cfg)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)


# --- a spec that is both ---------------------------------------------------------

def test_the_spec_holds_a_latent_pool_and_recurrent_leaves(tiny):
    model, _ = tiny
    spec = model.cache_spec(ROWS, SEQ, page_size=PAGE)
    assert spec.latent_v_dim == 32 and spec.layers == ("layers_2",)
    assert spec.recurrent_layers == ("layers_0", "layers_1", "layers_3")
    tree = kvcache.init_kv_cache(spec)
    assert set(tree["layers_2"]) == {"k"}
    assert tree["layers_2"]["k"].shape == (spec.n_pages, 1, 40, PAGE)
    assert set(tree["layers_0"]) == {"kda", "conv"}
    assert tree["layers_0"]["kda"].shape == (ROWS, 4, 16, 16)
    assert tree["layers_0"]["conv"].shape == (3, ROWS, 3 * 64)


def build(tiny, **kw):
    model, params = tiny
    cfg = dict(INF)
    cfg.update(kw.pop("config", {}))
    return InferenceEngine(model, params, config=cfg, **kw)


@pytest.mark.parametrize("feature,both", [
    ("prefix_cache", False), ("tier", True), ("model_axis", True),
    ("speculative", True), ("codec", False), ("page_moves", False),
    ("resume", False), ("partition", False)])
def test_each_refusing_feature_refuses_the_spec(tiny, feature, both):
    """What refuses a recurrent state or a latent pool refuses this
    model before anything is traced; what refuses both says both
    reasons in one error that either name catches."""
    model, params = tiny
    if feature == "prefix_cache":
        with pytest.raises(kvcache.RecurrentStateUnsupported):
            build(tiny, config={"prefix_cache": True})
        return
    if feature == "codec":
        with pytest.raises(kvcache.LatentPoolUnsupported):
            build(tiny, config={"kv_cache_dtype": "int8"})
        return
    if feature == "partition":
        with pytest.raises(kvcache.LatentPoolUnsupported):
            kvcache.kv_partition_specs(model.cache_spec(
                ROWS, SEQ, page_size=PAGE))
        return
    if feature == "tier":
        with pytest.raises(kvcache.LatentAndRecurrentUnsupported) as e:
            build(tiny, config={"tier": "prefill"})
    elif feature == "model_axis":
        from deepspeed_tpu.parallel.mesh import build_mesh
        mesh = build_mesh({"model": 2, "data": 4})
        with pytest.raises(kvcache.LatentAndRecurrentUnsupported) as e:
            build(tiny, mesh=mesh)
    elif feature == "speculative":
        from deepspeed_tpu.inference.speculative import build_speculative
        eng = build(tiny)
        with pytest.raises(kvcache.LatentAndRecurrentUnsupported) as e:
            build_speculative(eng, {"speculative": {"k": 2}})
    else:
        eng = build(tiny)
        if feature == "page_moves":
            with pytest.raises(kvcache.RecurrentStateUnsupported):
                eng.gather_pages([1])
        else:
            with pytest.raises(kvcache.RecurrentStateUnsupported):
                eng.prefill(0, list(range(40)), table(0), start=CHUNK)
        return
    assert both
    assert isinstance(e.value, kvcache.RecurrentStateUnsupported)
    assert isinstance(e.value, kvcache.LatentPoolUnsupported)
    assert "state" in str(e.value) and "latent" in str(e.value)

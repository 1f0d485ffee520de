"""`models/mla_moe.py` (latent attention over sigmoid-routed experts held
as a share) against its plain reference
(`benchmarks/suite/reference/mla_moe_ref.py`), at the tiny widths, in
float32 on the CPU: the engine's prefill-then-decode logits through a
latent pool against the reference's full forward; the absorbed and the
expanded form of one attention layer; YaRN against its closed form; the
sigmoid router with a bias that changes the choice; the guide's share
test (the shares' routed parts and the shared expert once add up to the
uncut layer); `moe/dropless.py`'s held rows zero by construction and
OLMoE's call unchanged bit for bit; what refuses a latent pool by type,
and the prefix cache and park/resume, which move latent pages like any
others.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.suite.reference import mla_moe_ref as ref
from deepspeed_tpu.inference.cache import (LatentPoolUnsupported,
                                           init_kv_cache, paged_read_kv,
                                           paged_write_kv)
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import mla_moe as mm
from deepspeed_tpu.moe import dropless

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
ENGINE = dict(max_batch=4, seq_buckets=(64,), prefill_chunk=16, page_size=8,
              attention_block_k=8)


def ref_cfg(cfg, **kw):
    """The reference's dict of a program config (the configuration
    file's keys)."""
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    out.update(rope_scaling=dict(cfg.rope_scaling),
               n_layer=cfg.num_hidden_layers,
               assumed={"experts_held": list(cfg.experts_held)}, **kw)
    return out


@pytest.fixture(scope="module")
def tiny():
    cfg = mm.mla_moe_tiny(**F32)
    model = mm.MlaMoeLM(cfg)
    return cfg, model, mm.init_mla_moe_params(model, jax.random.PRNGKey(0))


def published():
    """The published widths' numbers (no array is made)."""
    return mm.kimi_k2_share()


# --- YaRN ------------------------------------------------------------------

def test_yarn_scale_and_frequencies_against_the_closed_form():
    cfg = published()
    # s = 192^-0.5 (0.1 ln 64 + 1)^2 = 0.07217 x 2.0047
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert cfg.softmax_scale == pytest.approx(0.1447, abs=5e-5)
    inv = mm.yarn_inv_freq(64, 50000.0, mm.YARN)
    plain = 50000.0 ** -(np.arange(32) / 32)
    # the correction dimensions of beta_fast 32 and beta_slow 1 over a
    # context of 4096: 8.9 and 19.2, floored and ceiled
    low, high = 8, 20
    np.testing.assert_allclose(inv[:low + 1], plain[:low + 1], rtol=1e-12)
    np.testing.assert_allclose(inv[high:], plain[high:] / 64, rtol=1e-12)
    mid = (low + high) // 2             # half way up the ramp
    assert inv[mid] == pytest.approx(0.5 * plain[mid] * (1 + 1 / 64))
    assert np.all(np.diff(inv) < 0)
    # the reference writes it from the definition on its own
    np.testing.assert_allclose(
        ref.yarn_inv_freq(ref_cfg(cfg)), inv, rtol=1e-12)
    assert ref.softmax_scale(ref_cfg(cfg)) == pytest.approx(
        cfg.softmax_scale)
    # cos and sin carry mscale / mscale_all_dim = 1 here
    cos, sin = mm.yarn_cos_sin(cfg, jnp.asarray([[0, 5000]]))
    np.testing.assert_allclose(np.asarray(cos[0, 0]), 1.0)
    np.testing.assert_allclose(np.asarray(sin[0, 1]),
                               np.sin(5000 * inv), atol=2e-3)


def test_the_share_is_the_published_model_cut_as_the_issue_says():
    cfg = published()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank) == (7168, 64, 1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.experts_held, cfg.vocab_size) == (384, 8, (0, 12), 20480)
    assert [cfg.is_dense(i) for i in range(3)] == [True, 0, 0]
    spec = cfg.cache_spec(32, 17408, page_size=128, n_pages=2049)
    assert (spec.n_head, spec.head_dim, spec.latent_v_dim) == (1, 576, 512)
    # 8,064 B a token over 7 layers: 2.11 GB of latents
    shapes = jax.eval_shape(lambda: init_kv_cache(spec))
    assert set(shapes["layers_3"]) == {"k"}
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(shapes))
    assert nbytes == 7 * 2049 * 576 * 128 * 2


# --- the engine against the reference's full forward -----------------------

@pytest.mark.parametrize("chunk", [16, 8])
@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_engine_prefill_then_decode_matches_the_reference(tiny, impl, chunk):
    """Two ragged prompts over several chunks and pages (chunks of two
    pages and of one), the second into pages a longer prompt has used (a
    recycled page: its stale tail is past the new prompt's positions),
    both then decoded together: every logit against the reference's
    forward of the whole sequence."""
    cfg, model, params = tiny
    eng = InferenceEngine(model, params, config=dict(
        ENGINE, attention_impl=impl, prefill_chunk=chunk))
    rng = np.random.default_rng(3)
    rows = {1: rng.integers(0, 256, 37).tolist(),
            3: rng.integers(0, 256, 21).tolist()}
    tables = np.zeros((4, eng.pages_per_row), np.int32)
    tables[1] = np.arange(1, 9)
    tables[3] = np.arange(16, 8, -1)
    eng.prefill(3, rng.integers(0, 256, 50).tolist(), tables[3])
    got = {r: [eng.prefill(r, p, tables[r])] for r, p in rows.items()}
    seqs = {r: list(p) for r, p in rows.items()}
    toks, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
    for r in rows:
        toks[r], pos[r] = int(got[r][0].argmax()), len(rows[r])
    for _ in range(5):
        for r in rows:
            seqs[r].append(int(toks[r]))
        nxt, logits = eng.decode(toks, pos, tables)
        for r in rows:
            got[r].append(logits[r].copy())
            toks[r], pos[r] = nxt[r], pos[r] + 1
    assert eng.compile_counts() == {"prefill": 1, "decode": 1}
    for r, prompt in rows.items():
        at = np.arange(len(prompt) - 1, len(prompt) + 5)
        want = np.asarray(ref.forward(params, seqs[r], ref_cfg(cfg),
                                      rows=at)[0])
        np.testing.assert_allclose(np.stack(got[r]), want, atol=2e-5)


def test_decode_span_counts_the_pairs_of_live_rows(tiny):
    from deepspeed_tpu.telemetry import spans
    cfg, model, params = tiny
    eng = InferenceEngine(model, params, config=dict(
        ENGINE, attention_impl="flash"))
    tables = np.zeros((4, eng.pages_per_row), np.int32)
    tables[0], tables[2] = np.arange(1, 9), np.arange(9, 17)
    for r in (0, 2):
        eng.prefill(r, [5, 6, 7], tables[r])
    t0 = spans.clock()
    eng.decode(np.ones(4, np.int32), np.full(4, 3, np.int32), tables)
    attrs = [r for r in spans.recent(t0) if r[0] == "decode"][-1][3]
    layers = sum(not cfg.is_dense(i) for i in range(cfg.num_hidden_layers))
    assert attrs["moe_pairs_routed"] == 2 * cfg.num_experts_per_tok * layers
    assert 0 <= attrs["moe_pairs_held"] <= attrs["moe_pairs_routed"]
    assert attrs["moe_experts_touched"] <= min(
        attrs["moe_pairs_held"], layers * cfg.experts_held[1])
    assert attrs["kv_rows_written"] == 2


def test_reference_walks_the_stream_in_blocks(tiny, monkeypatch):
    """The reference's layers take the stream a block of tokens at a
    time, over every token's latents: blocks of 24 over 83 tokens (the
    last one padded) give what one block of all 83 gives, the logits,
    every layer's latents and one attention layer on its own input."""
    cfg, _, params = tiny
    seq = np.random.default_rng(5).integers(0, 256, 83)
    x = jax.random.normal(jax.random.PRNGKey(2), (83, cfg.hidden_size))
    p = params["layers_0"]["attn"]
    whole = ref.forward(params, seq, ref_cfg(cfg))
    whole_attn = ref.attention(x, p, ref_cfg(cfg))
    monkeypatch.setattr(ref, "TOKEN_BLOCK", 24)
    logits, lats = ref.forward(params, seq, ref_cfg(cfg))
    assert logits.shape == (83, cfg.vocab_size)
    np.testing.assert_allclose(logits, whole[0], atol=2e-5)
    for name, lat in lats.items():
        assert lat.shape == (83, cfg.latent_dim)
        np.testing.assert_allclose(lat, whole[1][name], atol=1e-5)
    np.testing.assert_allclose(ref.attention(x, p, ref_cfg(cfg)),
                               whole_attn, atol=2e-6)


# --- one attention layer ---------------------------------------------------

@pytest.mark.parametrize("walk_block", [1024, 16])
def test_absorbed_and_expanded_attention_agree_with_the_reference(
        tiny, monkeypatch, walk_block):
    """Chunks through the block walk (expanded; the whole row one block,
    and blocks of two pages under the running max and sum), then one
    token through the decode step (absorbed: the kernel and the dense
    oracle)."""
    from deepspeed_tpu.inference import cache
    monkeypatch.setattr(cache, "WALK_BLOCK", walk_block)
    cfg, _, params = tiny
    p = params["layers_0"]["attn"]
    spec = dataclasses.replace(cfg, num_hidden_layers=1).cache_spec(
        1, 64, page_size=8)
    x = jax.random.normal(jax.random.PRNGKey(1), (41, cfg.hidden_size))
    table = jnp.arange(8, 0, -1, dtype=jnp.int32)[None]

    def run(impl):
        layer, pool, ys = mm.LatentAttention(cfg), init_kv_cache(spec)[
            "layers_0"], []
        for lo, hi in ((0, 16), (16, 32), (32, 40), (40, 41)):
            pos = jnp.arange(lo, hi, dtype=jnp.int32)[None]
            y, pool = layer.apply(
                {"params": p}, x[None, lo:hi], pool, pos, table,
                mm.yarn_cos_sin(cfg, pos), {"impl": impl, "block_k": 8})
            ys.append(y[0])
        return np.asarray(jnp.concatenate(ys)), pool

    want = np.asarray(ref.attention(x, p, ref_cfg(cfg)))
    pools = []
    for impl in ("dense", "flash"):
        got, pool = run(impl)
        np.testing.assert_allclose(got, want, atol=3e-6, err_msg=impl)
        pools.append(np.asarray(pool["k"]))
    # the pool holds the reference's latents, whatever wrote the step's
    lat = np.asarray(ref.latents(x, p, ref_cfg(cfg)))
    k, none = paged_read_kv({"k": jnp.asarray(pools[0])}, table,
                            jnp.float32)
    assert none is None
    np.testing.assert_allclose(np.asarray(k)[0, :41, 0], lat, atol=2e-6)
    for other in pools[1:]:
        np.testing.assert_allclose(other, pools[0], atol=2e-6)


@pytest.mark.parametrize("rows,expand", [(1, False), (2, True)])
def test_a_latent_chunk_is_one_row_through_expand(rows, expand):
    """Several tokens at once over a latent pool are one prompt's chunk,
    expanded block by block: no absorbed walk and no `[heads, chunk,
    bucket]` dense path stands behind a call that says otherwise."""
    from deepspeed_tpu.inference.cache import cached_attention
    cfg = mm.mla_moe_tiny(**F32)
    spec = cfg.cache_spec(2, 32, page_size=8)
    pool = init_kv_cache(spec)["layers_0"]
    H, D = cfg.num_attention_heads, cfg.latent_dim
    q = jnp.zeros((rows, 4, H, D))
    lat = jnp.zeros((rows, 4, 1, D))
    pos = jnp.tile(jnp.arange(4, dtype=jnp.int32), (rows, 1))
    table = jnp.arange(1, 1 + 4 * rows, dtype=jnp.int32).reshape(rows, 4)
    fn = (lambda l: (l[:, None, :8].repeat(H, 1),) * 2) if expand else None
    with pytest.raises(ValueError, match="one prompt's chunk"):
        cached_attention(q, lat, None, pool, pos, jnp.float32, table,
                         scale=cfg.softmax_scale, v_dim=cfg.kv_lora_rank,
                         expand=fn)


def test_latent_leaf_write_and_read():
    """`paged_write_kv` on a pool of one leaf: a chunk inside a page, a
    chunk of several pages, a decode step's tokens; `paged_read_kv`
    gives the latents back and no values."""
    spec = mm.mla_moe_tiny().cache_spec(2, 32, page_size=8)
    pool = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), init_kv_cache(spec)["layers_0"])
    assert set(pool) == {"k"} and pool["k"].shape == (9, 1, 40, 8)
    rng = np.random.default_rng(0)
    table = jnp.asarray([[3, 1, 4, 2], [8, 7, 6, 5]], jnp.int32)
    want = np.zeros((2, 32, 40), np.float32)

    def write(pool, row, lo, n):
        vals = rng.standard_normal((1, n, 1, 40)).astype(np.float32)
        want[row, lo:lo + n] = vals[0, :, 0]
        pos = jnp.arange(lo, lo + n, dtype=jnp.int32)[None]
        return paged_write_kv(pool, jnp.asarray(vals), None, pos,
                              table[row:row + 1])

    pool = write(pool, 0, 0, 16)        # two whole pages
    pool = write(pool, 0, 16, 4)        # inside a page
    pool = write(pool, 1, 0, 8)
    step = rng.standard_normal((2, 1, 1, 40)).astype(np.float32)
    want[0, 20], want[1, 8] = step[0, 0, 0], step[1, 0, 0]
    pool = paged_write_kv(pool, jnp.asarray(step), None,
                          jnp.asarray([[20], [8]], jnp.int32), table)
    k, v = paged_read_kv(pool, table, jnp.float32)
    assert v is None
    np.testing.assert_array_equal(np.asarray(k)[:, :, 0], want)
    with pytest.raises(ValueError, match="latents alone"):
        paged_write_kv(pool, jnp.asarray(step), jnp.asarray(step),
                       jnp.asarray([[20], [8]], jnp.int32), table)


# --- the experts -----------------------------------------------------------

def _expert_params(cfg, key, held=None):
    c = dataclasses.replace(cfg, experts_held=held or cfg.experts_held)
    x = jnp.zeros((1, 4, cfg.hidden_size), cfg.dtype)
    return mm.HeldExperts(c).init(
        {"params": key}, x, jnp.ones((1, 4), bool))["params"]


def test_sigmoid_router_against_the_reference_with_a_bias_that_moves_it(
        tiny):
    cfg, _, params = tiny
    p = params["layers_1"]["experts"]
    x = jax.random.normal(jax.random.PRNGKey(2), (200, cfg.hidden_size))
    route = dropless.sigmoid_top_k(p["e_score_correction_bias"],
                                   cfg.routed_scaling_factor, True)
    w, chosen, aux = route(x, p["router"], cfg.num_experts_per_tok)
    rw, rchosen = ref.route(x, p, ref_cfg(cfg))
    assert aux == {}
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(rchosen))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), rtol=1e-6)
    # renormalised, then scaled
    np.testing.assert_allclose(np.asarray(w).sum(-1),
                               cfg.routed_scaling_factor, rtol=1e-5)
    # the bias moves the choice and not the weights: without it other
    # experts are chosen for a share of the tokens, and where the same
    # are chosen their weights are the same
    plain = dropless.sigmoid_top_k(jnp.zeros_like(
        p["e_score_correction_bias"]), cfg.routed_scaling_factor, True)
    w0, chosen0, _ = plain(x, p["router"], cfg.num_experts_per_tok)
    same = np.all(np.sort(chosen, -1) == np.sort(chosen0, -1), -1)
    assert 0.05 < 1 - same.mean() < 0.95
    np.testing.assert_allclose(np.sort(np.asarray(w)[same], -1),
                               np.sort(np.asarray(w0)[same], -1),
                               rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: the four shares' routed parts and the
    shared expert once are the uncut reference's layer output."""
    cfg, _, _ = tiny
    E = cfg.n_routed_experts
    full = _expert_params(cfg, jax.random.PRNGKey(4), held=(0, E))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.hidden_size))
    flat = x.reshape(-1, cfg.hidden_size)
    mask = jnp.ones((2, 24), bool)
    want = np.asarray(ref.experts(flat, full, ref_cfg(cfg), 0))
    shared = np.asarray(ref.mlp(flat, full["shared"]))
    total, pairs = 0.0, 0
    for first in range(0, E, 4):
        c = dataclasses.replace(cfg, experts_held=(first, 4))
        share = dict(full, **{k: full[k][first:first + 4]
                              for k in ("w_gate", "w_up", "w_down")})
        y, counters = mm.HeldExperts(c).apply({"params": share}, x, mask)
        # a share against the reference given the same share
        np.testing.assert_allclose(
            np.asarray(y).reshape(want.shape),
            np.asarray(ref.experts(flat, share, ref_cfg(cfg), first)),
            atol=1e-5)
        total = total + np.asarray(y).reshape(want.shape) - shared
        pairs += int(counters.pairs_held)
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    # every pair fell on exactly one share
    assert pairs == 48 * cfg.num_experts_per_tok


def test_rows_behind_the_held_groups_are_zero_by_construction(
        tiny, monkeypatch):
    """The grouped matmuls leave rows past the groups' sum as they found
    them. Here they find NaN there: the layer's output may not know."""
    cfg, _, params = tiny
    p = params["layers_1"]["experts"]
    x = jax.random.normal(jax.random.PRNGKey(6), (40, cfg.hidden_size))
    mask = jnp.arange(40) % 5 != 0
    args = (x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            cfg.num_experts_per_tok)
    kw = dict(route=dropless.sigmoid_top_k(
        p["e_score_correction_bias"], cfg.routed_scaling_factor),
        first_expert=cfg.experts_held[0], token_mask=mask)
    want, stats = dropless.dropless_moe(*args, **kw)
    real = dropless.grouped_matmul

    def leaves_garbage(rows, bank, group_sizes):
        out = real(rows, bank, group_sizes)
        live = jnp.arange(out.shape[0]) < group_sizes.sum()
        return jnp.where(live[:, None], out, jnp.nan)

    monkeypatch.setattr(dropless, "grouped_matmul", leaves_garbage)
    got, again = dropless.dropless_moe(*args, **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # masked tokens route nowhere, and every pair is held or dropped
    held = int(stats["tokens_per_expert"].sum())
    assert stats["tokens_per_expert"].shape == (cfg.experts_held[1],)
    assert held + int(stats["dropped"]) == 40 * cfg.num_experts_per_tok
    assert not np.asarray(want)[~np.asarray(mask)].any()
    rw, rchosen = ref.route(x, p, ref_cfg(cfg))
    first, n = cfg.experts_held
    mine = (np.asarray(rchosen) >= first) & (np.asarray(rchosen) < first + n)
    assert held == int((mine & np.asarray(mask)[:, None]).sum())


def _old_dropless_moe(x, router, w_gate, w_up, w_down, top_k):
    """`moe/dropless.py:_dropless_moe` as PR 33 left it, for the
    bit-for-bit test."""
    n_tokens, n_experts = x.shape[0], router.shape[1]
    logits = dropless.router_logits(x, router)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    pair_expert = experts.reshape(-1)
    group_sizes = jnp.zeros((n_experts,), jnp.int32).at[pair_expert].add(1)
    order = jnp.argsort(pair_expert, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.size, dtype=jnp.int32), unique_indices=True)
    rows = dropless._gather_tokens(x, order, inverse, top_k)
    dt = x.dtype
    hidden = jax.nn.silu(
        dropless.grouped_matmul(rows, w_gate.astype(dt), group_sizes)) * \
        dropless.grouped_matmul(rows, w_up.astype(dt), group_sizes)
    out = dropless.grouped_matmul(hidden, w_down.astype(dt), group_sizes)
    out = dropless._gather_pairs(out, order, inverse).reshape(
        n_tokens, top_k, -1)
    y = jnp.einsum("nk,nkm->nm", weights, out.astype(jnp.float32))
    return y.astype(x.dtype), probs.sum(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_olmoe_dropless_moe_is_unchanged_bit_for_bit(dtype):
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    N, M, I, E, k = 64, 32, 16, 8, 2
    x = jax.random.normal(keys[0], (N, M), dtype)
    router = 0.5 * jax.random.normal(keys[1], (M, E))
    banks = [0.2 * jax.random.normal(key, shape) for key, shape in zip(
        keys[2:], ((E, M, I), (E, M, I), (E, I, M)))]

    def loss(fn):
        def f(x, router, *banks):
            y, extra = fn(x, router, *banks, k)
            prob_sum = extra["prob_sum"] if isinstance(extra, dict) \
                else extra
            return (y.astype(jnp.float32) ** 2).sum() + \
                (prob_sum ** 2).sum(), y
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))

    (new_loss, new_y), new_grads = loss(dropless.dropless_moe)(
        x, router, *banks)
    (old_loss, old_y), old_grads = loss(_old_dropless_moe)(
        x, router, *banks)
    assert np.asarray(new_y).tobytes() == np.asarray(old_y).tobytes()
    assert float(new_loss) == float(old_loss)
    for a, b in zip(new_grads, old_grads):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# --- what refuses a latent pool, and what simply works ----------------------

def test_typed_refusals(tiny):
    cfg, model, params = tiny
    for codec in ("int8", "f8e4m3fn"):
        with pytest.raises(LatentPoolUnsupported, match="scale"):
            InferenceEngine(model, params, config=dict(
                ENGINE, kv_cache_dtype=codec))
    mesh = jax.make_mesh((2,), ("model",), devices=jax.devices()[:2])
    with pytest.raises(LatentPoolUnsupported, match="one head"):
        InferenceEngine(model, params, config=ENGINE, mesh=mesh)
    with pytest.raises(LatentPoolUnsupported, match="speculative"):
        InferenceEngine(model, params, config=dict(
            ENGINE, speculative={"k": 2, "draft_layers": 1}))
    for tier in ("prefill", "decode"):
        with pytest.raises(LatentPoolUnsupported, match="tier"):
            InferenceEngine(model, params, config=dict(ENGINE, tier=tier))
    # a share's exchange between chips is not written
    from deepspeed_tpu.ops.pallas.flash_attention import placed_on_mesh
    p = params["layers_1"]["experts"]
    data = jax.make_mesh((2,), ("data",), devices=jax.devices()[:2])
    with placed_on_mesh(data, "data", None):
        with pytest.raises(dropless.ExpertExchangeUnsupported):
            dropless.dropless_moe(
                jnp.zeros((8, cfg.hidden_size)), p["router"], p["w_gate"],
                p["w_up"], p["w_down"], 2, first_expert=4)
    from deepspeed_tpu.inference.cache import kv_partition_specs
    with pytest.raises(LatentPoolUnsupported, match="one head"):
        kv_partition_specs(cfg.cache_spec(4, 64, page_size=8))
    # plain storage overrides are pools of latents like any other
    eng = InferenceEngine(model, params, config=dict(
        ENGINE, kv_cache_dtype="bf16"))
    assert eng.cache["layers_0"]["k"].dtype == jnp.bfloat16
    # the kernel keeps two slots of ONE block a latent pool, and the
    # step's one new lane: the published sizes fit a v5e's VMEM 25 times
    from deepspeed_tpu.ops.pallas.flash_decode import (PAGED_VMEM_BUDGET,
                                                       paged_vmem_bytes)
    need = paged_vmem_bytes(1, 576, 128, jnp.bfloat16, False, latent=True)
    assert need == 2 * 576 * 128 * 2 + 2 * 576 * 128 * 4 + 4 * 576 * 4
    assert need < PAGED_VMEM_BUDGET / 16
    assert need < paged_vmem_bytes(1, 576, 128, jnp.bfloat16, False)


def _serve(engine, requests):
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler, Request)
    sched = ContinuousBatchingScheduler(engine)
    done = {}
    for rid, prompt, session in requests:
        out = sched.run([Request(rid=rid, prompt=prompt, max_new_tokens=6,
                                 session_id=session)])
        done[rid] = list(out[-1].tokens)
    return done, sched


def test_prefix_cache_shares_latent_pages(tiny):
    """Pages are pages: a prompt asked again skips the chunks its shared
    pages hold, and the chunk that runs walks over them."""
    cfg, model, params = tiny
    prompt = np.random.default_rng(8).integers(0, 256, 45).tolist()
    eng = InferenceEngine(model, params, config=dict(
        ENGINE, attention_impl="flash"))
    assert eng.prefix_cache
    done, sched = _serve(eng, [("a", prompt, None), ("b", prompt, None),
                               ("c", prompt[:32] + [1, 2, 3], None)])
    assert sched.paging.prefix_hits == 2
    assert done["a"] == done["b"]
    alone, _ = _serve(InferenceEngine(model, params, config=dict(
        ENGINE, attention_impl="flash", prefix_cache=False)),
        [("c", prompt[:32] + [1, 2, 3], None)])
    assert done["c"] == alone["c"]


def test_park_and_resume_move_latent_pages_through_the_host(tiny):
    cfg, model, params = tiny
    prompt = np.random.default_rng(9).integers(0, 256, 30).tolist()
    eng = InferenceEngine(model, params, config=dict(
        ENGINE, attention_impl="flash", host_park_threshold=0.99))
    done, sched = _serve(eng, [("a", prompt, "s")])
    follow = prompt + done["a"] + [7, 8, 9]
    again, _ = _serve(InferenceEngine(model, params, config=dict(
        ENGINE, attention_impl="flash")), [("b", follow, None)])
    from deepspeed_tpu.inference.scheduler import Request
    out = sched.run([Request(rid="b", prompt=follow, max_new_tokens=6,
                             session_id="s")])
    facts = sched.paging.facts()
    assert facts["pages_evacuated"] > 0 and facts["pages_paged_in"] > 0
    assert sched.paging.sessions_resumed == 1
    assert list(out[-1].tokens) == again["b"]

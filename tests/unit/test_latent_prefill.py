"""The prefill kernel over a latent pool
(`ops/pallas/latent_prefill.py`, interpret mode on the CPU) at the
published head sizes (keys 128 + 64, values 128): one block of the walk
against the walk's own arithmetic for each thing a block can be to the
mask; one attention layer's chunks under ``"flash"`` against the XLA
walk (``"dense"``) and against the float32 reference
(`benchmarks/suite/reference/mla_moe_ref.py`); which path each ``impl``
takes, and that no ``[heads, chunk, block]`` array stands outside the
kernel; the prefill span's counters.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.suite.reference import mla_moe_ref as ref
from deepspeed_tpu.inference import cache
from deepspeed_tpu.inference.cache import init_kv_cache
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import mla_moe as mm
from deepspeed_tpu.ops.pallas.latent_prefill import (
    flash_prefill_latent_block)
from deepspeed_tpu.telemetry import spans
from tests.unit.test_mla_moe import ENGINE, F32, ref_cfg

HEADS, PAGE, BLOCK = 2, 32, 256     # a block of the walk: 8 pages


def head_sized(**kw):
    """The tiny model with the published head sizes."""
    return mm.mla_moe_tiny(num_attention_heads=HEADS, qk_nope_head_dim=128,
                           qk_rope_head_dim=64, v_head_dim=128,
                           max_position_embeddings=2048, **kw)


@pytest.fixture(scope="module")
def layer_params():
    cfg = head_sized(**F32)
    spec = dataclasses.replace(cfg, num_hidden_layers=1).cache_spec(
        1, BLOCK, page_size=PAGE)
    pos = jnp.arange(8, dtype=jnp.int32)[None]
    return mm.LatentAttention(cfg).init(
        {"params": jax.random.PRNGKey(3)},
        jnp.zeros((1, 8, cfg.hidden_size)), init_kv_cache(spec)["layers_0"],
        pos, jnp.ones((1, BLOCK // PAGE), jnp.int32),
        mm.yarn_cos_sin(cfg, pos), {"impl": "dense", "block_k": PAGE})[
            "params"]


# --- one block -------------------------------------------------------------

def _walk_block(q, kn, kr, v, carry, q0, k0, scale):
    """`cache.latent_prefill_attention`'s own step, in XLA."""
    m_prev, l_prev, acc = carry
    H, S = kn.shape[:2]
    kb = jnp.concatenate([kn, jnp.broadcast_to(kr, (H,) + kr.shape)], -1)
    s = jnp.einsum("htd,hsd->hts", q, kb,
                   preferred_element_type=jnp.float32)
    k_pos, q_pos = k0 + jnp.arange(S), q0 + jnp.arange(q.shape[1])
    s = jnp.where(k_pos[None, None, :] <= q_pos[None, :, None], s * scale,
                  jnp.finfo(jnp.float32).min)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    pr, corr = jnp.exp(s - m_new), jnp.exp(m_prev - m_new)
    pv = jnp.einsum("hts,hsv->htv", pr.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    return m_new, l_prev * corr + pr.sum(-1, keepdims=True), acc * corr + pv


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk,q_start,k_start", [
    (256, 512, 256),     # wholly before the chunk: not masked
    (512, 512, 512),     # the diagonal: four strips of queries
    (256, 400, 256),     # a square block crossed off the diagonal
    (128, 384, 256),     # a chunk that ends inside the block
    (128, 256, 256),     # ... and one that starts where the block does
    (256, 0, 0),         # the first block of all: the carry is empty
], ids=["under", "diagonal", "crossing", "ends-inside", "starts-with",
        "first"])
def test_one_block_is_the_walks_own_step(dtype, chunk, q_start, k_start):
    BLOCK = 512 if chunk == 512 else 256    # strips need 4 x 128 lanes
    ks = jax.random.split(jax.random.PRNGKey(q_start + chunk), 4)
    normal = lambda k, *shape: jax.random.normal(  # noqa: E731
        k, shape, jnp.float32).astype(dtype)
    q = normal(ks[0], HEADS, chunk, 192)
    kn, kr = normal(ks[1], HEADS, BLOCK, 128), normal(ks[2], BLOCK, 64)
    v = normal(ks[3], HEADS, BLOCK, 128)
    carry = (jnp.full((HEADS, chunk, 1), -jnp.inf), jnp.zeros(
        (HEADS, chunk, 1)), jnp.zeros((HEADS, chunk, 128)))
    if k_start:
        # a carry that has taken a block in: the correction matters
        carry = _walk_block(q, kn[:, ::-1], kr[::-1], v[:, ::-1], carry,
                            q_start, 0, 0.1447)
    want = _walk_block(q, kn, kr, v, carry, q_start, k_start, 0.1447)
    # the kernel's layout: queries and values a position a lane, the
    # keys as their product lies
    lanes = lambda a: jnp.swapaxes(a, 1, 2)     # noqa: E731
    got = flash_prefill_latent_block(
        lanes(q), jnp.swapaxes(kn, 0, 1), kr, lanes(v),
        tuple(lanes(a) for a in carry), q_start, k_start, scale=0.1447)
    tol = 1e-5 if dtype == jnp.float32 else 2e-3
    for name, g, w in zip("m l acc".split(), got, want):
        np.testing.assert_allclose(lanes(g), w, rtol=tol, atol=tol * float(
            np.abs(w).max()), err_msg=name)


# --- one attention layer ---------------------------------------------------

def run_layer(cfg, p, x, n_table, table, chunks, impl, dirty=False):
    """``x`` ``[n, hidden]`` through the layer in ``chunks`` (``lo,
    hi``; rows past ``n`` are padding) into a pool of its own."""
    spec = dataclasses.replace(cfg, num_hidden_layers=1).cache_spec(
        1, n_table * PAGE, page_size=PAGE, n_pages=table.max() + 1)
    layer = mm.LatentAttention(cfg)
    padded = jnp.zeros((chunks[-1][1], x.shape[1]), x.dtype).at[
        :x.shape[0]].set(x)

    @jax.jit
    def program(p, padded):
        pool = init_kv_cache(spec)["layers_0"]
        if dirty:
            # every page held a tenant before, and what it left is large
            pool = {"k": 40.0 * jax.random.normal(
                jax.random.PRNGKey(9), pool["k"].shape, pool["k"].dtype)}
        ys = []
        for lo, hi in chunks:
            pos = jnp.arange(lo, hi, dtype=jnp.int32)[None]
            y, pool = layer.apply(
                {"params": p}, padded[None, lo:hi], pool, pos,
                jnp.asarray(table)[None], mm.yarn_cos_sin(cfg, pos),
                {"impl": impl, "block_k": PAGE})
            ys.append(y[0])
        return jnp.concatenate(ys)[:x.shape[0]]

    return np.asarray(program(p, padded), np.float32)


def chunks_of(n, chunk):
    return [(lo, lo + chunk) for lo in range(0, n, chunk)]


CASES = {
    # the diagonal block alone (blocks of 512: in four strips)
    "empty-prefix": dict(n=512, chunk=512, n_table=16, block=512),
    # the third chunk walks two blocks before its own
    "prefix-of-blocks": dict(n=1536, chunk=512, n_table=48, block=512),
    # the last chunk holds 88 tokens and 168 rows of padding
    "ragged-last-chunk": dict(n=600, chunk=256, n_table=24),
    # pages handed over in descending order, each with a tenant's
    # remains, the last live block half stale
    "reused-pages-descending": dict(n=384, chunk=128, n_table=16,
                                    descending=True, dirty=True),
    # chunks of half a block: every other prefix ends inside one
    "prefix-ends-inside-a-block": dict(n=640, chunk=128, n_table=24),
}


@pytest.mark.parametrize("case", list(CASES))
def test_flash_chunks_against_the_walk_and_the_reference(
        layer_params, monkeypatch, case):
    c = dict(CASES[case])
    monkeypatch.setattr(cache, "WALK_BLOCK", c.get("block", BLOCK))
    cfg, n, n_table = head_sized(**F32), c["n"], c["n_table"]
    table = np.arange(1, n_table + 1, dtype=np.int32)
    if c.get("descending"):
        table = table[::-1].copy()
    x = jax.random.normal(jax.random.PRNGKey(n), (n, cfg.hidden_size))
    want = np.asarray(ref.attention(x, layer_params, ref_cfg(cfg)))
    scale = np.abs(want).max()
    got = {impl: run_layer(cfg, layer_params, x, n_table, table,
                           chunks_of(n, c["chunk"]), impl,
                           dirty=c.get("dirty", False))
           for impl in ("flash", "dense")}
    assert np.abs(got["flash"] - got["dense"]).max() <= 2e-6 * scale
    for impl, y in got.items():
        assert np.abs(y - want).max() <= 1e-5 * scale, impl


def test_flash_chunks_in_bfloat16_read_as_the_walk_does(layer_params,
                                                        monkeypatch):
    """The cell's precision: bfloat16 operands, the kernel against the
    walk on the same pool, both against the float32 reference."""
    monkeypatch.setattr(cache, "WALK_BLOCK", BLOCK)
    cfg = head_sized()
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                               layer_params)
    x = jax.random.normal(jax.random.PRNGKey(5), (512, cfg.hidden_size))
    want = np.asarray(ref.attention(
        x.astype(jnp.bfloat16).astype(jnp.float32), p, ref_cfg(cfg)))
    scale = np.abs(want).max()
    err = {}
    for impl in ("flash", "dense"):
        y = run_layer(cfg, p, x.astype(jnp.bfloat16), 16,
                      np.arange(1, 17, dtype=np.int32),
                      chunks_of(512, 256), impl)
        err[impl] = np.abs(y - want).max() / scale
    assert err["flash"] <= 0.02 and err["dense"] <= 0.02
    assert abs(err["flash"] - err["dense"]) <= 0.5 * err["dense"]


# --- which path, and what stands outside the kernel ------------------------

def _values_outside_kernels(jaxpr):
    """Element counts of every value a jaxpr's equations make, those of
    its nested jaxprs too, a ``pallas_call``'s inside left out; and
    how many ``pallas_call``s there are."""
    sizes, kernels = [], 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            kernels += 1
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                inner, n = _values_outside_kernels(sub)
                sizes += inner
                kernels += n
        sizes += [int(np.prod(v.aval.shape)) for v in eqn.outvars
                  if hasattr(v.aval, "shape")]
    return sizes, kernels


@pytest.mark.parametrize("impl", ["flash", "dense"])
def test_no_scores_array_outside_the_kernel(layer_params, monkeypatch, impl):
    """Under ``"flash"`` a latent chunk's program holds one kernel call
    and no value of ``heads x chunk x WALK_BLOCK`` elements or more
    outside it; under ``"dense"`` it holds no kernel and the walk's
    float32 scores are such a value."""
    monkeypatch.setattr(cache, "WALK_BLOCK", BLOCK)
    cfg = head_sized(**F32)
    spec = dataclasses.replace(cfg, num_hidden_layers=1).cache_spec(
        1, BLOCK, page_size=PAGE)
    pos = jnp.arange(BLOCK, dtype=jnp.int32)[None]

    def chunk(p, x, pool):
        return mm.LatentAttention(cfg).apply(
            {"params": p}, x, pool, pos,
            jnp.arange(1, BLOCK // PAGE + 1, dtype=jnp.int32)[None],
            mm.yarn_cos_sin(cfg, pos), {"impl": impl, "block_k": PAGE})

    jaxpr = jax.make_jaxpr(chunk)(
        layer_params, jnp.zeros((1, BLOCK, cfg.hidden_size)),
        init_kv_cache(spec)["layers_0"])
    sizes, kernels = _values_outside_kernels(jaxpr.jaxpr)
    scores = HEADS * BLOCK * cache.WALK_BLOCK
    if impl == "flash":
        assert kernels == 1 and max(sizes) < scores
    else:
        assert kernels == 0 and max(sizes) >= scores


# --- the counters ----------------------------------------------------------

@pytest.mark.parametrize("impl", ["flash", "dense"])
def test_prefill_span_counts_the_walks_blocks(monkeypatch, impl):
    """41 tokens in chunks of 16 over blocks of two 8-token pages: the
    three calls end at positions 15, 31, 47 and walk 1 + 2 + 3 blocks,
    through the kernel under ``"flash"`` and not under ``"dense"``."""
    monkeypatch.setattr(cache, "WALK_BLOCK", 16)
    cfg = mm.mla_moe_tiny(**F32)
    model = mm.MlaMoeLM(cfg)
    eng = InferenceEngine(
        model, mm.init_mla_moe_params(model, jax.random.PRNGKey(0)),
        config=dict(ENGINE, attention_impl=impl))
    t0 = spans.clock()
    eng.prefill(0, list(range(1, 42)), np.arange(1, 9))
    attrs = [r for r in spans.recent(t0) if r[0] == "prefill"][-1][3]
    assert attrs["chunks"] == 3 and attrs["pad_tokens"] == 7
    assert attrs["attn_blocks"] == 6
    assert attrs["attn_blocks_kernel"] == (6 if impl == "flash" else 0)


def test_other_pools_prefill_spans_carry_no_walk():
    """A pool of keys and values has no walk: its span says nothing of
    blocks, and its prefill program is not told the engine's
    ``attention_impl``."""
    from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny
    model = GPT2LMHead(gpt2_tiny(n_layer=1, dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(model, params, config=dict(
        ENGINE, attention_impl="flash"))
    t0 = spans.clock()
    eng.prefill(0, [1, 2, 3], np.arange(1, 9))
    attrs = [r for r in spans.recent(t0) if r[0] == "prefill"][-1][3]
    assert "attn_blocks" not in attrs and attrs["chunks"] == 1
    text = eng._prefill.lower(*eng.prefill_lowering_args()).as_text()
    assert "pallas" not in text and "tpu_custom_call" not in text

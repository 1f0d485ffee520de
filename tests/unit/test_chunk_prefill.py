"""A prompt's chunk over a pool of per-head pages through its kernel
(`ops/pallas/chunk_prefill.py`, ISSUE 58): parity with the dense arm of
`inference/cache.py:cached_attention` at the serving cells' head
geometries (small chunks and few pages, Pallas interpret mode), who
takes the kernel (`cache.chunk_kernel_takes`) and who keeps the dense
arm bit for bit, the kernel's block shapes and the ``prefill`` span's
counters."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import cache as C
from deepspeed_tpu.ops.pallas import KernelGeometryError, chunk_prefill

# pages of 256 (the cells' are 128: `test_tpu_compile*.py` compile those):
# four to a key block, half the interpreter's work
PAGE = 256
# a row's table: six pages (1,536 positions), so that a second key block
# of four pages runs past its end
ENTRIES = 6
# name: query heads, key heads, head size, scale (the cells' own)
GEOMETRIES = {
    "lfm2_g4_d64": (8, 2, 64, 64 ** -0.5),
    "granite_g4_d64": (8, 2, 64, 0.015625),
    "qwen3_next_g8_d256": (8, 1, 256, 256 ** -0.5),
    "nemotron_g16_d128": (16, 1, 128, 128 ** -0.5),
}
# name: the chunk's length, its first position, the chunk's real tokens
CHUNKS = {
    # one key block, its pages past the first the trash page
    "first_chunk_rest_of_table_trash": (128, 0, 128),
    # a key block seen whole, then the diagonal's, whose last two pages
    # lie past the table's end; the prefix on scattered pages
    "later_chunk_scattered_pages_past_the_tables_end": (128, 1152, 128),
    "padded_tail": (128, 1024, 37),
    # two query blocks (of 128, at 8 and 16 query heads a key head), the
    # second across two key blocks, both masked
    "two_query_blocks_across_key_blocks": (256, 768, 256),
}
# every geometry at every chunk of 128 (Granite's, which is LFM2's under
# another scale, at one); a chunk of 256 is another lowering of the
# kernel, made where it is two query blocks
CASES = [(g, c) for g in sorted(GEOMETRIES) for c in sorted(CHUNKS)
         if (CHUNKS[c][0] == 128 and (g != "granite_g4_d64" or
                                      "later" in c))
         or (CHUNKS[c][0] == 256 and g == "nemotron_g16_d128")]


def operands(Hq, H, D, T, c0, n_valid, dtype=jnp.bfloat16, rows=1, seed=0):
    """A pool of scattered pages holding a random prefix, a chunk of
    ``T`` at ``c0`` (its padded tail zeros, as token 0's would be
    something fixed) and the row's table: used pages in no order, the
    rest the trash page."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n_pages = 2 * ENTRIES * rows + 1
    rnd = lambda k, shape: jax.random.normal(    # noqa: E731
        k, shape, jnp.float32).astype(dtype)
    pool = {"k": rnd(ks[0], (n_pages, H, D, PAGE)),
            "v": rnd(ks[1], (n_pages, H, D, PAGE))}
    real = (jnp.arange(T) < n_valid)[None, :, None, None]
    q = rnd(ks[2], (rows, T, Hq, D))
    k_new = jnp.where(real, rnd(ks[3], (rows, T, H, D)), 0).astype(dtype)
    v_new = jnp.where(real, rnd(ks[4], (rows, T, H, D)), 0).astype(dtype)
    used = -(-(c0 + T) // PAGE)
    perm = np.random.RandomState(seed).permutation(np.arange(1, n_pages))
    table = np.zeros((rows, ENTRIES), np.int32)
    for r in range(rows):
        table[r, :used] = perm[r * ENTRIES:r * ENTRIES + used]
    pos = jnp.broadcast_to(c0 + jnp.arange(T, dtype=jnp.int32), (rows, T))
    return pool, q, k_new, v_new, pos, jnp.asarray(table)


@functools.lru_cache(maxsize=None)
def attend(impl, scale, dtype):
    return jax.jit(lambda pool, q, k, v, pos, table: C.cached_attention(
        q, k, v, pool, pos, dtype, table, impl=impl, scale=scale))


def reference(pool, q, pos, table, scale):
    """The same attention in float32 over the pool's stored values
    (numpy: nothing to compile)."""
    k, v = (np.asarray(a[0]) for a in C.paged_read_kv(pool, table,
                                                      jnp.float32))
    _, T, Hq, D = q.shape
    H = k.shape[1]
    qg = np.asarray(q.astype(jnp.float32))[0].reshape(T, H, Hq // H, D)
    seen = np.arange(k.shape[0])[None, :] <= np.asarray(pos)[0][:, None]
    out = np.zeros((T, H, Hq // H, D), np.float32)
    for h in range(H):
        for g in range(Hq // H):
            s = np.where(seen, (qg[:, h, g] @ k[:, h].T) * np.float32(scale),
                         -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[:, h, g] = (p / p.sum(-1, keepdims=True)) @ v[:, h]
    return out.reshape(1, T, Hq, D)


@pytest.mark.parametrize("geometry,chunk", CASES)
def test_kernel_against_the_dense_arm(geometry, chunk):
    """Both round their output to bfloat16, so they stand a bfloat16
    step apart at most (0.78 % of a value), and the kernel no further
    from the float32 reference than the dense arm; the pool comes back
    the same to the bit (the chunk written, nothing else touched)."""
    Hq, H, D, scale = GEOMETRIES[geometry]
    T, c0, n_valid = CHUNKS[chunk]
    pool, q, k_new, v_new, pos, table = operands(Hq, H, D, T, c0, n_valid)
    assert C.chunk_kernel_takes("flash", 1, T, Hq // H, D, q.dtype,
                                pool["k"].dtype)
    y_dense, pool_dense = attend("dense", scale, jnp.bfloat16)(
        pool, q, k_new, v_new, pos, table)
    y, pool_out = attend("flash", scale, jnp.bfloat16)(
        pool, q, k_new, v_new, pos, table)
    assert y.shape == y_dense.shape and y.dtype == y_dense.dtype
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(pool_out[leaf].astype(jnp.float32)),
            np.asarray(pool_dense[leaf].astype(jnp.float32)))
    y, y_dense = (np.asarray(a.astype(jnp.float32)) for a in (y, y_dense))
    assert np.isfinite(y).all()     # the padded tail's rows too
    y, y_dense = y[:, :n_valid], y_dense[:, :n_valid]
    assert np.abs(y - y_dense).max() <= 8e-3 * np.abs(y_dense).max()
    ref = reference(pool_out, q, pos, table,
                    C._dense_scale(scale, D, jnp.bfloat16))[:, :n_valid]
    rms = lambda a: np.sqrt(((a - ref) ** 2).mean())    # noqa: E731
    assert rms(y) <= 1.1 * rms(y_dense) + 1e-6
    assert rms(y) <= 4e-3 * np.sqrt((ref ** 2).mean())


def test_program_calls_the_kernel_once_and_reads_no_bucket():
    """The lowered call: one kernel, and no array as long as the bucket
    times the chunk (the dense arm's scores)."""
    Hq, H, D, scale = GEOMETRIES["lfm2_g4_d64"]
    args = operands(Hq, H, D, 128, 1152, 128)
    text = jax.jit(lambda *a: attend("flash", scale, jnp.bfloat16)(
        *a)).lower(*args).as_text()
    assert text.count("call @_chunk_call") == 1
    assert f"x128x{ENTRIES * PAGE}xf32" not in text
    dense = jax.jit(lambda *a: attend("dense", scale, jnp.bfloat16)(
        *a)).lower(*args).as_text()
    assert f"x128x{ENTRIES * PAGE}xf32" in dense
    assert "_chunk_call" not in dense


def _quantised(pool):
    k, k_scale = C._quantize(jnp.moveaxis(pool["k"], -1, 1), "int8")
    v, v_scale = C._quantize(jnp.moveaxis(pool["v"], -1, 1), "int8")
    return {"k": jnp.moveaxis(k, 1, -1), "v": jnp.moveaxis(v, 1, -1),
            "k_scale": jnp.moveaxis(k_scale, 1, -1),
            "v_scale": jnp.moveaxis(v_scale, 1, -1)}


# name: (operands' keywords, cached_attention's keywords, what the pool
# becomes): every call that stays the dense arm's under impl="flash"
DENSE_ARM = {
    "speculative_verify_two_rows": (dict(rows=2), {}, None),
    "quantised_pool": ({}, {}, _quantised),
    "tp_mesh": ({}, {"mesh": "one_device"}, None),
    "float32_chunk_of_64": (dict(T=64, dtype=jnp.float32), {}, None),
    "chunk_under_a_query_block": (dict(T=64), {}, None),
    "float32_queries_bfloat16_pool": (dict(dtype=jnp.float32), {},
                                      lambda pool: jax.tree_util.tree_map(
                                          lambda a: a.astype(jnp.bfloat16),
                                          pool)),
}


@pytest.mark.parametrize("case", sorted(DENSE_ARM))
def test_the_dense_arm_keeps_what_the_kernel_does_not_take(case):
    """Under ``impl="flash"`` the call is the one ``impl="dense"``
    makes, as the parent made it: the same lowered program to the
    letter (so the same result to the bit), no kernel in it."""
    kw, attn, repool = DENSE_ARM[case]
    kw = dict(dict(T=128, dtype=jnp.bfloat16), **kw)
    dtype = kw["dtype"]
    pool, q, k_new, v_new, pos, table = operands(
        8, 2, 64, kw["T"], 128, kw["T"], dtype, kw.get("rows", 1))
    if repool is not None:
        pool = repool(pool)
    if attn.get("mesh"):
        attn = {"mesh": jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]), ("model",))}

    def text(impl):
        def call(*a):
            return C.cached_attention(a[1], a[2], a[3], a[0], a[4], dtype,
                                      a[5], impl=impl, scale=0.125, **attn)
        return jax.jit(call).lower(
            pool, q, k_new, v_new, pos, table).as_text()
    flash = text("flash")
    assert "_chunk_call" not in flash
    assert flash == text("dense")


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("args,takes", [
    # impl, rows, tokens, group, head size, queries' dtype, pool's dtype,
    # quantised, mesh: the five cells' prefill calls first
    (("flash", 1, 1024, 4, 64, BF16, BF16), True),        # LFM2
    (("flash", 1, 512, 4, 64, BF16, BF16), True),         # Granite
    (("flash", 1, 1024, 8, 256, BF16, BF16), True),       # Qwen3-Next
    (("flash", 1, 1024, 16, 128, BF16, BF16), True),      # Nemotron
    (("flash", 1, 64, 1, 64, F32, F32), False),           # the chat cell
    (("dense", 1, 1024, 4, 64, BF16, BF16), False),
    (("flash", 2, 1024, 4, 64, BF16, BF16), False),       # verify
    (("flash", 1, 1, 4, 64, BF16, BF16), False),          # a decode step
    (("flash", 1, 1000, 4, 64, BF16, BF16), False),       # no whole blocks
    (("flash", 1, 1024, 4, 64, F32, F32), False),
    (("flash", 1, 1024, 4, 64, F32, BF16), False),
    (("flash", 1, 1024, 4, 64, jnp.float16, jnp.float16), False),
    (("flash", 1, 1024, 4, 64, BF16, jnp.int8, True), False),
    (("flash", 1, 1024, 4, 64, BF16, BF16, True), False),
    (("flash", 1, 1024, 4, 64, BF16, BF16, False, "a mesh"), False),
    (("flash", 1, 1024, 1, 64, BF16, BF16), False),       # GPT-2's heads
    (("flash", 1, 1024, 1, 128, BF16, BF16), True),
    (("flash", 1, 1024, 3, 64, BF16, BF16), False),       # 192 lanes
])
def test_who_takes_the_kernel(args, takes):
    assert C.chunk_kernel_takes(*args) is takes


@pytest.mark.parametrize("T,G,page,blocks", [
    (1024, 4, 128, (256, 8)),       # LFM2: 1,024 rows a grid step
    (512, 4, 128, (256, 8)),        # Granite
    (1024, 8, 128, (128, 8)),       # Qwen3-Next
    (1024, 16, 128, (128, 8)),      # Nemotron: the smallest query block
    (1024, 1, 128, (1024, 8)),
    (384, 4, 128, (128, 8)),        # 256 does not divide the chunk
    (64, 2, 128, (64, 8)),          # a chunk under a query block: whole
    (128, 4, 16, (128, 64)),
    (128, 4, 2048, (128, 1)),       # a page over the key block: one
])
def test_block_shapes(T, G, page, blocks):
    assert chunk_prefill.chunk_blocks(T, G, page) == blocks


def test_geometries_the_kernel_refuses():
    pool, q, *_, table = operands(8, 2, 64, 128, 0, 128)
    take = functools.partial(chunk_prefill.flash_prefill_paged,
                             scale=0.125)
    with pytest.raises(KernelGeometryError, match="sublane"):
        chunk_prefill.chunk_blocks(12, 4, 128)
    # compiled, a key head's lanes of [T, Hq x D] must be whole tiles
    with pytest.raises(KernelGeometryError, match="lane tiles"):
        take(q[0, :, :2], pool["k"], pool["v"], table[0], 0,
             interpret=False)
    with pytest.raises(ValueError, match="queries' dtype"):
        take(q[0].astype(jnp.float32), pool["k"], pool["v"], table[0], 0)
    with pytest.raises(ValueError, match="one\n? *row's page table|table"):
        take(q[0], pool["k"], pool["v"], table, 0)


def test_float32_operands_match_the_dense_arm_closely():
    """The kernel is not the path of a float32 chunk, but it takes one
    (the chip's comparison at the chat cell's shape runs it so)."""
    pool, q, k_new, v_new, pos, table = operands(
        4, 2, 64, 64, 192, 64, jnp.float32)
    y_dense, pool = attend("dense", 0.125, jnp.float32)(
        pool, q, k_new, v_new, pos, table)
    y = chunk_prefill.flash_prefill_paged(
        q[0], pool["k"], pool["v"], table[0], 192, scale=0.125)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense[0]),
                               rtol=2e-5, atol=2e-6)


# --- the engine: the prefill span's counters ----------------------------------

def _toy():
    from deepspeed_tpu.models import granite_hybrid as gh
    # two query heads of 64 a key head: one lane tile
    cfg = gh.granite_hybrid_tiny(
        dtype=jnp.bfloat16, param_dtype=jnp.float32, hidden_size=128,
        num_attention_heads=2, num_key_value_heads=1, mamba_n_heads=16,
        max_position_embeddings=512)
    return gh.GraniteHybridLM(cfg), cfg


def test_prefill_span_counts_the_chunks_the_kernel_took(monkeypatch):
    """Chunk calls times attention layers, all of them through the
    kernel, and the program is what the counter says: the kernel's
    jitted call, traced once for all layers and both calls (a toy hybrid
    in bfloat16 at a chunk of 128: the prompt runs through the kernel,
    interpreted)."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models import granite_hybrid as gh
    from deepspeed_tpu.telemetry import spans
    model, cfg = _toy()
    params = gh.init_granite_hybrid_params(model, jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, config={
        "max_batch": 2, "seq_buckets": (512,), "prefill_chunk": 128,
        "page_size": PAGE, "attention_block_k": 128,
        "attention_impl": "flash"})
    layers = len(cfg.names(gh.ATTENTION))
    assert layers == eng.spec.n_layer == 2
    chunk_prefill._chunk_call.clear_cache()
    traced, real = [], chunk_prefill._chunk_kernel
    monkeypatch.setattr(chunk_prefill, "_chunk_kernel",
                        lambda *a: traced.append(a) or real(*a))
    t0 = spans.clock()
    logits = eng.prefill(0, list(range(1, 150)), np.arange(1, 3))
    assert np.isfinite(logits).all()
    attrs = [r for r in spans.recent(t0) if r[0] == "prefill"][-1][3]
    assert attrs["chunks"] == 2
    assert attrs["attn_plain_calls"] == 2 * layers
    assert attrs["attn_plain_calls_kernel"] == 2 * layers
    assert len(traced) == 1


@pytest.mark.parametrize("impl,chunk,dtype,codec,sharded,taken", [
    ("flash", 128, jnp.bfloat16, None, False, True),
    ("dense", 128, jnp.bfloat16, None, False, False),
    ("flash", 64, jnp.bfloat16, None, False, False),
    ("flash", 128, jnp.float32, None, False, False),
    ("flash", 128, jnp.bfloat16, "int8", False, False),
    ("flash", 128, jnp.bfloat16, None, True, False),
])
def test_the_engine_asks_the_call_sites_predicate(impl, chunk, dtype, codec,
                                                  sharded, taken):
    """`InferenceEngine._chunk_kernel_takes` on what an engine holds
    (stood in for: an engine is seconds to build)."""
    import types
    from deepspeed_tpu.inference.engine import InferenceEngine
    model, cfg = _toy()
    cfg = dataclasses.replace(cfg, dtype=dtype)
    spec = cfg.cache_spec(2, 512, page_size=128)
    if codec:
        spec = dataclasses.replace(spec, codec=codec, dtype=jnp.int8)
    eng = types.SimpleNamespace(
        model=types.SimpleNamespace(config=cfg), attention_impl=impl,
        prefill_chunk=chunk, spec=spec,
        _attn_mesh="a mesh" if sharded else None)
    assert InferenceEngine._chunk_kernel_takes(eng) is taken
    # a model that says nothing of its heads is not taken
    eng.model = types.SimpleNamespace()
    assert InferenceEngine._chunk_kernel_takes(eng) is False

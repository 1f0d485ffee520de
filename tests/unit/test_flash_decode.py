"""Flash-decode kernel unit tests (`ops/pallas/flash_decode.py`).

The kernel (`flash_decode_paged`: one grid step a row, the row's live
blocks of all heads fetched by manual DMA) is tested in Pallas
interpret mode (the CPU path the engine itself uses off-TPU) against a
float64 reference computed from the same pool: parity at mixed
positions with rows that hold no request, every head count and size
the configurations hand it, every pool dtype with its in-kernel
dequantization, the head-sharded call under a TP ``shard_map``, and
the three things its time rests on being safe: a stale tail, the trash
page and every page a row does not own may hold NaN; no table entry
past a row's occupancy is read; what it visits is `paged_grid_blocks`.

The mask-hoist pin: the dense cached path builds its ``[max_batch, 1,
max_seq]`` position mask ONCE per decode step (`models/gpt2.py`
computes it in ``GPT2LMHead`` and threads it to every block), so the
lowered decode program's count of iotas over ``max_seq`` must not scale
with ``n_layer`` — before the hoist each layer re-emitted the mask iota.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.cache import _quantize
from deepspeed_tpu.ops.pallas.flash_decode import (
    KernelGeometryError, check_decode_geometry, flash_decode_paged,
    paged_grid_blocks)


def test_input_validation():
    q, k, v, positions, tables = _paged_case(1, 4, 16, poison=0.0)
    with pytest.raises(ValueError, match="one query token"):
        flash_decode_paged(np.concatenate([q, q], 1), k, v, positions,
                           tables)
    with pytest.raises(ValueError, match="page_tables rows"):
        flash_decode_paged(q, k, v, positions, tables[:-1])
    # a block never straddles a page: it divides page_size
    with pytest.raises(KernelGeometryError, match="multiple"):
        flash_decode_paged(q, k, v, positions, tables, block_k=12)
    with pytest.raises(KernelGeometryError, match=">= 1"):
        flash_decode_paged(q, k, v, positions, tables, block_k=0)
    with pytest.raises(ValueError, match="both k_scale and v_scale"):
        flash_decode_paged(
            q, k, v, positions, tables,
            k_scale=np.ones(k.shape[:2] + k.shape[3:], np.float32))
    # past the page, block_k clamps to it
    out = flash_decode_paged(q, k, v, positions, tables, block_k=4 * PAGE)
    np.testing.assert_allclose(
        np.asarray(out), _paged_ref(q, k, v, positions, tables), atol=2e-6)


def _decode_stablehlo_iotas(n_layer, scan_layers=False):
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=n_layer, n_head=4, dtype=jnp.float32,
                     scan_layers=scan_layers)
    model = GPT2LMHead(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(model, params, config={
        "max_batch": 2, "seq_buckets": (16, 32), "prefill_chunk": 4})
    text = eng._decode.lower(*eng.decode_lowering_args()).as_text()
    # the mask's iota runs over max_seq positions; the page write's own
    # (one lane of a page, a layer) runs over page_size
    assert eng.page_size != eng.max_seq == 32
    return text.count("stablehlo.iota dim = 0 : tensor<32xi32>")


@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["unrolled", "scan"])
def test_dense_mask_is_hoisted_out_of_layers(scan_layers):
    """The traced decode step emits the position-mask iota ONCE however
    deep the model is: 2- and 4-layer engines lower to one iota over
    ``max_seq`` (pre-hoist, unrolled models emitted one mask iota per
    layer)."""
    two = _decode_stablehlo_iotas(2, scan_layers)
    four = _decode_stablehlo_iotas(4, scan_layers)
    assert two == four == 1


# ---------------------------------------------------------------------------
# the paged kernel: one grid step a row, its live blocks by manual DMA
# ---------------------------------------------------------------------------

PAGE, N_PT = 16, 4                    # 4 pages of 16 positions a row
# (position, live): mid-page, a row without a request (position 0, table
# all trash), the last position of the last page, a fresh row holding
# one token, a second dead row, the first position of a page
ROWS = [(21, True), (0, False), (PAGE * N_PT - 1, True), (0, True),
        (0, False), (PAGE, True)]


def _paged_case(seed, heads, head_dim, poison=float("nan")):
    """A pool whose every page no live row owns (the trash page
    included) is ``poison``, with the rows of ``ROWS``: numpy ``(q, k,
    v, positions, tables)``. Table entries past a row's occupancy are
    an id past the pool's end."""
    rng = np.random.default_rng(seed)
    n_rows = len(ROWS)
    n_pages = n_rows * N_PT + 1
    shape = (n_pages, heads, head_dim, PAGE)
    k = np.full(shape, poison, np.float32)
    v = np.full(shape, poison, np.float32)
    q = rng.standard_normal((n_rows, 1, heads, head_dim)).astype(np.float32)
    positions = np.zeros(n_rows, np.int32)
    tables = np.zeros((n_rows, N_PT), np.int32)
    free = list(rng.permutation(np.arange(1, n_pages)))
    for b, (pos, live) in enumerate(ROWS):
        if not live:
            continue
        positions[b] = pos
        tables[b] = n_pages + 7
        for i in range(pos // PAGE + 1):
            page = tables[b, i] = free.pop()
            k[page] = rng.standard_normal(shape[1:])
            v[page] = rng.standard_normal(shape[1:])
    return q, k, v, positions, tables


def _paged_ref(q, k, v, positions, tables):
    """float64, straight from the pool; a dead row gives zeros."""
    out = np.zeros(q.shape, np.float64)
    for b in range(q.shape[0]):
        if tables[b, 0] == 0:
            continue
        n = positions[b] + 1
        pages = tables[b, :(n + PAGE - 1) // PAGE]
        kk = np.concatenate([k[p] for p in pages], -1)[..., :n]
        vv = np.concatenate([v[p] for p in pages], -1)[..., :n]
        s = np.einsum("hd,hds->hs", q[b, 0].astype(np.float64),
                      kk.astype(np.float64)) * q.shape[-1] ** -0.5
        w = np.exp(s - s.max(-1, keepdims=True))
        out[b, 0] = np.einsum("hs,hds->hd", w / w.sum(-1, keepdims=True),
                              vv.astype(np.float64))
    return out


# GPT-2 medium's 16 heads, XL's 25, a TP=4 shard's 4; head size 64 and
# OLMoE's 128
@pytest.mark.parametrize("block_k", [8, 16])
@pytest.mark.parametrize("heads,head_dim",
                         [(16, 64), (25, 64), (4, 64), (16, 128)])
def test_paged_matches_dense_reference(heads, head_dim, block_k):
    q, k, v, positions, tables = _paged_case(0, heads, head_dim)
    out = np.asarray(flash_decode_paged(q, k, v, positions, tables,
                                        block_k=block_k))
    np.testing.assert_allclose(out, _paged_ref(q, k, v, positions, tables),
                               atol=2e-6)
    dead = [b for b, (_, live) in enumerate(ROWS) if not live]
    assert not out[dead].any()          # no request: zeros, not garbage


@pytest.mark.parametrize("storage", ["float32", "bfloat16", "int8",
                                     "f8e4m3fn", "f8e5m2"])
def test_paged_every_pool_dtype(storage):
    """Plain pools against the reference over the rounded pool, codec
    pools with their `[n_pages, H, page]` scale leaves against it over
    the explicitly dequantized one: the fusion is what is compared."""
    q, k, v, positions, tables = _paged_case(1, 4, 16, poison=0.0)
    scales = {}
    if storage in ("float32", "bfloat16"):
        k_s, v_s = (jnp.asarray(x, storage) for x in (k, v))
        k_d, v_d = (np.asarray(x, np.float32) for x in (k_s, v_s))
    else:
        # the pool's layout: positions last, a scale a (page, head, slot)
        k_s, ks = _quantize(jnp.asarray(k).swapaxes(2, 3), storage)
        v_s, vs = _quantize(jnp.asarray(v).swapaxes(2, 3), storage)
        k_s, v_s = k_s.swapaxes(2, 3), v_s.swapaxes(2, 3)
        scales = {"k_scale": ks, "v_scale": vs}
        k_d = np.asarray(k_s.astype(jnp.float32) * ks[:, :, None, :])
        v_d = np.asarray(v_s.astype(jnp.float32) * vs[:, :, None, :])
    out = flash_decode_paged(q, k_s, v_s, positions, tables, block_k=8,
                             **scales)
    np.testing.assert_allclose(np.asarray(out),
                               _paged_ref(q, k_d, v_d, positions, tables),
                               atol=2e-6)


def test_paged_stale_tail_and_foreign_pages_are_invisible():
    """NaN on the trash page and on every page no row owns, huge values
    past each row's position inside its last live block, and table
    entries past each row's occupancy that point past the pool: none of
    it reaches the output, bit for bit."""
    q, k, v, positions, tables = _paged_case(2, 4, 16)
    clean_k, clean_v = np.nan_to_num(k), np.nan_to_num(v)
    clean_t = np.where(tables > k.shape[0], 0, tables)
    clean = flash_decode_paged(q, clean_k, clean_v, positions, clean_t,
                               block_k=8)
    for b, (pos, live) in enumerate(ROWS):
        if live and (pos + 1) % PAGE:
            last = tables[b, pos // PAGE]
            k[last, :, :, pos % PAGE + 1:] = 1e4
            v[last, :, :, pos % PAGE + 1:] = -1e4
    poisoned = flash_decode_paged(q, k, v, positions, tables, block_k=8)
    assert np.isfinite(np.asarray(poisoned)).all()
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(clean))


@pytest.mark.parametrize("block_k", [4, 8, 16])
def test_paged_grid_blocks_is_what_live_rows_hold(block_k):
    _, _, _, positions, tables = _paged_case(3, 4, 16)
    want = sum(pos // block_k + 1 for pos, live in ROWS if live)
    assert paged_grid_blocks(positions, tables, block_k) == (want, want)
    # a dense grid over the same rectangle: what the kernel visited
    # before it walked
    assert want < len(ROWS) * N_PT * (PAGE // block_k)
    assert paged_grid_blocks(np.zeros(3, np.int32),
                             np.zeros((3, N_PT), np.int32),
                             block_k) == (0, 0)


def test_paged_tp_shard_map_matches_unsharded():
    """Under `shard_map` over the pool's head axis each instance sees
    its 4 of 16 heads and the stitched result is the unsharded one."""
    from jax.sharding import Mesh, PartitionSpec as P

    q, k, v, positions, tables = _paged_case(4, 16, 16, poison=0.0)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("model",))
    head, pool = P(None, None, "model", None), P(None, "model", None, None)
    sharded = jax.shard_map(
        lambda q_, k_, v_, p_, t_: flash_decode_paged(
            q_, k_, v_, p_, t_, block_k=8),
        mesh=mesh, in_specs=(head, pool, pool, P(None), P(None, None)),
        out_specs=head, check_vma=False)
    out = sharded(*(jnp.asarray(x) for x in (q, k, v, positions, tables)))
    ref = flash_decode_paged(q, k, v, positions, tables, block_k=8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("storage,block_k,fits", [
    ("float32", 128, True),       # the serve cell: 2 MB of 16
    ("float32", 512, True),       # 8 MB
    ("float32", 1024, False),     # 16 MB of slots alone: Mosaic refuses
    ("int8", 2048, True),         # 8 MB and the scale rows
    ("int8", 4096, False),
])
def test_paged_blocks_must_fit_vmem(storage, block_k, fits):
    """All 16 heads of a block, two slots each of K and V: a geometry
    past the 16 MB a kernel may use is refused, typed, when the engine
    is built (`check_decode_geometry`) and at the call. The line is
    where a described v5e draws it (`tests/unit/test_tpu_compile.py`
    compiles the serve cell's; a scratch compile of each case here,
    PR 27)."""
    quant = storage == "int8"
    args = (block_k, block_k, jnp.dtype(storage))
    if fits:
        assert check_decode_geometry(*args, 16, 64, quant) == block_k
        return
    with pytest.raises(KernelGeometryError, match="VMEM"):
        check_decode_geometry(*args, 16, 64, quant)
    # a TP=4 shard of the same pool holds a quarter of the heads
    assert check_decode_geometry(*args, 4, 64, quant) == block_k

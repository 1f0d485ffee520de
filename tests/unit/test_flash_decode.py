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

**The step's write** (PR 33): the same call puts each live row's new
key and value into the block holding its position and writes that block
back. It is held to the code it replaced (`inference/cache.py:
_write_tokens`, then the read-only arithmetic): the output and the WHOLE
pool, every grouping, pool dtype and block size, position 0, a block's
first lane, a page's last, one block and several, dead rows between
live ones (the trash page and every page no live row owns untouched),
two rows sharing prefix pages. The tests of the read alone hand the
kernel, as the step's new lane, the lane the pool already holds
(`attend`), so the pool must come back as it went in.

The mask-hoist pin: the dense cached path builds its ``[max_batch, 1,
max_seq]`` position mask ONCE per decode step (`models/gpt2.py`
computes it in ``GPT2LMHead`` and threads it to every block), so the
lowered decode program's count of iotas over ``max_seq`` must not scale
with ``n_layer`` — before the hoist each layer re-emitted the mask iota.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.cache import (_new_leaves, _quantize,
                                           _write_tokens)
from deepspeed_tpu.ops.pallas.flash_decode import (
    KernelGeometryError, check_decode_geometry, flash_decode_paged,
    paged_grid_blocks)


def attend(q, k, v, positions, tables, k_scale=None, v_scale=None, **kw):
    """The kernel's read alone: each live row's new lane is the one the
    pool holds at its position already, so the call must hand the pool
    back bit for bit; returns the attention output."""
    pool = {"k": jnp.asarray(k)}
    if v is not None:           # a latent pool has no values of its own
        pool["v"] = jnp.asarray(v)
    if k_scale is not None:
        pool.update(k_scale=jnp.asarray(k_scale),
                    v_scale=jnp.asarray(v_scale))
    positions, tables = np.asarray(positions), np.asarray(tables)
    page = pool["k"].shape[-1]
    live = tables[:, 0] != 0
    pages = np.where(live, tables[np.arange(len(tables)),
                                  positions // page], 0)
    # [B, 1, H, D] payloads and [B, 1, H] scales, as a write's are
    new = {name: leaf[pages, ..., positions % page][:, None]
           for name, leaf in pool.items()}
    out, after = flash_decode_paged(q, new, pool, positions, tables, **kw)
    for name, leaf in pool.items():
        np.testing.assert_array_equal(
            np.asarray(after[name]).view(np.uint8),
            np.asarray(leaf).view(np.uint8), err_msg=name)
    return out


def test_input_validation():
    q, k, v, positions, tables = _paged_case(1, 4, 16, poison=0.0)
    new, pool = {"k": q, "v": q}, {"k": k, "v": v}
    with pytest.raises(ValueError, match="one query token"):
        flash_decode_paged(np.concatenate([q, q], 1), new, pool,
                           positions, tables)
    with pytest.raises(ValueError, match="page_tables rows"):
        flash_decode_paged(q, new, pool, positions, tables[:-1])
    # a block never straddles a page: it divides page_size
    with pytest.raises(KernelGeometryError, match="multiple"):
        flash_decode_paged(q, new, pool, positions, tables, block_k=12)
    with pytest.raises(KernelGeometryError, match=">= 1"):
        flash_decode_paged(q, new, pool, positions, tables, block_k=0)
    with pytest.raises(ValueError, match="both scales or neither"):
        one = np.ones(k.shape[:2] + k.shape[3:], np.float32)
        flash_decode_paged(q, dict(new, k_scale=q[..., 0]),
                           dict(pool, k_scale=one), positions, tables)
    with pytest.raises(ValueError, match="new leaves"):
        flash_decode_paged(q, {"k": q}, pool, positions, tables)
    # a latent pool (k alone) says how much of a key is its value, and
    # no other pool does
    with pytest.raises(ValueError, match="v_dim"):
        flash_decode_paged(q, {"k": q}, {"k": k}, positions, tables)
    with pytest.raises(ValueError, match="v_dim"):
        flash_decode_paged(q, new, pool, positions, tables, v_dim=8)
    with pytest.raises(ValueError, match="v_dim"):
        flash_decode_paged(q, {"k": q}, {"k": k}, positions, tables,
                           v_dim=17)
    # past the page, block_k clamps to it
    out = attend(q, k, v, positions, tables, block_k=4 * PAGE)
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


def _paged_ref(q, k, v, positions, tables, scale=None):
    """float64, straight from the pool; a dead row gives zeros."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    out = np.zeros(q.shape[:-1] + v.shape[2:3], np.float64)
    for b in range(q.shape[0]):
        if tables[b, 0] == 0:
            continue
        n = positions[b] + 1
        pages = tables[b, :(n + PAGE - 1) // PAGE]
        kk = np.concatenate([k[p] for p in pages], -1)[..., :n]
        vv = np.concatenate([v[p] for p in pages], -1)[..., :n]
        s = np.einsum("hd,hds->hs", q[b, 0].astype(np.float64),
                      kk.astype(np.float64)) * scale
        w = np.exp(s - s.max(-1, keepdims=True))
        out[b, 0] = np.einsum("hs,hds->hd", w / w.sum(-1, keepdims=True),
                              vv.astype(np.float64))
    return out


# a latent pool (ISSUE 34): one 40-wide "head" whose first 32 entries are
# also the value, under 4 query heads and the model's own scale
LATENT_D, LATENT_V, LATENT_G, LATENT_SCALE = 40, 32, 4, 0.3


def _latent_ref(q, k, positions, tables):
    """Each query head over the one latent head, float64."""
    return np.concatenate(
        [_paged_ref(q[:, :, g:g + 1], k, k[:, :, :LATENT_V], positions,
                    tables, LATENT_SCALE) for g in range(q.shape[2])], 2)


# GPT-2 medium's 16 heads, XL's 25, a TP=4 shard's 4; head size 64 and
# OLMoE's 128; a latent pool
@pytest.mark.parametrize("block_k", [8, 16])
@pytest.mark.parametrize("heads,head_dim",
                         [(16, 64), (25, 64), (4, 64), (16, 128),
                          ("latent", LATENT_D)])
def test_paged_matches_dense_reference(heads, head_dim, block_k):
    if heads == "latent":
        _, k, _, positions, tables = _paged_case(0, 1, head_dim)
        q = np.random.default_rng(5).standard_normal(
            (len(ROWS), 1, LATENT_G, head_dim)).astype(np.float32)
        out = np.asarray(attend(q, k, None, positions, tables,
                                block_k=block_k, v_dim=LATENT_V,
                                scale=LATENT_SCALE))
        assert out.shape == (len(ROWS), 1, LATENT_G, LATENT_V)
        want = _latent_ref(q, k, positions, tables)
    else:
        q, k, v, positions, tables = _paged_case(0, heads, head_dim)
        out = np.asarray(attend(q, k, v, positions, tables,
                                block_k=block_k))
        want = _paged_ref(q, k, v, positions, tables)
    np.testing.assert_allclose(out, want, atol=2e-6)
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
    out = attend(q, k_s, v_s, positions, tables, block_k=8, **scales)
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
    clean = attend(q, clean_k, clean_v, positions, clean_t, block_k=8)
    for b, (pos, live) in enumerate(ROWS):
        if live and (pos + 1) % PAGE:
            last = tables[b, pos // PAGE]
            k[last, :, :, pos % PAGE + 1:] = 1e4
            v[last, :, :, pos % PAGE + 1:] = -1e4
    poisoned = attend(q, k, v, positions, tables, block_k=8)
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
    k_new, v_new = (jnp.asarray(np.random.default_rng(5).standard_normal(
        (2,) + q.shape), jnp.float32))

    def call(q_, kn_, vn_, k_, v_, p_, t_):
        out, pool_ = flash_decode_paged(
            q_, {"k": kn_, "v": vn_}, {"k": k_, "v": v_}, p_, t_, block_k=8)
        return out, pool_["k"], pool_["v"]
    sharded = jax.shard_map(
        call, mesh=mesh,
        in_specs=(head, head, head, pool, pool, P(None), P(None, None)),
        out_specs=(head, pool, pool), check_vma=False)
    args = tuple(jnp.asarray(x)
                 for x in (q, k_new, v_new, k, v, positions, tables))
    # the pool goes in and comes out on its head axis, written
    for got, want in zip(sharded(*args), call(*args)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(call(*args)[1]), k)


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


# ---------------------------------------------------------------------------
# the step's write, inside the kernel (PR 33)
# ---------------------------------------------------------------------------

# (position, live): the pool's first lane (one block), a dead row, the
# last lane of a row's last page (several blocks), a page's first lane,
# a second dead row between live ones, mid-page, a page's last lane,
# and two rows that share their first two pages (a prefix) and write
# their own third: at lane 8, a block's first lane when block_k is 8,
# and at lane 3
WRITE_ROWS = [(0, True), (0, False), (PAGE * N_PT - 1, True), (PAGE, True),
              (0, False), (21, True), (PAGE - 1, True),
              (2 * PAGE + 8, True), (2 * PAGE + 3, True)]
SHARED = (7, 8)


def _write_case(seed, heads, head_dim, group, storage):
    """A pool filled everywhere (so that any stray write shows), the
    rows of ``WRITE_ROWS`` and a step's new keys and values: ``(q, new,
    pool, positions, tables)``, ``pool`` as the engine holds it (a
    codec pool with its scale leaves), ``new`` as `cached_attention`
    hands it to the kernel."""
    rng = np.random.default_rng(seed)
    n_rows = len(WRITE_ROWS)
    n_pages = n_rows * N_PT + 1
    shape = (n_pages, heads, head_dim, PAGE)
    k, v = (rng.standard_normal(shape).astype(np.float32) for _ in "kv")
    if storage == "latent":
        pool = {"k": jnp.asarray(k)}
    elif storage == "float32":
        pool = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
    else:
        pool = {}
        for name, x in (("k", k), ("v", v)):
            payload, scale = _quantize(jnp.asarray(x).swapaxes(2, 3),
                                       storage)
            pool[name] = payload.swapaxes(2, 3)
            pool[name + "_scale"] = scale
    q = rng.standard_normal((n_rows, 1, heads * group, head_dim)).astype(
        np.float32)
    k_new, v_new = (jnp.asarray(rng.standard_normal(
        (n_rows, 1, heads, head_dim)), jnp.float32) for _ in "kv")
    positions = np.zeros(n_rows, np.int32)
    tables = np.zeros((n_rows, N_PT), np.int32)
    free = list(rng.permutation(np.arange(1, n_pages)))
    for b, (pos, live) in enumerate(WRITE_ROWS):
        if live:
            positions[b] = pos
            for i in range(pos // PAGE + 1):
                tables[b, i] = free.pop()
    tables[SHARED[1], :2] = tables[SHARED[0], :2]
    if storage == "latent":
        v_new = None
    return (q, _new_leaves(pool, k_new, v_new), pool, positions, tables)


def _dequantized(pool):
    if "v" not in pool:
        return np.asarray(pool["k"]), np.asarray(pool["k"])[:, :, :12]
    if "k_scale" not in pool:
        return np.asarray(pool["k"]), np.asarray(pool["v"])
    return tuple(np.asarray(pool[n].astype(jnp.float32)
                            * pool[n + "_scale"][:, :, None, :])
                 for n in "kv")


@pytest.mark.parametrize("block_k", [PAGE, PAGE // 2],
                         ids=["block=page", "block<page"])
@pytest.mark.parametrize("storage", ["float32", "int8", "f8e4m3fn",
                                     "latent"])
@pytest.mark.parametrize("group", [1, 4])
def test_fused_write_is_the_loop_then_the_read(group, storage, block_k):
    """The fused call against what it replaced: `_write_tokens` (every
    row's token into its page's slab, dead rows' into the trash page),
    then the read-only arithmetic over the written pool. ``latent``: a
    pool of one leaf and one head, the values the first 12 of a key's
    16 entries, every query head over it: one lane written, not two."""
    latent = {"v_dim": 12} if storage == "latent" else {}
    heads = 1 if latent else 4
    q, new, pool, positions, tables = _write_case(
        11 + group, heads, 16, group, storage)
    before = {name: np.asarray(leaf) for name, leaf in pool.items()}
    pages = tables[np.arange(len(tables)), positions // PAGE]
    want = _write_tokens(pool, {n: x[:, 0] for n, x in new.items()},
                         jnp.asarray(pages), jnp.asarray(positions % PAGE))
    out, got = flash_decode_paged(q, new, pool, positions, tables,
                                  block_k=block_k, **latent)
    assert set(got) == set(pool)
    for name, leaf in got.items():
        leaf, loop = np.asarray(leaf), np.asarray(want[name])
        assert leaf.dtype == before[name].dtype
        # every page but the trash page, payload and scales, bit for bit
        np.testing.assert_array_equal(leaf[1:].view(np.uint8),
                                      loop[1:].view(np.uint8), err_msg=name)
        # the loop put the dead rows' tokens there; the kernel nothing
        np.testing.assert_array_equal(leaf[0].view(np.uint8),
                                      before[name][0].view(np.uint8))
        # and the live rows' lanes did change (scales may round alike)
        if name in "kv":
            written = [b for b, (_, live) in enumerate(WRITE_ROWS) if live]
            assert all((leaf[pages[b], ..., positions[b] % PAGE]
                        != before[name][pages[b], ..., positions[b] % PAGE]
                        ).any() for b in written)
    # the shared prefix pages are no row's to write
    for name in got:
        np.testing.assert_array_equal(
            np.asarray(got[name])[tables[SHARED[0], :2]],
            before[name][tables[SHARED[0], :2]])
    # the new lane is attended over: the read alone, over the written
    # pool, gives the same output bit for bit, and float64 agrees
    scales = {n: want[n] for n in want if n.endswith("_scale")}
    again = attend(q, want["k"], want.get("v"), positions, tables,
                   block_k=block_k, **scales, **latent)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))
    k_d, v_d = _dequantized(want)
    q64 = q.reshape(len(q), 1, heads, group, 16)
    out64 = np.asarray(out).reshape(q64.shape[:-1] + (-1,))
    for g in range(group):
        np.testing.assert_allclose(
            out64[:, :, :, g],
            _paged_ref(q64[:, :, :, g], k_d, v_d, positions, tables),
            atol=2e-6)


def test_fused_write_under_jit_with_the_pool_donated():
    """As the engine calls it: the pool donated, the call's pool leaves
    aliased input to output. Two steps in a row: the second attends
    over what the first wrote."""
    q, new, pool, positions, tables = _write_case(3, 4, 16, 1, "float32")
    step = jax.jit(lambda pool, q, new, pos, pt: flash_decode_paged(
        q, new, pool, pos, pt, block_k=8), donate_argnums=(0,))
    live = np.array([alive for _, alive in WRITE_ROWS])
    room = live & (positions % PAGE < PAGE - 1)    # the next lane is theirs
    pages = tables[np.arange(len(tables)), positions // PAGE]
    loop = _write_tokens(pool, {n: x[:, 0] for n, x in new.items()},
                         jnp.asarray(pages), jnp.asarray(positions % PAGE))
    nxt = np.where(room, positions + 1, positions)
    loop = _write_tokens(loop, {n: 2 * x[:, 0] for n, x in new.items()},
                         jnp.asarray(pages), jnp.asarray(nxt % PAGE))
    _, pool = step(pool, q, new, positions, tables)
    out, pool = step(pool, q, {n: 2 * x for n, x in new.items()}, nxt,
                     tables)
    for name in "kv":
        np.testing.assert_array_equal(np.asarray(pool[name])[1:],
                                      np.asarray(loop[name])[1:])
    np.testing.assert_allclose(
        np.asarray(out), _paged_ref(q, *_dequantized(loop), nxt, tables),
        atol=2e-6)


# ---------------------------------------------------------------------------
# who loads this kernel's file and runs none of it (PR 33)
# ---------------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_IMPORT_WHAT_THESE_FILES_IMPORT = """
import ast, importlib, json, sys
sys.path.insert(0, sys.argv[1])
for path in sys.argv[2:]:
    for node in ast.walk(ast.parse(open(path).read())):
        names = []
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names = [node.module] + [f"{node.module}.{a.name}"
                                     for a in node.names]
        for name in names:
            try:
                importlib.import_module(name)
            except ImportError:
                pass        # a name taken from a module, not a module
from jax._src import xla_bridge
print(json.dumps({
    "modules": sorted(m for m in sys.modules
                      if m.startswith("deepspeed_tpu")),
    "backend_up": xla_bridge.backends_are_initialized()}))
"""


def _training_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return [m for m in manifest["end_to_end"]
            if m["name"] == "train_tokens_per_s_per_chip"][0]["workloads"]


def _driver_file(cell):
    suite = os.path.join(ROOT, "benchmarks", "suite")
    with open(os.path.join(suite, "workloads", cell + ".json")) as f:
        return os.path.join(suite, "drivers", json.load(f)["driver"] + ".py")


@pytest.fixture(scope="module")
def training_process():
    """What a training cell's process has loaded once it has imported
    everything `run.py` and the training drivers import, at module
    level or inside a function: a fresh interpreter, nothing run."""
    files = [os.path.join(ROOT, "benchmarks", "suite", "run.py")] + sorted(
        {_driver_file(cell) for cell in _training_cells()})
    done = subprocess.run(
        [sys.executable, "-c", _IMPORT_WHAT_THESE_FILES_IMPORT, ROOT] + files,
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    return files, json.loads(done.stdout.splitlines()[-1])


@pytest.mark.parametrize("cell", _training_cells())
def test_training_processes_load_no_serving_code(training_process, cell):
    """The separation PR 32's refusal turned on (its `setup_s` in
    `train-olmoe-1b-7b-seq4096`, a process that runs none of the
    change): a training cell's process imports nothing under
    `deepspeed_tpu/inference/`, and of the files the fused write
    touches it loads `ops/pallas/flash_decode.py` alone, through
    `ops/pallas/__init__.py`, for its definitions."""
    files, facts = training_process
    assert _driver_file(cell) in files
    loaded = set(facts["modules"])
    assert len(loaded) > 20 and "deepspeed_tpu.runtime.engine" in loaded
    assert not [m for m in loaded if m.startswith("deepspeed_tpu.inference")]
    touched = {"deepspeed_tpu.inference.cache",
               "deepspeed_tpu.inference.engine",
               "deepspeed_tpu.analysis.kernels",
               "deepspeed_tpu.ops.pallas.flash_decode"}
    assert touched & loaded == {"deepspeed_tpu.ops.pallas.flash_decode"}
    # importing all of it built no array: no backend was brought up
    assert facts["backend_up"] is False


def test_the_kernels_file_is_definitions_at_module_level():
    """Importing `deepspeed_tpu.ops.pallas` costs a training process
    what reading the file costs: `flash_decode.py`'s module level is a
    docstring, imports, constants, functions and a class: no array is
    built there and nothing traced (the one decorator is the `jax.jit`
    round `_paged_call`, which wraps at import and traces at the first
    decode step, in a process that has one)."""
    import ast

    from deepspeed_tpu.ops.pallas import flash_decode
    with open(flash_decode.__file__) as f:
        tree = ast.parse(f.read())

    def constant(node):
        return isinstance(node, ast.Constant) or (
            isinstance(node, ast.BinOp) and constant(node.left)
            and constant(node.right))
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            continue
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            assert [ast.unparse(d) for d in node.decorator_list] in (
                [], ["functools.partial(jax.jit, static_argnames="
                     "('block_k', 'interpret', 'scale', 'v_dim', 'window'))"]), \
                node.name
            continue
        if isinstance(node, ast.Expr):
            assert isinstance(node.value, ast.Constant)     # the docstring
            continue
        assert isinstance(node, ast.Assign) and constant(node.value), \
            ast.unparse(node)


# ---------------------------------------------------------------------------
# values narrower than keys, a window over a ring, a sink (ISSUE 47)
# ---------------------------------------------------------------------------

# sha256 (first 16 hex digits) of the traced call's jaxpr at the parent
# of PR 47 (`011388f`), the kernel's body included, in interpret mode on
# the CPU, where the text is path-free (`PERF.md` 7 (as)): the call with
# none of v_dim < D, window, sink must be traced to what it was traced to
GEOMETRIES = {
    "mha": ((2, 2, 16, jnp.float32), "966ddfcad92e067d"),
    "gqa": ((2, 8, 16, jnp.bfloat16), "4566682bcb207001"),
    "latent": ((1, 4, 24, jnp.bfloat16, 16), "7f6541179a81eaef"),
    "int8": ((2, 2, 16, jnp.int8, None, True), "b157ea577fe40a31"),
}


def _traced(H, Hq, D, dtype, latent_v=None, quant=False):
    B, ps, npg, ppr = 3, 8, 9, 4
    pool = {"k": jnp.zeros((npg, H, D, ps), dtype)}
    new = {"k": jnp.zeros((B, 1, H, D), dtype)}
    if latent_v is None:
        pool["v"], new["v"] = pool["k"], new["k"]
    if quant:
        for name in ("k_scale", "v_scale"):
            pool[name] = jnp.zeros((npg, H, ps), jnp.float32)
            new[name] = jnp.zeros((B, 1, H), jnp.float32)
    q = jnp.zeros((B, 1, Hq, D), jnp.float32)

    def call(q, new, pool, pos, pt):
        return flash_decode_paged(
            q, new, pool, pos, pt, block_k=8, interpret=True,
            scale=(0.1 if latent_v else None), v_dim=latent_v)
    return str(jax.make_jaxpr(call)(
        q, new, pool, jnp.zeros((B,), jnp.int32),
        jnp.zeros((B, ppr), jnp.int32)))


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_the_defaults_are_traced_to_what_they_were(geometry):
    import hashlib
    args, want = GEOMETRIES[geometry]
    text = _traced(*args)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


def _windowed(window, sink, Dv, block_k=8, N=45):
    """Rows at several positions over pools of garbage through the
    kernel, their keys written a step at a time; against float64."""
    rng = np.random.default_rng(window * 7 + Dv)
    H, G, D, ps = 2, 4, 24, 8
    ring = window // ps + 1 if window else 0
    per = ring or 6
    B = 3
    k_pool = rng.normal(size=(B * per + 1, H, D, ps)).astype(np.float32) * 9
    v_pool = rng.normal(size=(B * per + 1, H, Dv, ps)).astype(np.float32) * 9
    pool = {"k": jnp.asarray(k_pool), "v": jnp.asarray(v_pool)}
    tables = np.zeros((B, per), np.int32)
    tables[0] = np.arange(per, 0, -1)
    tables[2] = np.arange(2 * per + 1, 3 * per + 1)     # row 1: no request
    starts = np.asarray([0, 0, 3])      # row 2 is three tokens ahead
    ks = rng.normal(size=(B, N + 3, H, D)).astype(np.float32)
    vs = rng.normal(size=(B, N + 3, H, Dv)).astype(np.float32)
    qs = rng.normal(size=(B, N + 3, H * G, D)).astype(np.float32)
    b = None if sink is None else jnp.asarray(sink, jnp.float32)
    worst = 0.0
    for t in range(N):
        pos = starts + t
        # (row 2 has its first three positions written by hand)
        if t == 0:
            for p in range(3):
                page = tables[2, (p // ps) % per]
                k_pool = np.asarray(pool["k"]).copy()
                v_pool = np.asarray(pool["v"]).copy()
                k_pool[page, :, :, p % ps] = ks[2, p]
                v_pool[page, :, :, p % ps] = vs[2, p]
                pool = {"k": jnp.asarray(k_pool), "v": jnp.asarray(v_pool)}
        take = lambda a: jnp.asarray(       # noqa: E731
            np.stack([a[r, pos[r]] for r in range(B)]))[:, None]
        out, pool = flash_decode_paged(
            take(qs), {"k": take(ks), "v": take(vs)}, pool,
            jnp.asarray(pos * (tables[:, 0] != 0)), jnp.asarray(tables),
            block_k=block_k, scale=0.2, window=window, sink=b)
        out = np.asarray(out, np.float64)
        assert not out[1].any()         # the row without a request
        for r in (0, 2):
            p = pos[r]
            lo = max(0, p - window + 1) if window else 0
            for h in range(H * G):
                s = 0.2 * ks[r, lo:p + 1, h // G].astype(np.float64) @ \
                    qs[r, p, h].astype(np.float64)
                m = max(s.max(), -np.inf if sink is None else sink[h])
                e = np.exp(s - m)
                den = e.sum() + (0 if sink is None else np.exp(sink[h] - m))
                want = (e / den) @ vs[r, lo:p + 1, h // G]
                worst = max(worst, np.abs(out[r, 0, h] - want).max())
    return worst, pool, tables


SINK = np.linspace(-1.0, 3.0, 8)


@pytest.mark.parametrize("window, sink, Dv", [
    (0, None, 16), (0, SINK, 24), (16, None, 24), (16, SINK, 16),
    (8, SINK, 16), (24, SINK, 8)])
def test_window_sink_and_narrow_values_against_float64(window, sink, Dv):
    """Every combination the kernel's three additions make: the walk
    starts at the window's first block of a ring that has wrapped, keys
    are admitted by position, a sink joins the denominator and no value,
    the V block is narrower than the K block; stale tenants everywhere."""
    worst, pool, tables = _windowed(window, sink, Dv)
    assert worst < 2e-5
    # a page no live row owns is as it was handed in: row 1's span
    per = tables.shape[1]
    assert np.asarray(pool["k"])[per + 1:2 * per + 1].std() > 5


def test_window_with_blocks_smaller_than_a_page():
    worst, _, _ = _windowed(16, SINK, 16, block_k=4, N=30)
    assert worst < 2e-5


def test_paged_grid_blocks_of_a_window():
    """A window's rows hold the blocks from that of ``p - window + 1``
    to that of ``p``: at most ``window / block_k + 1``, whatever ``p``."""
    tables = np.asarray([[3, 1], [0, 0], [2, 4]])
    for pos, want in (([5, 0, 127], 2), ([128, 9, 255], 3), ([300, 0, 4000], 4),
                      ([255, 0, 256], 3)):
        live, launched = paged_grid_blocks(pos, tables, 128, window=128)
        assert live == launched == want
    assert paged_grid_blocks([300, 0, 4000], tables, 128) == (35, 35)


def test_a_ring_too_small_for_its_window_is_refused():
    pool = {"k": jnp.zeros((5, 2, 16, 8)), "v": jnp.zeros((5, 2, 16, 8))}
    new = {"k": jnp.zeros((1, 1, 2, 16)), "v": jnp.zeros((1, 1, 2, 16))}
    with pytest.raises(ValueError, match="cannot hold a window"):
        flash_decode_paged(jnp.zeros((1, 1, 2, 16)), new, pool,
                           jnp.zeros((1,), jnp.int32),
                           jnp.ones((1, 2), jnp.int32), block_k=8, window=16)
    quant = dict(pool, k_scale=jnp.zeros((5, 2, 8)),
                 v_scale=jnp.zeros((5, 2, 8)))
    qnew = dict(new, k_scale=jnp.zeros((1, 1, 2)),
                v_scale=jnp.zeros((1, 1, 2)))
    with pytest.raises(ValueError, match="plain storage"):
        flash_decode_paged(jnp.zeros((1, 1, 2, 16)), qnew, quant,
                           jnp.zeros((1,), jnp.int32),
                           jnp.ones((1, 3), jnp.int32), block_k=8, window=16)

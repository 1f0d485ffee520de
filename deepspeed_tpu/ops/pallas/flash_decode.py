"""Flash-decode: split-K attention over the serving ring-buffer cache.

The decode step of the serving engine (`inference/engine.py`) attends
one query token per row over the full ``[max_batch, max_seq]`` KV
cache. The dense path dequantizes the whole cache to compute dtype and
runs a ``[1, max_seq]`` softmax per head — O(max_seq) HBM traffic per
token no matter how short the active requests are. This kernel is the
FlashDecoding-style fix, specialized for the ring buffer:

- **split-K online softmax**: the cache row streams through VMEM in
  ``block_k``-sized KV blocks; partial max/sum accumulators merge
  across blocks in scratch (the cross-block log-sum-exp merge), so the
  ``[1, max_seq]`` score row never materializes.
- **active-length block skipping**: each cache row's occupancy is its
  ``positions[b]`` scalar, prefetched into SMEM before the grid runs.
  Blocks entirely past a row's position are predicated off with
  ``pl.when`` AND their index map clamps to the last active block —
  Pallas skips the DMA when consecutive grid steps ask for the same
  block, so HBM traffic scales with the *occupied* cache, not
  ``max_seq``.
- **fused KV dequantization**: int8/f8e4m3fn/f8e5m2 cache blocks
  (`inference/cache.py` codec storage) enter the kernel in their
  storage dtype with the per-(row, position, head) scales streamed as
  a side input; scores and probs are rescaled in-register. The
  quantized cache never materializes an fp32 copy in HBM — the dense
  path's ``read_kv`` dequant is exactly what this deletes.
- **head folding**: heads fold into the grid's leading dim
  (``[B, S, H, D] → [B*H, S, D]``, the `flash_attention.py` layout),
  so a tensor-parallel head shard (`cache.kv_partition_specs`) runs
  the same kernel over its local heads under ``shard_map`` — the
  block-spec arithmetic never sees the global head count.

- **page-table gathers** (:func:`flash_decode_paged`): the paged pool
  layout (`inference/cache.py` ``page_size > 0``) feeds the kernel a
  second scalar-prefetch input — each row's ``[pages_per_row]`` page
  table — and the KV index map composes the clamp with a table lookup:
  logical block → clamp to the row's last active block → physical
  ``(page, intra-page block)``. The clamp runs BEFORE the lookup, so
  the map only ever dereferences table entries the row has actually
  filled — dead and unallocated pages never cost a DMA, the paged
  generalization of the ring kernel's block skipping. The pool is
  ``[n_pages, H, D, page_size]``, positions on the lanes: with (page,
  head) merged into one leading dim a KV block is cut from it as
  ``[1, D, block_k]``, the transpose of the ring kernel's ``[1,
  block_k, D]``, and the one kernel body contracts whichever it was
  handed. At D = 64 that order, unlike the ring's, fills whole (8, 128)
  tiles, so the pool's default layout in HBM is the kernel's and XLA
  copies nothing around the call (`tests/unit/test_tpu_compile.py`
  holds it at 0 pool-shaped copies; 3 per leaf before, `PERF.md`,
  PR 25).

Both kernels compile for the chip (`tests/unit/test_tpu_compile.py`
pins that against a described v5e). The online-softmax running max/sum
live in ``(1, 1)`` VMEM scratch and are only ever read and written
whole, as vectors — Mosaic refuses scalar stores to VMEM. Quantized
scales enter lane-major (``[..., 1, block_k]`` rows), the layout the
``[1, block_k]`` score row multiplies without a relayout.

Off-TPU the kernel runs in Pallas interpret mode (CPU test meshes);
the dense cached-attention path stays available as the parity oracle
behind ``inference.attention.impl``.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import DEFAULT_MASK_VALUE

DEFAULT_BLOCK_K = 128
# the kernels' names in the HLO and in a device trace (see
# flash_attention.py)
DECODE_NAME, DECODE_PAGED_NAME = "ds_flash_decode", "ds_flash_decode_paged"

# TPU native sublane tile per element width (lane dim is always 128):
# a compiled block whose second-minor dim doesn't tile to this pads to
# full register tiles on every touch.
_SUBLANE_TILES = {4: 8, 2: 16, 1: 32}
_LANES = 128


class KernelGeometryError(ValueError):
    """Invalid flash-decode block geometry, raised at call time.

    Subclasses ``ValueError`` so existing call sites (and tests)
    catching the untyped validation keep working; the distinct type
    lets the static analyzer (`analysis/kernels.py`) and the serving
    engine report geometry problems as what they are instead of a
    silently mis-lowered kernel (or, for ``block_k <= 0``, an opaque
    ``ZeroDivisionError`` from the grid arithmetic).
    """


def _validate_block_k(block_k, extent, extent_name, kv_dtype, interpret,
                      lanes=False):
    """Clamp and validate ``block_k`` against the KV extent it tiles.

    ``extent`` is ``max_seq`` for the ring layout and ``page_size``
    for the paged one (a KV block never straddles a page). The
    sublane-tile check only gates the COMPILED path (``interpret``
    False, i.e. a real TPU lowering where Mosaic's tiling constraints
    bite on sub-tile quantized blocks); interpret-mode CPU runs accept
    any divisor so CI toys stay small. ``lanes`` adds the lane rule:
    some block has ``block_k`` as its minor dim — the scale rows of a
    codec cache, and every block of the paged pool.
    """
    block_k = int(block_k)
    if block_k < 1:
        raise KernelGeometryError(
            f"attention block_k must be >= 1, got {block_k}")
    block_k = min(block_k, int(extent))
    if extent % block_k:
        raise KernelGeometryError(
            f"{extent_name} {extent} must be a multiple of attention "
            f"block_k {block_k}")
    tile = _SUBLANE_TILES.get(jnp.dtype(kv_dtype).itemsize, 8)
    if not interpret and block_k % tile and block_k != extent:
        raise KernelGeometryError(
            f"attention block_k {block_k} is not a multiple of the "
            f"{jnp.dtype(kv_dtype).name} sublane tile {tile} — the "
            f"compiled kernel would pad every KV block to full "
            f"register tiles; pick a multiple of {tile} (or cover the "
            f"whole {extent_name})")
    if not interpret and lanes and block_k % _LANES and block_k != extent:
        raise KernelGeometryError(
            f"attention block_k {block_k} over a quantized cache or a "
            f"paged pool must be a multiple of {_LANES} (or cover the "
            f"whole {extent_name} {extent}): scales and pool pages "
            f"stream lane-major, block_k positions to a row")
    return block_k


def check_decode_geometry(block_k, extent, extent_name, kv_dtype, lanes):
    """The call-time block validation, for the device this process
    compiles for — so the serving engine refuses, typed, a geometry the
    chip's compiler would refuse when it is BUILT, not at the first
    decode step (and never by serving through another path). Returns
    the clamped ``block_k``."""
    interpret = jax.devices()[0].platform != "tpu"
    return _validate_block_k(block_k, extent, extent_name, kv_dtype,
                             interpret, lanes)


def _fold_heads(x):
    """[B, S, H, D] → [B*H, S, D] (heads into the grid's leading dim)."""
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _flash_decode_kernel(H, D, block_k, n_kb, quant, paged=False):
    """Kernel factory: one (row*head, kv-block) grid step.

    Scalar-prefetch arg 0 is the ``[B]`` positions vector (SMEM);
    scratch carries the online-softmax state (acc [1, D], running max
    and sum [1, 1]) across the sequential kv-block dim — all three are
    read and written whole, as vectors. The paged variant carries the
    page tables as a second scalar-prefetch arg — consumed ONLY by the
    index maps: a KV block is a KV block wherever it was fetched
    from. The two layouts differ in the block's memory order, ``(1, bk,
    D)`` from the ring and ``(1, D, bk)`` from the paged pool, so the
    two dots contract the block's D (keys) or position (values) axis
    where this layout has it; scales are a ``(1, 1, bk)`` row in both.
    """
    d_axis, pos_axis = (0, 1) if paged else (1, 0)

    def kernel(pos_ref, *all_refs):
        refs = list(all_refs)
        if paged:
            refs.pop(0)                 # page tables: index-map food only
        q_ref, k_ref, v_ref = refs[:3]
        refs = refs[3:]
        ks_ref = refs.pop(0) if quant else None
        vs_ref = refs.pop(0) if quant else None
        o_ref, acc_ref, m_ref, l_ref = refs
        bh = pl.program_id(0)
        ki = pl.program_id(1)
        p = pos_ref[bh // H]

        @pl.when(ki == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[:] = jnp.zeros_like(l_ref)

        # Block-level active-length predicate: a block whose first
        # position is past the row's occupancy contributes nothing —
        # skip the whole grid step (its DMA was already elided by the
        # clamped index map).
        run = (ki * block_k) <= p

        @pl.when(run)
        def _compute():
            qb = q_ref[0].astype(jnp.float32)              # [1, D]
            kb = k_ref[0].astype(jnp.float32)      # [bk, D] | [D, bk]
            s = jax.lax.dot_general(
                qb, kb, (((1,), (d_axis,)), ((), ())),
                preferred_element_type=jnp.float32)        # [1, bk]
            if quant:
                # fused dequant: scale the SCORES by the key scales
                # (dot distributes over the per-position scalar) —
                # the kb block itself stays in storage dtype. The scale
                # row is [1, bk] f32, lane-major like the scores.
                s = s * ks_ref[0]
            s = s * (D ** -0.5)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(k_pos <= p, s, DEFAULT_MASK_VALUE)
            m_prev = m_ref[:]                              # [1, 1]
            m_new = jnp.maximum(m_prev,
                                s.max(axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[:] = l_ref[:] * corr + pr.sum(axis=-1, keepdims=True)
            m_ref[:] = m_new
            if quant:
                # value scales fold into the probs the same way
                pr = pr * vs_ref[0]
            vb = v_ref[0].astype(jnp.float32)      # [bk, D] | [D, bk]
            acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
                pr, vb, (((1,), (pos_axis,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(ki == n_kb - 1)
        def _finish():
            o_ref[0] = (acc_ref[:] /
                        jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)

    return kernel


def _softmax_scratch(D):
    return [pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32)]


def flash_decode(q, k, v, positions, k_scale=None, v_scale=None,
                 block_k=DEFAULT_BLOCK_K, interpret=None):
    """Split-K flash decode over one layer's cache buffers.

    ``q``: ``[B, 1, H, D]`` compute-dtype query (the decode step's
    single token per row). ``k``/``v``: ``[B, S, H, D]`` cache buffers
    in STORAGE dtype — compute dtype, or a codec dtype
    (int8/f8e4m3fn/f8e5m2) with ``k_scale``/``v_scale`` ``[B, S, H]``
    f32 absmax scales (`inference/cache.py` layout). ``positions``:
    ``[B]`` int32, each row's current write position (the mask admits
    cache index ``s`` iff ``s <= positions[b]`` — identical to the
    dense oracle's). Returns ``[B, 1, H, D]`` in ``q.dtype``.

    ``interpret=None`` auto-selects: compiled kernel on TPU, Pallas
    interpret mode elsewhere. Under tensor parallelism call through
    ``shard_map`` with the head axis sharded (`cache.kv_partition_
    specs`); the kernel only ever sees local heads.
    """
    B, S, H, D = k.shape
    if q.shape != (B, 1, H, D):
        raise ValueError(
            f"flash_decode takes one query token per row: q shape "
            f"{q.shape} != {(B, 1, H, D)}")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    quant = k_scale is not None
    block_k = _validate_block_k(block_k, S, "max_seq", k.dtype, interpret,
                                quant)
    n_kb = S // block_k

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, 1, D)
    kh = _fold_heads(k)
    vh = _fold_heads(v)

    def q_map(bh, ki, pos_ref):
        return (bh, 0, 0)

    def _active(bh, ki, pos_ref):
        # Clamp past-occupancy block indices to the row's last active
        # block: consecutive grid steps then request the SAME block and
        # Pallas elides the DMA — the skipped blocks cost no HBM reads.
        return jnp.minimum(ki, pos_ref[bh // H] // block_k)

    def kv_map(bh, ki, pos_ref):
        return (bh, _active(bh, ki, pos_ref), 0)

    def sc_map(bh, ki, pos_ref):
        return (bh, 0, _active(bh, ki, pos_ref))

    in_specs = [
        pl.BlockSpec((1, 1, D), q_map),
        pl.BlockSpec((1, block_k, D), kv_map),
        pl.BlockSpec((1, block_k, D), kv_map),
    ]
    args = [qh, kh, vh]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, block_k), sc_map),
                     pl.BlockSpec((1, 1, block_k), sc_map)]
        args += [k_scale.transpose(0, 2, 1).reshape(B * H, 1, S),
                 v_scale.transpose(0, 2, 1).reshape(B * H, 1, S)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, n_kb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, D), q_map),
        scratch_shapes=_softmax_scratch(D),
    )
    call = pl.pallas_call(
        _flash_decode_kernel(H, D, block_k, n_kb, quant),
        name=DECODE_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, 1, D), q.dtype),
        interpret=interpret,
    )
    with jax.named_scope(DECODE_NAME):
        out = call(jnp.asarray(positions, jnp.int32), *args)
    return out.reshape(B, H, 1, D).transpose(0, 2, 1, 3)


def flash_decode_paged(q, k, v, positions, page_tables, k_scale=None,
                       v_scale=None, block_k=DEFAULT_BLOCK_K,
                       interpret=None):
    """Split-K flash decode over a paged KV pool.

    ``q``: ``[B, 1, H, D]`` as in :func:`flash_decode`. ``k``/``v``:
    the POOL buffers ``[n_pages, H, D, page_size]`` in
    storage dtype (scales ``[n_pages, H, page_size]`` when quantized —
    `inference/cache.py` paged layout). ``page_tables``: ``[B,
    pages_per_row]`` int32 physical page ids per row (entry 0 = the
    trash page for unallocated slots). ``positions``: ``[B]`` int32
    write positions, same mask contract as the ring kernel.

    Both scalar-prefetch inputs live in SMEM before the grid runs; the
    KV index map clamps the logical block to the row's last active
    block FIRST and only then looks up the physical page, so blocks
    past a row's occupancy re-request the previous physical block
    (DMA elided) and unallocated table entries are never dereferenced.
    ``block_k`` clamps to ``page_size`` and must tile it — a KV block
    never straddles a page boundary, which is what keeps the gather a
    single block index per grid step.
    """
    n_pages, H, D, page_size = k.shape
    B = q.shape[0]
    if q.shape != (B, 1, H, D):
        raise ValueError(
            f"flash_decode_paged takes one query token per row: q "
            f"shape {q.shape} != {(B, 1, H, D)}")
    if page_tables.shape[0] != B:
        raise ValueError(
            f"page_tables rows {page_tables.shape[0]} != batch {B}")
    n_pt = page_tables.shape[1]
    S = n_pt * page_size
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    quant = k_scale is not None
    # positions are the lane axis of every block, scales or not
    block_k = _validate_block_k(block_k, page_size, "page_size",
                                k.dtype, interpret, lanes=True)
    n_kb = S // block_k
    bpp = page_size // block_k          # kv-blocks per page

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, 1, D)

    def q_map(bh, ki, pos_ref, pt_ref):
        return (bh, 0, 0)

    def _physical(bh, ki, pos_ref, pt_ref):
        # clamp BEFORE the table lookup: the map only dereferences
        # entries covering positions the row has written.
        kc = jnp.minimum(ki, pos_ref[bh // H] // block_k)
        return pt_ref[bh // H, kc // bpp], kc % bpp

    # (page, head) merge into one leading dim, as the ring kernel folds
    # (row, head). K and V keep their last two extents, so the merge
    # moves no byte; a scale pool becomes one [1, page_size] row per
    # (page, head), which XLA re-tiles (1/16 of the int8 pool's bytes).
    # Payload and scale blocks share one map: both are (page * H + head,
    # 0, block within the page)
    def kv_map(bh, ki, pos_ref, pt_ref):
        page, intra = _physical(bh, ki, pos_ref, pt_ref)
        return (page * H + bh % H, 0, intra)

    in_specs = [
        pl.BlockSpec((1, 1, D), q_map),
        pl.BlockSpec((1, D, block_k), kv_map),
        pl.BlockSpec((1, D, block_k), kv_map),
    ]
    args = [qh, k.reshape(n_pages * H, D, page_size),
            v.reshape(n_pages * H, D, page_size)]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, block_k), kv_map),
                     pl.BlockSpec((1, 1, block_k), kv_map)]
        args += [k_scale.reshape(n_pages * H, 1, page_size),
                 v_scale.reshape(n_pages * H, 1, page_size)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * H, n_kb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, D), q_map),
        scratch_shapes=_softmax_scratch(D),
    )
    call = pl.pallas_call(
        _flash_decode_kernel(H, D, block_k, n_kb, quant, paged=True),
        name=DECODE_PAGED_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, 1, D), q.dtype),
        interpret=interpret,
    )
    with jax.named_scope(DECODE_PAGED_NAME):
        out = call(jnp.asarray(positions, jnp.int32),
                   jnp.asarray(page_tables, jnp.int32), *args)
    return out.reshape(B, H, 1, D).transpose(0, 2, 1, 3)

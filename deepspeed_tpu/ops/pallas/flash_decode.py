"""Flash-decode: split-K attention over the serving KV cache.

The decode step of the serving engine (`inference/engine.py`) attends
one query token per row over that row's cached keys and values. The
dense path dequantizes the whole cache to compute dtype and runs a
``[1, max_seq]`` softmax per head: O(max_seq) HBM traffic per token no
matter how short the active requests are. Two kernels replace it, one
per cache layout. They share the mathematics (below) and nothing of
their grids, because the two layouts put the heads in different
places: the ring's ``[B, S, H, D]`` has them between positions and
``D``, the pool's ``[n_pages, H, D, page]`` outside both.

What both do:

- **split-K online softmax**: a row's cache streams through VMEM in
  ``block_k``-sized KV blocks; running max, sum and output merge across
  blocks (the cross-block log-sum-exp merge), so the ``[1, max_seq]``
  score row never materializes. Scores, softmax and accumulators are
  float32 whatever the storage dtype.
- **the mask contract**: cache index ``s`` is admitted for row ``b``
  iff ``s <= positions[b]``, the dense oracle's rule. Stale tenants of
  a recycled row or page past that position are invisible.
- **fused KV dequantization**: int8/f8e4m3fn/f8e5m2 blocks
  (`inference/cache.py` codec storage) enter the kernel in their
  storage dtype with their per-position scales as lane-major rows
  beside them; scores are rescaled by the key scales and probabilities
  by the value scales, in registers. The quantized cache never
  materializes an fp32 copy in HBM.
- **local heads only**: under tensor parallelism the caller wraps the
  kernel in ``shard_map`` with the cache's head axis sharded
  (`cache.kv_partition_specs`); ``H`` below is whatever the kernel is
  handed, and no arithmetic sees the global head count.

**The ring kernel** (:func:`flash_decode`), grid ``(B * H, S /
block_k)``: heads fold into the grid's leading dim (``[B, S, H, D] →
[B*H, S, D]``, the `flash_attention.py` layout), one ``(1, block_k,
D)`` block a step by ``BlockSpec``. Each row's occupancy is its
``positions[b]`` scalar, prefetched into SMEM; blocks past it are
predicated off with ``pl.when`` AND their index map clamps to the last
active block, so Pallas, which skips the DMA when consecutive grid
steps ask for the same block, reads only the occupied cache. The grid
step itself is still launched. The online-softmax state lives in ``(1,
D)`` / ``(1, 1)`` VMEM scratch, read and written whole (Mosaic refuses
scalar stores to VMEM).

**The paged kernel** (:func:`flash_decode_paged`), grid ``(B,)``: one
grid step per row walks that row's live span and nothing else. The
pool stays where it is (``ANY`` memory: no ``BlockSpec``, no pipelined
copy); inside the step a loop over the row's ``positions[b] // block_k
+ 1`` live blocks fetches each ``(H, D, block_k)`` block — all heads
of the row at once, cut straight from the 4-D pool, contiguous in HBM
when ``block_k == page_size`` — by a manual DMA into one of two VMEM
slots while the other is being attended over. The physical page comes
from the row's scalar-prefetched page table, looked up only for blocks
the row has filled, so no unallocated entry is dereferenced. A row
whose first table entry is the trash page 0 holds no request: it
starts no DMA, does no arithmetic and returns zeros. So the kernel's
time follows the cache the live rows hold — not ``max_batch x heads x
max_seq / block_k`` (6,144 launched steps a layer at the serving
cell's shape before PR 27, `PERF.md` section 6) — and
:func:`paged_grid_blocks` is that visit set as arithmetic, for the
engine's counters and the static analyzer. The scores are one dot
batched over the heads, each a ``[1, D] x [D, block_k]`` product at
the MXU's default precision, as before.

Both kernels compile for the chip (`tests/unit/test_tpu_compile.py`
pins that against a described v5e, the paged one's grid included).
Off-TPU they run in Pallas interpret mode (CPU test meshes); the dense
cached-attention path stays available as the parity oracle behind
``inference.attention.impl``.
"""

import jax
import jax.numpy as jnp
import numpy as np
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


def check_decode_geometry(block_k, extent, extent_name, kv_dtype, lanes,
                          paged_heads=None):
    """The call-time block validation, for the device this process
    compiles for — so the serving engine refuses, typed, a geometry the
    chip's compiler would refuse when it is BUILT, not at the first
    decode step (and never by serving through another path). Returns
    the clamped ``block_k``. ``paged_heads = (heads, head_dim, quant)``
    of the paged pool one device holds also checks that the paged
    kernel's all-head blocks fit VMEM."""
    interpret = jax.devices()[0].platform != "tpu"
    block_k = _validate_block_k(block_k, extent, extent_name, kv_dtype,
                                interpret, lanes)
    if paged_heads is not None:
        heads, head_dim, quant = paged_heads
        _check_paged_vmem(heads, head_dim, block_k, kv_dtype, quant)
    return block_k


def _fold_heads(x):
    """[B, S, H, D] → [B*H, S, D] (heads into the grid's leading dim)."""
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _flash_decode_kernel(H, D, block_k, n_kb, quant):
    """The ring kernel's body: one (row*head, kv-block) grid step.

    Scalar-prefetch arg 0 is the ``[B]`` positions vector (SMEM);
    scratch carries the online-softmax state (acc [1, D], running max
    and sum [1, 1]) across the sequential kv-block dim — all three are
    read and written whole, as vectors. A KV block is ``(1, block_k,
    D)``, scales a ``(1, 1, block_k)`` row.
    """

    def kernel(pos_ref, q_ref, k_ref, v_ref, *refs):
        refs = list(refs)
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
            kb = k_ref[0].astype(jnp.float32)              # [bk, D]
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
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
            vb = v_ref[0].astype(jnp.float32)              # [bk, D]
            acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
                pr, vb, (((1,), (0,)), ((), ())),
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


# the trash page: never handed out (`inference/paging.py`), so a row
# whose table starts with it holds no request
TRASH_PAGE = 0
# what Mosaic lets one kernel keep in VMEM on a v5e unless told
# otherwise (`analysis/cost.py` carries the same figure per platform)
PAGED_VMEM_BUDGET = 16 * 2 ** 20


def paged_grid_blocks(positions, page_tables, block_k):
    """``(live, launched)`` KV blocks a layer for one decode step's
    inputs, in blocks of all heads: ``live`` is what the rows hold, the
    sum over live rows of ``pos // block_k + 1``; ``launched`` is what
    :func:`flash_decode_paged` visits. The two are equal by
    construction: the kernel's loop bound is this arithmetic. (The
    grid before PR 27 visited ``rows x pages_per_row x page_size /
    block_k`` whatever the rows held.) Host-side, numpy."""
    positions = np.asarray(positions).reshape(-1)
    live = np.asarray(page_tables)[:, 0] != TRASH_PAGE
    blocks = int((positions[live] // int(block_k) + 1).sum())
    return blocks, blocks


def paged_vmem_bytes(heads, head_dim, block_k, kv_dtype, quant):
    """VMEM the paged kernel's step holds: two slots each of the K and
    V ``(H, D, block_k)`` blocks (and of their scale rows), and the
    pipelined query and output blocks. (The float32 operands of the
    dots are made a head at a time, never a whole block: a described
    v5e compiles int8 blocks of 12 MB and refuses float32 ones of 16.)"""
    elems = int(heads) * int(head_dim) * int(block_k)
    need = 2 * 2 * elems * jnp.dtype(kv_dtype).itemsize
    if quant:
        need += 2 * 2 * int(heads) * int(block_k) * 4
    return need + 2 * 2 * int(heads) * int(head_dim) * 4


def _check_paged_vmem(heads, head_dim, block_k, kv_dtype, quant):
    need = paged_vmem_bytes(heads, head_dim, block_k, kv_dtype, quant)
    if need > PAGED_VMEM_BUDGET:
        raise KernelGeometryError(
            f"paged flash decode keeps two (heads={heads}, "
            f"head_dim={head_dim}, block_k={block_k}) "
            f"{jnp.dtype(kv_dtype).name} blocks each of K and V in "
            f"VMEM: {need} bytes exceed the {PAGED_VMEM_BUDGET} a "
            f"kernel may use — lower attention_block_k (or page_size)")


def _paged_decode_kernel(H, D, block_k, bpp, quant):
    """The paged kernel's body: one grid step = one row's live span.

    Scalar-prefetch args: ``[B]`` positions and ``[B, pages_per_row]``
    page tables (SMEM). ``k_hbm`` / ``v_hbm`` (and the scale pools) are
    the whole pool, left in HBM; ``kbuf`` / ``vbuf`` are the two VMEM
    slots of a ``(H, D, block_k)`` block, ``sem`` one DMA semaphore per
    (operand, slot). Block ``i`` of the row is waited for in slot ``i %
    2`` while block ``i + 1`` streams into the other. The online-
    softmax state is the loop's carry: running max and sum ``[H, 1]``,
    output ``[H, D]``.
    """

    def kernel(pos_ref, pt_ref, q_ref, k_hbm, v_hbm, *refs):
        refs = list(refs)
        ks_hbm = refs.pop(0) if quant else None
        vs_hbm = refs.pop(0) if quant else None
        o_ref, kbuf, vbuf = refs[:3]
        refs = refs[3:]
        ksbuf = refs.pop(0) if quant else None
        vsbuf = refs.pop(0) if quant else None
        sem, = refs
        b = pl.program_id(0)
        p = pos_ref[b]

        def copies(i, slot):
            # the table is read for blocks the row has filled only
            # (i <= p // block_k): no unallocated entry is dereferenced
            page = pt_ref[b, i // bpp]
            if bpp == 1:
                lanes = slice(None)         # a whole page: contiguous
            else:
                lanes = pl.ds(pl.multiple_of((i % bpp) * block_k,
                                             block_k), block_k)
            out = [
                pltpu.make_async_copy(k_hbm.at[page, :, :, lanes],
                                      kbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page, :, :, lanes],
                                      vbuf.at[slot], sem.at[1, slot])]
            if quant:
                out += [
                    pltpu.make_async_copy(ks_hbm.at[page, :, lanes],
                                          ksbuf.at[slot], sem.at[2, slot]),
                    pltpu.make_async_copy(vs_hbm.at[page, :, lanes],
                                          vsbuf.at[slot], sem.at[3, slot])]
            return out

        live = pt_ref[b, 0] != TRASH_PAGE

        @pl.when(jnp.logical_not(live))
        def _no_request():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(live)
        def _row():
            n_blocks = p // block_k + 1
            for c in copies(0, 0):
                c.start()
            qb = q_ref[0].astype(jnp.float32)[:, None, :]   # [H, 1, D]

            def block(i, carry):
                m_prev, l_prev, acc = carry
                slot = i % 2

                @pl.when(i + 1 < n_blocks)
                def _prefetch():
                    for c in copies(i + 1, 1 - slot):
                        c.start()
                for c in copies(i, slot):
                    c.wait()
                kb = kbuf[slot].astype(jnp.float32)         # [H, D, bk]
                s = jax.lax.dot_general(
                    qb, kb, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32
                ).reshape(H, block_k)
                if quant:
                    # fused dequant, as in the ring kernel: the scale
                    # rows are [H, bk] f32, lane-major like the scores
                    s = s * ksbuf[slot]
                s = s * (D ** -0.5)
                k_pos = i * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (H, block_k), 1)
                s = jnp.where(k_pos <= p, s, DEFAULT_MASK_VALUE)
                m_new = jnp.maximum(m_prev,
                                    s.max(axis=-1, keepdims=True))
                pr = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_new = l_prev * corr + pr.sum(axis=-1, keepdims=True)
                if quant:
                    pr = pr * vsbuf[slot]
                vb = vbuf[slot].astype(jnp.float32)         # [H, D, bk]
                pv = jax.lax.dot_general(
                    pr[:, None, :], vb, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)     # [H, 1, D]
                return m_new, l_new, acc * corr + pv.reshape(H, D)

            _, l, acc = jax.lax.fori_loop(
                0, n_blocks, block,
                (jnp.full((H, 1), -jnp.inf, jnp.float32),
                 jnp.zeros((H, 1), jnp.float32),
                 jnp.zeros((H, D), jnp.float32)))
            o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    return kernel


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

    One grid step a row; the row's ``positions[b] // block_k + 1`` live
    blocks are fetched from the pool by manual DMA inside it, all heads
    to a block, so neither a block past a row's position nor a table
    entry past its occupancy is ever touched. A row whose table starts
    with the trash page (no request in that slot: the scheduler hands
    such rows position 0 and an all-zero table) runs nothing and
    returns zeros. ``block_k`` clamps to ``page_size`` and must tile it
    — a KV block never straddles a page boundary, which is what keeps
    the fetch one slab of one page.
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
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    quant = k_scale is not None
    # positions are the lane axis of every block, scales or not
    block_k = _validate_block_k(block_k, page_size, "page_size",
                                k.dtype, interpret, lanes=True)
    _check_paged_vmem(H, D, block_k, k.dtype, quant)

    def row(b, pos_ref, pt_ref):
        return (b, 0, 0)

    pool = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, H, D), row), pool, pool]
    args = [q.reshape(B, H, D), k, v]
    scratch = [pltpu.VMEM((2, H, D, block_k), k.dtype),
               pltpu.VMEM((2, H, D, block_k), v.dtype)]
    if quant:
        in_specs += [pool, pool]
        args += [k_scale, v_scale]
        scratch += [pltpu.VMEM((2, H, block_k), jnp.float32),
                    pltpu.VMEM((2, H, block_k), jnp.float32)]
    scratch.append(pltpu.SemaphoreType.DMA((4 if quant else 2, 2)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, D), row),
        scratch_shapes=scratch,
    )
    call = pl.pallas_call(
        _paged_decode_kernel(H, D, block_k, page_size // block_k, quant),
        name=DECODE_PAGED_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )
    with jax.named_scope(DECODE_PAGED_NAME):
        out = call(jnp.asarray(positions, jnp.int32),
                   jnp.asarray(page_tables, jnp.int32), *args)
    return out.reshape(B, 1, H, D)

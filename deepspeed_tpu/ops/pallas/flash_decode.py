"""Flash-decode: split-K attention over the serving KV pool.

The decode step of the serving engine (`inference/engine.py`) attends
one query token per row over that row's cached keys and values. The
dense path dequantizes the row's gathered pages to compute dtype and
runs a ``[1, max_seq]`` softmax per head: O(max_seq) HBM traffic per
token no matter how short the active requests are. One kernel
(:func:`flash_decode_paged`) replaces it, cut to the pool's layout
``[n_pages, H, D, page]`` (`inference/cache.py`):

- **split-K online softmax**: a row's cache streams through VMEM in
  ``block_k``-sized KV blocks; running max, sum and output merge across
  blocks (the cross-block log-sum-exp merge), so the ``[1, max_seq]``
  score row never materializes. Scores, softmax and accumulators are
  float32 whatever the storage dtype.
- **the mask contract**: cache index ``s`` is admitted for row ``b``
  iff ``s <= positions[b]``, the dense oracle's rule. Stale tenants of
  a recycled page past that position are invisible.
- **fused KV dequantization**: int8/f8e4m3fn/f8e5m2 blocks
  (`inference/cache.py` codec storage) enter the kernel in their
  storage dtype with their per-position scales as lane-major rows
  beside them; scores are rescaled by the key scales and probabilities
  by the value scales, in registers. The quantized cache never
  materializes an fp32 copy in HBM.
- **local heads only**: under tensor parallelism the caller wraps the
  kernel in ``shard_map`` with the pool's head axis sharded
  (`cache.kv_partition_specs`); ``H`` below is whatever the kernel is
  handed, and no arithmetic sees the global head count.

**The grid is ``(B,)``**: one grid step per row walks that row's live
span and nothing else. The pool stays where it is (``ANY`` memory: no ``BlockSpec``, no pipelined
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
the MXU's default precision.

The kernel compiles for the chip (`tests/unit/test_tpu_compile.py`
pins that against a described v5e, its grid included). Off-TPU it
runs in Pallas interpret mode (CPU test meshes); the dense
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
# the kernel's name in the HLO and in a device trace (see
# flash_attention.py)
DECODE_PAGED_NAME = "ds_flash_decode_paged"

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


def _validate_block_k(block_k, page_size, interpret):
    """Clamp and validate ``block_k`` against the page it tiles (a KV
    block never straddles a page). The lane rule only gates the
    COMPILED path (``interpret`` False, i.e. a real TPU lowering where
    Mosaic's tiling constraints bite): positions are the minor dim of
    every pool block and scale row, so a block is whole lanes or the
    whole page; interpret-mode CPU runs accept any divisor so CI toys
    stay small.
    """
    block_k = int(block_k)
    if block_k < 1:
        raise KernelGeometryError(
            f"attention block_k must be >= 1, got {block_k}")
    block_k = min(block_k, int(page_size))
    if page_size % block_k:
        raise KernelGeometryError(
            f"page_size {page_size} must be a multiple of attention "
            f"block_k {block_k}")
    if not interpret and block_k % _LANES and block_k != page_size:
        raise KernelGeometryError(
            f"attention block_k {block_k} over a paged pool must be a "
            f"multiple of {_LANES} (or cover the whole page_size "
            f"{page_size}): scales and pool pages stream lane-major, "
            f"block_k positions to a row")
    return block_k


def check_decode_geometry(block_k, page_size, kv_dtype, heads, head_dim,
                          quant):
    """The call-time block validation, for the device this process
    compiles for — so the serving engine refuses, typed, a geometry the
    chip's compiler would refuse when it is BUILT, not at the first
    decode step (and never by serving through another path). Returns
    the clamped ``block_k``. ``heads`` x ``head_dim`` is what one
    device holds of the pool: the kernel's all-head blocks must fit
    VMEM."""
    interpret = jax.devices()[0].platform != "tpu"
    block_k = _validate_block_k(block_k, page_size, interpret)
    _check_paged_vmem(heads, head_dim, block_k, kv_dtype, quant)
    return block_k


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


def _paged_decode_kernel(H, D, block_k, bpp, quant, G=1, scale=None):
    """The paged kernel's body: one grid step = one row's live span.

    ``H`` is the pool's (key/value) heads; with ``G`` > 1 query heads
    to each (grouped-query attention) the query block is ``[H, G, D]``
    and the ``G`` queries of a group attend over the one fetched
    ``(D, block_k)`` block of their key head: scores, running max and
    sum and the output carry a group axis (``rows`` below), the fetches
    do not change. ``G`` = 1 is the kernel as it was. ``scale``
    multiplies the scores (``None``: ``D ** -0.5``).

    Scalar-prefetch args: ``[B]`` positions and ``[B, pages_per_row]``
    page tables (SMEM). ``k_hbm`` / ``v_hbm`` (and the scale pools) are
    the whole pool, left in HBM; ``kbuf`` / ``vbuf`` are the two VMEM
    slots of a ``(H, D, block_k)`` block, ``sem`` one DMA semaphore per
    (operand, slot). Block ``i`` of the row is waited for in slot ``i %
    2`` while block ``i + 1`` streams into the other. The online-
    softmax state is the loop's carry: running max and sum ``[H, 1]``,
    output ``[H, D]``.
    """

    rows = (H,) if G == 1 else (H, G)
    scale = D ** -0.5 if scale is None else float(scale)

    def over_heads(a):
        """A per-(head, position) array against the scores' rows."""
        return a if G == 1 else a[:, None, :]

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
            qb = q_ref[0].astype(jnp.float32)       # [H, D] | [H, G, D]
            if G == 1:
                qb = qb[:, None, :]                             # [H, 1, D]

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
                ).reshape(rows + (block_k,))
                if quant:
                    # fused dequant: scale the SCORES by the key scales
                    # (dot distributes over the per-position scalar);
                    # rows are [H, bk] f32, lane-major like the scores
                    s = s * over_heads(ksbuf[slot])
                s = s * scale
                k_pos = i * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, rows + (block_k,), len(rows))
                s = jnp.where(k_pos <= p, s, DEFAULT_MASK_VALUE)
                m_new = jnp.maximum(m_prev,
                                    s.max(axis=-1, keepdims=True))
                pr = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_new = l_prev * corr + pr.sum(axis=-1, keepdims=True)
                if quant:
                    pr = pr * over_heads(vsbuf[slot])
                vb = vbuf[slot].astype(jnp.float32)         # [H, D, bk]
                pv = jax.lax.dot_general(
                    pr[:, None, :] if G == 1 else pr, vb,
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)     # [H, G, D]
                return m_new, l_new, acc * corr + pv.reshape(rows + (D,))

            _, l, acc = jax.lax.fori_loop(
                0, n_blocks, block,
                (jnp.full(rows + (1,), -jnp.inf, jnp.float32),
                 jnp.zeros(rows + (1,), jnp.float32),
                 jnp.zeros(rows + (D,), jnp.float32)))
            o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    return kernel


def flash_decode_paged(q, k, v, positions, page_tables, k_scale=None,
                       v_scale=None, block_k=DEFAULT_BLOCK_K,
                       interpret=None, scale=None):
    """Split-K flash decode over a paged KV pool.

    ``q``: ``[B, 1, Hq, D]`` compute-dtype query (the decode step's
    single token per row); ``Hq`` is the pool's ``H`` or a multiple of
    it (grouped-query attention: query head ``h`` attends over key head
    ``h // (Hq // H)``). ``scale`` multiplies the scores (``None``:
    ``D ** -0.5``). ``k``/``v``: the POOL buffers
    ``[n_pages, H, D, page_size]`` in storage dtype (scales ``[n_pages, H, page_size]`` when quantized —
    `inference/cache.py` paged layout). ``page_tables``: ``[B,
    pages_per_row]`` int32 physical page ids per row (entry 0 = the
    trash page for unallocated slots). ``positions``: ``[B]`` int32,
    each row's current write position (the mask admits cache index
    ``s`` iff ``s <= positions[b]`` — identical to the dense oracle's).
    Returns ``[B, 1, H, D]`` in ``q.dtype``. ``interpret=None``
    auto-selects: compiled kernel on TPU, Pallas interpret mode
    elsewhere.

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
    B, Hq = q.shape[0], q.shape[2]
    G = Hq // H
    if q.shape != (B, 1, G * H, D) or G < 1:
        raise ValueError(
            f"flash_decode_paged takes one query token per row, and a "
            f"whole number of query heads to each of the pool's {H}: q "
            f"shape {q.shape} != {(B, 1, 'G *', H, D)}")
    if page_tables.shape[0] != B:
        raise ValueError(
            f"page_tables rows {page_tables.shape[0]} != batch {B}")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    quant = k_scale is not None
    block_k = _validate_block_k(block_k, page_size, interpret)
    _check_paged_vmem(H, D, block_k, k.dtype, quant)

    # the query and the output as the kernel sees them: [B, H, D], or
    # with a group axis [B, H, G, D] (a reshape of the model's layout:
    # query head h is (h // G, h % G))
    qshape = (H, D) if G == 1 else (H, G, D)

    def row(b, pos_ref, pt_ref):
        return (b,) + (0,) * len(qshape)

    pool = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1,) + qshape, row), pool, pool]
    args = [q.reshape((B,) + qshape), k, v]
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
        out_specs=pl.BlockSpec((1,) + qshape, row),
        scratch_shapes=scratch,
    )
    call = pl.pallas_call(
        _paged_decode_kernel(H, D, block_k, page_size // block_k, quant,
                             G, scale),
        name=DECODE_PAGED_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B,) + qshape, q.dtype),
        interpret=interpret,
    )
    with jax.named_scope(DECODE_PAGED_NAME):
        out = call(jnp.asarray(positions, jnp.int32),
                   jnp.asarray(page_tables, jnp.int32), *args)
    return out.reshape(B, 1, Hq, D)

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

**A latent pool** (ISSUE 34): a pool with no ``v`` leaf holds one
"head" a layer, a token's compressed key-value latent beside its shared
rotary key (`models/mla_moe.py`), and every query head attends over it
(``G`` = all the query heads, ``H`` = 1). The kernel is the same: the
values are the leading ``v_dim`` sublanes of the key block it has
fetched, so a block is read once and is both operands; the scores are a
``[G, D] x [D, block_k]`` product, the first decode shape here whose
queries fill rows of the MXU, and its operands stay in the pool's
bfloat16 (the other pools' one-row products go through float32
copies). The step's write is one leaf's lane instead of two.

**Values narrower than keys, a window, a sink** (ISSUE 47,
`models/mimo_v2.py`). A pool's ``v`` leaf may be narrower than its
``k`` leaf (keys of 192 over values of 128): the value block's slots
and the output are as wide as the ``v`` leaf. With ``window`` > 0 the
row's table is a **ring** (`inference/cache.py`): position ``p`` lies
in entry ``(p // page_size) % ring``, the walk starts at the block of
``p - window + 1`` and not at 0, and a key is admitted iff ``p - window
< s <= p``, by its position and not by where it lies; the step's write
goes to the ring entry of ``p``. ``sink``: a learned logit a query head
that joins the softmax's denominator and no value: the running max
starts at it and the running sum at 1. With none of the three the
kernel is traced as it was.

The kernel compiles for the chip (`tests/unit/test_tpu_compile.py`
pins that against a described v5e, its grid included). Off-TPU it
runs in Pallas interpret mode (CPU test meshes); the dense
cached-attention path stays available as the parity oracle behind
``inference.attention.impl``.
"""

import functools

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
                          quant, latent=False, v_dim=None):
    """The call-time block validation, for the device this process
    compiles for — so the serving engine refuses, typed, a geometry the
    chip's compiler would refuse when it is BUILT, not at the first
    decode step (and never by serving through another path). Returns
    the clamped ``block_k``. ``heads`` x ``head_dim`` is what one
    device holds of the pool: the kernel's all-head blocks must fit
    VMEM. ``v_dim``: the values' width where it is not the keys'."""
    interpret = jax.devices()[0].platform != "tpu"
    block_k = _validate_block_k(block_k, page_size, interpret)
    _check_paged_vmem(heads, head_dim, block_k, kv_dtype, quant, latent,
                      v_dim)
    return block_k


# the trash page: never handed out (`inference/paging.py`), so a row
# whose table starts with it holds no request
TRASH_PAGE = 0
# what Mosaic lets one kernel keep in VMEM on a v5e unless told
# otherwise (`analysis/cost.py` carries the same figure per platform)
PAGED_VMEM_BUDGET = 16 * 2 ** 20


def paged_grid_blocks(positions, page_tables, block_k, window=0):
    """``(live, launched)`` KV blocks a layer for one decode step's
    inputs, in blocks of all heads: ``live`` is what the rows hold, the
    sum over live rows of ``pos // block_k + 1``; ``launched`` is what
    :func:`flash_decode_paged` visits. The two are equal by
    construction: the kernel's loop bound is this arithmetic. (The
    grid before PR 27 visited ``rows x pages_per_row x page_size /
    block_k`` whatever the rows held.) With ``window`` > 0 a row holds
    the blocks its window reaches, from that of ``p - window + 1`` to
    that of ``p``, and the kernel's walk starts there. Host-side,
    numpy."""
    positions = np.asarray(positions).reshape(-1)
    live = np.asarray(page_tables)[:, 0] != TRASH_PAGE
    pos = positions[live]
    first = np.maximum(pos - int(window) + 1, 0) // int(block_k) \
        if window else 0
    blocks = int((pos // int(block_k) - first + 1).sum())
    return blocks, blocks


def paged_vmem_bytes(heads, head_dim, block_k, kv_dtype, quant,
                     latent=False, v_dim=None):
    """VMEM the paged kernel's step holds: two slots each of the K and
    V ``(H, D, block_k)`` blocks (of the one block of a ``latent`` pool,
    which has no V; and of their scale rows), the
    pipelined query and output blocks, and the pipelined block of the
    step's new keys and values, float32, a row to a lane (and of their
    scales). (The float32 operands of the dots are made a head at a
    time, never a whole block: a described v5e compiles int8 blocks of
    12 MB and refuses float32 ones of 16.) ``v_dim``: the V block's
    sublanes where they are fewer than the K block's."""
    elems = int(heads) * int(head_dim) * int(block_k)
    leaves = 1 if latent else 2
    need = leaves * 2 * elems * jnp.dtype(kv_dtype).itemsize
    if v_dim is not None and not latent:
        need -= 2 * int(heads) * (int(head_dim) - int(v_dim)) * \
            int(block_k) * jnp.dtype(kv_dtype).itemsize
    new = leaves * 2 * int(heads) * int(head_dim) * _LANES * 4
    if quant:
        need += 2 * 2 * int(heads) * int(block_k) * 4
        new += 2 * 2 * int(heads) * _LANES * 4
    return need + new + 2 * 2 * int(heads) * int(head_dim) * 4


def _check_paged_vmem(heads, head_dim, block_k, kv_dtype, quant,
                      latent=False, v_dim=None):
    need = paged_vmem_bytes(heads, head_dim, block_k, kv_dtype, quant,
                            latent, v_dim)
    if need > PAGED_VMEM_BUDGET:
        raise KernelGeometryError(
            f"paged flash decode keeps two (heads={heads}, "
            f"head_dim={head_dim}, block_k={block_k}) "
            f"{jnp.dtype(kv_dtype).name} blocks each of K and V in "
            f"VMEM: {need} bytes exceed the {PAGED_VMEM_BUDGET} a "
            f"kernel may use — lower attention_block_k (or page_size)")


def _paged_decode_kernel(H, D, block_k, bpp, quant, G=1, scale=None,
                         v_dim=None, kv_dim=None, window=0, ring=0,
                         sink=False):
    """The paged kernel's body: one grid step = one row's live span,
    the row's new key and value written into it.

    ``H`` is the pool's (key/value) heads; with ``G`` > 1 query heads
    to each (grouped-query attention) the query block is ``[H, G, D]``
    and the ``G`` queries of a group attend over the one fetched
    ``(D, block_k)`` block of their key head: scores, running max and
    sum and the output carry a group axis (``rows`` below), the fetches
    do not change. ``G`` = 1 is the kernel as it was. ``scale``
    multiplies the scores (``None``: ``D ** -0.5``). ``v_dim``: the
    pool is a latent one, with no V leaf: the values are the first
    ``v_dim`` of the fetched key block's ``D`` sublanes, the output is
    ``v_dim`` wide, and the two products take their operands as the
    pool stores them (no float32 copies: ``G`` queries fill MXU rows).

    Scalar-prefetch args: ``[B]`` positions and ``[B, pages_per_row]``
    page tables (SMEM). ``pools`` (K, V and, quantized, their scales)
    are the whole pool, left in HBM: the call's outputs, which alias
    its pool inputs, so one buffer is read and written. ``bufs`` are
    the two VMEM slots of each operand's ``(H, D, block_k)`` block,
    ``sem`` one DMA semaphore per (operand, slot, direction). Block
    ``i`` of the row is waited for in slot ``i % 2`` while block ``i +
    1`` streams into the other. The online-softmax state is the loop's
    carry: running max and sum ``[H, 1]``, output ``[H, D]``.

    **The write** (PR 33). The row's last block ``p // block_k`` holds
    position ``p``, which this step fills: once fetched, lane ``p %
    block_k`` of every head is replaced in its slot by the row's new
    key and value (``new_ref``: ``[2, H, D, 128]`` float32, row ``b``
    on lane ``b % 128``, turned so that it lands on the lane it goes
    to), the slot is DMA'd back to where it came from, and the block
    is attended over as any other. The page is the row's alone
    (`inference/paging.py`: writes never target shared pages), so no
    other grid step reads or writes it. The write-back is not waited
    for in the row's own step: ``pend[slot]`` (SMEM) says that one is
    under way from ``slot``, and whoever fetches into that slot next,
    or the last grid step, waits first (``settle``). A row without a
    request starts no DMA and writes nothing.

    ``kv_dim``: the V leaf's sublanes where they are fewer than ``D``
    (the new value comes padded to ``D`` beside the new key). ``window``
    > 0: the table is a ring of ``ring`` pages, the walk starts at the
    block of ``p - window + 1`` and a key is admitted by its position
    inside the window. ``sink``: a ref of one logit a query head comes
    after the new keys and values; the running max starts at it and the
    running sum at 1 (its weight in the denominator), with no value.
    """

    rows = (H,) if G == 1 else (H, G)
    scale = D ** -0.5 if scale is None else float(scale)
    latent = v_dim is not None
    n_pay = 1 if latent else 2          # payload leaves: K (and V)
    n_pool = n_pay + (2 if quant else 0)
    Dv = v_dim if latent else (kv_dim or D)

    def over_heads(a):
        """A per-(head, position) array against the scores' rows."""
        return a if G == 1 else a[:, None, :]

    def kernel(pos_ref, pt_ref, q_ref, new_ref, *refs):
        refs = list(refs)
        snew_ref = refs.pop(0) if quant else None
        sink_ref = refs.pop(0) if sink else None
        del refs[:n_pool]           # the pool as handed in: see pools
        o_ref = refs.pop(0)
        pools, bufs = refs[:n_pool], refs[n_pool:2 * n_pool]
        sem, pend = refs[2 * n_pool:]
        b = pl.program_id(0)
        p = pos_ref[b]

        def copies(i, slot, back=False):
            """Block ``i`` of the row into ``slot``, or (``back``) out
            of it to where it came from."""
            # the table is read for blocks the row has filled only
            # (i <= p // block_k): no unallocated entry is dereferenced
            page = pt_ref[b, (i // bpp) % ring if window else i // bpp]
            if bpp == 1:
                lanes = slice(None)         # a whole page: contiguous
            else:
                lanes = pl.ds(pl.multiple_of((i % bpp) * block_k,
                                             block_k), block_k)
            out = []
            for j, (hbm, buf) in enumerate(zip(pools, bufs)):
                hbm = hbm.at[page, :, :, lanes] if j < n_pay \
                    else hbm.at[page, :, lanes]
                ends = (buf.at[slot], hbm) if back else (hbm, buf.at[slot])
                out.append(pltpu.make_async_copy(
                    *ends, sem.at[j, slot, int(back)]))
            return out

        def settle(slot):
            """``slot`` may be fetched into again: the write-back an
            earlier row started from it, if any, has landed."""
            @pl.when(pend[slot] != 0)
            def _landed():
                # a wait reads the semaphore and the block's size
                for c in copies(0, slot, back=True):
                    c.wait()
                pend[slot] = 0

        @pl.when(b == 0)
        def _nothing_under_way():
            pend[0] = 0
            pend[1] = 0

        live = pt_ref[b, 0] != TRASH_PAGE

        @pl.when(jnp.logical_not(live))
        def _no_request():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(live)
        def _row():
            n_blocks = p // block_k + 1
            if window:
                # the block that holds the window's first position
                first = jnp.maximum(p - window + 1, 0) // block_k
                settle(first % 2)
                for c in copies(first, first % 2):
                    c.start()
            else:
                first = 0
                settle(0)
                for c in copies(0, 0):
                    c.start()
            # [H, D] | [H, G, D]
            qb = q_ref[0] if latent else q_ref[0].astype(jnp.float32)
            if G == 1:
                qb = qb[:, None, :]                             # [H, 1, D]

            def put(slot):
                """The row's new key and value (and scales) onto lane
                ``p % block_k`` of the block in ``slot``, every other
                lane as it was fetched."""
                off = p % block_k
                # row b's column, turned from lane b % 128 to lane off
                turn = (off - b) % _LANES
                new = pltpu.roll(new_ref[...], turn, 3)  # [n_pay, H, D, 128]
                snew = pltpu.roll(snew_ref[...], turn, 2) if quant else None
                for j, buf in enumerate(bufs):
                    col = new[j] if j < n_pay else snew[j - n_pay]
                    if j == 1 and Dv != D:
                        col = col[:, :Dv]   # the value came padded to D
                    if block_k <= _LANES:
                        col = col[..., :block_k]
                    else:
                        col = jnp.concatenate(
                            [col] * (block_k // _LANES), axis=-1)
                    blk = buf[slot]
                    hit = jax.lax.broadcasted_iota(
                        jnp.int32, blk.shape, blk.ndim - 1) == off
                    buf[slot] = jnp.where(
                        hit, col, blk.astype(jnp.float32)).astype(blk.dtype)

            def block(i, carry):
                m_prev, l_prev, acc = carry
                slot = i % 2

                @pl.when(i + 1 < n_blocks)
                def _prefetch():
                    settle(1 - slot)
                    for c in copies(i + 1, 1 - slot):
                        c.start()
                for c in copies(i, slot):
                    c.wait()

                @pl.when(i + 1 == n_blocks)
                def _write():
                    put(slot)
                    for c in copies(i, slot, back=True):
                        c.start()
                    pend[slot] = 1
                kb = bufs[0][slot]                          # [H, D, bk]
                if not latent:
                    kb = kb.astype(jnp.float32)
                s = jax.lax.dot_general(
                    qb, kb, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32
                ).reshape(rows + (block_k,))
                if quant:
                    # fused dequant: scale the SCORES by the key scales
                    # (dot distributes over the per-position scalar);
                    # rows are [H, bk] f32, lane-major like the scores
                    s = s * over_heads(bufs[2][slot])
                s = s * scale
                k_pos = i * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, rows + (block_k,), len(rows))
                seen = k_pos <= p
                if window:
                    seen = jnp.logical_and(seen, k_pos > p - window)
                s = jnp.where(seen, s, DEFAULT_MASK_VALUE)
                m_new = jnp.maximum(m_prev,
                                    s.max(axis=-1, keepdims=True))
                pr = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_new = l_prev * corr + pr.sum(axis=-1, keepdims=True)
                if quant:
                    pr = pr * over_heads(bufs[3][slot])
                if latent:
                    # the values are the key block's leading sublanes
                    vb, pr = kb[:, :Dv], pr.astype(kb.dtype)
                else:
                    vb = bufs[1][slot].astype(jnp.float32)  # [H, D, bk]
                pv = jax.lax.dot_general(
                    pr[:, None, :] if G == 1 else pr, vb,
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)     # [H, G, Dv]
                return m_new, l_new, acc * corr + pv.reshape(rows + (Dv,))

            if sink:
                stat0 = (sink_ref[...].astype(jnp.float32),
                         jnp.ones(rows + (1,), jnp.float32))
            else:
                stat0 = (jnp.full(rows + (1,), -jnp.inf, jnp.float32),
                         jnp.zeros(rows + (1,), jnp.float32))
            _, l, acc = jax.lax.fori_loop(
                first, n_blocks, block,
                stat0 + (jnp.zeros(rows + (Dv,), jnp.float32),))
            o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

        @pl.when(b == pl.num_programs(0) - 1)
        def _drain():
            settle(0)
            settle(1)

    return kernel


def _rows_on_lanes(new, dtype):
    """A decode step's new keys and values (or their scales; a latent
    pool's keys alone), ``[B, 1, ...]`` arrays, rounded to the pool's
    ``dtype``, as one float32 ``[len(new), ..., ceil(B / 128) * 128]``:
    a row to a lane, which is where a position lies in the pool's
    blocks. Every storage dtype's values are float32 values."""
    x = jnp.stack([a[:, 0] for a in new]).astype(dtype).astype(
        jnp.float32)
    x = jnp.moveaxis(x, 1, -1)
    pad = -x.shape[-1] % _LANES
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def flash_decode_paged(q, new, pool, positions, page_tables,
                       block_k=DEFAULT_BLOCK_K, interpret=None, scale=None,
                       v_dim=None, window=0, sink=None):
    """One decode step's attention over a paged KV pool, the step's own
    keys and values written into the pool on the way: returns ``(out,
    pool)``.

    ``q``: ``[B, 1, Hq, D]`` compute-dtype query (the decode step's
    single token per row); ``Hq`` is the pool's ``H`` or a multiple of
    it (grouped-query attention: query head ``h`` attends over key head
    ``h // (Hq // H)``). ``scale`` multiplies the scores (``None``:
    ``D ** -0.5``). ``pool``: a layer's leaves, ``k`` / ``v``
    ``[n_pages, H, D, page_size]`` in storage dtype and, quantized,
    ``k_scale`` / ``v_scale`` ``[n_pages, H, page_size]``
    (`inference/cache.py` paged layout). ``new``: the same keys with
    what the step adds at each row's position, ``[B, 1, H, D]`` (cast to
    the pool's dtype here; for a codec pool the payload quantized
    outside, as every write's is) and ``[B, 1, H]`` scales. ``page_tables``: ``[B, pages_per_row]`` int32 physical
    page ids per row (entry 0 = the trash page for unallocated slots).
    A pool without a ``v`` leaf is a latent one (``new`` has none
    either): the values are the leading ``v_dim`` (required, at most
    ``D``) entries of each key, and ``out`` is ``[B, 1, Hq, v_dim]``.
    ``positions``: ``[B]`` int32, each row's current write position
    (the mask admits cache index ``s`` iff ``s <= positions[b]`` —
    identical to the dense oracle's). ``out`` is ``[B, 1, Hq, D]`` in
    ``q.dtype``; the returned pool's leaves alias the ones handed in
    (``input_output_aliases``), so a caller that donates its pool has
    nothing pool-shaped copied. ``interpret=None`` auto-selects:
    compiled kernel on TPU, Pallas interpret mode elsewhere.

    One grid step a row; the row's ``positions[b] // block_k + 1`` live
    blocks are fetched from the pool by manual DMA inside it, all heads
    to a block, so neither a block past a row's position nor a table
    entry past its occupancy is ever touched. The last of them holds
    the position the step fills: it gets the row's ``new`` lane in VMEM
    before its scores are made and is written back whole, every other
    lane as it was fetched. A row whose table starts with the trash
    page (no request in that slot: the scheduler hands such rows
    position 0 and an all-zero table) runs nothing, writes nothing and
    returns zeros. ``block_k`` clamps to ``page_size`` and must tile it
    — a KV block never straddles a page boundary, which is what keeps
    the fetch one slab of one page.

    The ``v`` leaf may be narrower than the ``k`` leaf (``[n_pages, H,
    Dv, page_size]``): ``out`` is then ``Dv`` wide. ``window`` > 0:
    ``page_tables`` is the rows' ring (position ``p`` in entry ``(p //
    page_size) % width``) and a row attends over the last ``window``
    positions, its own among them. ``sink``: ``[Hq]`` float32, a logit
    a query head in the softmax's denominator that carries no value.
    """
    H, D, page_size = pool["k"].shape[1:]
    B, Hq = q.shape[0], q.shape[2]
    if q.shape != (B, 1, Hq // H * H, D) or Hq < H:
        raise ValueError(
            f"flash_decode_paged takes one query token per row, and a "
            f"whole number of query heads to each of the pool's {H}: q "
            f"shape {q.shape} != {(B, 1, 'G *', H, D)}")
    if page_tables.shape[0] != B:
        raise ValueError(
            f"page_tables rows {page_tables.shape[0]} != batch {B}")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    latent = "v" not in pool
    if set(new) != set(pool) or set(pool) not in (
            {"k"}, {"k", "v"}, {"k", "v", "k_scale", "v_scale"}):
        raise ValueError(
            f"the step's new leaves {sorted(new)} must be the pool's "
            f"{sorted(pool)}: k and v with both scales or neither, or "
            f"(a latent pool) k alone")
    if latent != (v_dim is not None) or (latent and not 0 < v_dim <= D):
        raise ValueError(
            f"v_dim {v_dim} goes with a latent pool (k alone, its values "
            f"the first v_dim <= {D} entries of a key) and with no other; "
            f"the pool's leaves are {sorted(pool)}")
    block_k = _validate_block_k(block_k, page_size, interpret)
    kv_dim = None if latent else pool["v"].shape[2]
    if (window or sink is not None or kv_dim not in (None, D)) and \
            "k_scale" in pool:
        raise ValueError(
            "a window, a sink and values narrower than keys go with a "
            "pool in plain storage")
    if window and (window < 1 or
                   (page_tables.shape[1] - 1) * page_size < window):
        raise ValueError(
            f"a ring of {page_tables.shape[1]} pages of {page_size} "
            f"cannot hold a window of {window} and the page being written")
    _check_paged_vmem(H, D, block_k, pool["k"].dtype, "k_scale" in pool,
                      latent, kv_dim)
    return _paged_call(q, new, pool, jnp.asarray(positions, jnp.int32),
                       jnp.asarray(page_tables, jnp.int32), sink,
                       block_k=block_k, interpret=bool(interpret),
                       scale=scale, v_dim=v_dim, window=int(window))


# jitted, so that a model's layers share one trace and one lowering of
# the kernel: in a process that holds a large engine every traced
# equation of a kernel body costs milliseconds (`PERF.md`, PR 30), and
# the decode program calls this once a layer
@functools.partial(jax.jit, static_argnames=("block_k", "interpret", "scale",
                                             "v_dim", "window"))
def _paged_call(q, new, pool, positions, page_tables, sink=None, *, block_k,
                interpret, scale, v_dim=None, window=0):
    k = pool["k"]
    H, D, page_size = k.shape[1:]
    B, Hq = q.shape[0], q.shape[2]
    G = Hq // H
    quant = "k_scale" in pool
    payload = ("k",) if v_dim is not None else ("k", "v")
    names = payload + (("k_scale", "v_scale") if quant else ())
    Dv = v_dim if v_dim is not None else pool["v"].shape[2]

    # the query and the output as the kernel sees them: [B, H, D], or
    # with a group axis [B, H, G, D] (a reshape of the model's layout:
    # query head h is (h // G, h % G))
    qshape = (H, D) if G == 1 else (H, G, D)
    oshape = qshape[:-1] + (Dv,)

    def row(b, pos_ref, pt_ref):
        return (b,) + (0,) * len(qshape)

    def lanes_of(ndim):
        # the block of 128 rows that holds row b
        return lambda b, pos_ref, pt_ref: (0,) * (ndim - 1) + (b // _LANES,)

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1,) + qshape, row),
                pl.BlockSpec((len(payload), H, D, _LANES), lanes_of(4))]
    fresh = [new[name] for name in payload]
    if v_dim is None and Dv != D:
        # beside the key in one array: the value padded to the key's width
        fresh[1] = jnp.pad(fresh[1], [(0, 0)] * 3 + [(0, D - Dv)])
    args = [q.reshape((B,) + qshape), _rows_on_lanes(fresh, k.dtype)]
    scratch = [pltpu.VMEM((2, H, pool[name].shape[2], block_k), k.dtype)
               for name in payload]
    if quant:
        in_specs.append(pl.BlockSpec((2, H, _LANES), lanes_of(3)))
        args.append(_rows_on_lanes([new["k_scale"], new["v_scale"]],
                                   jnp.float32))
        scratch += [pltpu.VMEM((2, H, block_k), jnp.float32),
                    pltpu.VMEM((2, H, block_k), jnp.float32)]
    if sink is not None:
        stat = qshape[:-1] + (1,)
        in_specs.append(pl.BlockSpec(
            stat, lambda b, pos_ref, pt_ref: (0,) * len(stat)))
        args.append(sink.astype(jnp.float32).reshape(stat))
    scratch += [pltpu.SemaphoreType.DMA((len(names), 2, 2)),
                pltpu.SMEM((2,), jnp.int32)]
    leaves = [pool[name] for name in names]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs + [anywhere] * len(leaves),
        out_specs=[pl.BlockSpec((1,) + oshape, row)]
        + [anywhere] * len(leaves),
        scratch_shapes=scratch,
    )
    call = pl.pallas_call(
        _paged_decode_kernel(H, D, block_k, page_size // block_k, quant,
                             G, scale, v_dim, None if v_dim else Dv, window,
                             page_tables.shape[1], sink is not None),
        name=DECODE_PAGED_NAME,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B,) + oshape, q.dtype)]
        + [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in leaves],
        # operand 2 + len(args) + j (the two scalar operands count) is
        # the pool's leaf j, and so is output 1 + j
        input_output_aliases={2 + len(args) + j: 1 + j
                              for j in range(len(leaves))},
        interpret=interpret,
    )
    with jax.named_scope(DECODE_PAGED_NAME):
        out, *leaves = call(positions, page_tables, *args, *leaves)
    return out.reshape(B, 1, Hq, Dv), dict(zip(names, leaves))

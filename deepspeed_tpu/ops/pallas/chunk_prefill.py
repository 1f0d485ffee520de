"""Prefill attention over a pool of per-head pages: a chunk in one call.

A prompt's chunk of queries attends over the row's live prefix, its own
keys included, in a pool of per-head keys and values
(`inference/cache.py:cached_attention`, the chunk already written by
``paged_write_kv``): cache index ``s`` is seen by the query at position
``p`` iff ``s <= p``. In plain XLA the row's whole table is gathered
(the bucket, whatever the prompt's length) and the float32 scores of a
layer, ``[Hq, T, bucket]`` (1.2 GB at 32 x 1024 x 9216), are written to
HBM, masked, put through a softmax and cast, each a pass over HBM
(`PERF.md` section 6, PR 58: 5.2 ms a layer a call at that shape
whatever the prefix, against 0.2 to 0.7 ms in this kernel at a prefix
of 1,024 to 5,120). :func:`flash_prefill_paged` is that attention as one
kernel:

- **the grid is ``(key heads, query blocks)``**: a grid step takes one
  key head's ``G`` query heads together, ``bq`` queries of each, as the
  ``[bq, G x D]`` lane block of the model's own ``[T, Hq x D]``: no
  transpose of the queries or of the output crosses HBM. The ``G``
  heads' rows are laid under one another in VMEM (``[G x bq, D]``), so
  that one product a page feeds ``G x bq`` rows to the MXU over keys
  fetched once (`ops/pallas/window_prefill.py` lays its band so).
- **the keys and values are read where they lie**: the pool's ``[n_pages,
  H, D, page]`` leaves stay in HBM (``ANY`` memory, no ``BlockSpec``,
  nothing gathered); a grid step walks its query block's key blocks of
  ``pages`` pages each, a page's ``[D, page]`` tile of this head fetched
  through the scalar-prefetched page table by a DMA of its own into one
  of two VMEM slots while the other is attended over
  (`ops/pallas/flash_decode.py` reads the pool so). **A query block
  visits the key blocks up to its own last position and no further**:
  nothing past the chunk is fetched, nothing above a query block's
  diagonal is computed, and only the blocks the diagonal crosses are
  masked. A page's keys lie a position a lane, so the scores are the
  plain product ``[G bq, D] x [D, page]`` and the values' product
  contracts the lanes of both operands.
- **the running max, sum and accumulator are VMEM scratch** from a
  query block's first key block to its last, divided once at the end:
  no array of ``T x S`` and no carry crosses HBM.
- **the dense arm's grouped arithmetic**: operands to the MXU as stored
  (bfloat16 in the serving cells), scores float32, scaled and masked by
  position in float32, max, exponent and sum float32, probabilities cast
  to the values' dtype for their product, accumulator float32. A padded
  tail's queries see their own (padded) keys, so every row's sum is
  positive: finite, and never read.

Block shapes follow from ``T``, ``G``, ``D`` and the page's size
(:func:`chunk_blocks`); a geometry the kernel cannot take raises
`KernelGeometryError` at trace time. The call is jitted, so a model's
attention layers share one trace and one lowering (`PERF.md`, PR 30).
Off-TPU it runs in Pallas interpret mode; the `tests/unit/
test_tpu_compile*.py` files compile it for a described v5e at the four
serving cells' geometries.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import (LANES,
                                                      VMEM_LIMIT_BYTES)
from deepspeed_tpu.ops.pallas.flash_decode import KernelGeometryError

# the kernel's name in the HLO and in a device trace
PREFILL_PAGED_NAME = "ds_flash_prefill_paged"
# queries of one head to a grid step at least, where the chunk has them:
# the kernel's smallest query block (a shorter chunk goes whole)
QUERY_BLOCK = 128
# rows of scores to a grid step (a key head's G query heads x bq), and
# keys to a block of the walk: the running max and sum (a lane tile a
# row) and the wait for a block's pages are paid once a tile. On a v5e
# (my chip runs, PR 58: a chunk of 1,024 at a prefix of 4,096, ms a
# call) 4 query heads of 64 a key head: 512 keys 1.17, 1,024 keys 0.71,
# 2,048 keys 0.88 (the diagonal's waste and the tile's 8 MB); 16 of
# 128: 0.97, 0.70, 0.85; 8 of 256, where the MXU and not the vector
# unit bounds: 0.59, 0.60. Rows: 2,048 for 1,024 gain 2 %. (Read by
# setting these two before a call: they are looked up at trace time.)
ROWS = 1024
KEY_BLOCK = 1024
_SUBLANES = 8


def chunk_blocks(T, G, page_size):
    """``(bq, pages)``: queries of a head to a grid step and pages to a
    key block of the walk. ``bq`` is the largest of 128, 256, 512, 1024
    that divides the chunk and keeps ``G x bq`` at `ROWS` or under (128
    at least), or the whole chunk where 128 does not divide it; a key
    block is `KEY_BLOCK` positions or one page, whichever is more."""
    if T % _SUBLANES:
        raise KernelGeometryError(
            f"the chunk's kernel lays a head's queries on whole sublane "
            f"tiles: a chunk of {T} is no multiple of {_SUBLANES}")
    bq = T
    if T % QUERY_BLOCK == 0:
        bq = QUERY_BLOCK
        while T % (2 * bq) == 0 and 2 * bq * G <= ROWS:
            bq *= 2
    return bq, max(1, KEY_BLOCK // int(page_size))


def _chunk_kernel(G, D, Dv, bq, pages, page_size, n_entries, scale):
    mask_value = float(jnp.finfo(jnp.float32).min)
    bk = pages * page_size
    contract_lanes = (((1,), (1,)), ((), ()))

    def kernel(table_ref, c0_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
               sem, qs, m_ref, l_ref, acc_ref):
        h = pl.program_id(0)
        # the block's first and last query, as positions of the row
        q_first = c0_ref[0] + pl.program_id(1) * bq
        n_blocks = (q_first + bq - 1) // bk + 1
        # key blocks every query of the block sees whole
        n_clear = jnp.minimum((q_first + 1) // bk, n_blocks)

        def copies(j, slot):
            """Key block ``j``'s pages of this head into ``slot``. An
            entry past the table's end (a last block the table does not
            fill) reads the last entry again: no query sees that far."""
            out = []
            for r in range(pages):
                page = table_ref[jnp.minimum(j * pages + r, n_entries - 1)]
                out.append(pltpu.make_async_copy(
                    k_hbm.at[page, h], kbuf.at[slot, r], sem.at[0, slot, r]))
                out.append(pltpu.make_async_copy(
                    v_hbm.at[page, h], vbuf.at[slot, r], sem.at[1, slot, r]))
            return out

        def take(masked, j, carry):
            """Key block ``j`` into the running max, sum and
            accumulator; under ``masked`` row ``r`` (position ``q_first
            + r``) sees the block's column ``c`` iff ``j bk + c`` is at
            or below it."""
            slot = j % 2

            @pl.when(j + 1 < n_blocks)
            def _prefetch():
                for c in copies(j + 1, 1 - slot):
                    c.start()
            for c in copies(j, slot):
                c.wait()
            q = qs[...]
            s = jnp.concatenate(
                [jnp.dot(q, kbuf[slot, r],
                         preferred_element_type=jnp.float32)
                 for r in range(pages)], axis=1) * scale     # [G bq, bk]
            if masked:
                r = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                c = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                seen = c - r <= q_first - j * bk
                s = jnp.where(seen[None], s.reshape(G, bq, bk),
                              mask_value).reshape(G * bq, bk)
            m_prev = m_ref[...]                              # [G bq, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
            m_ref[...] = m_new
            p = p.astype(vbuf.dtype)
            pv = sum(jax.lax.dot_general(
                p[:, r * page_size:(r + 1) * page_size], vbuf[slot, r],
                contract_lanes, preferred_element_type=jnp.float32)
                for r in range(pages))                       # [G bq, Dv]
            acc_ref[...] = acc_ref[...] * corr + pv
            return carry

        for c in copies(0, 0):
            c.start()
        for g in range(G):
            qs[g * bq:(g + 1) * bq, :] = q_ref[:, g * D:(g + 1) * D]
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        jax.lax.fori_loop(0, n_clear, functools.partial(take, False), 0)
        jax.lax.fori_loop(n_clear, n_blocks, functools.partial(take, True),
                          0)
        y = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        for g in range(G):
            o_ref[:, g * Dv:(g + 1) * Dv] = \
                y[g * bq:(g + 1) * bq].astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("scale", "bq", "pages",
                                             "interpret"))
def _chunk_call(table, c0, q, k_pool, v_pool, *, scale, bq, pages,
                interpret):
    T, Hq, D = q.shape
    H, Dv, page_size = v_pool.shape[1:]
    G = Hq // H

    def block(h, i, *_):
        return (i, h)

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    call = pl.pallas_call(
        _chunk_kernel(G, D, Dv, bq, pages, page_size, table.shape[0],
                      scale),
        name=PREFILL_PAGED_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H, T // bq),
            in_specs=[pl.BlockSpec((bq, G * D), block), anywhere, anywhere],
            out_specs=pl.BlockSpec((bq, G * Dv), block),
            scratch_shapes=[
                pltpu.VMEM((2, pages, D, page_size), k_pool.dtype),
                pltpu.VMEM((2, pages, Dv, page_size), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2, pages)),
                pltpu.VMEM((G * bq, D), q.dtype),
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, Dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, Hq * Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )
    with jax.named_scope(PREFILL_PAGED_NAME):
        y = call(table, c0, q.reshape(T, Hq * D), k_pool, v_pool)
    return y.reshape(T, Hq, Dv)


def flash_prefill_paged(q, k_pool, v_pool, page_table, c0, *, scale,
                        interpret=None):
    """One prompt's chunk over the row's live prefix in a page pool, the
    chunk's own keys and values already in their pages.

    ``q`` ``[T, Hq, D]`` (the chunk's queries, the model's layout, at
    the contiguous positions ``c0 .. c0 + T - 1``), ``k_pool`` ``[n_pages,
    H, D, page]`` / ``v_pool`` ``[n_pages, H, Dv, page]`` (the pool's
    leaves of ``q.dtype``, a position a lane), ``page_table``
    ``[pages_per_row]`` int32 (the row's physical pages; an entry past
    the chunk's last position is never read for what it holds), ``c0``
    an int32 scalar, traced. ``Hq`` is a multiple ``G`` of ``H`` (query
    head ``h`` over key head ``h // G``). The query at position ``p``
    sees cache index ``s`` iff ``s <= p``. Returns ``[T, Hq, Dv]`` in
    ``q.dtype``. ``interpret=None`` auto-selects: the compiled kernel on
    TPU, interpret mode elsewhere."""
    T, Hq, D = q.shape
    H, Dv, page_size = v_pool.shape[1:]
    if Hq % H or k_pool.shape[1:] != (H, D, page_size) or \
            k_pool.shape[0] != v_pool.shape[0] or page_table.ndim != 1 or \
            not q.dtype == k_pool.dtype == v_pool.dtype:
        raise ValueError(
            f"flash_prefill_paged takes a chunk's queries [T, G x H, D] "
            f"over a pool's keys [n_pages, H, D, page] and values "
            f"[n_pages, H, Dv, page] of the queries' dtype through one "
            f"row's page table: q {q.shape} {q.dtype}, k {k_pool.shape} "
            f"{k_pool.dtype}, v {v_pool.shape} {v_pool.dtype}, table "
            f"{page_table.shape}")
    bq, pages = chunk_blocks(T, Hq // H, page_size)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if not interpret and H > 1 and ((Hq // H) * D % LANES or
                                    (Hq // H) * Dv % LANES):
        raise KernelGeometryError(
            f"a key head's {Hq // H} query heads of {D} (values of {Dv}) "
            f"are no whole lane tiles of the model's [T, Hq x D]: the "
            f"chunk's kernel cuts its query and output blocks there")
    return _chunk_call(jnp.asarray(page_table, jnp.int32),
                       jnp.reshape(jnp.asarray(c0, jnp.int32), (1,)),
                       q, k_pool, v_pool, scale=float(scale), bq=bq,
                       pages=pages, interpret=bool(interpret))

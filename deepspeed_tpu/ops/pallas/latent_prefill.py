"""Prefill attention over a latent pool: one block of a chunk's walk.

A prompt's chunk of queries attends over the row's live prefix in a
latent pool block by block (`inference/cache.py:
latent_prefill_attention`): a block's latents are gathered through the
page table and expanded to per-head keys and values, and the chunk's
running max, sum and output take the block in. In plain XLA the float32
scores of one block, ``[heads, chunk, block]`` (268 MB at 64 x 1024 x
1024), cross HBM three times: written, read for the max, read for the
exponent (`PERF.md` section 6, PR 34: 1.3 ms a block a layer against
0.3 ms of operations). :func:`flash_prefill_latent_block` is that one
step of the walk as a kernel: a head's scores live in VMEM and nothing
``[heads, chunk, block]`` exists outside it.

- **the grid is ``(heads,)``**: a grid step takes one head's queries
  ``[dn + dr, chunk]``, its block of keys ``[block, dn]`` (a lane block
  of the expansion's own ``[block, heads * dn]`` output) beside the
  rotary key all heads share ``[block, dr]`` (one vector a position,
  never copied 64 times), its values ``[dv, block]`` and its rows of
  the carry, and hands the carry back. The score tile is the whole
  ``[block, chunk]``: the training kernels found bigger tiles to win up
  to 1024 x 1024 (`PERF.md` section 6, PR 30).
- **the tile is keys first**, ``[block, chunk]``, a query to a lane: a
  query's max and sum are then rows ``[1, chunk]`` that broadcast over
  the tile's sublanes, and the carry's ``m`` and ``l`` are 4 KB a head
  in HBM. Queries first they are columns ``[chunk, 1]``, which the
  (8, 128) tiling pads to 128 lanes: 134 MB a block through HBM for
  0.5 MB of numbers, and every operation on them touches 128 registers
  for 8 (0.84 ms a block against 0.59 on the chip, `PERF.md` section 6,
  PR 35). Every product is a plain ``[M, K] x [K, N]``.
- **the walk's arithmetic**: the operands go to the MXU as they are
  stored (bfloat16 in the serving cell), the scores are float32, scaled
  in float32, masked by the pool's rule (cache index ``s`` for the
  query at ``p`` iff ``s <= p``), the max, exponent and sum float32,
  the probabilities cast to the values' dtype for their product, the
  accumulator float32. Nothing is divided here: the walk divides once,
  after its last block.
- **what a block is to the mask** is decided on the chip from the two
  positions the call is handed (the chunk's first query, the block's
  first key): a block wholly before the chunk is not masked; a square
  block that starts where the chunk does (the diagonal, the last block
  of every chunk the engine runs) is four strips of queries, each over
  the keys up to the diagonal's end in it, so three eighths of it are
  never computed (eight strips read slower); any other block is
  computed whole and masked.
- **the carry** ``(m [H, 1, T], l [H, 1, T], acc [H, dv, T])`` float32
  goes through HBM between blocks, aliased in to out.

The call is jitted, so a model's layers share one trace and one
lowering of the kernel (`PERF.md`, PR 30). Off-TPU it runs in Pallas
interpret mode; `tests/unit/test_tpu_compile.py` compiles it for a
described v5e at the serving cell's shape.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import (LANES,
                                                      VMEM_LIMIT_BYTES)

# the kernel's name in the HLO and in a device trace
PREFILL_LATENT_NAME = "ds_flash_prefill_latent"


def _block_kernel(T, S, dn, scale):
    mask_value = float(jnp.finfo(jnp.float32).min)
    # a diagonal block in strips of queries, where a strip's queries and
    # keys end on a lane tile
    strips = 4 if T == S and T % (4 * LANES) == 0 else 1
    whole = slice(0, S), slice(0, T)

    def kernel(bounds_ref, q_ref, kn_ref, kr_ref, v_ref, m_in, l_in, acc_in,
               m_out, l_out, acc_out):
        q0, k0 = bounds_ref[0], bounds_ref[1]

        def tile(keys, queries, masked):
            qb = q_ref[0, :, queries]                    # [dn + dr, nq]
            s = jnp.dot(kn_ref[keys, :], qb[:dn],
                        preferred_element_type=jnp.float32) + \
                jnp.dot(kr_ref[keys, :], qb[dn:],
                        preferred_element_type=jnp.float32)
            s = s * scale                                # [nk, nq]
            if masked:
                k_pos = k0 + keys.start + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0)
                q_pos = q0 + queries.start + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(k_pos <= q_pos, s, mask_value)
            m_prev = m_in[0, :, queries]                 # [1, nq]
            m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_out[0, :, queries] = l_in[0, :, queries] * corr + \
                p.sum(axis=0, keepdims=True)
            m_out[0, :, queries] = m_new
            acc_out[0, :, queries] = acc_in[0, :, queries] * corr + \
                jnp.dot(v_ref[0, :, keys], p.astype(v_ref.dtype),
                        preferred_element_type=jnp.float32)

        under = k0 + S - 1 <= q0
        crossing = jnp.logical_not(under)
        pl.when(under)(lambda: tile(*whole, False))
        if strips > 1:
            diagonal = k0 == q0
            crossing = jnp.logical_and(crossing, jnp.logical_not(diagonal))
            step = T // strips

            @pl.when(diagonal)
            def _():
                for i in range(strips):
                    tile(slice(0, (i + 1) * step),
                         slice(i * step, (i + 1) * step), True)

        pl.when(crossing)(lambda: tile(*whole, True))

    return kernel


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _block_call(bounds, q, k_nope, k_rope, v, m, l, acc, *, scale,
                interpret):
    H, dq, T = q.shape
    S, _, dn = k_nope.shape
    dv = v.shape[1]

    def head(h, bounds_ref):
        return (h, 0, 0)

    carry = [pl.BlockSpec((1, 1, T), head), pl.BlockSpec((1, 1, T), head),
             pl.BlockSpec((1, dv, T), head)]
    call = pl.pallas_call(
        _block_kernel(T, S, dn, scale),
        name=PREFILL_LATENT_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H,),
            in_specs=[pl.BlockSpec((1, dq, T), head),
                      # head h's lanes of the expansion's [S, H * dn]
                      pl.BlockSpec((S, dn), lambda h, bounds_ref: (0, h)),
                      pl.BlockSpec((S, dq - dn),
                                   lambda h, bounds_ref: (0, 0)),
                      pl.BlockSpec((1, dv, S), head)] + carry,
            out_specs=carry),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in (m, l, acc)],
        # the carry in place: operands 5, 6, 7 (the scalar operand
        # counts) are outputs 0, 1, 2
        input_output_aliases={5: 0, 6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )
    with jax.named_scope(PREFILL_LATENT_NAME):
        return tuple(call(bounds, q, k_nope.reshape(S, H * dn), k_rope, v,
                          m, l, acc))


def flash_prefill_latent_block(q, k_nope, k_rope, v, carry, q_start,
                               k_start, *, scale, interpret=None):
    """Take one block of keys and values into a chunk's running softmax.

    ``q`` ``[H, dn + dr, T]``: the chunk's queries, heads first, a query
    to a lane, a head's rotary entries behind its ``dn`` others;
    ``k_nope`` ``[S, H, dn]``, ``k_rope`` ``[S, dr]`` (shared by the
    heads), ``v`` ``[H, dv, S]``: the block, expanded, each as the
    product that makes it lies; ``carry``: ``(m [H, 1, T], l [H, 1, T],
    acc [H, dv, T])`` float32, as the walk starts it (``-inf``, 0, 0).
    ``q_start`` / ``k_start`` (int32 scalars, traced): the absolute
    position of the chunk's first query and of the block's first key;
    both run on contiguously. Returns the new carry; the attention's
    output is ``acc / l`` after the last block. ``interpret=None``
    auto-selects: the compiled kernel on TPU, Pallas interpret mode
    elsewhere."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    bounds = jnp.stack([jnp.asarray(q_start, jnp.int32),
                        jnp.asarray(k_start, jnp.int32)])
    return _block_call(bounds, q, k_nope, k_rope, v, *carry,
                       scale=float(scale), interpret=bool(interpret))

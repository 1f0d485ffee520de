"""Prefill attention of a window layer: a chunk's band in one call.

A prompt's chunk of queries attends over its own keys and the
``window`` positions before it, which the row's ring still holds
(`inference/cache.py:window_prefill_attention`): query ``t`` sees key
``j`` iff ``0 <= t - j < window``. In plain XLA the chunk goes in blocks
of ``window`` queries over ``2 x window`` keys: the float32 scores of a
layer, ``[blocks, Hq, window, 2 x window]`` (302 MB at 2 x 72 x 512 x
1024), are written to HBM and read back four or five times, beside a
stack of overlapping spans that copies every key twice (`PERF.md`
section 6, PR 52: 2.4 ms a layer a call against 0.2 ms of operations).
:func:`window_prefill_band` is that band as one kernel:

- **the grid is ``(key heads, query blocks)``**: a grid step takes one
  key head's ``G`` query heads together, ``bq`` queries of each, as the
  ``[bq, G x D]`` lane block of the model's own ``[T, Hq x D]``: no
  transpose of the queries or of the output crosses HBM. The ``G``
  heads' rows are laid under one another in VMEM (``[G x bq, D]``), so
  that one product a key block feeds ``G x bq`` rows to the MXU over
  keys fetched once.
- **the keys of a key head stay in VMEM** for all its query blocks:
  ``[front + T, D]``, the ``window`` positions before the chunk behind
  a pad that brings the chunk's first key to a tile boundary, then the
  chunk's own. A query block walks **only the key blocks its band
  admits**: the ``bq + front`` entries from its own first row's on, in
  blocks of ``bk`` (all of them at once where the scores fit the VMEM
  budget). A block wholly before the prompt's start (a first chunk's
  ring holds nothing of this prompt) is skipped, and so is a query
  block wholly behind the chunk's real tokens.
- **the running max, sum and accumulator are VMEM scratch** from a
  query block's first key block to its last, divided once at the end:
  no ``[.., window, 2 x window]`` array, no stack of spans and no carry
  crosses HBM.
- **the band's arithmetic**: operands to the MXU as stored (bfloat16 in
  both serving cells), scores float32, scaled and masked in float32
  (by the band, and by ``position >= 0``), max, exponent and sum
  float32, probabilities cast to the values' dtype for their product,
  accumulator float32. ``sink``: a logit a query head where the running
  max starts, and 1 in the sum. Keys of ``D`` over values of ``Dv``.

Block shapes follow from ``T``, ``window``, ``G``, ``D`` and the VMEM
limit (:func:`band_blocks`); a geometry the kernel cannot take raises
`KernelGeometryError` at trace time. The call is jitted, so a model's
window layers share one trace and one lowering (`PERF.md`, PR 30).
Off-TPU it runs in Pallas interpret mode; `tests/unit/
test_tpu_compile_laguna.py` and `test_tpu_compile_mimo_v2.py` compile it
for a described v5e at the serving cells' geometries.
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
WINDOW_BAND_NAME = "ds_window_prefill_band"
# queries of one head to a grid step: the kernel's smallest query block.
# A block of bq queries computes bq + window keys a query for the window
# it needs, so a small block wastes least
QUERY_BLOCK = 128
_SUBLANES = 8


def _tile(bq):
    """What a key block starts on: a lane tile where the query block is
    whole lane tiles (the chip's shapes), else a sublane tile."""
    return LANES if bq % LANES == 0 else _SUBLANES


def band_blocks(T, window, G, D, Dv, dtype):
    """``(bq, front, bk)``: queries of a head to a grid step, the padded
    length of what lies before the chunk in the keys' array, and keys to
    a block of the walk. ``bq`` is `QUERY_BLOCK` where that divides the
    chunk, else the whole chunk; ``front`` is ``window`` rounded up to
    the tile a key block starts on; a query block's ``bq + front`` keys
    go as one block where a grid step's VMEM allows, else in the largest
    blocks that divide them and fit."""
    bq = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    if bq % _SUBLANES:
        raise KernelGeometryError(
            f"the window band's kernel lays a head's queries on whole "
            f"sublane tiles: a chunk of {T} is no multiple of {_SUBLANES}")
    align = _tile(bq)
    front = -(-int(window) // align) * align
    span = bq + front
    item = jnp.dtype(dtype).itemsize
    fixed = 2 * (front + T) * (D + Dv) * item       # a head's keys, values
    fixed += 2 * bq * G * (D + Dv) * item           # query and output blocks
    fixed += G * bq * (D * item + (Dv + 2 * LANES) * 4)     # the scratch
    for n in range(1, span // align + 1):
        bk = span // n
        if span % n or bk % align:
            continue
        # a block's float32 scores, their exponents and the cast of those
        if fixed + G * bq * bk * (4 + 4 + item) <= VMEM_LIMIT_BYTES // 2:
            return bq, front, bk
    raise KernelGeometryError(
        f"the window band's kernel keeps {G} heads x {bq} queries and a "
        f"key head's {front + T} keys and values of {D} and {Dv} in VMEM: "
        f"{fixed} bytes leave no room for a block of scores under half "
        f"the {VMEM_LIMIT_BYTES} it may use: lower prefill_chunk")


def _band_kernel(G, D, Dv, bq, bk, front, window, scale, sink):
    mask_value = float(jnp.finfo(jnp.float32).min)
    n_kb = (bq + front) // bk
    align = _tile(bq)
    pad = front - window

    def kernel(bounds_ref, *refs):
        refs = list(refs)
        sink_ref = refs.pop(0) if sink else None
        q_ref, k_ref, v_ref, o_ref, qs, m_ref, l_ref, acc_ref = refs
        h, q0 = pl.program_id(0), pl.program_id(1) * bq
        c0, n_valid = bounds_ref[0], bounds_ref[1]
        # entries before this one hold nothing of the prompt
        first = front - c0

        def take(jb):
            """Key block ``jb`` of this query block's walk into the
            running max, sum and accumulator. Entry ``e`` of the keys'
            array is chunk position ``e - front``; local column ``c`` of
            the walk is entry ``q0 + c``, and row ``r`` is chunk position
            ``q0 + r``: it sees ``r + pad < c <= r + front``."""
            e0 = pl.multiple_of(q0 + jb * bk, align)
            kb = k_ref[0, pl.ds(e0, bk), :]                  # [bk, D]
            vb = v_ref[0, pl.ds(e0, bk), :]                  # [bk, Dv]
            s = jax.lax.dot_general(
                qs[...], kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [G bq, bk]
            r = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            c = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + jb * bk
            seen = (c > r + pad) & (c <= r + front) & (c >= first - q0)
            s = jnp.where(seen[None], s.reshape(G, bq, bk),
                          mask_value).reshape(G * bq, bk)
            m_prev = m_ref[...]                              # [G bq, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
            m_ref[...] = m_new
            acc_ref[...] = acc_ref[...] * corr + jnp.dot(
                p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)

        @pl.when(q0 < n_valid)
        def _attend():
            for g in range(G):
                rows = slice(g * bq, (g + 1) * bq)
                qs[rows, :] = q_ref[:, g * D:(g + 1) * D]
                if sink:
                    m_ref[rows, :] = jnp.full((bq, 1), sink_ref[h * G + g],
                                              jnp.float32)
            if sink:
                l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
            else:
                m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
                l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
            for jb in range(n_kb):
                pl.when(q0 + (jb + 1) * bk > first)(
                    functools.partial(take, jb))
            y = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
            for g in range(G):
                o_ref[:, g * Dv:(g + 1) * Dv] = \
                    y[g * bq:(g + 1) * bq].astype(o_ref.dtype)

        @pl.when(q0 >= n_valid)
        def _padding():
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def _band_call(bounds, q, k_before, v_before, k_new, v_new, sink, *, window,
               scale, interpret):
    T, Hq, D = q.shape
    H, Dv = v_new.shape[1:]
    G = Hq // H
    bq, front, bk = band_blocks(T, window, G, D, Dv, q.dtype)

    def keys(before, new):          # [H, front + T, d], the pad in front
        ext = jnp.concatenate([before, new])
        ext = jnp.pad(ext, ((front - window, 0), (0, 0), (0, 0)))
        return jnp.transpose(ext, (1, 0, 2))

    def head(h, i, *_):
        return (h, 0, 0)

    def block(h, i, *_):
        return (i, h)

    prefetch = [bounds] if sink is None else \
        [bounds, sink.astype(jnp.float32)]
    call = pl.pallas_call(
        _band_kernel(G, D, Dv, bq, bk, front, window, scale,
                     sink is not None),
        name=WINDOW_BAND_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(H, T // bq),
            in_specs=[pl.BlockSpec((bq, G * D), block),
                      pl.BlockSpec((1, front + T, D), head),
                      pl.BlockSpec((1, front + T, Dv), head)],
            out_specs=pl.BlockSpec((bq, G * Dv), block),
            scratch_shapes=[
                pltpu.VMEM((G * bq, D), q.dtype),
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, 1), jnp.float32),
                pltpu.VMEM((G * bq, Dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, Hq * Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )
    with jax.named_scope(WINDOW_BAND_NAME):
        y = call(*prefetch, q.reshape(T, Hq * D), keys(k_before, k_new),
                 keys(v_before, v_new))
    return y.reshape(T, Hq, Dv)


def window_prefill_band(q, k_before, v_before, k_new, v_new, c0, n_valid, *,
                        window, scale, sink=None, interpret=None):
    """One prompt's chunk over its own keys and the ``window`` positions
    before it.

    ``q`` ``[T, Hq, D]`` (the chunk's queries, the model's layout),
    ``k_before`` ``[window, H, D]`` / ``v_before`` ``[window, H, Dv]``:
    what the row's ring holds of the positions ``c0 - window .. c0 -
    1``, in that order (an entry before the prompt's start is never
    seen, whatever it holds); ``k_new`` ``[T, H, D]`` / ``v_new`` ``[T,
    H, Dv]``: the chunk's own; all of one dtype, ``Hq`` a multiple ``G``
    of ``H`` (query head ``h`` over key head ``h // G``). ``c0``: the
    chunk's first position, ``n_valid``: how many of its tokens are real
    (int32 scalars, traced): a block of queries wholly behind them comes
    back zero, a row behind them in a block with real ones meaningless.
    ``sink`` ``[Hq]``: a logit a query head in the denominator. Returns
    ``[T, Hq, Dv]`` in ``q.dtype``. ``interpret=None`` auto-selects: the
    compiled kernel on TPU, interpret mode elsewhere."""
    T, Hq, D = q.shape
    H, Dv = v_new.shape[1:]
    W = int(window)
    if W < 1 or Hq % H or k_new.shape != (T, H, D) or \
            k_before.shape != (W, H, D) or v_before.shape != (W, H, Dv):
        raise ValueError(
            f"window_prefill_band takes a chunk's queries [T, G x H, D], "
            f"its keys [T, H, D] and values [T, H, Dv] and the window's "
            f"[{W}, H, D | Dv] before them: q {q.shape}, k_new "
            f"{k_new.shape}, v_new {v_new.shape}, k_before "
            f"{k_before.shape}, v_before {v_before.shape}")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    bounds = jnp.stack([jnp.asarray(c0, jnp.int32),
                        jnp.asarray(n_valid, jnp.int32)])
    return _band_call(bounds, q, k_before, v_before, k_new, v_new, sink,
                      window=W, scale=float(scale),
                      interpret=bool(interpret))

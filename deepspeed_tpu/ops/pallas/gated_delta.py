"""The gated delta rule's two kernels: the chunked form of one prefill
call, and one decode step over the rows that hold a request.

`ops/gated_delta.py` has the recurrence and the WY form a prefill call
runs; the first kernel is that form with a chunk's matrices in VMEM.
See the module's docstring there for the algebra and the precision,
which this file keeps: float32 ``g``, ``beta``, decays, system, solution
and state; ``k k^T`` and ``q k^T`` from operands in the compute dtype,
accumulated in float32; every product that reads or writes the state,
and every product of the solve, on float32 operands at the highest
precision.

- **the grid is ``(key heads, chunks / 2)``**, the chunk axis last
  and walked in order. A grid step takes two chunks of one key head
  (`_CHUNKS_A_STEP`; one where the call has an odd number): their
  tokens of ``q`` and ``k`` (one lane block of the mixer's own
  ``[T, Hk * K]``, read once and serving the head's ``Hv / Hk`` value
  heads; nothing is repeated through HBM), those heads' ``v`` (one
  ``[., group * V]`` lane block) and their running ``G`` and ``beta``
  as rows (a row to a column is a masked sum over the lanes, in the
  kernel). The heads' state ``[group, K, V]`` float32 is the state
  output's own block, whose index does not move along the chunk axis:
  it is in VMEM from the call's first chunk to its last, filled from
  the state that came in at chunk 0 and written back once.
- **nothing ``[Q, Q]`` leaves VMEM**: ``decay``, ``k k^T``, ``q k^T``
  and the unit lower-triangular system ``I + A`` are built, used and
  dropped in the grid step.
- **the solve is a blocked forward substitution** (exact; no series)
  of the system against both right-hand sides at once, ``[beta v |
  beta k e^G]``, ``V + K`` columns on the lanes. The chunk is cut into
  blocks of ``_BLOCK`` = 16 rows. A diagonal block is solved by column
  sweeps on the vector units: step ``j`` takes row ``j`` of the
  block's right-hand sides (a sublane broadcast), the block's column
  ``j`` (a lane broadcast) and subtracts their outer product, 15 steps
  of a ``[16, V + K]`` tile a block, every lane busy. The blocks below
  it take the solved rows in by one MXU product a block, ``[rows
  below, 16] x [16, V + K]``. Sixteen is where the two meet: a sweep's
  cost grows with the square of the block and its chain with the block
  (a chunk solved by sweeps alone is a chain of 63 dependent steps),
  while a product with fewer than 16 rows of weights leaves the MXU
  waiting for its own latency (on the chip, ms a layer a call at
  blocks of 8 / 16 / 32 / 64: 0.85 / 0.73 / 0.76 / 0.99; `PERF.md`
  section 6, PR 44). The grid step's chunks and their
  value heads are one leading axis of every array of the solve, so
  four chains interleave.
- the chunk's deltas ``U - W S``, its output ``(q e^G) S + (q k^T .
  decay) D`` and the next state ``e^(G_C) S + (k e^(G_C - G))^T D``
  follow in the same grid step, chunk after chunk; ``W`` and ``q
  e^G`` meet the state in one product.

**The decode step** (`gated_delta_step`, HLO name ``ds_gdn_step_rows``;
PR 50) is the recurrence itself, and what it costs is the state it
moves: 2 MB a row a layer in and 2 MB out at the cell's 32 heads of 128
x 128 float32, against seven operations an element. So the kernel
visits the rows that hold a request and no other:

- **a list of the live rows**, made in the program from the mixer's
  ``live`` (a live row's place is the running count of the flags before
  it; 128 flags, one compare and sum), goes in as scalar-prefetched
  ``(rows [R], n)`` beside the rows' decays ``e^g`` and ``beta``, one
  float32 scalar a row a head in SMEM. The grid is ``(R,)``: step ``i <
  n`` takes row ``rows[i]``'s whole state ``[Hv, K, V]`` as one block,
  in and out; a step behind the list maps to the block the last live
  step had, so nothing is fetched, computed or written for it (~0.1 us
  a step on the chip: 92 dead steps of 128 are 1.5 us a layer; a
  grid bound that is the count itself compiles too and saves no more
  than that; `PERF.md` section 6, PR 50).
- **the state is the call's own output** (``input_output_aliases``):
  a row off the list is not read, not written and not copied. With no
  row live the pipeline still writes back the one block the grid ends
  on, so the first step hands row 0's state through as it came.
- **in the grid step**, ``q`` and ``k`` come as columns (``[K, 2 Hk]``,
  float32, transposed outside: a key head's column across the lanes is
  one lane broadcast, shared by its ``Hv / Hk`` value heads); a head's
  ``[K, V]`` state meets its scalar decay, the read ``S^T k`` and the
  output ``S^T q`` are sums over the sublanes, the outer product a
  sublane broadcast of the delta: float32 products and sums on the
  vector units, as the plain pass has them, no MXU pass. The heads are
  unrolled.
- a dead row's block of ``o`` is never written: the caller's ``where``
  makes it zero.

On the chip the step runs at the speed of its DMA: a kernel that only
copies a live row's state through takes the same time (`PERF.md`
section 6, PR 50).

Both calls are jitted, so a model's layers share one trace and one
lowering (`PERF.md`, PR 30). Off-TPU they run in Pallas interpret mode;
`tests/unit/test_tpu_compile_qwen3_next.py` compiles them for a
described v5e at the serving cell's shape.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the kernel's name in the HLO and in a device trace
GATED_DELTA_NAME = "ds_gated_delta_chunked"

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# rows of a diagonal block of the substitution
_BLOCK = 16
# chunks a grid step: their solves share no data, so their chains of
# sweeps interleave (0.73 -> 0.67 ms a layer a call at the cell's shape;
# 4 and 8 give 0.64 and 0.61 for 1.6 and 3.1 s of compile)
_CHUNKS_A_STEP = 2


def _bmm(a, b):
    """``[g, m, k] x [g, k, n]`` on float32 operands, highest precision."""
    return jnp.einsum("gmk,gkn->gmn", a, b, precision=_HIGHEST,
                      preferred_element_type=_F32)


def _chunk_kernel(Q, K, V, group, cps):
    B = min(_BLOCK, Q)
    blocks = Q // B
    n = cps * group

    def kernel(q_ref, k_ref, v_ref, rows_ref, s_in, o_ref, s_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            s_ref[...] = s_in[...]

        nt = (((1,), (1,)), ((), ()))
        row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)

        def per_head(a):
            # a chunk's [Q, .] once a value head: [n, Q, .]
            return jnp.concatenate(
                [jnp.broadcast_to(x[None], (group,) + x.shape) for x in a])
        qs = [q_ref[i * Q:(i + 1) * Q] for i in range(cps)]
        ks = [k_ref[i * Q:(i + 1) * Q] for i in range(cps)]
        kk = per_head([jax.lax.dot_general(
            k, k, nt, preferred_element_type=_F32) for k in ks])
        qk = per_head([jax.lax.dot_general(
            q, k, nt, preferred_element_type=_F32) for q, k in zip(qs, ks)])
        k32 = per_head([k.astype(_F32) for k in ks])    # [n, Q, K]
        q32 = per_head([q.astype(_F32) for q in qs])
        v32 = jnp.stack([v_ref[i * Q:(i + 1) * Q, j * V:(j + 1) * V]
                         .astype(_F32)
                         for i in range(cps) for j in range(group)])

        rows = rows_ref[0, 0]                           # [2 n, Q]
        G_row = jnp.expand_dims(rows[:n], 1)            # [n, 1, Q]
        beta_row = jnp.expand_dims(rows[n:], 1)

        def as_column(r):
            # a head's row [1, Q] as a column [Q, 1]
            return jnp.sum(jnp.where(row == col, r, 0.0), axis=-1,
                           keepdims=True)
        G = as_column(G_row)                            # [n, Q, 1]
        beta = as_column(beta_row)
        decay = jnp.exp(jnp.where(row >= col, G - G_row, -jnp.inf))
        system = jnp.where(row > col, beta * kk * decay, 0.0)   # A
        e_G = jnp.exp(G)
        rhs = beta * jnp.concatenate([v32, k32 * e_G], axis=-1)

        # (I + A) X = rhs, block by block; rhs: the rows not yet solved
        solved = []
        for b in range(blocks):
            lo, hi = b * B, (b + 1) * B
            x = rhs[:, :B]                              # [n, B, V + K]
            a = system[:, lo:hi, lo:hi]
            for j in range(B - 1):
                # a's column j is 0 down to row j: rows <= j stay
                x = x - a[:, :, j:j + 1] * x[:, j:j + 1]
            solved.append(x)
            if hi < Q:
                rhs = rhs[:, B:] - _bmm(system[:, hi:, lo:hi], x)
        X = jnp.concatenate(solved, axis=1)
        U, W = X[..., :V], X[..., V:]
        q_in = q32 * e_G
        qkd = qk * decay
        G_end = G[:, Q - 1:Q]                           # [n, 1, 1]
        k_out = k32 * jnp.exp(G_end - G)                # [n, Q, K]
        whole = jnp.exp(G_end)

        S = s_ref[...]                                  # [group, K, V]
        for i in range(cps):
            at = slice(i * group, (i + 1) * group)
            read = _bmm(jnp.concatenate([W[at], q_in[at]], axis=1), S)
            delta = U[at] - read[:, :Q]
            o = read[:, Q:] + _bmm(qkd[at], delta)
            S = whole[at] * S + jnp.einsum(
                "gqk,gqv->gkv", k_out[at], delta, precision=_HIGHEST,
                preferred_element_type=_F32)
            for j in range(group):
                o_ref[i * Q:(i + 1) * Q, j * V:(j + 1) * V] = o[j]
        s_ref[...] = S

    return kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _chunked_call(q, k, v, g, beta, state, *, chunk, interpret):
    T, Hk, K = q.shape
    Hv, V = v.shape[1:]
    group, Q = Hv // Hk, chunk
    c = T // Q
    cps = _CHUNKS_A_STEP if c % _CHUNKS_A_STEP == 0 else 1
    # a key head's value heads' running G and beta, a chunk's tokens on
    # the lanes: [Hk, c / cps, 2 cps group, Q]
    def rows(a):
        return a.reshape(c // cps, cps, Q, Hk, group).transpose(
            3, 0, 1, 4, 2).reshape(Hk, c // cps, cps * group, Q)
    gb = jnp.concatenate(
        [rows(jnp.cumsum(g.reshape(c, Q, Hv), axis=1)), rows(beta)], axis=2)

    tokens = lambda h, i: (i, h)                        # noqa: E731
    heads = lambda h, i: (h, 0, 0)                      # noqa: E731
    call = pl.pallas_call(
        _chunk_kernel(Q, K, V, group, cps),
        name=GATED_DELTA_NAME,
        grid=(Hk, c // cps),
        in_specs=[pl.BlockSpec((cps * Q, K), tokens),
                  pl.BlockSpec((cps * Q, K), tokens),
                  pl.BlockSpec((cps * Q, group * V), tokens),
                  pl.BlockSpec((1, 1, 2 * cps * group, Q),
                               lambda h, i: (h, i, 0, 0)),
                  pl.BlockSpec((group, K, V), heads)],
        out_specs=[pl.BlockSpec((cps * Q, group * V), tokens),
                   pl.BlockSpec((group, K, V), heads)],
        out_shape=[jax.ShapeDtypeStruct((T, Hv * V), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope(GATED_DELTA_NAME):
        o, state = call(q.reshape(T, Hk * K), k.reshape(T, Hk * K),
                        v.reshape(T, Hv * V), gb, state)
    return o.reshape(T, Hv, V), state


def gated_delta_chunked(q, k, v, g, beta, state, chunk):
    """`ops.gated_delta.gated_delta_chunked` as one kernel call: ``q``,
    ``k`` ``[T, Hk, K]`` with ``Hk`` dividing ``v``'s ``Hv`` heads (a
    key head serves ``Hv / Hk`` consecutive value heads), the rest as
    there; ``T`` a multiple of ``chunk``. The compiled kernel on TPU,
    Pallas interpret mode elsewhere."""
    interpret = jax.devices()[0].platform != "tpu"
    return _chunked_call(q, k, v, g.astype(_F32), beta.astype(_F32),
                         state.astype(_F32), chunk=int(chunk),
                         interpret=interpret)


# the step kernel's name in the HLO and in a device trace
GATED_DELTA_STEP_NAME = "ds_gdn_step_rows"


def _step_kernel(Hk, group, K, V):
    Hv = Hk * group

    def kernel(rows_ref, n_ref, decay_ref, beta_ref, qk_ref, v_ref, s_in,
               o_ref, s_ref):
        i, n = pl.program_id(0), n_ref[0]

        @pl.when(i < n)
        def _():
            at = rows_ref[i] * Hv
            qk = qk_ref[0]                              # [K, 2 Hk]
            v32 = v_ref[0].astype(_F32)                 # [Hv, V]
            for h in range(Hk):
                # a key head's q and k, columns, across the lanes
                q = jnp.broadcast_to(qk[:, h:h + 1], (K, V))
                k = jnp.broadcast_to(qk[:, Hk + h:Hk + h + 1], (K, V))
                for j in range(h * group, (h + 1) * group):
                    S = decay_ref[at + j] * s_in[0, j]  # [K, V]
                    read = jnp.sum(S * k, axis=0, keepdims=True)
                    delta = beta_ref[at + j] * (v32[j:j + 1] - read)
                    S = S + k * delta
                    s_ref[0, j] = S
                    o_ref[0, j:j + 1] = jnp.sum(S * q, axis=0, keepdims=True)

        # the pipeline writes the block the grid ends on whatever the
        # steps did: with no row live it is row 0's, handed through
        @pl.when((i == 0) & (n == 0))
        def _():
            s_ref[...] = s_in[...]

    return kernel


def live_row_list(live):
    """``(rows [R], count [R])`` of the flags ``live`` ``[R]``: the live
    rows' numbers in order, then zeros; the running count of live rows
    (its last entry their number). `ops/pallas/kda.py`'s step walks the
    same list."""
    idx = jnp.arange(live.shape[0], dtype=jnp.int32)
    count = jnp.cumsum(live.astype(jnp.int32))
    rows = jnp.sum(jnp.where(live & (count - 1 == idx[:, None]), idx, 0),
                   axis=1)
    return rows, count


def listed_row(i, rows_ref, n_ref, *_):
    """The row of grid step ``i`` of a walk over `live_row_list`: steps
    behind the list stay on its last row, so nothing moves for them."""
    return rows_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(q, k, v, g, beta, state, live, *, interpret):
    R, Hk, K = q.shape
    Hv, V = v.shape[1:]
    rows, count = live_row_list(live)
    # q and k as columns: [R, K, 2 Hk]
    qk = jnp.swapaxes(jnp.concatenate([q, k], axis=1).astype(_F32), 1, 2)

    row3 = lambda *a: (listed_row(*a), 0, 0)            # noqa: E731
    row4 = lambda *a: (listed_row(*a), 0, 0, 0)         # noqa: E731
    call = pl.pallas_call(
        _step_kernel(Hk, Hv // Hk, K, V),
        name=GATED_DELTA_STEP_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(R,),
            in_specs=[pl.BlockSpec((1, K, 2 * Hk), row3),
                      pl.BlockSpec((1, Hv, V), row3),
                      pl.BlockSpec((1, Hv, K, V), row4)],
            out_specs=[pl.BlockSpec((1, Hv, V), row3),
                       pl.BlockSpec((1, Hv, K, V), row4)]),
        out_shape=[jax.ShapeDtypeStruct((R, Hv, V), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # operand 6 (the four scalar operands count) is the state, and
        # so is output 1: a row that is not listed is not touched
        input_output_aliases={6: 1},
        interpret=interpret,
    )
    with jax.named_scope(GATED_DELTA_STEP_NAME):
        o, state = call(rows, count[-1:], jnp.exp(g).reshape(-1),
                        beta.reshape(-1), qk, v, state)
    # a row off the list: a block of ``o`` nobody wrote
    return jnp.where(live[:, None, None], o, 0.0), state


def gated_delta_step(q, k, v, g, beta, state, live):
    """`ops.gated_delta.gated_delta_step` as one kernel call over the
    live rows. The compiled kernel on TPU, Pallas interpret mode
    elsewhere."""
    interpret = jax.devices()[0].platform != "tpu"
    return _step_call(q, k, v, g.astype(_F32), beta.astype(_F32),
                      state.astype(_F32), live, interpret=interpret)

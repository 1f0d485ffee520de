"""Kimi Delta Attention's two kernels: the chunked form of one prefill
call, and one decode step over the rows that hold a request.

`ops/kda.py` has the recurrence and the algebra; `ops/pallas/
gated_delta.py` has the same two kernels for a decay that is one scalar
a head, and this file keeps its structure (the state resident in VMEM
from a call's first chunk to its last, the blocked forward substitution,
the walk over a list of live rows) and its precision (float32 ``g``,
``beta``, decays, system, solution and state; every product that meets a
decay, the system or the state on float32 operands at the highest
precision). What a decay **a channel** changes:

- **the pair terms are no product of ``k k^T`` with a decay matrix.**
  ``sum_c k_ic k_jc e^(G_ic - G_jc)`` is a matrix product only of ``k
  e^G`` with ``k e^-G``, and ``e^-G`` over a chunk of 64 tokens at ``g
  >= -5`` a token reaches ``e^320``. So the chunk is cut again, into the
  substitution's own blocks of ``_BLOCK`` = 16 tokens, and every
  exponent is taken against the running sum at a block's **middle**
  (``M_b``, after its eighth token): the tokens of block ``b`` carry ``k
  e^(G - M_b)`` and ``q e^(G - M_b)`` and meet the chunk's keys as ``k_j
  e^(M_b - G_j)``. By the configuration's bound ``g >= -5``
  (`ops/kda.py`) both exponents lie in [-40, 40] inside the block, so
  neither factor leaves a float32's normal range whatever ``k``'s small
  entries are (taken against the block's start, the factors are
  ``e^-80`` and ``e^80``, and ``e^-80`` times an entry of a unit key
  under 6e-4 is a denormal, which the chip flushes: ``q_i . k_i`` of a
  block's last token then read 0.7 % off; `PERF.md` section 6, PR 55);
  a key before the block carries a factor <= 1 that may underflow,
  as the pair's true decay then does; a key behind the block is masked
  before the exponent is taken. One ``[2 x 16, K] x [K, Q]`` product a
  block gives the block's rows of the system and of ``q k^T`` both.
- **the state's decay is a column**: ``diag(e^(G_C)) S`` scales the
  state's rows, so the chunk's last running sum, a row over the lanes,
  is turned (a masked sum over the lanes) before it meets the state.
- a grid step takes two chunks of two heads (`_CHUNKS_A_STEP`,
  `_HEADS_A_STEP`; one where the call has an odd number), so four
  substitutions' chains interleave, as the scalar kernel's two chunks of
  a key head's two value heads do.

**The decode step** (`kda_step`, HLO name ``ds_kda_step_rows``) is the
scalar kernel's walk over `live_row_list` with one more column a head:
``q``, ``k`` and ``e^g`` come as columns ``[K, 3 H]`` (transposed
outside), a head's ``[K, V]`` state meets its decay column by a lane
broadcast, the rest is the scalar step. The state is the call's own
output: a row off the list is not read, not written and not copied.

Both calls are jitted, so a model's seven layers share one trace and one
lowering. Off-TPU they run in Pallas interpret mode;
`tests/unit/test_tpu_compile_ling.py` compiles them for a described v5e
at the serving cell's shape.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.gated_delta import (_BLOCK, _F32, _HIGHEST,
                                                  _bmm, listed_row,
                                                  live_row_list)

# the kernels' names in the HLO and in a device trace
KDA_SCAN_NAME = "ds_kda_scan_chunks"
KDA_STEP_NAME = "ds_kda_step_rows"

# chunks and heads a grid step: their solves share no data
_CHUNKS_A_STEP = 2
_HEADS_A_STEP = 2


def _chunk_kernel(Q, K, V, hb, cps):
    B = min(_BLOCK, Q)
    blocks = Q // B
    n = cps * hb

    def kernel(q_ref, k_ref, v_ref, G_ref, beta_ref, s_in, o_ref, s_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            s_ref[...] = s_in[...]

        def items(ref, width):
            # a grid step's (chunk, head) pairs, one leading axis
            return jnp.stack(
                [ref[i * Q:(i + 1) * Q, j * width:(j + 1) * width]
                 .astype(_F32) for i in range(cps) for j in range(hb)])
        q32, k32 = items(q_ref, K), items(k_ref, K)     # [n, Q, K]
        v32, G = items(v_ref, V), items(G_ref, K)

        row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        # the pairs' beta, rows [n, Q], as columns [n, Q, 1]
        beta = jnp.sum(jnp.where(row == col,
                                 jnp.expand_dims(beta_ref[0, 0], 1), 0.0),
                       axis=-1, keepdims=True)
        token = jax.lax.broadcasted_iota(jnp.int32, (Q, K), 0)
        # M_b: the running sum at the middle of each block
        half = max(B // 2, 1)
        mids = [G[:, b * B + half - 1:b * B + half] for b in range(blocks)]
        own = jnp.concatenate(
            [jnp.broadcast_to(m, (n, B, K)) for m in mids], axis=1)
        inside = jnp.exp(G - own)                       # e^-40 .. e^35
        kd, qd = k32 * inside, q32 * inside
        system, qkd = [], []
        for b in range(blocks):
            lo, hi = b * B, (b + 1) * B
            # the chunk's keys as block b's tokens meet them
            kb = k32 * jnp.exp(jnp.where(token < hi, mids[b] - G,
                                         -jnp.inf))
            pair = jnp.einsum(
                "gik,gjk->gij",
                jnp.concatenate([kd[:, lo:hi], qd[:, lo:hi]], axis=1), kb,
                precision=_HIGHEST, preferred_element_type=_F32)
            # (a slice of `row` here and Mosaic's layout pass aborts)
            r = lo + jax.lax.broadcasted_iota(jnp.int32, (B, Q), 0)
            c = jax.lax.broadcasted_iota(jnp.int32, (B, Q), 1)
            system.append(jnp.where(r > c, beta[:, lo:hi] * pair[:, :B],
                                    0.0))
            qkd.append(jnp.where(r >= c, pair[:, B:], 0.0))
        system = jnp.concatenate(system, axis=1)        # A: [n, Q, Q]
        qkd = jnp.concatenate(qkd, axis=1)
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
        G_end = G[:, Q - 1:Q]                           # [n, 1, K]
        k_out = k32 * jnp.exp(G_end - G)
        # e^(G_C) down the state's rows: a row [1, K] as a column [K, 1]
        eye = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0) == \
            jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
        whole = jnp.sum(jnp.where(eye, jnp.exp(G_end), 0.0), axis=-1,
                        keepdims=True)                  # [n, K, 1]

        S = s_ref[...]                                  # [hb, K, V]
        for i in range(cps):
            at = slice(i * hb, (i + 1) * hb)
            read = _bmm(jnp.concatenate([W[at], q_in[at]], axis=1), S)
            delta = U[at] - read[:, :Q]
            o = read[:, Q:] + _bmm(qkd[at], delta)
            S = whole[at] * S + jnp.einsum(
                "gqk,gqv->gkv", k_out[at], delta, precision=_HIGHEST,
                preferred_element_type=_F32)
            for j in range(hb):
                o_ref[i * Q:(i + 1) * Q, j * V:(j + 1) * V] = o[j]
        s_ref[...] = S

    return kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _chunked_call(q, k, v, g, beta, state, *, chunk, interpret):
    T, H, K = q.shape
    V = v.shape[-1]
    Q = chunk
    c = T // Q
    cps = _CHUNKS_A_STEP if c % _CHUNKS_A_STEP == 0 else 1
    hb = _HEADS_A_STEP if H % _HEADS_A_STEP == 0 else 1
    # the running sum of g inside each chunk, as the tokens lie
    G = jnp.cumsum(g.reshape(c, Q, H * K), axis=1).reshape(T, H * K)
    # a grid step's pairs' beta, a chunk's tokens on the lanes:
    # [H / hb, c / cps, cps hb, Q]
    rows = beta.reshape(c // cps, cps, Q, H // hb, hb).transpose(
        3, 0, 1, 4, 2).reshape(H // hb, c // cps, cps * hb, Q)

    tokens = lambda h, i: (i, h)                        # noqa: E731
    heads = lambda h, i: (h, 0, 0)                      # noqa: E731
    call = pl.pallas_call(
        _chunk_kernel(Q, K, V, hb, cps),
        name=KDA_SCAN_NAME,
        grid=(H // hb, c // cps),
        in_specs=[pl.BlockSpec((cps * Q, hb * K), tokens),
                  pl.BlockSpec((cps * Q, hb * K), tokens),
                  pl.BlockSpec((cps * Q, hb * V), tokens),
                  pl.BlockSpec((cps * Q, hb * K), tokens),
                  pl.BlockSpec((1, 1, cps * hb, Q),
                               lambda h, i: (h, i, 0, 0)),
                  pl.BlockSpec((hb, K, V), heads)],
        out_specs=[pl.BlockSpec((cps * Q, hb * V), tokens),
                   pl.BlockSpec((hb, K, V), heads)],
        out_shape=[jax.ShapeDtypeStruct((T, H * V), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope(KDA_SCAN_NAME):
        o, state = call(q.reshape(T, H * K), k.reshape(T, H * K),
                        v.reshape(T, H * V), G, rows, state)
    return o.reshape(T, H, V), state


def kda_chunked(q, k, v, g, beta, state, chunk):
    """`ops.kda.kda_chunked` as one kernel call; ``T`` a multiple of
    ``chunk``, ``chunk`` a multiple of 16 (or smaller than it). The
    compiled kernel on TPU, Pallas interpret mode elsewhere."""
    interpret = jax.devices()[0].platform != "tpu"
    return _chunked_call(q, k, v, g.astype(_F32), beta.astype(_F32),
                         state.astype(_F32), chunk=int(chunk),
                         interpret=interpret)


def _step_kernel(H, K, V):
    def kernel(rows_ref, n_ref, beta_ref, cols_ref, v_ref, s_in, o_ref,
               s_ref):
        i, n = pl.program_id(0), n_ref[0]

        @pl.when(i < n)
        def _():
            at = rows_ref[i] * H
            cols = cols_ref[0]                          # [K, 3 H]
            v32 = v_ref[0].astype(_F32)                 # [H, V]
            for h in range(H):
                # a head's q, k and decay, columns, across the lanes
                q, k, decay = (
                    jnp.broadcast_to(cols[:, j * H + h:j * H + h + 1],
                                     (K, V)) for j in range(3))
                S = decay * s_in[0, h]                  # [K, V]
                read = jnp.sum(S * k, axis=0, keepdims=True)
                delta = beta_ref[at + h] * (v32[h:h + 1] - read)
                S = S + k * delta
                s_ref[0, h] = S
                o_ref[0, h:h + 1] = jnp.sum(S * q, axis=0, keepdims=True)

        # the pipeline writes the block the grid ends on whatever the
        # steps did: with no row live it is row 0's, handed through
        @pl.when((i == 0) & (n == 0))
        def _():
            s_ref[...] = s_in[...]

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(q, k, v, g, beta, state, live, *, interpret):
    R, H, K = q.shape
    V = v.shape[-1]
    rows, count = live_row_list(live)
    # q, k and e^g as columns: [R, K, 3 H]
    cols = jnp.swapaxes(jnp.concatenate(
        [q.astype(_F32), k.astype(_F32), jnp.exp(g)], axis=1), 1, 2)

    row3 = lambda *a: (listed_row(*a), 0, 0)            # noqa: E731
    row4 = lambda *a: (listed_row(*a), 0, 0, 0)         # noqa: E731
    call = pl.pallas_call(
        _step_kernel(H, K, V),
        name=KDA_STEP_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R,),
            in_specs=[pl.BlockSpec((1, K, 3 * H), row3),
                      pl.BlockSpec((1, H, V), row3),
                      pl.BlockSpec((1, H, K, V), row4)],
            out_specs=[pl.BlockSpec((1, H, V), row3),
                       pl.BlockSpec((1, H, K, V), row4)]),
        out_shape=[jax.ShapeDtypeStruct((R, H, V), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # operand 5 (the three scalar operands count) is the state, and
        # so is output 1: a row that is not listed is not touched
        input_output_aliases={5: 1},
        interpret=interpret,
    )
    with jax.named_scope(KDA_STEP_NAME):
        o, state = call(rows, count[-1:], beta.reshape(-1), cols, v, state)
    # a row off the list: a block of ``o`` nobody wrote
    return jnp.where(live[:, None, None], o, 0.0), state


def kda_step(q, k, v, g, beta, state, live):
    """`ops.kda.kda_step` as one kernel call over the live rows. The
    compiled kernel on TPU, Pallas interpret mode elsewhere."""
    interpret = jax.devices()[0].platform != "tpu"
    return _step_call(q, k, v, g.astype(_F32), beta.astype(_F32),
                      state.astype(_F32), live, interpret=interpret)

"""Gated delta rule (Gated DeltaNet) sequence ops for the serving path:
the chunked form a prefill call runs and the one-step update a decode
step runs. The short causal convolution before them is `ops/ssm.py`'s.

The recurrence, per value head (``k_t``, ``q_t`` the head's ``K``-wide
key and query, ``v_t`` its ``V``-wide value, ``g_t <= 0`` and ``beta_t``
in (0, 1) one scalar each a head and token)::

    S   <- exp(g_t) S                       S: [K, V]
    d_t  = beta_t (v_t - S^T k_t)           the delta: what S lacks of v_t
    S   <- S + k_t (x) d_t
    o_t  = S^T q_t

Mamba-2's update (`ops/ssm.py`) writes ``x (x) B`` whatever the state
holds; here **the write depends on a read of the state through the
key**, so one step is a matrix-vector product before the outer product,
and a chunk of tokens cannot be folded into one masked product: the
deltas of a chunk depend on each other.

**Prefill** (:func:`gated_delta_chunked`; Yang, Kautz & Hatamizadeh
2024, arXiv:2412.06464, section 3.3, the WY form): the sequence is cut
into chunks of ``chunk`` tokens. With ``G_i`` the running sum of ``g``
inside a chunk, the deltas solve a unit lower-triangular system::

    (I - A) D = beta (v - e^G k S_in),   A_ij = -beta_i (k_i . k_j) e^(G_i - G_j)  (j < i)

by forward substitution (exact, no series: a product of ``A``'s powers
cancels where a chunk's keys are alike), once for both right-hand
sides: ``U = (I - A)^-1 (beta v)``
and ``W = (I - A)^-1 (beta k e^G)``, so a chunk's deltas are ``U - W
S_in``. Its output is ``(q e^G) S_in`` plus the causal ``(q k^T e^(G_i
- G_j))`` times the deltas, and ``S_out = e^(G_C) S_in + (k e^(G_C -
G))^T`` times them. The state passes from chunk to chunk and from call
to call in float32. **Decode**
(:func:`gated_delta_step`) is the recurrence itself, one step for
every row that holds a request.

Precision, fixed by the configuration (`models/qwen3_next.py`): ``g``,
``beta``, every decay, the system, its solution and the state are
float32 whatever the activations are; the products of activations
(``k k^T``, ``q k^T``) take their operands in the compute dtype and
accumulate in float32; every product that reads or writes the state
runs on float32 operands at the highest precision.

Padding: a token with ``g`` 0 and ``beta`` 0 decays nothing and writes
nothing (its delta is 0 whatever ``k`` and ``v`` are), so the caller
masks a ragged tail by zeroing both; a dead decode row keeps its state
bit for bit (``live``): the step neither reads nor writes it, and the
row's output is zero.

Which form runs where: :func:`gated_delta_chunked`, the name the mixer
(`models/qwen3_next.py`) calls under the scope ``ds_gdn_scan``, is one
Pallas kernel call (`ops/pallas/gated_delta.py`: a grid step takes two
chunks of a key head, a chunk's ``decay``, ``k k^T``, ``q k^T`` and
system live and die in VMEM, the substitution is blocked at 16 rows,
the heads' state stays in VMEM from the call's first chunk to its
last), compiled on a TPU and in Pallas interpret mode elsewhere; one
path, whatever the shapes. :func:`gated_delta_chunked_plain` is the same
algebra in plain XLA (`jax.lax.linalg.triangular_solve`, the chunks
under ``lax.scan``): the form the tests hold the kernel to, run by no
program (on the chip 2.14 ms a layer a 1,024-token call against the
kernel's 0.67, two thirds of it XLA's ``InvertDiagBlocksLowerTriangular``;
`PERF.md` section 6, PR 44). :func:`gated_delta_step`, which the mixer
calls under the scope ``ds_gdn_step``, is one Pallas kernel call too
(``ds_gdn_step_rows``: a grid step takes one row of a list of the live
rows made in the program, all its heads' state in one block, and the
state is the call's own output, so a row off the list costs nothing);
:func:`gated_delta_step_plain` is the masked pass over every row it
replaced (on the chip 7.8 ms a decode step's six layers at 128 rows
whatever is live, against 1.5 at 36 live rows; `PERF.md` section 6,
PR 50), the tests' yardstick, run by no program.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import gated_delta as _kernel

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _chunk_of(T, chunk):
    Q = min(int(chunk), T)
    if T % Q:
        raise ValueError(f"sequence {T} is not a multiple of the delta "
                         f"rule's chunk {Q}")
    return Q


def gated_delta_chunked(q, k, v, g, beta, state, chunk):
    """One sequence through the recurrence in chunks.

    ``q``, ``k`` ``[T, Hk, K]`` (compute dtype; normalised and scaled by
    the caller; a key head serves ``H / Hk`` consecutive value heads),
    ``v`` ``[T, H, V]``, ``g`` ``[T, H]`` float32 (<= 0; 0 on padding),
    ``beta`` ``[T, H]`` float32 (0 on padding), ``state`` ``[H, K, V]``
    float32 (the state before the first token). ``T`` is a multiple of
    ``chunk``. Returns ``(o [T, H, V] float32, state after the last
    token)``.
    """
    return _kernel.gated_delta_chunked(q, k, v, g, beta, state,
                                       _chunk_of(q.shape[0], chunk))


def gated_delta_chunked_plain(q, k, v, g, beta, state, chunk):
    """:func:`gated_delta_chunked` in plain XLA."""
    T, H = v.shape[:2]
    V = v.shape[-1]
    Q = _chunk_of(T, chunk)
    c = T // Q
    q, k = (jnp.repeat(a, H // a.shape[1], axis=1) for a in (q, k))
    # chunk-major, heads before tokens: [c, H, Q, .]
    def heads_first(a):
        return jnp.moveaxis(a.reshape(c, Q, H, *a.shape[2:]), 2, 1)
    qc, kc, vc = heads_first(q), heads_first(k), heads_first(v)
    gc, bc = heads_first(g), heads_first(beta)          # [c, H, Q]
    G = jnp.cumsum(gc, axis=-1)                         # <= 0
    seg = G[..., :, None] - G[..., None, :]             # G_i - G_j
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))    # 0 above
    strict = jnp.tril(jnp.ones((Q, Q), bool), -1)

    # the system: (I - A) [U | W] = beta [v | k e^G]
    kk = jnp.einsum("chik,chjk->chij", kc, kc,
                    preferred_element_type=_F32)
    system = jnp.where(strict, bc[..., None] * kk * decay, 0.0) + \
        jnp.eye(Q, dtype=_F32)                          # I - A
    rhs = jnp.concatenate(
        [vc.astype(_F32),
         kc.astype(_F32) * jnp.exp(G)[..., None]], axis=-1) * \
        bc[..., None]
    solved = jax.lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True)
    U, W = solved[..., :V], solved[..., V:]

    # what a chunk reads of the state, and what it leaves to it
    qk = jnp.einsum("chik,chjk->chij", qc, kc,
                    preferred_element_type=_F32) * decay
    q_in = qc.astype(_F32) * jnp.exp(G)[..., None]      # q e^G
    k_out = kc.astype(_F32) * jnp.exp(G[..., -1:] - G)[..., None]
    whole = jnp.exp(G[..., -1])                         # [c, H]

    def one(S, xs):
        U_i, W_i, qk_i, q_i, k_i, whole_i = xs
        delta = U_i - jnp.einsum("hik,hkv->hiv", W_i, S,
                                 precision=_HIGHEST)
        o = jnp.einsum("hik,hkv->hiv", q_i, S, precision=_HIGHEST) + \
            jnp.einsum("hij,hjv->hiv", qk_i, delta, precision=_HIGHEST)
        S = whole_i[:, None, None] * S + jnp.einsum(
            "hik,hiv->hkv", k_i, delta, precision=_HIGHEST)
        return S, o

    # o: [c, H, Q, V]
    state, o = jax.lax.scan(one, state, (U, W, qk, q_in, k_out, whole))
    o = jnp.moveaxis(o, 1, 2).reshape(T, H, V)
    return o, state


def gated_delta_step(q, k, v, g, beta, state, live):
    """One step of every live row. ``q``, ``k`` ``[R, Hk, K]`` (``Hk``
    dividing ``v``'s ``H`` heads, as in :func:`gated_delta_chunked`),
    ``v`` ``[R, H, V]``, ``g``, ``beta`` ``[R, H]`` float32, ``state``
    ``[R, H, K, V]`` float32, ``live`` ``[R]`` bool. Returns ``(o [R, H,
    V] float32, new state)``; a row that is not live keeps its state,
    which is neither read nor written, and its ``o`` is zero."""
    return _kernel.gated_delta_step(q, k, v, g, beta, state, live)


def gated_delta_step_plain(q, k, v, g, beta, state, live):
    """:func:`gated_delta_step` in plain XLA: one masked pass over every
    row."""
    H = v.shape[1]
    q32, k32 = (jnp.repeat(a.astype(_F32), H // a.shape[1], axis=1)
                for a in (q, k))
    decayed = jnp.exp(g)[..., None, None] * state
    read = jnp.sum(decayed * k32[..., None], axis=-2)   # S^T k
    delta = beta[..., None] * (v.astype(_F32) - read)
    new = decayed + k32[..., None] * delta[..., None, :]
    o = jnp.sum(new * q32[..., None], axis=-2)          # S^T q
    live = live[:, None, None]
    return jnp.where(live, o, 0.0), jnp.where(live[..., None], new, state)

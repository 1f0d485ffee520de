"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692) sequence
ops for the serving path: the delta rule of `ops/gated_delta.py` with a
decay **a channel**, the chunked form a prefill call runs and the
one-step update a decode step runs. The short causal convolutions
before them are `ops/ssm.py`'s.

The recurrence, per head (``k_t``, ``q_t`` the head's ``K``-wide key and
query, ``v_t`` its ``V``-wide value, ``g_t <= 0`` a ``K``-wide vector
and ``beta_t`` in (0, 1) a scalar, a head and token)::

    S   <- diag(exp(g_t)) S                 S: [K, V]; row c forgets by g_tc
    d_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t (x) d_t
    o_t  = S^T q_t

With one scalar a head (``g_t`` constant over the channels) this is the
gated delta rule, and both forms give `ops/gated_delta.py`'s result.

**Prefill** (:func:`kda_chunked`): chunks of ``chunk`` tokens. With
``G_i`` the running sum of ``g`` inside a chunk (a vector), the deltas
solve the unit lower-triangular system::

    (I - A) D = beta (v - (k e^G) S_in),   A_ij = -beta_i sum_c k_ic k_jc e^(G_ic - G_jc)  (j < i)

by forward substitution, once for both right-hand sides (``U``, ``W``
as there); ``o_i = (q_i e^(G_i)) S_in + sum_{j<=i} (sum_c q_ic k_jc
e^(G_ic - G_jc)) d_j`` and ``S_out = diag(e^(G_C)) S_in + sum_j (k_j
e^(G_C - G_j)) d_j^T``. The decay no longer factors out of ``k_i .
k_j``: the pair term is a matrix product only of ``k e^G`` with ``k
e^-G``, and ``e^-G`` overflows unless **the decay is bounded and the
chunk is cut again**. The configuration bounds it (``g >= -5`` a token:
``kda_safe_gate``, ``kda_lower_bound`` of `models/ling_hybrid.py`), and
the kernel takes every exponent against the middle of a sub-block of 16
tokens: within ``e^-40`` and ``e^40`` inside a sub-block, a factor <= 1
beside that across sub-blocks. **A caller whose ``g`` can fall under -5
a token may not use the kernel**: :func:`kda_chunked` does not check
(``g`` is a traced value); the model's gate cannot produce one.

**Decode** (:func:`kda_step`) is the recurrence itself, one step for
every row that holds a request.

Precision, fixed by the configuration, as `ops/gated_delta.py` states
it: ``g``, ``beta``, every decay, the system, its solution and the
state float32; the decayed keys and queries are float32, so the pair
terms run at the highest precision too; every product that reads or
writes the state on float32 operands at the highest precision.

Padding: a token with ``g`` 0 and ``beta`` 0 decays nothing and writes
nothing; a dead decode row keeps its state bit for bit.

Which form runs where: :func:`kda_chunked` (the mixer calls it under the
scope ``ds_kda_scan``) and :func:`kda_step` (under ``ds_kda_step``) are
one Pallas kernel call each (`ops/pallas/kda.py`: ``ds_kda_scan_chunks``,
``ds_kda_step_rows``), compiled on a TPU and in interpret mode
elsewhere. :func:`kda_chunked_plain` and :func:`kda_step_plain` are the
same algebra in plain XLA (the pair terms by their definition, a ``[Q,
Q, K]`` array of exponents under the causal mask, so no bound on ``g``
is needed): what the tests hold the kernels to, run by no program.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.gated_delta import _chunk_of
from deepspeed_tpu.ops.pallas import kda as _kernel

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def kda_chunked(q, k, v, g, beta, state, chunk):
    """One sequence through the recurrence in chunks.

    ``q``, ``k`` ``[T, H, K]`` (compute dtype; normalised and scaled by
    the caller), ``v`` ``[T, H, V]``, ``g`` ``[T, H, K]`` float32 (in
    [-5, 0]; 0 on padding), ``beta`` ``[T, H]`` float32 (0 on padding),
    ``state`` ``[H, K, V]`` float32. ``T`` is a multiple of ``chunk``.
    Returns ``(o [T, H, V] float32, state after the last token)``."""
    return _kernel.kda_chunked(q, k, v, g, beta, state,
                               _chunk_of(q.shape[0], chunk))


def kda_chunked_plain(q, k, v, g, beta, state, chunk):
    """:func:`kda_chunked` in plain XLA."""
    T, H, K = q.shape
    V = v.shape[-1]
    Q = _chunk_of(T, chunk)
    c = T // Q
    # chunk-major, heads before tokens: [c, H, Q, .]
    def heads_first(a):
        return jnp.moveaxis(a.astype(_F32).reshape(c, Q, H, *a.shape[2:]),
                            2, 1)
    qc, kc, vc = heads_first(q), heads_first(k), heads_first(v)
    bc = heads_first(beta)                              # [c, H, Q]
    G = jnp.cumsum(heads_first(g), axis=2)              # [c, H, Q, K]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    # e^(G_i - G_j) a channel, 0 above the diagonal: [c, H, Q, Q, K]
    decay = jnp.exp(jnp.where(lower[..., None],
                              G[..., :, None, :] - G[..., None, :, :],
                              -jnp.inf))
    kk = jnp.einsum("chik,chjk,chijk->chij", kc, kc, decay,
                    precision=_HIGHEST)
    qk = jnp.einsum("chik,chjk,chijk->chij", qc, kc, decay,
                    precision=_HIGHEST)
    strict = jnp.tril(jnp.ones((Q, Q), bool), -1)
    system = jnp.where(strict, bc[..., None] * kk, 0.0) + \
        jnp.eye(Q, dtype=_F32)                          # I - A
    rhs = jnp.concatenate([vc, kc * jnp.exp(G)], axis=-1) * bc[..., None]
    solved = jax.lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True)
    U, W = solved[..., :V], solved[..., V:]
    q_in = qc * jnp.exp(G)
    k_out = kc * jnp.exp(G[..., -1:, :] - G)
    whole = jnp.exp(G[..., -1, :])                      # [c, H, K]

    def one(S, xs):
        U_i, W_i, qk_i, q_i, k_i, whole_i = xs
        delta = U_i - jnp.einsum("hik,hkv->hiv", W_i, S,
                                 precision=_HIGHEST)
        o = jnp.einsum("hik,hkv->hiv", q_i, S, precision=_HIGHEST) + \
            jnp.einsum("hij,hjv->hiv", qk_i, delta, precision=_HIGHEST)
        S = whole_i[:, :, None] * S + jnp.einsum(
            "hik,hiv->hkv", k_i, delta, precision=_HIGHEST)
        return S, o

    state, o = jax.lax.scan(one, state.astype(_F32),
                            (U, W, qk, q_in, k_out, whole))
    return jnp.moveaxis(o, 1, 2).reshape(T, H, V), state


def kda_step(q, k, v, g, beta, state, live):
    """One step of every live row. ``q``, ``k`` ``[R, H, K]``, ``v``
    ``[R, H, V]``, ``g`` ``[R, H, K]`` float32, ``beta`` ``[R, H]``
    float32, ``state`` ``[R, H, K, V]`` float32, ``live`` ``[R]`` bool.
    Returns ``(o [R, H, V] float32, new state)``; a row that is not
    live keeps its state, which is neither read nor written, and its
    ``o`` is zero."""
    return _kernel.kda_step(q, k, v, g, beta, state, live)


def kda_step_plain(q, k, v, g, beta, state, live):
    """:func:`kda_step` in plain XLA: one masked pass over every row."""
    q32, k32 = q.astype(_F32), k.astype(_F32)
    decayed = jnp.exp(g)[..., None] * state
    read = jnp.sum(decayed * k32[..., None], axis=-2)   # S^T k
    delta = beta[..., None] * (v.astype(_F32) - read)
    new = decayed + k32[..., None] * delta[..., None, :]
    o = jnp.sum(new * q32[..., None], axis=-2)          # S^T q
    live = live[:, None, None]
    return jnp.where(live, o, 0.0), jnp.where(live[..., None], new, state)

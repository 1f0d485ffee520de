"""1-bit Adam: error-feedback momentum-compressed data parallelism.

Capability parity with the reference's ``OnebitAdam``
(`runtime/fp16/onebit_adam.py:18`): a ``freeze_step`` warmup of plain Adam
with dense gradient averaging, then a "compression stage" where the second
moment is frozen and the *momentum* is averaged across data-parallel
workers with error-feedback 1-bit compression
(:func:`deepspeed_tpu.runtime.comm.compressed.compressed_allreduce`).

TPU-native mechanism: where the reference disables the engine's gradient
allreduce (onebit_adam.py:372) and runs an mpi4py/cupy side channel, here
the whole update is one function designed to run inside ``shard_map`` over
the ``data`` mesh axis — local (un-averaged) gradients flow in, the
compressed collective rides ICI/DCN, and the error residuals are explicit
state sharded over the same axis.

Math mirrors the reference exactly: no bias correction, frozen ``v`` after
``freeze_step`` (onebit_adam.py:262-303), update
``m / (sqrt(v) + eps) + wd * p``.
"""

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from deepspeed_tpu.runtime.comm.compressed import (
    compressed_allreduce, error_feedback_sizes)

__all__ = ["OnebitAdamState", "init_onebit_state",
           "init_pipeline_onebit_state", "onebit_adam_update"]


class OnebitAdamState(NamedTuple):
    m: Any                      # momentum pytree, fp32, replicated
    v: Any                      # second moment pytree, fp32 (frozen post-warmup)
    step: jnp.ndarray           # i32 — applied steps
    worker_error: jnp.ndarray   # [world, padded_n], shard rank r holds row r
    server_error: jnp.ndarray   # [padded_n], rank r holds its served chunk


def param_count(params):
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


def init_onebit_state(params, world: int) -> OnebitAdamState:
    n = param_count(params)
    padded, _ = error_feedback_sizes(n, world)
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return OnebitAdamState(
        m=jax.tree_util.tree_map(zeros, params),
        v=jax.tree_util.tree_map(zeros, params),
        step=jnp.asarray(0, jnp.int32),
        worker_error=jnp.zeros((world, padded), jnp.float32),
        server_error=jnp.zeros((padded,), jnp.float32),
    )


def pipeline_mp_mask(params, model):
    """Per-leaf bools in ``tree_leaves(params['body'])`` order: True for
    model-sharded ``mp_*`` leaves. The single source of truth for the 3D
    1-bit layout — both the error-buffer sizing here and the engine's
    group split (`engine.py:_pipeline_onebit_step`) consume
    it, so the slice offsets cannot drift from the group sizes."""
    from deepspeed_tpu.runtime.pipe.pipeline import _is_mp_leaf
    return [model > 1 and _is_mp_leaf(path, leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                params["body"])[0]]


def _pipeline_local_sizes(params, num_stages, model=1):
    """(mp_local, rep_local, rest_n): flat element counts as seen by ONE
    (stage, model-rank) device — ``mp_*`` body leaves divide their shard
    dim over ``model``, every other body leaf is model-replicated."""
    mask = pipeline_mp_mask(params, model)
    mp_n = rep_n = 0
    for (path, leaf), is_mp in zip(
            jax.tree_util.tree_flatten_with_path(params["body"])[0], mask):
        if is_mp:
            assert leaf.shape[2] % model == 0, (path, leaf.shape, model)
            mp_n += int(leaf.size) // model
        else:
            rep_n += int(leaf.size)
    rest_n = sum(int(p.size) for k in ("prologue", "epilogue", "tied")
                 for p in jax.tree_util.tree_leaves(params[k]))
    assert mp_n % num_stages == 0 and rep_n % num_stages == 0, (
        mp_n, rep_n, num_stages)
    return mp_n // num_stages, rep_n // num_stages, rest_n


def init_pipeline_onebit_state(params, world: int, num_stages: int,
                               model: int = 1) -> OnebitAdamState:
    """State for the pipeline x 1-bit composition
    (`engine.py:_pipeline_onebit_step`): m/v mirror the
    (stacked, pipe-sharded) params; error-feedback buffers are per
    (stage[, model-rank], data-rank) over the device-LOCAL flat parameter
    count — every device runs its own compressed collective over ``data``
    within its (stage, model) group, so residuals live where the shards
    live.

    ``params`` is the pipeline tree {prologue, body, epilogue, tied} with
    the body stacked [S, L/S, ...]. Homogeneous stages ⇒ one local size.

    Groups that share content must compress IDENTICAL buffers or their
    copies silently diverge (the quantization scale is the whole-buffer
    L2, compressed.py:_compress):
    - body vs pipe-replicated rest → separate buffers (round 3);
    - with a ``model`` axis (3D, round 4), model-sharded ``mp_*`` leaves
      vs model-replicated body leaves → a third split, so the replicated
      leaves see the same scale on every model rank. Buffers concatenate
      [mp | body_rep | rest] along the last dim; worker/server errors get
      a model dim: [S, M, world, ...].
    """
    mp_n, rep_n, rest_n = _pipeline_local_sizes(params, num_stages, model)
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    m = jax.tree_util.tree_map(zeros, params)
    v = jax.tree_util.tree_map(zeros, params)
    pr, cr = error_feedback_sizes(max(rest_n, 8), world)
    if model > 1:
        pm, cm = error_feedback_sizes(max(mp_n, 8), world)
        pb, cb = error_feedback_sizes(max(rep_n, 8), world)
        return OnebitAdamState(
            m=m, v=v, step=jnp.asarray(0, jnp.int32),
            worker_error=jnp.zeros((num_stages, model, world, pm + pb + pr),
                                   jnp.float32),
            server_error=jnp.zeros((num_stages, model, world, cm + cb + cr),
                                   jnp.float32),
        )
    pb, cb = error_feedback_sizes(mp_n + rep_n, world)
    return OnebitAdamState(
        m=m, v=v, step=jnp.asarray(0, jnp.int32),
        worker_error=jnp.zeros((num_stages, world, pb + pr), jnp.float32),
        server_error=jnp.zeros((num_stages, world, cb + cr), jnp.float32),
    )


def pipeline_onebit_splits(params, world, num_stages, model=1):
    """The concatenation layout of the pipeline state's error buffers:
    ``model == 1`` → ((padded_body, chunk_body), (padded_rest,
    chunk_rest)); ``model > 1`` → ((padded_mp, chunk_mp), (padded_rep,
    chunk_rep), (padded_rest, chunk_rest))."""
    mp_n, rep_n, rest_n = _pipeline_local_sizes(params, num_stages, model)
    rest = error_feedback_sizes(max(rest_n, 8), world)
    if model > 1:
        return (error_feedback_sizes(max(mp_n, 8), world),
                error_feedback_sizes(max(rep_n, 8), world), rest)
    return error_feedback_sizes(mp_n + rep_n, world), rest


def onebit_adam_update(params,
                       local_grads,
                       state: OnebitAdamState,
                       lr,
                       beta1=0.9,
                       beta2=0.999,
                       eps=1e-8,
                       weight_decay=0.0,
                       freeze_step=100,
                       axis_name="data"):
    """One 1-bit Adam step; call inside ``shard_map`` over ``axis_name``.

    ``local_grads`` are this shard's *unaveraged* gradients; the dense
    warmup branch averages them with ``pmean``, the compression branch
    folds them into the momentum and averages that with the 1-bit
    collective. Returns ``(new_params, new_state)``.
    """
    step = state.step + 1
    g_flat, _ = ravel_pytree(local_grads)
    g_flat = g_flat.astype(jnp.float32)
    m_flat, unravel = ravel_pytree(state.m)
    v_flat, _ = ravel_pytree(state.v)
    n = g_flat.shape[0]
    # Local views under shard_map: worker_error is this rank's full-length
    # row; server_error is this rank's served chunk.
    padded_n = state.worker_error.shape[-1]
    we = state.worker_error.reshape(-1)
    se = state.server_error

    def warmup(_):
        g_avg = jax.lax.pmean(g_flat, axis_name)
        m_new = beta1 * m_flat + (1.0 - beta1) * g_avg
        v_new = beta2 * v_flat + (1.0 - beta2) * jnp.square(g_avg)
        return m_new, v_new, we, se

    def compressed(_):
        m_local = beta1 * m_flat + (1.0 - beta1) * g_flat
        m_pad = jnp.zeros((padded_n,), jnp.float32).at[:n].set(m_local)
        m_avg, we_new, se_new = compressed_allreduce(
            m_pad, we, se, axis_name, n_valid=n)
        return m_avg[:n], v_flat, we_new, se_new

    m_new, v_new, we_new, se_new = jax.lax.cond(
        step <= freeze_step, warmup, compressed, None)

    p_flat, unravel_p = ravel_pytree(params)
    p32 = p_flat.astype(jnp.float32)
    update = m_new / (jnp.sqrt(v_new) + eps)
    if weight_decay != 0.0:
        update = update + weight_decay * p32
    new_p = (p32 - lr * update).astype(p_flat.dtype)

    new_state = OnebitAdamState(
        m=unravel(m_new),
        v=unravel(v_new),
        step=step,
        worker_error=we_new.reshape(state.worker_error.shape),
        server_error=se_new,
    )
    return unravel_p(new_p), new_state


class OnebitAdam:
    """API-parity wrapper mirroring the reference constructor surface
    (`runtime/fp16/onebit_adam.py:18-60`)."""

    def __init__(self, params=None, deepspeed=None, lr=1e-3,
                 freeze_step=100000, bias_correction=True,
                 betas=(0.9, 0.999), eps=1e-8, eps_inside_sqrt=False,
                 weight_decay=0.0, max_grad_norm=0.0, amsgrad=False,
                 cuda_aware=False):
        if amsgrad:
            raise RuntimeError("1-bit Adam does not support the AMSGrad "
                               "variant.")
        self.lr = lr
        self.freeze_step = freeze_step
        self.betas = tuple(betas)
        self.eps = eps
        self.weight_decay = weight_decay

    def init(self, params, world=1):
        return init_onebit_state(params, world)

    def update(self, params, grads, state, lr=None, beta1=None,
               axis_name="data"):
        return onebit_adam_update(
            params, grads, state,
            lr=self.lr if lr is None else lr,
            beta1=self.betas[0] if beta1 is None else beta1,
            beta2=self.betas[1], eps=self.eps,
            weight_decay=self.weight_decay,
            freeze_step=self.freeze_step, axis_name=axis_name)

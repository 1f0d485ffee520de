"""DeepSpeedEngine: the central training wrapper, TPU-native.

Analog of the reference's ``DeepSpeedEngine`` (`runtime/engine.py:91` —
``forward``:783, ``backward``:824, ``step``:960, checkpoints:1215-1482), with
the hook-driven mutable-tensor machinery replaced by one compiled train step:

- grad accumulation   → ``lax.scan`` over microbatches inside the step
- DP gradient allreduce → GSPMD: mean loss over the data-sharded batch
- ZeRO 1/2/3          → sharding declarations (see `runtime/zero/sharding.py`)
- fp16 master weights → fp32 params cast to compute dtype inside the grad fn
- dynamic loss scale  → pure state machine + ``jnp.where`` skip (the
  data-dependent overflow skip lives *inside* jit)
- LR/momentum schedule → folded into the step as functions of the counter

The imperative ``forward``/``backward``/``step`` micro-batch API is kept as a
compatibility shim; ``train_batch`` is the fast path (one XLA program per
global batch).
"""

import collections
import contextlib
import functools
import os
import json
import signal
import socket
import time
import weakref
from typing import Any, Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.analysis.audit import (
    AuditError,
    AuditReport,
    audit_compiled_step,
    check_recompile,
    donated_jit,
)
from deepspeed_tpu.runtime.config import (
    ADAM_OPTIMIZER,
    DeepSpeedConfig,
    LAMB_OPTIMIZER,
    ONEBIT_ADAM_OPTIMIZER,
)
from deepspeed_tpu.runtime.fp16.loss_scaler import (
    LossScaleState,
    init_loss_scale_state,
    update_loss_scale,
)
from deepspeed_tpu.runtime.lr_schedules import get_lr_scheduler, OneCycle
from deepspeed_tpu.runtime.utils import check_overflow, clip_by_global_norm, global_norm
from deepspeed_tpu.runtime.zero.sharding import (
    build_zero_shardings, constrain_tree, make_param_caster)
from deepspeed_tpu.runtime.zero.stage3 import (
    make_gather_on_use_caster, zero3_remat_policy)
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
from deepspeed_tpu.runtime.elastic import (
    CheckpointTopologyError, check_topology, current_topology,
    stream_device_put)
from deepspeed_tpu.runtime.elastic.topology import spec_to_json
from deepspeed_tpu.runtime.resilience import fault_injection
from deepspeed_tpu.runtime.resilience.checkpoint import CheckpointManager
from deepspeed_tpu.runtime.resilience.hotckpt import (
    HotCheckpointCorruptError,
    HotCheckpointStore,
)
from deepspeed_tpu.runtime.resilience.guards import (
    ACTION_ABORT, ACTION_ROLLBACK, ACTION_SKIP_STEP,
    HealthGuardAbort, StepHealthMonitor)
from deepspeed_tpu.runtime.resilience.preemption import (
    PreemptedError, PreemptionHandler)
from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop
from deepspeed_tpu.ops.adam.fused_adam import adam_update, init_adam_state
from deepspeed_tpu.ops.fp8 import fp8_scope, init_state_bundle
from deepspeed_tpu.ops.lamb.fused_lamb import init_lamb_state, lamb_update
from deepspeed_tpu.parallel.collectives import record_collective_sites
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.telemetry import (
    Span, StepAnomalyDetector, TelemetrySession, TraceProfiler,
    set_default_session, spans)
from deepspeed_tpu.telemetry.timers import (
    SynchronizedWallClockTimer, ThroughputTimer)
from jax import shard_map
from deepspeed_tpu.utils.logging import log_dist, logger

MEMORY_OPT_ALLREDUCE_SIZE = 500000000
# a step's batch, [accum, rows, ...]: the rows lie over ``data``
_BATCH_ROWS = PartitionSpec(None, "data")


class DeviceState(NamedTuple):
    """Device-resident step state threaded through the compiled train step."""
    loss_scale: LossScaleState
    global_step: jnp.ndarray     # i32 — optimizer-step boundaries seen
    skipped_steps: jnp.ndarray   # i32 — overflow-skipped steps
    consecutive_skipped: jnp.ndarray  # i32 — current overflow-skip streak


def grad_epilogue(grads, scale, accum, fp16, clip, constrain=None,
                  vote=None, norm_reduce=None, clip_norm_reduce=None,
                  detect_nonfinite=False, nan_skip=False):
    """Shared post-gradient block for every train-step flavor: unscale and
    average over microbatches → optional sharding constraint → overflow
    check (optionally cross-shard voted) → grad norms → clipping.

    Returns ``(grads, overflow, nonfinite, grad_norm, applied_norm)``.
    ``norm_reduce`` maps a local norm to the reported one (identity for
    GSPMD steps, pmean under shard_map); ``clip_norm_reduce`` picks the
    norm the clip factor is computed from (must be rank-consistent under
    shard_map).

    ``detect_nonfinite`` forces the finiteness check on even for
    fp32/bf16 runs (the resilience NaN guard's in-jit detector — normally
    the check is compiled out when fp16 scaling is off); ``nan_skip``
    additionally folds the verdict into ``overflow`` so the existing
    overflow-skip machinery drops the poisoned update. ``nonfinite`` is
    always the raw detector verdict, independent of the skip decision."""
    denom = scale * accum
    grads = jax.tree_util.tree_map(
        lambda g: g.astype(jnp.float32) / denom, grads)
    if constrain is not None:
        grads = constrain(grads)
    if fp16 or detect_nonfinite:
        nonfinite = check_overflow(grads)
    else:
        nonfinite = jnp.asarray(False)
    if vote is not None:
        nonfinite = vote(nonfinite)
    overflow = nonfinite if (fp16 or nan_skip) else jnp.asarray(False)
    nr = norm_reduce if norm_reduce is not None else (lambda n: n)
    cnr = clip_norm_reduce if clip_norm_reduce is not None else (lambda n: n)
    local_norm = global_norm(grads)
    grad_norm = nr(local_norm)
    applied_norm = grad_norm
    if clip > 0:
        grads = clip_by_global_norm(grads, clip, norm=cnr(local_norm))
        applied_norm = nr(global_norm(grads))
    return grads, overflow, nonfinite, grad_norm, applied_norm


def loss_scale_epilogue(dstate, overflow, fp16, dynamic, scale_args):
    """Dynamic-loss-scale update + step/skip counters (reference
    stage2.py:1341-1362 overflow-skip semantics), shared by all steps."""
    if fp16 and dynamic:
        new_scale = update_loss_scale(dstate.loss_scale, overflow,
                                      **scale_args)
    else:
        new_scale = dstate.loss_scale
    overflow_i32 = overflow.astype(jnp.int32)
    return DeviceState(
        loss_scale=new_scale,
        global_step=dstate.global_step + 1,
        skipped_steps=dstate.skipped_steps + overflow_i32,
        # Streak of back-to-back skips: the host-visible signal that a
        # run is dead (always overflowing) vs. merely rescaling.
        consecutive_skipped=(dstate.consecutive_skipped + 1) * overflow_i32)


LOSS_SCALARS = "loss_scalars"     # key of a step's metrics, see below


def step_metrics(loss_sum, accum, grad_norm, applied_norm, lr, scale,
                 overflow, loss_reduce=None, dstate=None, nonfinite=None,
                 loss_scalars=None):
    loss = loss_sum / accum
    if loss_reduce is not None:
        loss = loss_reduce(loss)
    out = {
        "loss": loss,
        "grad_norm": grad_norm,
        "applied_grad_norm": applied_norm,
        "lr": lr,
        "loss_scale": scale,
        "overflow": overflow,
    }
    if dstate is not None:
        # Post-update counters (pass dstate_out): overflow skips are no
        # longer silent — a dead run shows a growing streak here.
        out["skipped_steps"] = dstate.skipped_steps
        out["consecutive_skipped_steps"] = dstate.consecutive_skipped
    if nonfinite is not None:
        out["grad_nonfinite"] = nonfinite
    if loss_scalars:
        # what the loss function handed out beside its loss (the mean
        # over microbatches, as the loss is): on the device until read
        out[LOSS_SCALARS] = loss_scalars if accum == 1 else \
            jax.tree_util.tree_map(lambda x: x / accum, loss_scalars)
    return out


def loss_and_scalars(out):
    """What a loss function returned, as ``(loss, scalars)``: it may
    return its loss alone, or the loss and a dict of scalars (a sparse
    model's load counters, the terms of its loss) that the train step
    hands out with the step's metrics."""
    return out if isinstance(out, tuple) else (out, {})


def loss_alone(loss_fn):
    """``loss_fn`` without the scalars it may hand out."""
    @functools.wraps(loss_fn)       # keeps direct_value_and_grad[_local]
    def alone(*args, **kwargs):
        return loss_and_scalars(loss_fn(*args, **kwargs))[0]
    return alone


def make_grad_accumulator(loss_fn, compute_dtype, accum, constrain=None,
                          cast_params=None, remat_policy=None,
                          fp8_plan=None):
    """Build ``accumulate(params, batch, rng, scale) -> (loss_sum, grads,
    scalars)`` (``(loss_sum, grads, fp8 state, scalars)`` under fp8):
    scaled-loss value-and-grad over one microbatch, or a ``lax.scan`` over
    ``accum`` microbatches (batch leading dim = accum). Shared by the dense
    and the 1-bit (shard_map) train steps.

    ``constrain`` (grad pytree → grad pytree) pins the gradient layout —
    under ZeRO-2 the scan *carry* is constrained to the sharded-gradient
    layout, so the replicated full gradient never materializes across
    microbatches (the IPG-partition contract of reference stage2.py:613-738;
    constraining only after the scan would leave the carry layout to XLA's
    guess).

    ``cast_params`` overrides the default fp32→compute-dtype cast — an
    engine whose masters lie sharded passes the cast-then-gather transform
    (`zero/sharding.py:make_param_caster` at ZeRO stages 1 and 2, or
    stage 3's explicit `zero/stage3.py:make_gather_on_use_caster`) so
    param all-gathers ride the wire at 16 bit. Without a ``remat_policy``
    an accumulation scan applies it ONCE, in front of the scan: the
    16-bit copy is gathered a step, not a microbatch, and each
    microbatch's cotangent goes back through the transform's own
    backward (fp32 first, then the masters' layout).

    ``remat_policy`` wraps the microbatch forward in ``jax.checkpoint``
    with that policy — the explicit ZeRO-3 step passes
    `zero/stage3.py:zero3_remat_policy` so the gathered 16-bit params are
    dropped at the fwd/bwd boundary and the backward re-gathers them from
    the fp32 shards (remat re-executes the same gathers on the same
    inputs, so numerics are bitwise-unchanged).

    ``fp8_plan`` (an `ops/fp8.py:Fp8Plan`) turns on fp8 delayed-scaling
    matmuls: ``accumulate`` then takes a trailing ``fp8_state`` dict of
    per-site amax-history bundles and returns ``(loss_sum, grads,
    fp8_state_out)``. The microbatch forward runs under ``fp8_scope``
    and the loss is differentiated w.r.t. ``(params, fp8_state)`` — the
    state's "gradients" ARE the rolled histories (the grad-as-state-
    update trick in `ops/fp8.py`). Across an accumulation scan the
    per-micro updates combine elementwise via ``jnp.maximum``: every
    micro sees the same input histories, so the max over their slot-0
    amaxes is the step's amax and the older slots agree.

    ``loss_fn`` may return ``(loss, scalars)`` (:func:`loss_and_scalars`):
    ``scalars`` are summed over the microbatches, and ``{}`` from a loss
    function that hands none out."""

    user_caster = cast_params
    if cast_params is None:
        def cast_params(p):
            return jax.tree_util.tree_map(
                lambda x: x.astype(compute_dtype), p)
    cast_params = _under_scope("ds_param_cast", cast_params)

    # A loss_fn may carry a hand-written (loss, grads) implementation that
    # cannot be expressed as jax.grad of a scalar function — the executed
    # 1F1B pipeline (pipe/pipeline.py:make_pipeline_value_and_grad_fn)
    # interleaves forward and backward ticks, which AD cannot.
    direct = getattr(loss_fn, "direct_value_and_grad", None)
    if direct is not None and user_caster is not None:
        # ADVICE r4: the direct path runs the loss_fn's own casts, so a
        # ZeRO-3 cast-then-gather caster built for it would silently fall
        # back to XLA's fp32 gather-then-cast — surface the lost
        # param-traffic halving instead of eating it.
        log_dist("cast_params is ignored on the direct value-and-grad "
                 "path: the 16-bit cast-then-gather wire does not apply; "
                 "param gathers will ride at fp32", ranks=[0])

    def forward_of(cast):
        def forward(p, micro_batch, rng, loss_kwargs):
            if fp8_plan is None:
                return loss_fn(cast(p), micro_batch, rng, **loss_kwargs)
            # fp8: the differentiated argument is (params, fp8_state); the
            # scope only needs to span the forward trace — the qdq
            # custom_vjps carry everything the backward needs in residuals.
            p, f8 = p
            with fp8_scope(fp8_plan, f8):
                return loss_fn(cast(p), micro_batch, rng, **loss_kwargs)
        return forward

    forward = forward_of(cast_params)
    if remat_policy is not None:
        forward = jax.checkpoint(forward, policy=remat_policy)
    # A caster that gathers is applied once in front of an accumulation
    # scan and the scan differentiates the loss of the 16-bit tree (a
    # gather inside the body is one a microbatch: XLA hoists no
    # collective out of a loop). Not under a remat policy, which drops
    # the gathered copy on purpose, nor for the default cast, which
    # moves nothing.
    hoist = user_caster is not None and remat_policy is None and \
        accum > 1 and direct is None
    forward_cast = forward_of(lambda p: p)

    def micro_grads(params, micro_batch, rng, scale, loss_kwargs,
                    fp8_state=None, forward=forward):
        """``(loss, grads[, fp8 state], scalars)`` of one microbatch."""
        if direct is not None:
            return (*direct(params, micro_batch, rng, scale,
                            **loss_kwargs), {})

        arg = params if fp8_state is None else (params, fp8_state)

        def scaled_loss(p):
            loss, scalars = loss_and_scalars(
                forward(p, micro_batch, rng, loss_kwargs))
            return loss * scale, (loss, scalars)
        (_, (loss, scalars)), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(arg)
        if fp8_state is None:
            return loss, grads, scalars
        grads, f8_out = grads
        return loss, grads, f8_out, scalars

    # The explicit ZeRO-3 caster exposes its SiteRecord registration as
    # a hook to be fired out here, outside the remat/shard_map trace
    # caches — inside them the log goes quiet on an audit's retrace.
    declare_sites = getattr(user_caster, "declare_sites", None)

    def accumulate(params, batch, rng, scale, loss_kwargs=None,
                   fp8_state=None):
        if declare_sites is not None and direct is None:
            declare_sites()
        assert (fp8_state is not None) == (
            fp8_plan is not None and direct is None), \
            "fp8_state must be passed exactly when an fp8_plan is active"
        loss_kwargs = loss_kwargs or {}
        if accum == 1:
            micro = jax.tree_util.tree_map(lambda x: x[0], batch)
            if fp8_state is None:
                return micro_grads(params, micro, rng, scale, loss_kwargs)
            return micro_grads(params, micro, rng, scale, loss_kwargs,
                               fp8_state)
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if constrain is not None:
            zeros = constrain(zeros)
        fwd, uncast = forward, None
        if hoist:
            params, uncast = jax.vjp(cast_params, params)
            fwd = forward_cast

        def to_masters(g):
            return g if uncast is None else uncast(g)[0]

        def summed(per_micro):
            return jax.tree_util.tree_map(lambda x: x.sum(0), per_micro)

        if fp8_state is not None:
            # Histories are non-negative amaxes and every micro sees the
            # same input state, so elementwise max over the per-micro
            # updates (zero-init is the identity) is the step's update.
            f8_zeros = jax.tree_util.tree_map(jnp.zeros_like, fp8_state)

            def body_fp8(carry, micro):
                g_acc, f8_acc, loss_acc, key = carry
                key, sub = jax.random.split(key)
                loss, g, f8_new, scalars = micro_grads(
                    params, micro, sub, scale, loss_kwargs, fp8_state, fwd)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc,
                                               to_masters(g))
                if constrain is not None:
                    g_acc = constrain(g_acc)
                f8_acc = jax.tree_util.tree_map(jnp.maximum, f8_acc,
                                                f8_new)
                return (g_acc, f8_acc, loss_acc + loss, key), scalars

            (grads, f8_out, loss_sum, _), scalars = jax.lax.scan(
                body_fp8,
                (zeros, f8_zeros, jnp.asarray(0.0, jnp.float32), rng),
                batch)
            return loss_sum, grads, f8_out, summed(scalars)

        def body(carry, micro):
            g_acc, loss_acc, key = carry
            key, sub = jax.random.split(key)
            loss, g, scalars = micro_grads(params, micro, sub, scale,
                                           loss_kwargs, forward=fwd)
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, to_masters(g))
            if constrain is not None:
                g_acc = constrain(g_acc)
            return (g_acc, loss_acc + loss, key), scalars

        (grads, loss_sum, _), scalars = jax.lax.scan(
            body, (zeros, jnp.asarray(0.0, jnp.float32), rng), batch)
        return loss_sum, grads, summed(scalars)

    return accumulate


class _StepInputs(NamedTuple):
    """What every kind of train step reads of the engine, gathered once
    (`DeepSpeedEngine._step_inputs`)."""
    accum: int
    compute_dtype: Any
    fp16: bool
    clip: float
    lr_fn: Optional[Callable]
    mom_fn: Callable
    opt_update: Callable
    scale_args: dict
    dynamic: bool
    static_scale: float
    pld_fn: Optional[Callable]
    detect: bool        # the NaN guard forces the finiteness check on
    nan_skip: bool      # and its verdict skips the update
    fault_on: bool      # fault injection is configured on


def _under_scope(name, fn):
    """``fn``, traced under the device-side scope ``name``
    (`telemetry/scopes.py`)."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return scoped


def _loss_scale(c, dstate):
    return dstate.loss_scale.cur_scale if (c.fp16 and c.dynamic) \
        else jnp.asarray(c.static_scale, jnp.float32)


def _step_head(c, accumulate, params, dstate, batch, rng, fp8_state=None,
               grad_fault=None, manual=False):
    """The head of every train step: the loss scale, the progressive
    layer drop's theta, and ``accumulate``'s scaled gradients. Returns
    ``(scale, loss_sum, grads, loss_scalars, fp8_new)``, ``fp8_new``
    None without an ``fp8_state``.

    ``manual``: the caller is inside a ``shard_map`` over ``data`` and
    sees its own rows, so the rng is folded by the shard's index.
    ``grad_fault``: the fault harness's multiplier, for the steps that
    take one (`DeepSpeedEngine._make_train_step`)."""
    scale = _loss_scale(c, dstate)
    if manual:
        rng = jax.random.fold_in(rng, jax.lax.axis_index("data"))
    loss_kw = {"pld_theta": c.pld_fn(dstate.global_step)} \
        if c.pld_fn is not None else None
    loss_sum, grads, *fp8_new, loss_scalars = accumulate(
        params, batch, rng, scale, loss_kw, fp8_state)
    if grad_fault is not None:
        grads = jax.tree_util.tree_map(lambda g: g * grad_fault, grads)
    return scale, loss_sum, grads, loss_scalars, \
        (fp8_new[0] if fp8_new else None)


def _step_tail(c, params, opt_state, dstate, lr_in, scale, loss_sum, grads,
               unscaled=False, constrain=None, vote=None, norm_reduce=None,
               clip_norm_reduce=None, loss_reduce=None,
               param_shardings=None, opt_shardings=None, loss_scalars=None,
               extra_metrics=None, carried=None):
    """Everything after a step kind has brought its gradients together:
    :func:`grad_epilogue` -> lr and beta1 of the step -> the optimizer's
    update -> overflow skips it (reference stage2.py:1341-1362) ->
    :func:`loss_scale_epilogue` -> :func:`step_metrics`. Returns
    ``(params, opt_state, dstate, metrics)``.

    What a kind asks of it: ``unscaled`` when its sync has already
    divided by scale x accum (the int8 exchange quantizes finite,
    scale-free values); ``constrain`` and the two shardings where GSPMD
    partitions the update, ``param_shardings`` over the parameters and
    ``opt_shardings`` over the optimizer state's ``m`` and ``v`` (its
    fields shaped like the parameters); ``vote``, ``norm_reduce`` and
    ``clip_norm_reduce`` as :func:`grad_epilogue` takes them and
    ``loss_reduce`` as :func:`step_metrics` does, where a manual region
    still holds local gradients; ``extra_metrics`` of its own.

    ``opt_state=None`` leaves the update out (ZeRO-Offload's is the
    host's): the clipped gradients come back in the parameters' place
    and ``beta1`` rides with the metrics. ``carried``, an ``(old, new)``
    pair of one more state the step threads, is selected like the
    optimizer's and returned fifth: an overflowed step keeps the OLD
    fp8 amax histories, or an inf/nan cotangent amax would poison the
    delayed scales for the next amax_history_len steps."""
    with jax.named_scope("ds_grad_epilogue"):
        grads, overflow, nonfinite, grad_norm, applied_norm = grad_epilogue(
            grads, jnp.asarray(1.0, jnp.float32) if unscaled else scale,
            1 if unscaled else c.accum, c.fp16, c.clip, constrain=constrain,
            vote=vote, norm_reduce=norm_reduce,
            clip_norm_reduce=clip_norm_reduce, detect_nonfinite=c.detect,
            nan_skip=c.nan_skip)

        lr = c.lr_fn(dstate.global_step) if c.lr_fn is not None else lr_in
        beta1 = c.mom_fn(dstate.global_step)

    def select(old, new, shardings=None):
        kept = jax.tree_util.tree_map(
            lambda o, n: jnp.where(overflow, o, n), old, new)
        return kept if shardings is None else \
            constrain_tree(kept, shardings)

    if opt_state is None:
        params_out, opt_out = grads, None
        extra_metrics = dict(extra_metrics or {}, beta1=beta1)
    else:
        with jax.named_scope("ds_opt_update"):
            new_params, new_opt = c.opt_update(params, grads, opt_state,
                                               lr, beta1)
            params_out = select(params, new_params, param_shardings)
            opt_out = type(opt_state)(**{
                name: select(getattr(opt_state, name),
                             getattr(new_opt, name),
                             opt_shardings if name in ("m", "v") else None)
                for name in opt_state._fields})

    with jax.named_scope("ds_grad_epilogue"):
        dstate_out = loss_scale_epilogue(dstate, overflow, c.fp16,
                                         c.dynamic, c.scale_args)
        metrics = step_metrics(loss_sum, c.accum, grad_norm, applied_norm,
                               lr, scale, overflow, loss_reduce=loss_reduce,
                               dstate=dstate_out, nonfinite=nonfinite,
                               loss_scalars=loss_scalars)
        metrics.update(extra_metrics or {})
        if carried is not None:
            return params_out, opt_out, dstate_out, metrics, \
                select(*carried)
    return params_out, opt_out, dstate_out, metrics


def place_kernels_on_mesh(loss_fn, mesh):
    """``loss_fn``, traced with its Pallas attention placed on ``mesh``.

    GSPMD cannot partition a Mosaic kernel, so every program the engine
    jits over more than one device — the train steps, ``eval_batch``,
    ``forward``/``backward`` — has to tell the kernel how its operands
    lie: the engine shards batch rows over ``data`` and tensor
    parallelism shards heads over ``model``
    (`ops/pallas/flash_attention.py:placed_on_mesh`). The steps that run
    the loss inside their own ``shard_map`` are untouched: there the
    axes are already manual and the kernel runs bare."""
    if mesh.size == 1:
        return loss_fn
    from deepspeed_tpu.ops.pallas.flash_attention import placed_on_mesh

    @functools.wraps(loss_fn)       # keeps direct_value_and_grad[_local]
    def placed(*args, **kwargs):
        with placed_on_mesh(mesh, rows="data", heads="model"):
            return loss_fn(*args, **kwargs)
    return placed


class DeepSpeedEngine:
    """Training engine around a pure ``loss_fn(params, batch, rng)``."""

    def __init__(self,
                 args=None,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 dist_init_required=None,
                 collate_fn=None,
                 config=None,
                 config_params=None,
                 loss_fn: Optional[Callable] = None,
                 params=None,
                 param_specs=None,
                 mesh=None,
                 seed: int = 0):
        # --- resolve the model contract ---------------------------------
        if loss_fn is None and model is not None and hasattr(model, "loss_fn"):
            loss_fn = model.loss_fn
        if params is None and model_parameters is not None:
            params = model_parameters
        if params is None and model is not None and hasattr(model, "params"):
            params = model.params
        assert loss_fn is not None, (
            "deepspeed_tpu needs a pure loss_fn(params, batch, rng) — pass "
            "loss_fn= directly or a model object exposing .loss_fn")
        assert params is not None, "initial params pytree required"
        self.module = model

        # --- config ------------------------------------------------------
        if config is None and config_params is not None:
            config = config_params
        if config is None and args is not None and \
                getattr(args, "deepspeed_config", None):
            config = args.deepspeed_config
        assert config is not None, "config (dict or json path) required"

        # Multi-host rendezvous first (no-op single-process): scripts
        # spawned by the launcher carry DS_TPU_* env and must join the
        # jax.distributed cluster before any device/mesh query (the
        # reference's dist.init_process_group at engine.py:135).
        from deepspeed_tpu.parallel.mesh import initialize_distributed
        try:
            initialize_distributed()
        except RuntimeError as e:
            if "before" in str(e) and "JAX" in str(e):
                raise RuntimeError(
                    "multi-process rendezvous env (DS_TPU_*) is set but "
                    "the XLA backend was already initialized — call "
                    "deepspeed_tpu.parallel.initialize_distributed() at "
                    "the top of your script, before creating any jax "
                    "array") from e
            raise
        self.mesh = mesh if mesh is not None else build_mesh(
            (config.get("mesh") if isinstance(config, dict) else None))
        # `_dense_step` hands out the scalars a loss function may
        # return beside its loss; every other program takes the loss alone
        self._loss_with_scalars = place_kernels_on_mesh(loss_fn, self.mesh)
        self.loss_fn = loss_alone(self._loss_with_scalars)
        self.dp_world_size = self.mesh.shape["data"]
        self.mp_world_size = self.mesh.shape["model"]
        self._config = DeepSpeedConfig(config, world_size=self.dp_world_size)
        if self._config.compilation_cache_dir:
            # before ANY engine jit (opt-state init compiles below);
            # JAX_COMPILATION_CACHE_DIR, where set, wins over the config
            from deepspeed_tpu.telemetry import compile_cache
            compile_cache.configure(self._config.compilation_cache_dir)

        # --- precision policy -------------------------------------------
        if self._config.fp16_enabled:
            self.compute_dtype = jnp.float16
        elif self._config.bf16_enabled or self._config.amp_enabled:
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32
        self.dynamic_loss_scale = (self._config.fp16_enabled and
                                   self._config.loss_scale == 0)
        if self._config.fp16_enabled and self._config.loss_scale > 0:
            self.static_loss_scale = float(self._config.loss_scale)
        else:
            self.static_loss_scale = 1.0

        # --- counters ----------------------------------------------------
        self.micro_steps = 0
        self.global_steps = 0

        # --- optimizer / schedule ----------------------------------------
        self._configure_optimizer(optimizer)
        self._configure_lr_scheduler(lr_scheduler)

        # --- shardings & placement ---------------------------------------
        base_specs = param_specs if param_specs is not None else \
            jax.tree_util.tree_map(lambda _: PartitionSpec(), params)
        self._offload = bool(self._config.zero_enabled and
                             self._config.zero_config.cpu_offload)
        self._shardings = build_zero_shardings(
            params, base_specs, self.mesh, self.zero_optimization_stage(),
            sharded_masters=self._sharded_masters())
        if self._config.zero_config.offload_16bit_grads and \
                not self._offload:
            log_dist("offload_16bit_grads: true has no effect without "
                     "cpu_offload: true (grads only cross the wire on the "
                     "offload path)", ranks=[0])
        if self._config.zero_config.offload_16bit_grads and \
                self._offload and self._config.fp16_enabled:
            # ADVICE r4: the 16-bit wire is bf16-gated (fp16 would flush
            # unscaled sub-6e-5 grad components) — say so instead of
            # silently transferring fp32.
            log_dist("offload_16bit_grads: true is inert under fp16 "
                     "compute (grads are unscaled on device before "
                     "transfer; fp16 would flush sub-6e-5 components). "
                     "Grads transfer at fp32 — use bf16 to get the "
                     "16-bit wire", ranks=[0])
        if self._offload:
            # ZeRO-Offload (reference stage2.py cpu_offload + csrc cpu_adam):
            # fp32 masters + moments live in host RAM inside the C++
            # DeepSpeedCPUAdam; the device holds compute-dtype params only,
            # and the compiled step produces gradients, not updates.
            assert self.optimizer_name in (ADAM_OPTIMIZER, "adamw"), (
                f"cpu_offload supports adam/adamw, got {self.optimizer_name}")
            # Offload×DP (round 5, reference stage-2 offload semantics:
            # each rank updates only its gradient partition,
            # stage2.py:1410-1423): under multi-process the compiled step
            # emits the gradient as a flat [D, chunk] array sharded over
            # the data axis, each process's host Adam updates its
            # contiguous shard of the flat master buffer, and the updated
            # params reassemble on device via an XLA all-gather riding
            # ICI — no host-side parameter exchange.
            self._offload_dp = jax.process_count() > 1
            if self._offload_dp:
                other = {k: v for k, v in self.mesh.shape.items()
                         if k != "data" and v > 1}
                assert not other, (
                    "multi-process cpu_offload supports pure data-parallel "
                    f"meshes only; non-data axes present: {other}")
            from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam
            opt_params = dict(self._config.optimizer_params or {})
            self.cpu_optimizer = DeepSpeedCPUAdam(
                params,
                lr=opt_params.get("lr", self._base_lr),
                betas=self._betas,
                eps=opt_params.get("eps", 1e-8),
                weight_decay=opt_params.get("weight_decay", 0.0),
                bias_correction=opt_params.get("bias_correction", True),
                adamw_mode=opt_params.get("adam_w_mode",
                                          self.optimizer_name == "adamw"))
            chunk_mb = self._config.zero_config.offload_chunk_mb
            if not chunk_mb or float(chunk_mb) <= 0:
                raise ValueError(
                    f"offload_chunk_mb must be a positive number of MB, "
                    f"got {chunk_mb!r}")
            # Fractional MB allowed; floor at 64 KB so a tiny value can't
            # degenerate into one pool submission per element.
            self._offload_chunk_bytes = max(
                64 << 10, int(float(chunk_mb) * (1 << 20)))
            if self._offload_dp:
                D = self.mesh.shape["data"]
                self._off_D = D
                self._off_chunk = -(-self.cpu_optimizer.total // D)
            self.params = self._upload_offload_params()
            self.opt_state = None
            self.last_host_phase_s = 0.0
        else:
            self.cpu_optimizer = None
            # Copy (never alias) the caller's params: the compiled train
            # step donates the engine's buffers, and donating the caller's
            # arrays would delete them out from under the caller.
            # (both under `initialize`'s ``setup/engine`` span)
            # Where the masters' layout shards them, one program copies
            # and slices on the device: a ``device_put`` that reshards
            # took 3.0 s for GPT-2 XL's tree on four chips, the program
            # 0.4 s warm (my chip run, PR 42), and no whole float32
            # copy stands beside the caller's.
            with Span("params"):
                def copied(tree):
                    return jax.tree_util.tree_map(
                        lambda p: jnp.array(p, dtype=jnp.float32, copy=True),
                        tree)
                layout = self._shardings["param"]
                if all(s.is_fully_replicated
                       for s in jax.tree_util.tree_leaves(layout)):
                    self.params = jax.device_put(copied(params), layout)
                else:
                    self.params = jax.jit(
                        copied, out_shardings=layout)(params)
            with Span("optimizer_state"):
                self.opt_state = jax.jit(
                    self.opt_init_fn,
                    out_shardings=self._opt_state_shardings())(self.params)
        self.device_state = self._init_device_state()

        # --- data --------------------------------------------------------
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(
                training_data, collate_fn=collate_fn)
        from deepspeed_tpu.runtime.dataloader import RepeatingLoader
        self._data_iter = iter(RepeatingLoader(self.training_dataloader)) \
            if self.training_dataloader is not None else None

        # --- aux ---------------------------------------------------------
        self.progressive_layer_drop = None
        if self._config.pld_enabled:
            self.progressive_layer_drop = ProgressiveLayerDrop(
                **(self._config.pld_params or {}))
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self._config.train_micro_batch_size_per_gpu *
            self._config.gradient_accumulation_steps,
            num_workers=self.dp_world_size,
            steps_per_output=self._config.steps_per_print)
        self.trace_profiler = TraceProfiler(
            **(self._config.profiling_params or {}))
        if self.trace_profiler.enabled:
            import atexit
            atexit.register(self.trace_profiler.close)

        # --- telemetry (deepspeed_tpu/telemetry) -------------------------
        # One session per engine: metrics registry + schema-versioned
        # event log + the span API train_batch wraps its host phases in.
        # Also installed as the process default (first engine wins) so
        # engine-external emitters (elastic reshard, bench.py) land in
        # the same log. metrics_history is the bounded step-event ring
        # tests and health guards read without file I/O.
        tl = self._config.telemetry
        self.telemetry = None
        self._cpu_mark = spans.CpuMark()    # train/step's cpu_s, always on
        self.metrics_history = collections.deque(maxlen=tl.history)
        self._batch_tokens = None
        self._anomaly_detector = None
        # Process identity stamped on run_start/step events and flight
        # dumps — the join key `ds_tpu_metrics aggregate` uses to build
        # the cross-host skew table from per-host logs.
        self._proc_meta = {"process_index": jax.process_index(),
                           "process_count": jax.process_count(),
                           "hostname": socket.gethostname()}
        if tl.enabled:
            self.telemetry = TelemetrySession.from_config(
                tl, meta={**self._proc_meta,
                          "flavor": self._telemetry_flavor(),
                          **self._forensics_extra()})
            set_default_session(self.telemetry, replace=False)
            import atexit
            atexit.register(self.telemetry.close)
            if tl.anomaly_trace_enabled:
                self._anomaly_detector = StepAnomalyDetector(
                    factor=tl.anomaly_trace_factor,
                    window=tl.anomaly_trace_window)
            self.telemetry.emit(
                "run_start",
                flavor=self._telemetry_flavor(),
                train_batch_size=self._config.train_batch_size,
                gradient_accumulation_steps=self._config
                .gradient_accumulation_steps,
                zero_stage=self.zero_optimization_stage(),
                dp_world_size=self.dp_world_size,
                mp_world_size=self.mp_world_size,
                n_devices=len(jax.devices()),
                device_kind=jax.devices()[0].device_kind,
                fp16=self.fp16_enabled(),
                bf16=self.bfloat16_enabled(),
                flops_per_token=tl.flops_per_token or None,
                **self._proc_meta,
                **self._forensics_extra())
        self.summary_writer = None
        if self._config.tensorboard_enabled and jax.process_index() == 0:
            self.summary_writer = self._get_summary_writer()

        # Activation checkpointing module config (reference
        # `_configure_checkpointing`, engine.py:412). An explicit user
        # configure() beforehand wins over the engine's JSON-derived one.
        from deepspeed_tpu.runtime.activation_checkpointing import (
            checkpointing as _act_ckpt)
        if not _act_ckpt.is_configured():
            _act_ckpt.configure(mpu_=mpu, deepspeed_config=self._config)

        self._rng = jax.random.PRNGKey(seed)
        self._compiled_train_step = None
        self._compiled_eval_step = None
        self._grad_buffer = None
        self._pending_batch = None
        self._last_metrics = {}
        # Error-feedback residual state for the int8 quantized all-reduce
        # (`runtime/comm/quantized.py`); populated lazily by
        # `_quantized_step` when comm_quantization.error_feedback
        # is on. Ephemeral comm state — intentionally not checkpointed.
        self._qcomm_residuals = None

        # --- resilience (runtime/resilience) -----------------------------
        rz = self._config.resilience
        self._fault_arg = False
        self._ckpt_manager = CheckpointManager(
            save_dir=rz.save_dir,
            keep_last_n=rz.keep_last_n,
            async_save=rz.async_save,
            io_retries=rz.io_retries,
            io_retry_base_s=rz.io_retry_base_s,
            io_timeout_s=rz.io_timeout_s)
        # In-memory hot-checkpoint tier (runtime/resilience/hotckpt.py):
        # the restore ladder's first stop, ahead of any disk checkpoint.
        self._hot_store = None
        if rz.hot_enabled:
            self._hot_store = HotCheckpointStore(
                capacity=rz.hot_capacity,
                mirror_dir=rz.hot_mirror_dir,
                mirror_keep=rz.hot_mirror_keep,
                process_index=jax.process_index())
        self._health_monitor = None
        if rz.guards_enabled:
            self._health_monitor = StepHealthMonitor(
                nan_action=rz.nan_guard_action,
                spike_action=rz.loss_spike_action,
                collapse_action=rz.scale_collapse_action,
                fp16_dynamic=self.fp16_enabled() and self.dynamic_loss_scale,
                spike_window=rz.loss_spike_window,
                spike_factor=rz.loss_spike_factor,
                spike_min_history=rz.loss_spike_min_history,
                collapse_patience=rz.scale_collapse_patience,
                min_scale=self._scale_args()["min_scale"])
        self._preemption = None
        if rz.save_on_sigterm:
            self._preemption = PreemptionHandler()
            self._preemption.install()
        # Forensics (telemetry/flight.py, telemetry/watchdog.py): crash
        # hooks go in AFTER the preemption handler so a SIGTERM dumps
        # the flight record first, then chains into the checkpoint-at-
        # next-boundary latch. The watchdog daemon starts here too.
        if self.telemetry is not None:
            if self.telemetry.flight is not None:
                self.telemetry.flight.install()
            if self.telemetry.watchdog is not None:
                self.telemetry.watchdog.start()
        if self.cpu_optimizer is not None:
            self.cpu_optimizer.host_adam_retries = rz.host_adam_retries
            self.cpu_optimizer.host_adam_timeout_s = rz.io_timeout_s

        # --- compiled-program analysis (deepspeed_tpu/analysis) ----------
        an = self._config.analysis
        self.last_audit_report = None
        self._recompile_reported = 1
        if an.enabled:
            log_dist("analysis: compile-time audit enabled "
                     f"(rules={list(an.rules) if an.rules else 'all'}, "
                     f"fail_on_findings={an.fail_on_findings}, "
                     f"check_recompile={an.check_recompile})", ranks=[0])

        if self._config.dump_state:
            self._config.print("DeepSpeedEngine configuration")

        if rz.auto_resume:
            resumed = self._auto_resume()
            if resumed:
                log_dist(f"resilience: auto-resumed from {resumed} at "
                         f"step {self.global_steps}", ranks=[0])
            else:
                log_dist("resilience: auto_resume found no valid "
                         "checkpoint; starting fresh", ranks=[0])

    # ------------------------------------------------------------------
    # configuration accessors (reference engine.py:241-396)
    # ------------------------------------------------------------------
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def zero_optimization_stage(self):
        return self._config.zero_optimization_stage

    def zero_optimization(self):
        return self._config.zero_enabled

    def fp16_enabled(self):
        return self._config.fp16_enabled

    def bfloat16_enabled(self):
        return self._config.bf16_enabled

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def steps_per_print(self):
        return self._config.steps_per_print

    def wall_clock_breakdown(self):
        return self._config.wall_clock_breakdown

    def memory_breakdown(self):
        return self._config.memory_breakdown

    def sparse_gradients_enabled(self):
        return self._config.sparse_gradients_enabled

    def progressive_layer_drop_enabled(self):
        return self._config.pld_enabled

    def dump_state(self):
        return self._config.dump_state

    @property
    def config(self):
        return self._config

    @property
    def loss_scale(self):
        if self.dynamic_loss_scale:
            return float(self.device_state.loss_scale.cur_scale)
        return self.static_loss_scale

    @property
    def skipped_steps(self):
        return int(self.device_state.skipped_steps)

    @property
    def step_metrics(self):
        """The last ``train_batch``'s metrics as the compiled step
        returned them (``loss``, ``grad_norm``, ``lr``, ...; under
        ``"loss_scalars"`` what the loss function handed out beside its
        loss). They stay on the device: reading one waits for the step,
        and a step nobody reads costs no transfer."""
        return self._last_metrics

    # ------------------------------------------------------------------
    # setup helpers
    # ------------------------------------------------------------------
    def _configure_optimizer(self, client_optimizer):
        """Resolve (init_fn, update_fn) — the analog of
        `_configure_basic_optimizer` (engine.py:577)."""
        if client_optimizer is not None and not isinstance(client_optimizer, str):
            # Client passed one of our optimizer wrapper objects.
            self.client_optimizer = client_optimizer
            self.optimizer_name = type(client_optimizer).__name__.lower()
            if self.optimizer_name == ONEBIT_ADAM_OPTIMIZER:
                # The wrapper's init needs the data-parallel world size for
                # the error-feedback buffers, and the optimizer needs the
                # shard_map train step (fp16-path scope, not ZeRO).
                assert self.zero_optimization_stage() == 0, (
                    "OneBitAdam is not compatible with ZeRO "
                    "(reference scope: fp16 optimizer path only)")
                world = self.dp_world_size
                if getattr(self.loss_fn, "direct_value_and_grad_local",
                           None) is not None:
                    # pipeline composition needs [stages[, model], world,
                    # padded] error buffers (per-(stage[, model-rank])
                    # collective groups); route through the pipeline-aware
                    # init, not the wrapper's DP-shaped one.
                    from deepspeed_tpu.runtime.fp16.onebit_adam import (
                        init_pipeline_onebit_state)
                    stages = self.mesh.shape["pipe"]
                    msize = self.mesh.shape.get("model", 1)
                    self.opt_init_fn = lambda p: init_pipeline_onebit_state(
                        p, world, stages, msize)
                else:
                    self.opt_init_fn = lambda p: client_optimizer.init(
                        p, world=world)
            else:
                self.opt_init_fn = client_optimizer.init
            self._opt_update = lambda p, g, s, lr, beta1: \
                client_optimizer.update(p, g, s, lr=lr, beta1=beta1)
            self._base_lr = getattr(client_optimizer, "lr", 1e-3)
            self._betas = getattr(client_optimizer, "betas", (0.9, 0.999))
            return
        self.client_optimizer = None

        name = (self._config.optimizer_name or ADAM_OPTIMIZER).lower()
        opt_params = dict(self._config.optimizer_params or {})
        lr = opt_params.pop("lr", 1e-3)
        betas = tuple(opt_params.pop("betas", (0.9, 0.999)))
        eps = opt_params.pop("eps", 1e-8)
        weight_decay = opt_params.pop("weight_decay", 0.0)
        bias_correction = opt_params.pop("bias_correction", True)
        self._base_lr = lr
        self.optimizer_name = name

        if name == ONEBIT_ADAM_OPTIMIZER:
            # 1-bit Adam runs the fp16-optimizer path, not ZeRO (same scope
            # as the reference, whose OnebitAdam goes through FP16_Optimizer)
            # and needs local per-shard grads, so the train step switches to
            # shard_map over the data axis.
            assert self.zero_optimization_stage() == 0, (
                "OneBitAdam is not compatible with ZeRO "
                "(reference scope: fp16 optimizer path only)")
            from deepspeed_tpu.runtime.fp16.onebit_adam import (
                init_onebit_state, init_pipeline_onebit_state,
                onebit_adam_update)
            freeze_step = opt_params.pop("freeze_step", 100000)
            world = self.dp_world_size
            if getattr(self.loss_fn, "direct_value_and_grad_local",
                       None) is not None:
                # pipeline x 1-bit composition: error buffers per
                # (stage[, model-rank], data-rank) over the device-local
                # flat size
                stages = self.mesh.shape["pipe"]
                msize = self.mesh.shape.get("model", 1)
                self.opt_init_fn = lambda p: init_pipeline_onebit_state(
                    p, world, stages, msize)
            else:
                self.opt_init_fn = lambda p: init_onebit_state(p, world)
            self._opt_update = lambda p, g, s, lr_, beta1: onebit_adam_update(
                p, g, s, lr=lr_, beta1=beta1, beta2=betas[1], eps=eps,
                weight_decay=weight_decay, freeze_step=freeze_step,
                axis_name="data")
        elif name in (ADAM_OPTIMIZER, "adamw"):
            adam_w_mode = opt_params.pop("adam_w_mode", name == "adamw")
            self.opt_init_fn = init_adam_state
            # "pallas": true routes the leaf update through the explicit
            # one-pass Pallas kernel (multi_tensor_adam.cu analog,
            # ops/pallas/fused_adam.py) — TPU only, and only with
            # unsharded optimizer state: pallas_call has no GSPMD
            # partitioning rule, so under ZeRO it would force per-step
            # all-gathers of exactly the state ZeRO shards.
            want_pallas = bool(opt_params.pop("pallas", False))
            use_pallas = want_pallas and \
                jax.devices()[0].platform == "tpu" and \
                self.zero_optimization_stage() == 0
            if want_pallas and not use_pallas:
                log_dist("optimizer 'pallas': true ignored (needs TPU and "
                         "ZeRO stage 0); using the XLA fused update",
                         ranks=[0])
            if use_pallas:
                from deepspeed_tpu.ops.pallas import (
                    pallas_adam_update)
                self._opt_update = \
                    lambda p, g, s, lr_, beta1: pallas_adam_update(
                        p, g, s, lr=lr_, beta1=beta1, beta2=betas[1],
                        eps=eps, weight_decay=weight_decay,
                        adam_w_mode=adam_w_mode,
                        bias_correction=bias_correction)
            else:
                self._opt_update = lambda p, g, s, lr_, beta1: adam_update(
                    p, g, s, lr=lr_, beta1=beta1, beta2=betas[1], eps=eps,
                    weight_decay=weight_decay, adam_w_mode=adam_w_mode,
                    bias_correction=bias_correction)
        elif name == LAMB_OPTIMIZER:
            max_coeff = opt_params.pop("max_coeff", 10.0)
            min_coeff = opt_params.pop("min_coeff", 0.01)
            self.opt_init_fn = init_lamb_state
            self._opt_update = lambda p, g, s, lr_, beta1: lamb_update(
                p, g, s, lr=lr_, beta1=beta1, beta2=betas[1], eps=eps,
                weight_decay=weight_decay, bias_correction=bias_correction,
                max_coeff=max_coeff, min_coeff=min_coeff)
        else:
            raise ValueError(f"unknown optimizer {name!r}; supported: adam, "
                             f"adamw, lamb, onebitadam")
        self._betas = betas

    def _configure_lr_scheduler(self, client_scheduler):
        """Schedule resolution (reference engine.py:398-444)."""
        self.lr_scheduler = None
        if client_scheduler is not None:
            self.lr_scheduler = client_scheduler
        elif self._config.scheduler_name is not None:
            self.lr_scheduler = get_lr_scheduler(self._config.scheduler_name,
                                                 self._config.scheduler_params or {})
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "lr_at"):
            # Our schedules fold into the compiled step (device-resident).
            self._lr_fn = self.lr_scheduler.lr_at
            self._lr_foldable = True
        elif self.lr_scheduler is not None and \
                hasattr(self.lr_scheduler, "get_lr"):
            # Foreign scheduler: read its lr host-side every step and feed it
            # into the compiled step as a scalar argument.
            self._lr_fn = None
            self._lr_foldable = False
            logger.info("client lr scheduler without lr_at(): lr will be "
                        "read host-side each step")
        else:
            base = self._base_lr
            self._lr_fn = lambda step: jnp.asarray(base, jnp.float32)
            self._lr_foldable = True
        if isinstance(self.lr_scheduler, OneCycle) and \
                self.lr_scheduler.cycle_momentum:
            self._mom_fn = self.lr_scheduler.mom_at
        else:
            beta1 = getattr(self, "_betas", (0.9, 0.999))[0]
            self._mom_fn = lambda step: jnp.asarray(beta1, jnp.float32)
        # Elastic batch re-factor may land on an inexact global batch; the
        # configured lr_scaling rule compensates by scaling the whole
        # schedule (exact factorizations leave scale == 1.0).
        self._elastic_lr_scale = float(
            getattr(self._config, "elastic_lr_scale", 1.0) or 1.0)
        if self._elastic_lr_scale != 1.0 and self._lr_foldable:
            inner, scale = self._lr_fn, self._elastic_lr_scale
            self._lr_fn = lambda step: inner(step) * jnp.float32(scale)

    def _opt_state_shardings(self):
        """Shardings for the optimizer-state pytree: the m/v moment trees
        follow the (possibly ZeRO-sharded) opt layout; the step counter
        replicates. AdamState and LambState share the (m, v, step) shape;
        OnebitAdamState adds data-sharded error-feedback residuals."""
        opt = self._shardings["opt"]
        rep = NamedSharding(self.mesh, PartitionSpec())
        sample = jax.eval_shape(self.opt_init_fn, self.params)
        from deepspeed_tpu.runtime.fp16.onebit_adam import OnebitAdamState
        if isinstance(sample, OnebitAdamState):
            if sample.worker_error.ndim == 4:
                # pipeline x model x 1-bit (three-way buffer split):
                # [stages, model, data_world, padded_local]. Latent until
                # data > stages: the 2-D default spec below sharded dim 0
                # over "data", which only divided by accident at data=2.
                err = NamedSharding(
                    self.mesh, PartitionSpec("pipe", "model", "data", None))
                return OnebitAdamState(m=opt, v=opt, step=rep,
                                       worker_error=err, server_error=err)
            if sample.worker_error.ndim == 3:
                # pipeline x 1-bit: [stages, data_world, padded_local]
                err = NamedSharding(self.mesh,
                                    PartitionSpec("pipe", "data", None))
                return OnebitAdamState(m=opt, v=opt, step=rep,
                                       worker_error=err, server_error=err)
            return OnebitAdamState(
                m=opt, v=opt, step=rep,
                worker_error=NamedSharding(
                    self.mesh, PartitionSpec("data", None)),
                server_error=NamedSharding(self.mesh, PartitionSpec("data")))
        return type(sample)(m=opt, v=opt, step=rep)

    def _current_host_lr(self):
        """Host-side lr for schedulers the compiled step can't fold."""
        if self._lr_foldable:
            return 0.0  # unused: lr comes from the folded schedule
        lrs = self.lr_scheduler.get_lr()
        lr = float(lrs[0] if isinstance(lrs, (list, tuple)) else lrs)
        return lr * self._elastic_lr_scale

    def _init_device_state(self):
        rep = NamedSharding(self.mesh, PartitionSpec())
        init_scale = float(self._config.initial_dynamic_scale) \
            if self.dynamic_loss_scale else self.static_loss_scale
        delayed_shift = 1
        if self._config.dynamic_loss_scale_args:
            delayed_shift = self._config.dynamic_loss_scale_args.get(
                "delayed_shift", 1)
        state = DeviceState(
            loss_scale=init_loss_scale_state(init_scale, delayed_shift),
            global_step=jnp.asarray(0, jnp.int32),
            skipped_steps=jnp.asarray(0, jnp.int32),
            consecutive_skipped=jnp.asarray(0, jnp.int32))
        return jax.device_put(state, rep)

    def _get_summary_writer(self):
        try:
            from torch.utils.tensorboard import SummaryWriter
        except Exception:
            logger.warning("tensorboard unavailable; disabling")
            return None
        base = os.environ.get("DLWS_JOB_ID", "local")
        log_dir = os.path.join(self._config.tensorboard_output_path or
                               os.path.join(".", "runs"), base,
                               self._config.tensorboard_job_name)
        os.makedirs(log_dir, exist_ok=True)
        return SummaryWriter(log_dir=log_dir)

    def deepspeed_io(self, dataset, batch_size=None, route=None,
                     collate_fn=None, num_local_io_workers=None,
                     data_sampler=None):
        """Build the DP-sharded loader (reference engine.py:706). The loader
        yields *global* batches of ``train_batch_size`` rows; the engine
        shards them over the data axis when feeding the compiled step."""
        if batch_size is None:
            batch_size = self._config.train_batch_size
        return DeepSpeedDataLoader(dataset,
                                   batch_size=batch_size,
                                   collate_fn=collate_fn,
                                   drop_last=True)

    # ------------------------------------------------------------------
    # the compiled train step
    # ------------------------------------------------------------------
    def _scale_args(self):
        args = dict(scale_factor=2.0, scale_window=1000, min_scale=1.0,
                    delayed_shift=1, consecutive_hysteresis=False)
        if self._config.dynamic_loss_scale_args:
            a = self._config.dynamic_loss_scale_args
            args.update(scale_window=a.get("scale_window", 1000),
                        min_scale=a.get("min_scale", 1.0),
                        delayed_shift=a.get("delayed_shift", 1))
        return args

    def _engine_accum_steps(self):
        """Microbatch count the compiled step scans over. PipelineEngine
        overrides to 1: its microbatching happens inside the pipeline."""
        return self._config.gradient_accumulation_steps

    def _pld_theta_fn(self):
        """Progressive-layer-drop theta(t) as a pure function of the device
        step counter, folded into the compiled step. The reference advances
        theta host-side and injects it into model kwargs every forward
        (engine.py:791-792, progressive_layer_drop.py:5); here the same
        schedule evaluates inside jit so no per-step recompile happens."""
        if not self._config.pld_enabled:
            return None
        p = self._config.pld_params or {}
        theta_bar = float(p.get("theta", 0.5))
        gamma = float(p.get("gamma", 0.001))

        def theta_fn(step):
            return (1.0 - theta_bar) * jnp.exp(
                -gamma * step.astype(jnp.float32)) + theta_bar

        return theta_fn

    def _step_kind(self):
        """Which builder makes this engine's train step. Only ``dense``
        differentiates the loss under GSPMD through ``cast_params``;
        every other kind runs the loss inside its own ``shard_map`` with
        the parameters' specs as ``in_specs`` (``pipeline``: the 1F1B
        program's own casts), or keeps 16-bit parameters (``offload``)."""
        if self._offload:
            return "offload"
        if self.optimizer_name == ONEBIT_ADAM_OPTIMIZER:
            return "onebit"
        if self.sparse_gradients_enabled():
            return "sparse"
        if self._config.comm_quantization.enabled:
            return "quantized"
        if getattr(self.loss_fn, "direct_value_and_grad", None) is not None:
            return "pipeline"
        return "dense"

    def _sharded_masters(self):
        """Whether the float32 masters lie sharded over ``data`` between
        steps at ZeRO stages 1 and 2, as ``m`` and ``v`` do, and every
        program begins with the gather of their 16-bit copy
        (:meth:`_param_caster`). Under float32 compute the copy is the
        master, the gather would carry the same bytes at either end of
        the step, and the replicated layout stays; so it does for the
        step kinds that hand the parameters to a manual region."""
        return (self.zero_optimization_stage() >= 1
                and self.compute_dtype != jnp.float32
                and self.dp_world_size > 1
                and self._step_kind() == "dense")

    def _param_caster(self):
        """``cast(params) -> compute-dtype params`` of the programs that
        read the masters under GSPMD (the dense train step, ``eval_batch``,
        ``backward``): cast-then-gather where they lie sharded over
        ``data`` (`zero/sharding.py:make_param_caster`), else None for
        the plain cast. ``param_gather`` of the ``compile`` event is its
        static split."""
        if not hasattr(self, "_param_caster_cache"):
            caster = None
            if self._step_kind() == "dense" and \
                    self.compute_dtype != jnp.float32:
                caster = make_param_caster(
                    self.params, self._shardings["param"], self.mesh,
                    self.compute_dtype)
            self._param_caster_cache = caster
        return self._param_caster_cache

    def _cast_for_loss(self):
        """:meth:`_param_caster`, or the plain cast where it is None:
        what ``eval_batch`` and ``backward`` hand their loss."""
        compute_dtype = self.compute_dtype
        return self._param_caster() or (lambda p: jax.tree_util.tree_map(
            lambda x: x.astype(compute_dtype), p))

    def _step_inputs(self):
        rz = self._config.resilience
        return _StepInputs(
            accum=self._engine_accum_steps(),
            compute_dtype=self.compute_dtype,
            fp16=self._config.fp16_enabled,
            clip=float(self._config.gradient_clipping or 0.0),
            lr_fn=self._lr_fn, mom_fn=self._mom_fn,
            opt_update=self._opt_update, scale_args=self._scale_args(),
            dynamic=self.dynamic_loss_scale,
            static_scale=self.static_loss_scale,
            pld_fn=self._pld_theta_fn(),
            detect=rz.nan_guard_action is not None,
            nan_skip=rz.nan_guard_action == ACTION_SKIP_STEP,
            fault_on=bool(rz.fault_injection))

    def _make_train_step(self):
        """The compiled train step of this engine's kind
        (:meth:`_step_kind`). Every kind is :func:`_step_head`, its own
        way of bringing the gradients together, :func:`_step_tail`, and
        a wrap (`donated_jit`, under :meth:`_over_data` where the kind
        runs in a manual region); the builders below hold what is a
        kind's own. The second entry says whether the step takes the
        fault harness's ``grad_fault`` argument."""
        kind = self._step_kind()
        if kind == "onebit" and getattr(
                self.loss_fn, "direct_value_and_grad_local",
                None) is not None:
            kind = "pipeline x onebit"
        build, takes_fault = {
            "dense": (self._dense_step, True),
            "pipeline": (self._dense_step, True),
            "offload": (self._offload_step, True),
            "quantized": (self._quantized_step, False),
            "sparse": (self._sparse_step, False),
            "onebit": (self._onebit_step, False),
            "pipeline x onebit": (self._pipeline_onebit_step, False),
        }[kind]
        c = self._step_inputs()
        self._fault_arg = c.fault_on and takes_fault
        if c.fault_on and not takes_fault:
            log_dist(f"fault_injection: the {kind} step does not take the "
                     "grad_fault argument; NaN-grad injection is inert on "
                     "this path", ranks=[0])
        return build(c)

    def _gspmd_layouts(self):
        """What :func:`_step_tail` takes where GSPMD partitions the
        update: the ZeRO-2 gradient layout and the output shardings."""
        grad_shardings = self._shardings["grad"] if \
            self.zero_optimization_stage() >= 2 else None
        return dict(
            constrain=(lambda g: constrain_tree(g, grad_shardings))
            if grad_shardings is not None else None,
            param_shardings=self._shardings["param"],
            opt_shardings=self._shardings["opt"])

    def _over_data(self, what, fn, in_specs, out_specs):
        """``fn`` as a manual region over the ``data`` axis: each shard
        sees its own rows of the batch (``_BATCH_ROWS``) and its local
        gradients. The specs are pytree prefixes, so one ``P()`` stands
        for a whole replicated tree, the metrics' dict included."""
        for ax, size in self.mesh.shape.items():
            assert ax == "data" or size == 1, (
                f"{what} supports pure data parallelism; mesh axis "
                f"{ax!r} has size {size}")
        return shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _persisting(self, inner, attr, ensure=None):
        """Host-side wrapper of a step that threads one more state than
        the engine's three, as its seventh argument (donated) and last
        output: the state lives on the engine as ``attr`` between calls
        (``ensure(batch, rng)`` allocates it on the first). ``.inner``
        is the jitted step, for `analysis/audit.py` to lower."""
        engine = self

        def compiled(params, opt_state, dstate, batch, rng, lr_in, *fault):
            state = ensure(batch, rng) if ensure is not None \
                else getattr(engine, attr)
            *out, state = inner(params, opt_state, dstate, batch, rng,
                                lr_in, state, *fault)
            setattr(engine, attr, state)
            return tuple(out)

        compiled.inner = inner
        return compiled

    def _dense_step(self, c):
        """``dense`` and ``pipeline``: GSPMD brings the gradients
        together (the mean loss over the data-sharded batch), so between
        head and tail there is nothing."""
        loss_fn = self._loss_with_scalars
        layouts = self._gspmd_layouts()
        # Sharded masters reach the loss through a cast-then-gather: the
        # wire carries the compute dtype, exactly (the reference gathers
        # the updated fp16 shards, never the fp32 masters: stage1.py:692).
        # Stages 1 and 2 (and `gather_on_use: false` stage 3, the bench
        # A/B baseline) gather the whole tree once, at the head of the
        # step, and keep the 16-bit copy for the backward
        # (`zero/sharding.py:make_param_caster`; placement is XLA's).
        # Stage 3's default is the explicit gather-on-use schedule
        # (`zero/stage3.py`): dep-chained per-leaf rings + a remat policy
        # that re-gathers in the backward instead of saving the gathered
        # copies.
        fp8_cfg = self._config.fp8
        fp8_plan = fp8_cfg.plan()
        if fp8_plan is not None and \
                getattr(loss_fn, "direct_value_and_grad", None) is not None:
            # The executed pipeline threads fp8 itself (current scaling,
            # pipe/pipeline.py) — the stateful delayed-scaling path only
            # applies to AD-differentiable loss_fns.
            fp8_plan = None
        caster = None
        remat_policy = None
        self._zero3_plan = None
        self._param_gather_plan = None
        zc = self._config.zero_config
        if c.compute_dtype != jnp.float32:
            if self.zero_optimization_stage() < 3 or not zc.gather_on_use:
                caster = self._param_caster()
                if self._sharded_masters():
                    # None: no leaf has a dimension the axis divides
                    self._param_gather_plan = getattr(
                        caster, "plan", {"gather_leaves": 0,
                                         "gather_bytes": 0})
            else:
                caster, plan = make_gather_on_use_caster(
                    self.params, layouts["param_shardings"], self.mesh,
                    c.compute_dtype,
                    chunks=int(zc.gather_chunks or 1),
                    prefetch=bool(zc.prefetch),
                    bidirectional=bool(zc.bidirectional),
                    wire_dtype=fp8_cfg.active_wire_dtype(),
                    wire_chunk=int(fp8_cfg.wire_chunk_size))
                if caster is not None:
                    self._zero3_plan = plan
                    remat_policy = zero3_remat_policy()
        accumulate = make_grad_accumulator(loss_fn, c.compute_dtype, c.accum,
                                           constrain=layouts["constrain"],
                                           cast_params=caster,
                                           remat_policy=remat_policy,
                                           fp8_plan=fp8_plan)

        def train_step(params, opt_state, dstate, batch, rng, lr_in,
                       fp8_state=None, grad_fault=None):
            scale, loss_sum, grads, loss_scalars, f8_new = _step_head(
                c, accumulate, params, dstate, batch, rng, fp8_state,
                grad_fault)
            # The reference's prescale_gradients /
            # gradient_predivide_factor knobs (allreduce_bucket pre/post
            # scaling, engine.py:1082) exist to keep fp16 reductions in
            # range; here the cross-replica mean is computed by XLA in
            # fp32, so they are accepted for config compatibility but are
            # intentionally no-ops.
            return _step_tail(
                c, params, opt_state, dstate, lr_in, scale, loss_sum, grads,
                loss_scalars=loss_scalars,
                carried=None if fp8_state is None else (fp8_state, f8_new),
                **layouts)

        # Inputs arrive pre-placed (device_put with committed shardings);
        # outputs are pinned by the tail's constraints, so plain jit with
        # donation suffices.
        if fp8_plan is None:
            def train_step_plain(params, opt_state, dstate, batch, rng,
                                 lr_in, grad_fault=None):
                return train_step(params, opt_state, dstate, batch, rng,
                                  lr_in, None, grad_fault)
            return donated_jit(train_step_plain, (0, 1, 2))

        # fp8: the amax-history state rides the step as the 1-bit
        # error-feedback residuals do. Discovery (allocating the
        # per-site bundles) is lazy on the first batch.
        self._fp8_state = getattr(self, "_fp8_state", None)
        compiled = self._persisting(donated_jit(train_step, (0, 1, 2, 6)),
                                    "_fp8_state", self._ensure_fp8_state)
        compiled.fp8 = True
        return compiled

    def _ensure_fp8_state(self, batch, rng):
        """Allocate the per-site fp8 amax-history bundles on first use.

        ``jax.eval_shape`` traces the loss once under a discovery-mode
        :func:`fp8_scope` — each :func:`fp8_dot_general` call records its
        ``"<site>:<idx>"`` key (per-site trace-order index) instead of
        consuming state — then one zero bundle is keyed per recorded
        site. Zero histories bootstrap to scale 1, so the first step is
        plain qdq at unit scale and the delayed scales warm up over the
        next ``amax_history_len`` steps."""
        if self._fp8_state is not None:
            return self._fp8_state
        plan = self._config.fp8.plan()
        compute_dtype = self.compute_dtype
        loss_fn = self.loss_fn
        kw = {}
        if self._config.pld_enabled:
            kw["pld_theta"] = jnp.asarray(1.0, jnp.float32)
        keys = []

        def probe(p, b, r):
            with fp8_scope(plan, None, keys):
                return loss_fn(jax.tree_util.tree_map(
                    lambda x: x.astype(compute_dtype), p), b, r, **kw)

        micro = jax.tree_util.tree_map(lambda x: x[0], batch)
        jax.eval_shape(probe, self.params, micro, rng)
        # Committed-replicated placement: the step's state OUTPUTS come
        # back committed, so an uncommitted zero-init would make the
        # second call a recompile (sharding mismatch on the donated arg).
        self._fp8_state = jax.device_put(
            {k: init_state_bundle(plan.amax_history_len) for k in keys},
            jax.sharding.NamedSharding(self.mesh,
                                       jax.sharding.PartitionSpec()))
        log_dist(f"fp8: delayed scaling active over {len(keys)} dot "
                 f"site(s)", ranks=[0])
        return self._fp8_state

    # ------------------------------------------------------------------
    # resilience: preemption + guard actions
    # ------------------------------------------------------------------
    def _check_preemption(self):
        """Step-boundary preemption point (called at the top of
        ``train_batch``). The fault harness delivers a *real* SIGTERM to
        this process so the production signal path is what gets tested;
        the handler only latches a flag, and the save + clean exit happen
        here, where engine state is consistent."""
        rz = self._config.resilience
        if rz.fault_injection and \
                fault_injection.preemption_due(self.global_steps):
            if self._preemption is not None:
                os.kill(os.getpid(), signal.SIGTERM)
            else:
                # No handler installed (save_on_sigterm off): preempt
                # directly rather than let default SIGTERM kill the
                # process mid-test.
                self._preempt_now()
        if self._preemption is not None and self._preemption.preempted:
            self._preempt_now()

    def _preempt_now(self):
        rz = self._config.resilience
        path = None
        if rz.save_dir:
            tag = f"global_step{self.global_steps}"
            self.save_checkpoint(rz.save_dir, tag=tag)
            self._ckpt_manager.wait()   # the exit must not race the write
            path = self._ckpt_manager.ckpt_path(rz.save_dir, tag)
        if self.telemetry is not None:
            self.telemetry.emit("preemption", step=self.global_steps,
                                path=str(path) if path else None)
        raise PreemptedError(
            f"preempted at step {self.global_steps}" +
            (f"; checkpoint saved to {path}" if path
             else "; no resilience.save_dir configured — nothing saved"),
            checkpoint_path=path)

    def _apply_guard_trip(self, trip):
        """Execute one GuardTrip's configured action. ``warn`` and
        ``skip_step`` need no host action (the monitor already logged;
        skip happened inside the compiled step). ``rollback`` reloads the
        newest valid checkpoint, escalating to abort when there is
        nothing to roll back to. An abort dumps the flight record first —
        the aborted run's black box must out-survive the raise."""
        if trip.action == ACTION_ROLLBACK:
            rz = self._config.resilience
            path = None
            # The hot RAM tier serves in-process rollbacks in seconds —
            # no disk read, no replay of the disk save interval. A
            # corrupt/mismatched snapshot falls through to disk.
            if self._hot_store is not None:
                t0 = time.perf_counter()
                try:
                    got = self._hot_store.restore()
                except HotCheckpointCorruptError as e:
                    logger.warning("rollback: hot RAM snapshot rejected: "
                                   "%s", e)
                    got = None
                if got is not None and self._install_hot_restore(
                        got, "hot_ram"):
                    path = "<hot_ram>"
                    self._emit_recovery("hot_ram", "<ram>", t0)
            if path is None:
                path, _ = self.load_checkpoint(rz.save_dir)
            if path is None:
                self._dump_flight(f"guard_abort:{trip.guard}",
                                  extra={"guard_trip": trip.as_event()})
                raise HealthGuardAbort(trip)
            log_dist(f"health guard '{trip.guard}' rolled back to {path} "
                     f"(step {self.global_steps})", ranks=[0])
        elif trip.action == ACTION_ABORT:
            self._dump_flight(f"guard_abort:{trip.guard}",
                              extra={"guard_trip": trip.as_event()})
            raise HealthGuardAbort(trip)

    def _dump_flight(self, reason, extra=None):
        """Dump the flight record if the recorder is configured (no-op
        otherwise); never raises."""
        flight = self.telemetry.flight if self.telemetry is not None \
            else None
        if flight is not None:
            return flight.dump(reason, extra=extra)
        return None

    def _forensics_extra(self):
        """Extra run facts stamped on run_start events and flight-dump
        meta. Subclasses (the pipeline engine) add their topology."""
        return {}

    def _arm_anomaly_trace(self, reason):
        """Anomaly-triggered trace capture: arm the TraceProfiler for the
        next ``capture_steps`` steps (no-op when anomaly_trace is off, a
        window is already active, or no trace dir is resolvable)."""
        if self._anomaly_detector is None or self.telemetry is None:
            return
        tl = self._config.telemetry
        trace_dir = self.trace_profiler.trace_dir
        if trace_dir is None and tl.crash_dump_dir:
            trace_dir = os.path.join(tl.crash_dump_dir, "anomaly_traces")
        if not self.trace_profiler.arm(
                self.global_steps, tl.anomaly_trace_capture_steps,
                trace_dir=trace_dir, reason=reason):
            return
        self.telemetry.emit(
            "anomaly", step=self.global_steps, reason=reason,
            capture_steps=tl.anomaly_trace_capture_steps,
            trace_dir=self.trace_profiler.trace_dir)

    def _quantized_step(self, c):
        """``quantized``: the int8 chunk-scaled gradient all-reduce
        (`runtime/comm/quantized.py`) in place of the fp32 GSPMD mean.

        Hybrid structure: gradient compute + quantized exchange run inside
        ``shard_map`` over the ``data`` axis (each rank sees local grads,
        exactly like the 1-bit path), but the tail runs OUTSIDE, in GSPMD
        — so the ZeRO-1/2 sharded update (and its param-refresh
        all-gather) composes unchanged, and the wire carries int8 grads +
        fp32 param refresh only."""
        from deepspeed_tpu.runtime.comm.quantized import (
            init_residuals, quantized_allreduce_tree)

        cq = self._config.comm_quantization
        assert getattr(self.loss_fn, "direct_value_and_grad", None) is None \
            and getattr(self.loss_fn, "direct_value_and_grad_local",
                        None) is None, (
            "comm_quantization needs jax.grad-able loss_fn (the pipeline's "
            "direct value-and-grad runs its own data-plane reduction)")

        chunk_size = int(cq.chunk_size)
        bucket_bytes = int(cq.bucket_mb) * 1024 * 1024
        ef = bool(cq.error_feedback)
        accumulate = make_grad_accumulator(self.loss_fn, c.compute_dtype,
                                           c.accum)
        layouts = self._gspmd_layouts()

        P = PartitionSpec
        rep = P()
        if ef and self._qcomm_residuals is None:
            res = init_residuals(self.params, self.dp_world_size,
                                 bucket_bytes, chunk_size)
            row = NamedSharding(self.mesh, P("data", None))
            self._qcomm_residuals = jax.device_put(res, jax.tree_util.
                                                   tree_map(lambda _: row,
                                                            res))

        def sync_local(params, dstate, batch, rng, residuals):
            """shard_map body: local grads → unscale → overflow vote →
            bucketed int8 exchange. Returns replicated (loss, grads,
            overflow) + this rank's new residual rows."""
            scale, loss_sum, grads, _, _ = _step_head(
                c, accumulate, params, dstate, batch, rng, manual=True)

            # Unscale BEFORE the exchange (the GSPMD path unscales after
            # its allreduce): absmax quantization scales must be computed
            # on finite values, and EF residuals must not depend on the
            # running loss scale.
            denom = scale * c.accum
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32) / denom, grads)
            if c.fp16:
                # Overflow is voted on LOCAL grads pre-quantization — an
                # inf/nan absmax poisons the int8 encoding (inf/inf = nan),
                # so overflowed steps ship zeros and are skipped anyway.
                overflow = jax.lax.pmax(
                    check_overflow(grads).astype(jnp.int32), "data") > 0
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.where(overflow, 0.0, g), grads)
            else:
                overflow = jnp.asarray(False)

            r = None
            if ef:
                r = {"worker": [w[0] for w in residuals["worker"]],
                     "server": [s[0] for s in residuals["server"]]}
            avg, new_r = quantized_allreduce_tree(
                grads, "data", chunk_size=chunk_size,
                bucket_bytes=bucket_bytes, residuals=r)
            loss_sum = jax.lax.pmean(loss_sum, "data")
            res_out = None
            if ef:
                res_out = {"worker": [w[None] for w in new_r["worker"]],
                           "server": [s[None] for s in new_r["server"]]}
            return loss_sum, avg, overflow, res_out

        res_specs = P("data", None) if ef else rep
        synced = self._over_data(
            "comm_quantization", sync_local,
            in_specs=(rep, rep, _BATCH_ROWS, rep, res_specs),
            out_specs=(rep, rep, rep, res_specs))

        def train_step(params, opt_state, dstate, batch, rng, lr_in,
                       residuals):
            loss_sum, grads, voted, new_res = synced(params, dstate, batch,
                                                     rng, residuals)
            # The tail on the replicated, already-averaged gradient: the
            # vote ORs in the pre-quantization cross-rank overflow.
            return (*_step_tail(
                c, params, opt_state, dstate, lr_in, _loss_scale(c, dstate),
                loss_sum, grads, unscaled=True, vote=lambda o: o | voted,
                **layouts), new_res)

        if not ef:
            # Signature-compatible with the dense step: residuals pinned
            # to None so jit sees the same 6 logical inputs.
            def train_step_no_res(params, opt_state, dstate, batch, rng,
                                  lr_in):
                return train_step(params, opt_state, dstate, batch, rng,
                                  lr_in, None)[:4]
            return donated_jit(train_step_no_res, (0, 1, 2))
        return self._persisting(donated_jit(train_step, (0, 1, 2, 6)),
                                "_qcomm_residuals")

    def _upload_offload_params(self):
        """Device copy of the host fp32 masters at compute dtype (init /
        checkpoint-load path; the per-step bf16 upload is chunked inside
        ``_train_batch_offload``'s ``on_chunk`` copy-back instead). Under
        bf16 the conversion runs in the fused C++ kernel on one flat
        buffer (the reference's fused fp16 copy-back,
        csrc/adam/cpu_adam.cpp)."""
        opt = self.cpu_optimizer
        if self.compute_dtype == jnp.bfloat16:
            flat = opt.params_bf16_flat()
            leaves = [flat[off:off + size].reshape(shape)
                      for off, size, shape in zip(opt.offsets, opt.sizes,
                                                  opt.shapes)]
            tree = jax.tree_util.tree_unflatten(opt.treedef, leaves)
        else:
            tree = jax.tree_util.tree_map(
                lambda v: v if self.compute_dtype == jnp.float32
                else v.astype(self.compute_dtype), opt.params())
        return jax.device_put(tree, self._shardings["param"])

    def _offload_step(self, c):
        """``offload``: the gradient-only step of ZeRO-Offload:
        loss/grads/overflow/clip/loss-scale on device, the optimizer
        update on the host C++ Adam (reference stage2.py:1410-1423)."""
        # bf16 only: it shares fp32's exponent range, so casting the
        # UNSCALED gradient is safe. fp16 would flush components under
        # ~6e-5 to zero/subnormal — the reference avoids this by moving
        # still-scaled fp16 grads (stage2.py:793); our epilogue unscales
        # on device, so fp16 transfer would defeat loss scaling.
        grads_16bit = (self._config.zero_config.offload_16bit_grads and
                       c.compute_dtype == jnp.bfloat16)
        accumulate = make_grad_accumulator(self.loss_fn, c.compute_dtype,
                                           c.accum)
        # Offload×DP: emit the gradient as a flat [D, chunk] array sharded
        # over the data axis — each process D2H-pulls only its shard (1/D
        # of the wire), the stage-2 partition the reference implements
        # with per-rank IPG buckets (stage2.py:613-738).
        flat_dp = (self._off_D, self._off_chunk) if self._offload_dp \
            else None
        mesh = self.mesh

        def grad_step(params, dstate, batch, rng, lr_in, grad_fault=None):
            scale, loss_sum, grads, _, _ = _step_head(
                c, accumulate, params, dstate, batch, rng,
                grad_fault=grad_fault)
            # No ZeRO grad-sharding constraint on the TREE: single-process
            # offload fetches the full gradient to host RAM; offload×DP
            # instead reshards the FLAT gradient to [D, chunk] over the
            # data axis below (flat_dp) so each process pulls only its
            # 1/D shard — the stage-2 partition, applied post-epilogue.
            grads, _, dstate_out, metrics = _step_tail(
                c, params, None, dstate, lr_in, scale, loss_sum, grads)
            if grads_16bit:
                # Reference parity: stage-2 offload moves fp16 grads to
                # pinned host memory (stage2.py:793) — 16-bit halves the
                # D2H wire; the host C++ Adam widens to fp32 during its
                # existing copy into the flat grad buffer (no extra pass).
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(c.compute_dtype), grads)
            if flat_dp is not None:
                D, chunk = flat_dp
                leaves = jax.tree_util.tree_leaves(grads)
                flat = jnp.concatenate([l.reshape(-1) for l in leaves])
                flat = jnp.pad(flat, (0, D * chunk - flat.shape[0]))
                flat = jax.lax.with_sharding_constraint(
                    flat.reshape(D, chunk),
                    NamedSharding(mesh, PartitionSpec("data")))
                return flat, dstate_out, metrics
            return grads, dstate_out, metrics

        return donated_jit(grad_step, (1,))

    def _train_batch_offload(self, placed, step_rng, lr_in, fault_extra=()):
        """Host half of the offload step: pull grads, C++ Adam update on
        the masters, push compute-dtype params back (the reference's
        async_accumulate + CPUAdam.step + copy-back, stage2.py:793-1423).

        The host phase is software-pipelined (round 5): all grad D2H
        transfers start async up front, then per ~64 MB leaf-aligned
        chunk the C++ Adam (+ fused bf16 convert) of chunk k runs in a
        worker thread while chunk k+1's bytes land — the TPU analog of
        the reference's overlap design. ``last_host_phase_s`` records the
        host wall time so bench rows can report the host fraction of the
        step."""
        if self._offload_dp:
            return self._train_batch_offload_dp(placed, step_rng, lr_in,
                                                fault_extra)
        grads, self.device_state, metrics = self._compiled_train_step(
            self.params, self.device_state, placed, step_rng, lr_in,
            *fault_extra)
        if not bool(metrics["overflow"]):   # blocks until device step done
            t0 = time.perf_counter()
            # Nested under the caller's `dispatch` span: the host-Adam
            # phase shows up as its own range inside the step's dispatch
            # window on both the event log and the xplane trace.
            with Span("host_adam", self.telemetry):
                opt = self.cpu_optimizer
                bf16 = self.compute_dtype == jnp.bfloat16
                lr, b1 = float(metrics["lr"]), float(metrics["beta1"])
                if bf16:
                    # Chunked copy-back: each chunk's leaves start their
                    # H2D upload (device_put is async) as soon as its
                    # Adam + bf16 convert lands, overlapping the
                    # remaining chunks' host compute. Safe to upload
                    # views of the shared bf16 buffer: it is next
                    # rewritten only after the following device step has
                    # consumed these params.
                    import ml_dtypes
                    shard_leaves = jax.tree_util.tree_leaves(
                        self._shardings["param"])
                    uploaded = [None] * len(opt.sizes)

                    def upload_chunk(li, lj):
                        flat = opt._bf16_buf.view(ml_dtypes.bfloat16)
                        for i in range(li, lj):
                            o, sz = opt.offsets[i], opt.sizes[i]
                            uploaded[i] = jax.device_put(
                                flat[o:o + sz].reshape(opt.shapes[i]),
                                shard_leaves[i])

                    opt.step_overlapped(
                        grads, lr=lr, beta1=b1, bf16_out=True,
                        chunk_bytes=self._offload_chunk_bytes,
                        on_chunk=upload_chunk)
                    self.params = jax.tree_util.tree_unflatten(
                        opt.treedef, uploaded)
                else:
                    opt.step_overlapped(
                        grads, lr=lr, beta1=b1,
                        chunk_bytes=self._offload_chunk_bytes)
                    self.params = self._upload_offload_params()
            self.last_host_phase_s = time.perf_counter() - t0
        return metrics

    def _train_batch_offload_dp(self, placed, step_rng, lr_in,
                                fault_extra=()):
        """Offload×DP host phase (reference stage-2 offload semantics):
        pull only this process's shard of the flat gradient, C++ Adam on
        the matching contiguous master range, reassemble full params on
        device via the XLA all-gather in the assemble jit. Host work and
        wire bytes are 1/D per process — DP over processes IS the
        parallelism (the reference parallelizes its CPU Adam the same
        way: each rank steps its own partition).

        Within the rank the phase is pipelined PER DATA-AXIS ROW, same
        worker pattern as the single-process path: row r+1's grad bytes
        land (blocking only on that row's async D2H) while the worker
        runs Adam + convert on row r, and each row's updated params
        start their H2D the moment its future resolves."""
        flat_shard, self.device_state, metrics = self._compiled_train_step(
            self.params, self.device_state, placed, step_rng, lr_in,
            *fault_extra)
        if bool(metrics["overflow"]):
            return metrics
        t0 = time.perf_counter()
        opt = self.cpu_optimizer
        D, chunk = self._off_D, self._off_chunk
        sharding, ranges = self._local_row_ranges()
        shards = {s.index[0].start or 0: s.data
                  for s in flat_shard.addressable_shards}
        for data in shards.values():
            start = getattr(data, "copy_to_host_async", None)
            if start is not None:
                start()
        rows = [r for r, *_ in ranges]
        assert rows == list(range(rows[0], rows[-1] + 1)), (
            f"non-contiguous local grad rows {rows}: the flat-shard "
            "partition assumes process-major device order on the data "
            "axis")
        assert set(rows) == set(shards), (rows, sorted(shards))
        bf16 = self.compute_dtype == jnp.bfloat16
        if bf16 and opt._bf16_buf is None:
            opt._bf16_buf = np.empty(opt.total, np.uint16)
        if opt._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            opt._pool = ThreadPoolExecutor(max_workers=1)
        opt._step += 1
        lr, b1 = float(metrics["lr"]), float(metrics["beta1"])
        futs = []
        try:
            for r, lo, n, _ in ranges:
                if n:
                    opt._grad_buf[lo:lo + n] = np.asarray(
                        shards[r], np.float32).reshape(-1)[:n]
                futs.append(opt.submit_update_range(
                    opt._step, lr, b1, lo, n, bf16) if n else None)
            if bf16:
                import ml_dtypes
                src, np_dtype = opt._bf16_buf.view(ml_dtypes.bfloat16), \
                    ml_dtypes.bfloat16
            else:
                src, np_dtype = opt.master, np.dtype(self.compute_dtype)
            arrays = []
            for (r, lo, n, d), f in zip(ranges, futs):
                if f is not None:
                    opt.drain_update(f, opt._step, lr, b1, lo, n, bf16)
                if n == chunk and src.dtype == np_dtype:
                    row = src[lo:lo + chunk].reshape(1, chunk)
                else:
                    row = np.zeros((1, chunk), np_dtype)
                    if n:
                        row[0, :n] = src[lo:lo + n]
                arrays.append(jax.device_put(row, d))
        finally:
            # On any failure above, no submitted Adam range may still be
            # running (or queued) once we unwind: the worker mutates the
            # shared master/moment buffers, and the next train_batch —
            # or interpreter teardown — would race it. Cancel what never
            # started, drain what did; secondary errors must not mask
            # the original exception.
            for f in futs:
                if f is not None and not f.cancel():
                    try:
                        f.result()
                    except Exception:
                        pass
        garr = jax.make_array_from_single_device_arrays(
            (D, chunk), sharding, arrays)
        self.params = self._offload_assemble_jit()(garr)
        self.last_host_phase_s = time.perf_counter() - t0
        return metrics

    def _local_row_ranges(self):
        """The host-range ↔ data-axis-row mapping for offload×DP — THE
        one place it lives (per-step reassembly and the checkpoint
        gather both iterate it): ``(sharding, [(row, lo, n, device)])``
        for this process's addressable rows of the global [D, chunk]
        flat layout, ``n`` clipped at ``total`` (the last row carries
        padding)."""
        opt = self.cpu_optimizer
        D, chunk, total = self._off_D, self._off_chunk, opt.total
        sharding = NamedSharding(self.mesh, PartitionSpec("data"))
        imap = sharding.devices_indices_map((D, chunk))
        rows = []
        for d in sharding.addressable_devices:
            r = imap[d][0].start or 0
            lo = r * chunk
            rows.append((r, lo, max(0, min(chunk, total - lo)), d))
        rows.sort()
        return sharding, rows

    def _scatter_local_rows(self, src, np_dtype):
        """Global [D, chunk] array over the data axis, each addressable
        device's row filled from this process's flat host buffer ``src``
        (zero-padded past ``total``) — the checkpoint-gather half of the
        mapping in :meth:`_local_row_ranges`."""
        D, chunk = self._off_D, self._off_chunk
        sharding, rows = self._local_row_ranges()
        arrays = []
        for _, lo, n, d in rows:
            row = np.zeros((1, chunk), np_dtype)
            if n:
                row[0, :n] = src[lo:lo + n]
            arrays.append(jax.device_put(row, d))
        return jax.make_array_from_single_device_arrays(
            (D, chunk), sharding, arrays)

    def _offload_assemble_jit(self):
        """Cached jit mapping the global data-sharded [D, chunk] flat
        param array to the engine's param pytree/shardings — XLA inserts
        the all-gather riding ICI."""
        if getattr(self, "_offload_assemble_fn", None) is None:
            opt = self.cpu_optimizer
            total = opt.total
            offsets, sizes, shapes = opt.offsets, opt.sizes, opt.shapes
            treedef = opt.treedef

            def assemble(flat2d):
                flat = flat2d.reshape(-1)[:total]
                leaves = [flat[o:o + s].reshape(shp)
                          for o, s, shp in zip(offsets, sizes, shapes)]
                return jax.tree_util.tree_unflatten(treedef, leaves)

            self._offload_assemble_fn = jax.jit(
                assemble, out_shardings=self._shardings["param"])
        return self._offload_assemble_fn

    def _offload_sync_host_state(self):
        """Make every process's full host master/moment buffers current
        (each process only updates its own range during offload×DP
        training) — an all-gather at fp32 through the device mesh, used
        before checkpointing so the saved state is complete and
        precision-lossless."""
        opt = self.cpu_optimizer
        total = opt.total
        if getattr(self, "_offload_gather_fn", None) is None:
            rep = NamedSharding(self.mesh, PartitionSpec())
            # Cached like _offload_assemble_jit: all three buffers (and
            # every later checkpoint) share one [D, chunk] program, so
            # rebuilding the jit per call just forces retrace+recompile.
            self._offload_gather_fn = jax.jit(lambda x: x,
                                              out_shardings=rep)
        gather = self._offload_gather_fn
        for buf in (opt.master, opt.exp_avg, opt.exp_avg_sq):
            garr = self._scatter_local_rows(buf, np.float32)
            buf[:] = np.asarray(gather(garr)).reshape(-1)[:total]

    def _sparse_grad_flags(self):
        """Pytree of bools (params structure): which leaves take the CSR
        sparse-gradient path. The reference auto-detects ``nn.Embedding``
        modules when ``sparse_gradients`` is on (engine.py:177-183); a
        functional engine has no modules, so detection is by param path —
        2-D leaves whose path mentions an embedding-ish name. Override per
        engine with ``engine.sparse_grad_predicate = lambda names, leaf:
        ...`` before the first ``train_batch``."""
        import re

        # "emb" only as a whole path component ("emb", "tok_emb.weight") so
        # e.g. "member" doesn't false-positive.
        pat = re.compile(
            r"embed|wte|wpe|vocab|token|lookup|(?:^|[._/])emb(?:[._/]|$)",
            re.I)
        pred = getattr(self, "sparse_grad_predicate", None) or (
            lambda names, leaf: leaf.ndim == 2 and
            any(pat.search(n) for n in names))

        def flag(path, leaf):
            names = [str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path]
            return bool(pred(names, leaf))

        flags = jax.tree_util.tree_map_with_path(flag, self.params)
        if not any(jax.tree_util.tree_leaves(flags)):
            # The reference's detection is structural (nn.Embedding,
            # engine.py:177-183) and so cannot miss; a name predicate can.
            # With sparse_gradients on and zero matches, every leaf would
            # silently take the dense path — say so loudly.
            logger.warning(
                "sparse_gradients is enabled but the embedding predicate "
                "matched NO parameter leaves — every gradient will use the "
                "dense allreduce path. Set engine.sparse_grad_predicate to "
                "select your embedding tables (param path names: %s).",
                [
                    "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                             for p in path)
                    for path, _ in
                    jax.tree_util.tree_flatten_with_path(self.params)[0]
                ][:16])
        return flags

    def _sparse_step(self, c):
        """``sparse``: CSR sparse embedding-gradient communication
        (reference `runtime/engine.py:177-183` auto-conversion and
        `engine.py:1157-1213` sparse allreduce).

        shard_map over the ``data`` axis: each shard takes local grads;
        embedding leaves are sparsified to their top-``k`` rows by L1 mass
        (``k`` = the shard's token budget, a static over-bound on touched
        rows, so the result is exact — the analog of the reference padding
        ranks to the max nnz) and exchanged by index/value all_gather;
        every other leaf takes a dense pmean.

        Exactness: a *tied* embedding (also used as the output head, e.g.
        GPT-2 wte) gets a dense gradient through the softmax — more touched
        rows than the token budget. Such leaves take a per-leaf in-jit
        dense fallback (a pmax-replicated vote over the mass the top-``k``
        truncation would drop selects ``pmean`` instead of the CSR
        exchange), so the step is *always* exact; ``sparse_grad_dropped`` /
        ``sparse_grad_dense_fallbacks`` metrics surface the lost bandwidth
        win and ``train_batch`` warns once; use
        ``engine.sparse_grad_predicate`` to exclude such leaves up front."""
        from deepspeed_tpu.runtime.csr_tensor import (csr_allreduce,
                                                      dense_to_csr)

        assert self.zero_optimization_stage() == 0, (
            "sparse_gradients is incompatible with ZeRO (the reference's "
            "CSR path is the non-ZeRO allreduce fallback, engine.py:1127)")

        accumulate = make_grad_accumulator(self.loss_fn, c.compute_dtype,
                                           c.accum)
        sparse_flags = self._sparse_grad_flags()

        def step_local(params, opt_state, dstate, batch, rng, lr_in):
            scale, loss_sum, grads, _, _ = _step_head(
                c, accumulate, params, dstate, batch, rng, manual=True)

            # Static token budget: rows touched locally per boundary is
            # bounded by the number of id elements in the local batch.
            tokens = sum(
                leaf.size for leaf in jax.tree_util.tree_leaves(batch)
                if jnp.issubdtype(leaf.dtype, jnp.integer))

            dropped = jnp.asarray(0.0, jnp.float32)
            fallbacks = jnp.asarray(0, jnp.int32)

            def reduce_leaf(is_sparse, g):
                nonlocal dropped, fallbacks
                if is_sparse and 0 < tokens < g.shape[0]:
                    csr = dense_to_csr(g, min(tokens, g.shape[0]))
                    # L1 mass the static top-k truncation would lose.
                    # Meaningfully nonzero ⇒ this leaf's grad is denser
                    # than the token budget (e.g. a *tied* embedding,
                    # whose LM-head softmax grad is dense over the vocab)
                    # — truncating would silently drop real gradient every
                    # step, so the leaf falls back to the exact dense
                    # pmean. The vote compares *relative* mass (full-array
                    # and top-k reductions round differently — an absolute
                    # >0 test would flap on ULP noise) and is pmax'd so
                    # every shard takes the same cond branch.
                    g_l1 = jnp.abs(g).sum().astype(jnp.float32)
                    leaf_dropped = jax.lax.pmax(
                        (g_l1 -
                         jnp.abs(csr.values).sum()).astype(jnp.float32),
                        "data")
                    use_dense = leaf_dropped > 1e-6 * jax.lax.pmax(
                        g_l1, "data")
                    # only count mass when the vote fires — below the
                    # relative threshold it is reduction-order noise
                    dropped += jnp.where(use_dense, leaf_dropped, 0.0)
                    fallbacks += use_dense.astype(jnp.int32)
                    return jax.lax.cond(
                        use_dense,
                        lambda: jax.lax.pmean(g, "data"),
                        lambda: csr_allreduce(csr, "data").to_dense())
                return jax.lax.pmean(g, "data")

            grads = jax.tree_util.tree_map(reduce_leaf, sparse_flags, grads)

            # Grads are now replicated-global, so no cross-shard vote or
            # norm reduction is needed past this point.
            return _step_tail(
                c, params, opt_state, dstate, lr_in, scale, loss_sum, grads,
                loss_reduce=lambda l: jax.lax.pmean(l, "data"),
                extra_metrics={"sparse_grad_dropped": dropped,
                               "sparse_grad_dense_fallbacks": fallbacks})

        rep = PartitionSpec()
        return donated_jit(self._over_data(
            "sparse_gradients", step_local,
            in_specs=(rep, rep, rep, _BATCH_ROWS, rep, rep),
            out_specs=rep), (0, 1, 2))

    def _onebit_step(self, c):
        """``onebit``: 1-bit Adam. Each shard keeps its *local*
        gradients, which the optimizer averages itself — densely (pmean)
        during warmup, with the 1-bit error-feedback collective after
        ``freeze_step`` (the analog of the reference disabling engine
        allreduce at onebit_adam.py:372 and running its MPI data
        plane)."""
        from deepspeed_tpu.runtime.fp16.onebit_adam import OnebitAdamState

        accumulate = make_grad_accumulator(self.loss_fn, c.compute_dtype,
                                           c.accum)

        def step_local(params, opt_state, dstate, batch, rng, lr_in):
            scale, loss_sum, grads, _, _ = _step_head(
                c, accumulate, params, dstate, batch, rng, manual=True)
            # Cross-shard overflow vote (reference stage2.py:1527-1551);
            # norms are pmean'd local-shard diagnostics (a true global norm
            # would need the dense allreduce this optimizer avoids), and
            # clipping scales by the pmax norm so every shard applies the
            # same (conservative, rank-consistent) factor.
            return _step_tail(
                c, params, opt_state, dstate, lr_in, scale, loss_sum, grads,
                vote=lambda o: jax.lax.pmax(
                    o.astype(jnp.int32), "data") > 0,
                norm_reduce=lambda n: jax.lax.pmean(n, "data"),
                clip_norm_reduce=lambda n: jax.lax.pmax(n, "data"),
                loss_reduce=lambda l: jax.lax.pmean(l, "data"))

        P = PartitionSpec
        rep = P()
        opt_specs = OnebitAdamState(
            m=rep, v=rep, step=rep, worker_error=P("data", None),
            server_error=P("data"))
        return donated_jit(self._over_data(
            "OneBitAdam", step_local,
            in_specs=(rep, opt_specs, rep, _BATCH_ROWS, rep, rep),
            out_specs=(rep, opt_specs, rep, rep)), (0, 1, 2))

    def _pipeline_onebit_step(self, c):
        """``pipeline x onebit``: the pipeline x 1-bit Adam composition
        (BASELINE config 5; beyond the reference, whose OnebitAdam rides
        the fp16-optimizer path only): the 1F1B program runs with
        ``data_local=True`` — its dense psum over ``data`` is skipped and
        gradients come back with a stacked data axis — then the 1-bit
        error-feedback collective + update runs in a second ``shard_map``
        over (pipe, data), each stage group averaging its own shard's
        momentum over its data replicas.

        Metric semantics: ``grad_norm`` here is the MEAN of the
        per-data-replica local gradient norms (and clipping scales by the
        MAX of them), not the norm of the data-averaged gradient that the
        dense train steps report. The data-averaged gradient is never
        formed on this path — materializing it (even just for its norm,
        whose square sums cross-replica products) would reintroduce the
        dense all-reduce the 1-bit collective exists to eliminate. The
        mean-of-norms upper-bounds the true averaged-gradient norm
        (triangle inequality), so treat it as a stability indicator, not
        a cross-config-comparable quantity."""
        from deepspeed_tpu.runtime.fp16.onebit_adam import OnebitAdamState

        for ax, size in self.mesh.shape.items():
            assert ax in ("data", "pipe", "model") or size == 1, (
                f"pipeline OneBitAdam supports pipe x model x data meshes; "
                f"axis {ax!r} has size {size}")
        direct_local = self.loss_fn.direct_value_and_grad_local
        fp16, clip, opt_update = c.fp16, c.clip, c.opt_update
        mesh = self.mesh
        model_size = mesh.shape.get("model", 1)
        tree_map = jax.tree_util.tree_map

        P = PartitionSpec
        param_specs = tree_map(lambda ns: ns.spec, self._shardings["param"])
        grad_specs = tree_map(lambda sp: P("data", *tuple(sp)), param_specs)
        err_spec = (P("pipe", "model", "data", None) if model_size > 1
                    else P("pipe", "data", None))

        from deepspeed_tpu.runtime.fp16.onebit_adam import (
            pipeline_onebit_splits)
        splits = pipeline_onebit_splits(
            self.params, self.dp_world_size, mesh.shape["pipe"], model_size)
        if model_size > 1:
            (pm, cm), (pb, cb), (pr, cr) = splits
            # static mask: which body leaves are model-sharded (mp_*) —
            # they compress separately from the model-replicated leaves
            # so replicated copies see the same quantization scale on
            # every model rank. Shared source of truth with the buffer
            # sizing (onebit_adam.pipeline_mp_mask).
            from deepspeed_tpu.runtime.fp16.onebit_adam import (
                pipeline_mp_mask)
            mp_mask = pipeline_mp_mask(self.params, model_size)
        else:
            (pb, cb), (pr, cr) = splits
            pm = cm = 0
            mp_mask = None

        def split_body(tree):
            """Local body tree → (mp leaves, replicated leaves) as list
            pytrees, in tree_leaves order."""
            leaves = jax.tree_util.tree_leaves(tree)
            mp = [x for x, is_mp in zip(leaves, mp_mask) if is_mp]
            rep = [x for x, is_mp in zip(leaves, mp_mask) if not is_mp]
            return mp, rep

        def merge_body(mp, rep, template):
            mp_it, rep_it = iter(mp), iter(rep)
            leaves = [next(mp_it) if is_mp else next(rep_it)
                      for is_mp in mp_mask]
            treedef = jax.tree_util.tree_structure(template)
            return jax.tree_util.tree_unflatten(treedef, leaves)

        def upd(p_l, g_l, m_l, v_l, we_l, se_l, step, lr_, b1, ovf):
            # Groups that share content compress SEPARATE buffers (a
            # joint one couples the quantization scale and silently
            # diverges the shared copies): body vs pipe-replicated rest;
            # under 3D also model-sharded mp leaves vs model-replicated
            # body leaves.
            body_p = {"body": tree_map(lambda a: a[0], p_l["body"])}
            body_g = {"body": tree_map(lambda a: a[0, 0], g_l["body"])}
            body_m = {"body": tree_map(lambda a: a[0], m_l["body"])}
            body_v = {"body": tree_map(lambda a: a[0], v_l["body"])}
            rest_keys = ("prologue", "epilogue", "tied")
            rest_p = {k: p_l[k] for k in rest_keys}
            rest_g = {k: tree_map(lambda a: a[0], g_l[k])
                      for k in rest_keys}
            rest_m = {k: m_l[k] for k in rest_keys}
            rest_v = {k: v_l[k] for k in rest_keys}

            we = we_l[0, 0] if model_size > 1 else we_l[0]   # [1, P]
            se = se_l[0, 0, 0] if model_size > 1 else se_l[0, 0]  # [C]

            def slice_state(m_t, v_t, w_lo, w_hi, c_lo, c_hi):
                return OnebitAdamState(
                    m=m_t, v=v_t, step=step,
                    worker_error=we[:, w_lo:w_hi],
                    server_error=se[c_lo:c_hi])

            groups = []      # (params, grads, state) per compressed group
            if model_size > 1:
                mp_p, rep_p = split_body(body_p)
                mp_g, rep_g = split_body(body_g)
                mp_m, rep_m = split_body(body_m)
                mp_v, rep_v = split_body(body_v)
                groups.append((mp_p, mp_g,
                               slice_state(mp_m, mp_v, 0, pm, 0, cm)))
                groups.append((rep_p, rep_g,
                               slice_state(rep_m, rep_v,
                                           pm, pm + pb, cm, cm + cb)))
            else:
                groups.append((body_p, body_g,
                               slice_state(body_m, body_v, 0, pb, 0, cb)))
            groups.append((rest_p, rest_g,
                           slice_state(rest_m, rest_v,
                                       pm + pb, pm + pb + pr,
                                       cm + cb, cm + cb + cr)))

            results = [opt_update(p, g, st, lr_, b1)
                       for p, g, st in groups]

            def sel(old, new):
                return tree_map(lambda o, n: jnp.where(ovf, o, n), old, new)

            new_rp, new_rst = results[-1]
            if model_size > 1:
                (mp_np, mp_nst), (rep_np, rep_nst) = results[0], results[1]
                new_body_p = merge_body(sel(groups[0][0], mp_np),
                                        sel(groups[1][0], rep_np),
                                        body_p)["body"]
                new_body_m = merge_body(sel(groups[0][2].m, mp_nst.m),
                                        sel(groups[1][2].m, rep_nst.m),
                                        body_p)["body"]
                new_body_v = merge_body(sel(groups[0][2].v, mp_nst.v),
                                        sel(groups[1][2].v, rep_nst.v),
                                        body_p)["body"]
                body_states = [mp_nst, rep_nst]
            else:
                new_bp, new_bst = results[0]
                new_body_p = sel(body_p, new_bp)["body"]
                new_body_m = sel(body_m, new_bst.m)["body"]
                new_body_v = sel(body_v, new_bst.v)["body"]
                body_states = [new_bst]
            new_p = dict(sel(rest_p, new_rp), body=new_body_p)
            new_m = dict(sel(rest_m, new_rst.m), body=new_body_m)
            new_v = dict(sel(rest_v, new_rst.v), body=new_body_v)
            new_we = jnp.where(
                ovf, we, jnp.concatenate(
                    [st.worker_error for st in body_states]
                    + [new_rst.worker_error], axis=-1))
            new_se = jnp.where(
                ovf, se, jnp.concatenate(
                    [st.server_error for st in body_states]
                    + [new_rst.server_error], axis=-1))
            new_step = jnp.where(ovf, step, new_rst.step)

            def restore_body(t):
                return dict(t, body=tree_map(lambda a: a[None], t["body"]))
            if model_size > 1:
                we_out, se_out = new_we[None, None], new_se[None, None, None]
            else:
                we_out, se_out = new_we[None], new_se[None, None]
            return (restore_body(new_p), restore_body(new_m),
                    restore_body(new_v), we_out, se_out, new_step)

        mapped_upd = shard_map(
            upd, mesh=mesh,
            in_specs=(param_specs, grad_specs, param_specs, param_specs,
                      err_spec, err_spec, P(), P(), P(), P()),
            out_specs=(param_specs, param_specs, param_specs, err_spec,
                       err_spec, P()),
            check_vma=False)

        def train_step(params, opt_state, dstate, batch, rng, lr_in):
            scale = _loss_scale(c, dstate)
            micro = tree_map(lambda x: x[0], batch)   # accum dim == 1
            loss, grads = direct_local(params, micro, rng, scale)

            # Unscale + overflow + clip on the STACKED (data-local) grads
            # — reductions only, never a dense cross-data averaging.
            grads = tree_map(lambda g: g.astype(jnp.float32) / scale, grads)
            nonfinite = check_overflow(grads) if (fp16 or c.detect) \
                else jnp.asarray(False)
            overflow = nonfinite if (fp16 or c.nan_skip) \
                else jnp.asarray(False)
            # Per-data-slice norms: sum of squares over every dim but the
            # stacked axis; identical on all ranks, so clipping by the max
            # slice norm is rank-consistent (the DP onebit's pmax analog).
            sq = sum(jnp.sum(jnp.square(g),
                             axis=tuple(range(1, g.ndim)))
                     for g in jax.tree_util.tree_leaves(grads))
            norms = jnp.sqrt(sq)                        # [data]
            # mean of local norms, NOT the averaged-gradient norm — see
            # the method docstring for why that is the only choice here.
            grad_norm = jnp.mean(norms)
            applied_norm = grad_norm
            if clip > 0:
                factor = jnp.minimum(
                    1.0, clip / (jnp.max(norms) + 1e-6))
                grads = tree_map(lambda g: g * factor, grads)
                applied_norm = grad_norm * factor

            lr = c.lr_fn(dstate.global_step) if c.lr_fn is not None \
                else lr_in
            beta1 = c.mom_fn(dstate.global_step)
            new_params, new_m, new_v, new_we, new_se, new_step = mapped_upd(
                params, grads, opt_state.m, opt_state.v,
                opt_state.worker_error, opt_state.server_error,
                opt_state.step, lr, beta1, overflow)
            opt_out = OnebitAdamState(m=new_m, v=new_v, step=new_step,
                                      worker_error=new_we,
                                      server_error=new_se)
            dstate_out = loss_scale_epilogue(dstate, overflow, fp16,
                                             c.dynamic, c.scale_args)
            metrics = step_metrics(loss, 1, grad_norm, applied_norm, lr,
                                   scale, overflow, dstate=dstate_out,
                                   nonfinite=nonfinite)
            return new_params, opt_out, dstate_out, metrics

        return donated_jit(train_step, (0, 1, 2))

    def _shard_batch(self, batch):
        """Host-side: this process's batch rows → [accum, per_step_global, ...]
        with the per-step dim sharded over ``data``.

        Single-host: the caller passes the full global batch
        (``train_batch_size`` rows). Multi-host: each process passes its
        ``train_batch_size // process_count`` share (what
        DeepSpeedDataLoader emits) and the global array is assembled from
        the per-process shards.
        """
        accum = self._engine_accum_steps()
        sharding = NamedSharding(self.mesh, PartitionSpec(None, "data"))
        n_proc = jax.process_count()
        expected = self._config.train_batch_size // n_proc

        def place(x):
            x = np.asarray(x)
            assert x.shape[0] == expected, (
                f"train_batch expects {expected} rows per process "
                f"(train_batch_size {self._config.train_batch_size} / "
                f"{n_proc} processes), got {x.shape[0]}")
            x = x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
            if n_proc == 1:
                return jax.device_put(x, sharding)
            return jax.make_array_from_process_local_data(sharding, x)

        return jax.tree_util.tree_map(place, batch)

    # ------------------------------------------------------------------
    # telemetry helpers
    # ------------------------------------------------------------------
    def _telemetry_flavor(self):
        """The step flavor stamped on telemetry events (audit taxonomy:
        dense/zero1-3/offload/quantized/pipeline/onebit/sparse)."""
        cached = getattr(self, "_telemetry_flavor_cache", None)
        if cached is None:
            from deepspeed_tpu.analysis.audit import _engine_flavor
            try:
                cached = _engine_flavor(self)
            except Exception:
                cached = "unknown"
            self._telemetry_flavor_cache = cached
        return cached

    @staticmethod
    def _scalar_metrics(metrics):
        """Host-scalar view of a step's metrics dict for the step event
        (missing keys — pipeline flavor, guards off — are just absent)."""
        out = {}
        for key in ("loss", "lr", "grad_norm", "applied_grad_norm",
                    "loss_scale"):
            if key in metrics:
                try:
                    out[key] = float(metrics[key])
                except Exception:
                    pass
        for key, cast in (("overflow", bool), ("grad_nonfinite", bool),
                          ("skipped_steps", int),
                          ("consecutive_skipped_steps", int)):
            if key in metrics:
                try:
                    out[key] = cast(metrics[key])
                except Exception:
                    pass
        try:    # one transfer for all of them
            out.update({k: float(v) for k, v in jax.device_get(
                metrics.get(LOSS_SCALARS, {})).items()})
        except Exception:
            pass
        return out

    def _register_train_step(self, placed, step_rng, lr_in):
        """The step by name, for whoever joins a profiler trace with its
        scopes (`telemetry/programs.py`): the step's function and the
        shapes of what this first call hands it; nothing is lowered. A
        dense step's closure holds no engine, so it can be lowered again
        once the engine is gone; the other kinds' may, and are
        registered by a weak reference."""
        from deepspeed_tpu.analysis.audit import _engine_fn_args
        from deepspeed_tpu.telemetry import programs

        def unwrapped(engine, batch):
            fn, args = _engine_fn_args(engine, *batch)
            return fn.__wrapped__, fn._ds_donate_argnums, \
                programs.shapes(args)

        if self._step_kind() in ("dense", "pipeline"):
            # with the call's own arrays: an fp8 step's amax state is
            # discovered from them (the step's own call would do it next)
            found = unwrapped(self, (placed, step_rng, lr_in))
            programs.register("train_step", lambda: found)
        else:
            ref, batch = weakref.ref(self), programs.shapes(
                (placed, step_rng, lr_in))
            programs.register(
                "train_step", lambda: ref() and unwrapped(ref(), batch))

    def _stamp_compile_facts(self, placed, step_rng, lr_in,
                             compile_seconds=None):
        """Emit the one-shot ``compile`` event: static facts of the
        compiled step so the run's log is self-describing. Reuses the
        analysis block's audit stats when that ran; otherwise (with
        ``telemetry.stamp_static_facts``) lowers the already-compiled
        step once (a jit-cache hit on the XLA side of the same avals)
        and extracts the collective/peak-memory accounting directly."""
        tl = self._config.telemetry
        facts = {"step": self.global_steps,
                 "flavor": self._telemetry_flavor(),
                 "flops_per_token": tl.flops_per_token or None,
                 "batch_tokens": self._batch_tokens}
        if compile_seconds is not None:
            facts["compile_seconds"] = round(compile_seconds, 4)
        if getattr(self, "_param_gather_plan", None) is not None:
            # whether the tree took the 16-bit gather: leaves and bytes
            # on the wire a step, and what stayed as it lay (no dimension
            # the axis divides, a tuple sub-spec)
            facts["param_gather"] = dict(self._param_gather_plan)
        if self._config.compilation_cache_dir:
            from deepspeed_tpu.telemetry import compile_cache
            cc = compile_cache.counts()
            facts["compile_cache_hits"] = cc["hits"]
            facts["compile_cache_misses"] = cc["misses"]
        stats = None
        if self.last_audit_report is not None:
            stats = self.last_audit_report.stats
        elif tl.stamp_static_facts:
            try:
                from deepspeed_tpu.analysis.audit import (
                    _engine_fn_args, audit_hlo)
                fn, args = _engine_fn_args(self, placed, step_rng, lr_in)
                hlo_text = fn.lower(*args).compile().as_text()
                stats = audit_hlo(
                    hlo_text, rules=[],
                    n_devices=int(self.mesh.shape.get("data", 1))).stats
            except Exception as e:   # stamping is best-effort telemetry
                facts["static_facts_error"] = str(e)
        if stats:
            cb = stats.get("collective_bytes") or {}
            facts["collective_bytes"] = {k: int(v)
                                         for k, v in cb.items()}
            bd = stats.get("collective_bytes_by_dtype") or {}
            if bd:
                # Per-element-dtype wire accounting: what separates an
                # fp8/int8 quantized wire (u8/s8/f8 bytes) from full-
                # precision traffic sharing the same op family.
                facts["collective_bytes_by_dtype"] = {
                    op: ({dt: int(b) for dt, b in d.items()}
                         if isinstance(d, dict) else int(d))
                    for op, d in bd.items()}
            facts["while_loops"] = stats.get("while_loops")
            pm = stats.get("peak_memory") or {}
            if pm:
                facts["static_peak_bytes"] = int(pm.get("peak_bytes", 0))
                facts["static_temp_peak_bytes"] = int(
                    pm.get("temp_peak_bytes", 0))
            # engine-context audits carry the live param-tree bytes;
            # the HLO-only path falls back to the compiled program's
            # parameter-buffer accounting
            facts["param_bytes"] = int(stats.get("param_bytes") or
                                       pm.get("parameter_bytes") or 0)
            # sub-pallas_call kernel analysis (analysis/kernels.py),
            # present when the audit ran with kernels=True — the
            # per-kernel VMEM/DMA facts ds_tpu_metrics summary renders
            if stats.get("kernels"):
                facts["kernels"] = stats["kernels"]
        self.telemetry.emit("compile", **facts)

    # ------------------------------------------------------------------
    # public training API
    # ------------------------------------------------------------------
    def _run_compile_audit(self, placed, step_rng, lr_in):
        """Opt-in compile-time audit (``analysis`` config block): lower
        the just-compiled step, run the rule catalog over its HLO, and
        surface findings through logging — or raise
        :class:`AuditError` when ``fail_on_findings`` is set."""
        report = audit_compiled_step(self, placed, step_rng, lr_in,
                                     rules=self._config.analysis.rules)
        self.last_audit_report = report
        cb = report.stats.get("collective_bytes", {})
        log_dist(
            f"analysis: audited compiled {report.flavor} step — "
            f"{len(report.findings)} finding(s), "
            f"{cb.get('total', 0) / 1e6:.2f}MB collectives/step "
            f"(trip-aware)", ranks=[0])
        for f in report.findings:
            log_dist(f"analysis[{f.rule}/{f.severity}]: {f.message}",
                     ranks=[0])
        if not report.ok and self._config.analysis.fail_on_findings:
            raise AuditError(report)

    def train_batch(self, batch=None):
        """One full optimizer step over a global batch (the fast path).

        ``batch``: pytree of arrays with leading dim ``train_batch_size``,
        or None to pull from the engine dataloader.
        """
        # Step boundary: a pending preemption checkpoints + exits HERE,
        # before this step consumes a batch (the dataloader position in
        # the checkpoint must not run ahead of the optimizer state).
        self._check_preemption()
        # The step on the span ring, telemetry on or off, as a serving
        # step is: ``train/step`` with ``dispatch`` inside it, carrying
        # the collector's seconds over the step and the thread's CPU
        # seconds beside the wall's since the last step that had them
        # (``spans.CpuMark``: wall without CPU is a blocked or
        # descheduled thread). It feeds no session: the step event's
        # phases are its children.
        attrs = {"step": self.global_steps}
        gc0 = spans.collector.seconds
        with Span("train/step", attrs=attrs):
            try:
                return self._train_batch(batch)
            finally:
                attrs["gc_s"] = spans.collector.seconds - gc0
                self._cpu_mark.stamp(attrs)

    def _train_batch(self, batch):
        # Telemetry off, a span lands in the ring and nowhere else (its
        # cost is pinned by the overhead micro-benchmark test).
        tele = self.telemetry
        watchdog = tele.watchdog if tele is not None else None
        step_wall_t0 = time.perf_counter() if tele is not None else 0.0
        if watchdog is not None:
            watchdog.step_start(self.global_steps)
        if batch is None:
            assert self._data_iter is not None, \
                "no training_data given; pass a batch explicitly"
            with Span("data_load", tele):
                batch = next(self._data_iter)
        first_compile = self._compiled_train_step is None
        if first_compile:
            self._compiled_train_step = self._make_train_step()
        if tele is not None and self._batch_tokens is None:
            # Rows x second dim of the first leaf: tokens for LM batches
            # ([rows, seq] ids), rows x features otherwise — consistent
            # within a run, which is what the MFU ratio needs.
            shape = np.shape(jax.tree_util.tree_leaves(batch)[0])
            self._batch_tokens = int(shape[0]) * (
                int(shape[1]) if len(shape) > 1 else 1)
        # Fault harness: the compiled step takes a trailing grad multiplier
        # only when fault injection is configured on (no recompile or
        # signature change for ordinary runs).
        fault_extra = (jnp.asarray(
            fault_injection.grad_fault_value(self.global_steps)),) \
            if self._fault_arg else ()

        self.trace_profiler.before_step(self.global_steps)
        # sync-timing only for wall_clock_breakdown runs or steps inside
        # the trace window — never run-wide for a windowed trace config
        step_t0 = time.time() if (
            self.wall_clock_breakdown() or
            self.trace_profiler.in_window(self.global_steps)) else None
        if self.wall_clock_breakdown():
            self.timers("train_batch").start()
        self.tput_timer.start()
        with Span("dispatch", tele):
            placed = self._shard_batch(batch)
            # Fault harness: a host-side sleep here simulates a stuck
            # collective/straggler inside the step — the watchdog test
            # seam (probe is armed-only, and only with fault injection
            # configured on).
            if self._config.resilience.fault_injection:
                # Hard process death inside the step — the supervisor
                # soak seam. For SIGKILL this call never returns.
                fault_injection.maybe_kill("step", self.global_steps)
                hang_s = fault_injection.hang_seconds(self.global_steps)
                if hang_s > 0.0:
                    with Span("injected_hang", tele):
                        time.sleep(hang_s)
            # Derive the step rng from the CHECKPOINTED step counter rather
            # than an in-memory split chain: a resumed engine replays the
            # exact dropout masks the original would have drawn, so training
            # curves stay continuous across save/load even with dropout on.
            # Stream id 0 keeps this disjoint from backward()'s micro stream.
            step_rng = jax.random.fold_in(
                jax.random.fold_in(self._rng, 0), self.global_steps)
            lr_in = jnp.asarray(self._current_host_lr(), jnp.float32)
            # First-call wall from here through the step dispatch is
            # trace+compile (the device execution is async): the
            # `compile` event's compile_seconds, which a warm persistent
            # cache (compilation_cache_dir) should drive to near zero.
            compile_t0 = time.perf_counter() if first_compile else None
            if first_compile:
                self._register_train_step(placed, step_rng, lr_in)
            if first_compile and self._config.analysis.enabled:
                # Compile-time audit: lowering here both triggers the one
                # real compile (the step call below is then a jit-cache
                # hit) and hands the audit the exact HLO that will execute.
                with Span("compile", tele):
                    self._run_compile_audit(placed, step_rng, lr_in)
            # Collective confessions for the flight recorder: the first
            # call traces the step, and the overlap/ring helpers log one
            # SiteRecord per collective group they emit while tracing.
            flight = tele.flight if tele is not None else None
            sites = None
            with contextlib.ExitStack() as stack:
                if first_compile and flight is not None:
                    sites = stack.enter_context(record_collective_sites())
                if self._offload:
                    metrics = self._train_batch_offload(placed, step_rng,
                                                        lr_in, fault_extra)
                else:
                    self.params, self.opt_state, self.device_state, \
                        metrics = self._compiled_train_step(
                            self.params, self.opt_state,
                            self.device_state, placed,
                            step_rng, lr_in, *fault_extra)
            if sites is not None:
                if not sites and self.last_audit_report is not None:
                    # analysis already traced the step (our call above was
                    # a jit-cache hit); reuse the audit's captured sites
                    jx = self.last_audit_report.stats.get("jaxpr") or {}
                    sites = jx.get("collective_sites") or []
                flight.record_collectives(sites)
        if first_compile and tele is not None:
            # One-shot static facts (overlaps the step's device execution:
            # the compiled call above is still in flight).
            self._stamp_compile_facts(
                placed, step_rng, lr_in,
                compile_seconds=time.perf_counter() - compile_t0)
        if step_t0 is not None or tele is not None:
            # block on the step's own outputs BEFORE stopping any timer:
            # effects_barrier (inside the timers) only waits for
            # *effectful* dispatch, not the pure compiled train step.
            # Telemetry syncs here too — the step event's wall time must
            # cover device execution, and device_wait IS the async-
            # dispatch slack (host-bound runs show it near zero).
            with Span("device_wait", tele):
                jax.block_until_ready(metrics["loss"])
        self.tput_timer.stop()
        if self.wall_clock_breakdown():
            self.timers("train_batch").stop()
            self.timers.log(["train_batch"],
                            memory_breakdown=self.memory_breakdown())
        if step_t0 is not None:
            self.trace_profiler.after_step(self.global_steps,
                                           time.time() - step_t0)
        else:
            self.trace_profiler.after_step(self.global_steps)

        # Only inspect the (device-resident) truncation metric on the first
        # step and at print boundaries — float() here would otherwise force
        # a host sync every step and defeat async dispatch.
        if "sparse_grad_dropped" in metrics and \
                not getattr(self, "_warned_sparse_dropped", False) and \
                (self.global_steps == 0 or (self.global_steps + 1) %
                 self._config.steps_per_print == 0):
            if float(metrics["sparse_grad_dropped"]) > 1e-7:
                self._warned_sparse_dropped = True
                logger.warning(
                    "sparse_gradients: %d embedding leaf/leaves had "
                    "gradients denser than the token budget (%.3e L1 mass "
                    "beyond top-k — tied output head?) and fell back to "
                    "the exact dense allreduce. Training is exact, but the "
                    "CSR bandwidth win is lost for those leaves; exclude "
                    "them via engine.sparse_grad_predicate to silence "
                    "this.",
                    int(metrics.get("sparse_grad_dense_fallbacks", 0)),
                    float(metrics["sparse_grad_dropped"]))

        self.micro_steps += self._config.gradient_accumulation_steps
        self.global_steps += 1

        # Recompile detector (analysis block): the step's jit cache must
        # hold exactly one entry after warm-up; growth means some input
        # changes aval every call and each step pays a fresh compile.
        an = self._config.analysis
        if an.enabled and an.check_recompile and \
                (an.rules is None or "recompile" in an.rules):
            findings = check_recompile(self,
                                       baseline=self._recompile_reported)
            if findings:
                self._recompile_reported = findings[0].details["cache_size"]
                if self.last_audit_report is not None:
                    self.last_audit_report.findings.extend(findings)
                for f in findings:
                    log_dist(f"analysis[{f.rule}/{f.severity}]: "
                             f"{f.message}", ranks=[0])
                if tele is not None:
                    # whose jit cache grew is the detector's finding;
                    # which function and how long, the compile ledger's
                    from deepspeed_tpu.telemetry import compile_cache
                    compiled = compile_cache.last_compile() or {}
                    tele.emit("recompile", step=self.global_steps,
                              cache_size=findings[0].details["cache_size"],
                              expected=findings[0].details["expected"],
                              message=findings[0].message,
                              fun=compiled.get("fun"),
                              compile_seconds=compiled.get("seconds"))
                    self._arm_anomaly_trace("recompile")
                if an.fail_on_findings:
                    raise AuditError(AuditReport(flavor="live",
                                                 findings=findings))
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        if self.lr_scheduler is not None and \
                hasattr(self.lr_scheduler, "step"):
            self.lr_scheduler.step()

        if self._health_monitor is not None:
            # Host-side guards need the step's scalars — this is the one
            # forced device sync guards cost per step (benchmarked in the
            # resilience bench row).
            cur_scale = float(metrics["loss_scale"]) \
                if self.fp16_enabled() and self.dynamic_loss_scale else None
            trips = self._health_monitor.observe(
                step=self.global_steps - 1,
                loss=float(metrics["loss"]),
                grad_nonfinite=bool(metrics.get("grad_nonfinite",
                                                metrics["overflow"])),
                cur_scale=cur_scale)
            metrics = dict(metrics)
            metrics.update(self._health_monitor.metrics())
            for trip in trips:
                if tele is not None:
                    # Emit BEFORE applying: rollback/abort below may load
                    # a checkpoint or raise, and the trip must be on
                    # record either way.
                    tele.emit("health_guard", **trip.as_event())
                    self._arm_anomaly_trace(f"health_guard:{trip.guard}")
                self._apply_guard_trip(trip)

        rz = self._config.resilience
        if self._hot_store is not None and \
                self.global_steps % rz.hot_interval_steps == 0:
            self._hot_snapshot()
        if rz.save_interval_steps and rz.save_dir and \
                self.global_steps % rz.save_interval_steps == 0:
            self.save_checkpoint(rz.save_dir)

        self._last_metrics = metrics

        if tele is not None:
            # Per-step event: scalar metrics (already materialized by the
            # device_wait sync above, so these float()s are transfers,
            # not stalls), the drained phase spans, and the end-to-end
            # host wall time. Ring-buffered on metrics_history for
            # file-less assertions.
            step_wall = time.perf_counter() - step_wall_t0
            if watchdog is not None:
                watchdog.step_end(self.global_steps - 1, step_wall)
            evt = tele.step_event(
                step=self.global_steps,
                flavor=self._telemetry_flavor(),
                wall_s=step_wall,
                phases={k: round(v, 6)
                        for k, v in tele.drain_phases().items()},
                tokens=self._batch_tokens,
                process_index=self._proc_meta["process_index"],
                hostname=self._proc_meta["hostname"],
                **self._scalar_metrics(metrics))
            self.metrics_history.append(evt)
            if self._anomaly_detector is not None:
                reason = self._anomaly_detector.observe(step_wall)
                if reason is not None:
                    self._arm_anomaly_trace(reason)

        if self.global_steps % self._config.steps_per_print == 0:
            loss = float(metrics["loss"])
            lr = float(metrics["lr"])
            log_dist(f"step={self.global_steps}, skipped="
                     f"{self.skipped_steps}, lr={lr:.6g}, loss={loss:.5f}",
                     ranks=[0])
            summ = self.trace_profiler.summary()
            if summ is not None:
                mean_s, min_s, max_s = summ
                log_dist(f"device step time: mean={mean_s * 1e3:.1f}ms "
                         f"min={min_s * 1e3:.1f}ms max={max_s * 1e3:.1f}ms",
                         ranks=[0])
        if self.summary_writer is not None:
            self.summary_writer.add_scalar("Train/loss",
                                           float(metrics["loss"]),
                                           self.global_steps)
            self.summary_writer.add_scalar("Train/lr", float(metrics["lr"]),
                                           self.global_steps)
            if self._config.fp16_enabled:
                self.summary_writer.add_scalar(
                    "Train/loss_scale", float(metrics["loss_scale"]),
                    self.global_steps)
        return metrics["loss"]

    def eval_batch(self, batch):
        """Forward-only loss over a global batch (no grad, no state change)."""
        if self._compiled_eval_step is None:
            loss_fn = self.loss_fn
            cast = self._cast_for_loss()

            def eval_step(params, batch):
                return loss_fn(cast(params), batch, None)

            self._compiled_eval_step = jax.jit(eval_step)
        placed = self._place_rows(batch)
        return self._compiled_eval_step(self.params, placed)

    def _place_rows(self, batch):
        """Place a [rows, ...] batch sharded over ``data``; multi-host safe."""
        sharding = NamedSharding(self.mesh, PartitionSpec("data"))
        n_proc = jax.process_count()

        def place(x):
            x = np.asarray(x)
            if n_proc == 1:
                return jax.device_put(x, sharding)
            return jax.make_array_from_process_local_data(sharding, x)

        return jax.tree_util.tree_map(place, batch)

    # ------------------------------------------------------------------
    # forward/backward/step compatibility shim (reference hot-loop API)
    # ------------------------------------------------------------------
    def forward(self, batch):
        """Compatibility: compute the micro-batch loss; remember the batch so
        ``backward()`` can compute gradients for it."""
        self._pending_batch = batch
        loss = self.eval_batch(batch)
        return loss

    def __call__(self, *args, **kwargs):
        # late-bound so subclasses overriding forward() are honored
        return self.forward(*args, **kwargs)

    def backward(self, loss=None, batch=None):
        """Compatibility: accumulate gradients for the pending micro-batch.
        (In JAX the gradient comes from re-running the fused fwd+bwd program,
        not from a stored graph — prefer ``train_batch``.)"""
        if batch is None:
            batch = self._pending_batch
        assert batch is not None, "call forward(batch) first or pass batch="
        if not hasattr(self, "_micro_grad_fn"):
            loss_fn = self.loss_fn
            cast = self._cast_for_loss()

            def grad_fn(params, b, rng, scale):
                def f(p):
                    loss = loss_fn(cast(p), b, rng)
                    return loss * scale, loss
                (_, loss), grads = jax.value_and_grad(f, has_aux=True)(params)
                return loss, grads

            self._micro_grad_fn = jax.jit(grad_fn)
        placed = self._place_rows(batch)
        # Counter-derived like train_batch's step rng (micro_steps is
        # checkpointed), so manual forward/backward loops also resume
        # with identical dropout masks. Stream id 1: a micro step must
        # never replay a train_batch step's mask even when the two
        # counters pass through equal values.
        rng = jax.random.fold_in(
            jax.random.fold_in(self._rng, 1), self.micro_steps)
        scale = jnp.asarray(self.loss_scale, jnp.float32)
        loss_val, grads = self._micro_grad_fn(self.params, placed, rng, scale)
        if self._grad_buffer is None:
            self._grad_buffer = grads
        else:
            self._grad_buffer = jax.tree_util.tree_map(
                jnp.add, self._grad_buffer, grads)
        self.micro_steps += 1
        self._pending_batch = None
        return loss_val

    def is_gradient_accumulation_boundary(self):
        return self.micro_steps % self._config.gradient_accumulation_steps == 0

    def step(self):
        """Compatibility: apply the buffered gradients at the accumulation
        boundary (reference `_take_model_step`, engine.py:922)."""
        if not self.is_gradient_accumulation_boundary():
            return
        assert self._grad_buffer is not None, "no gradients accumulated"
        accum = self._config.gradient_accumulation_steps
        denom = self.loss_scale * accum
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) / denom, self._grad_buffer)

        # fp16: overflow vote + skip + scale update (same semantics as the
        # compiled path / reference stage2.py:1341-1362).
        overflow = bool(check_overflow(grads)) if self.fp16_enabled() else False
        if not overflow:
            clip = float(self._config.gradient_clipping or 0.0)
            if clip > 0:
                grads = clip_by_global_norm(grads, clip)
            lr = self._lr_fn(self.device_state.global_step) \
                if self._lr_foldable else self._current_host_lr()
            beta1 = self._mom_fn(self.device_state.global_step)
            self.params, self.opt_state = self._opt_update(
                self.params, grads, self.opt_state, lr, beta1)
        if self.fp16_enabled() and self.dynamic_loss_scale:
            new_scale = update_loss_scale(self.device_state.loss_scale,
                                          overflow, **self._scale_args())
        else:
            new_scale = self.device_state.loss_scale
        self.device_state = DeviceState(
            loss_scale=new_scale,
            global_step=self.device_state.global_step + 1,
            skipped_steps=self.device_state.skipped_steps + int(overflow),
            consecutive_skipped=(self.device_state.consecutive_skipped + 1)
            * int(overflow))
        self._grad_buffer = None
        self.global_steps += 1
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "step"):
            self.lr_scheduler.step()

    # ------------------------------------------------------------------
    # checkpointing (reference engine.py:1215-1482)
    # ------------------------------------------------------------------
    def _get_ckpt_name(self, checkpoints_path, tag):
        return os.path.join(checkpoints_path, str(tag))

    def _topology(self):
        """This engine's topology fingerprint (manifest "topology" section):
        mesh shape, process count, ZeRO stage, offload flag, and the
        layer-param layout (stacked scan_layers vs per-layer) — what
        :func:`check_topology` compares on load to decide whether the
        checkpoint needs an elastic reshard."""
        from deepspeed_tpu.runtime.elastic.topology import param_layout
        return current_topology(self.mesh,
                                zero_stage=self.zero_optimization_stage(),
                                offload=self._offload,
                                param_layout=param_layout(self.params))

    def _arrays_manifest(self, state):
        """Per-leaf logical metadata (manifest "arrays" section): shape,
        dtype, and the PartitionSpec each leaf is laid out with — enough
        for the offline resharder to re-partition the checkpoint for a
        different world size without importing the model."""
        arrays = {}
        leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(state)
        for path, leaf in leaves_with_path:
            sharding = getattr(leaf, "sharding", None)
            spec = getattr(sharding, "spec", None)
            if spec is None:
                spec = PartitionSpec()  # host numpy / scalar: replicated
            arrays[jax.tree_util.keystr(path)] = {
                "shape": list(np.shape(leaf)),
                "dtype": str(leaf.dtype) if hasattr(leaf, "dtype")
                else str(np.asarray(leaf).dtype),
                "spec": spec_to_json(spec),
            }
        return arrays

    def _checkpoint_state_tree(self):
        """Array pytree a checkpoint persists (the orbax payload)."""
        # Under cpu_offload the device params are a compute-dtype copy;
        # checkpoint the fp32 host masters instead so no precision is lost
        # (parity with the non-offload fp32 param save). Under offload×DP
        # each process holds only its own range fresh — gather first.
        if self._offload and getattr(self, "_offload_dp", False):
            self._offload_sync_host_state()
        ckpt_params = self.cpu_optimizer.params() if self._offload \
            else self.params
        return {
            "params": ckpt_params,
            "opt_state": self._opt_state_to_tree(),
            "device_state": {
                "cur_scale": self.device_state.loss_scale.cur_scale,
                "cur_iter": self.device_state.loss_scale.cur_iter,
                "last_overflow_iter":
                    self.device_state.loss_scale.last_overflow_iter,
                "cur_hysteresis": self.device_state.loss_scale.cur_hysteresis,
                "global_step": self.device_state.global_step,
                "skipped_steps": self.device_state.skipped_steps,
                "consecutive_skipped": self.device_state.consecutive_skipped,
            },
        }

    def _checkpoint_meta(self, client_state):
        """JSON-serializable sidecar (meta.json)."""
        return {
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            # The dropout base key: resume determinism must not depend on
            # the resuming process passing the same seed= to initialize().
            "rng_base_key": np.asarray(self._rng).tolist(),
            "dp_world_size": self.dp_world_size,
            "mp_world_size": self.mp_world_size,
            "lr_scheduler": self.lr_scheduler.state_dict()
            if self.lr_scheduler is not None and
            hasattr(self.lr_scheduler, "state_dict") else None,
            "dataloader": self._data_iter.state_dict()
            if self._data_iter is not None else None,
            "client_state": client_state or {},
        }

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        """Single logical checkpoint with sharded async-capable writes
        (orbax/tensorstore) — supersedes the reference's file-per-rank layout
        while keeping its capabilities: counters, optimizer state, loss-scale
        state, lr-scheduler state, client state, elastic dp resize on load.

        Writes are preemption-safe: the CheckpointManager stages everything
        in a tmp dir, publishes it with one atomic rename, records an
        integrity manifest, retries transient I/O errors, and prunes old
        checkpoints per ``resilience.checkpoint.keep_last_n``. Raises
        :class:`CheckpointIOError` when I/O fails past the retry budget.
        """
        if tag is None:
            tag = f"global_step{self.global_steps}"
        tele = self.telemetry
        t0 = time.perf_counter()
        with Span("checkpoint", tele):
            state = self._checkpoint_state_tree()
            meta = self._checkpoint_meta(client_state)
            extra_manifest = {
                "topology": self._topology(),
                "arrays": self._arrays_manifest(state),
            }
            path = self._ckpt_manager.save(save_dir, tag, state, meta,
                                           save_latest=save_latest,
                                           extra_manifest=extra_manifest)
        log_dist(f"saved checkpoint {path}", ranks=[0])
        if tele is not None:
            # async_save: this is the staging duration; the publish
            # rename happens on the manager's writer thread.
            tele.emit("checkpoint_save", step=self.global_steps, tag=tag,
                      path=str(path),
                      duration_s=round(time.perf_counter() - t0, 6),
                      async_save=bool(self._ckpt_manager.async_save))
        return True

    def _opt_state_to_tree(self):
        if self._offload:
            # Moments + counter only: the masters are already the
            # checkpoint's "params" entry (saving both would double the
            # parameter bytes on disk).
            state = self.cpu_optimizer.state_dict()
            state.pop("master")
            return state
        s = self.opt_state
        tree = {"m": s.m, "v": s.v, "step": s.step}
        if hasattr(s, "worker_error"):
            tree["worker_error"] = s.worker_error
            tree["server_error"] = s.server_error
        return tree

    def _opt_state_from_tree(self, tree, template):
        extra = {}
        if hasattr(template, "worker_error"):
            we, se = tree["worker_error"], tree["server_error"]
            if tuple(np.shape(we)) != tuple(template.worker_error.shape):
                # Elastic dp resize: the error-feedback buffers are shaped
                # by the saved world size and can't be repartitioned —
                # restart error feedback from zero (one step of extra
                # compression noise, then back on track).
                logger.warning(
                    "onebit error buffers saved for a different dp world "
                    "size; resetting error feedback to zero")
                we = jnp.zeros(template.worker_error.shape, jnp.float32)
                se = jnp.zeros(template.server_error.shape, jnp.float32)
            extra = {"worker_error": we, "server_error": se}
        return type(template)(m=tree["m"], v=tree["v"],
                              step=jnp.asarray(tree["step"], jnp.int32),
                              **extra)

    @staticmethod
    def _reshape_for_restage(saved_tree, template_tree, what):
        """Pipeline restage-on-load: body param leaves are stacked
        [stages, layers_per_stage, ...] and stages own contiguous layer
        ranges (partition_uniform), so a checkpoint saved under a
        different stage count holds the same layers in a different
        row-major factorization — a pure reshape restores them (the
        capability the reference's per-layer checkpoint files exist for,
        `runtime/pipe/module.py:510-567`). ONLY the [stages, layers/stage]
        leading-dim refactorization is reshaped — the per-layer payload
        dims must match exactly, so a same-element-count leaf from a
        genuinely different model (transposed kernel, repacked heads)
        still raises instead of silently loading garbage."""
        def fix(path, s, t):
            s = jnp.asarray(s)
            t_shape = tuple(t.shape)
            if s.shape == t_shape:
                return s
            # Only pipeline-body leaves are stacked [stages, layers/stage,
            # ...payload]: the leaf must live under the "body" key AND be
            # at least rank-3 with identical payload dims. A 2-D transpose
            # ([in,out] vs [out,in]) or any non-body leaf never reshapes.
            under_body = bool(path) and \
                getattr(path[0], "key", None) == "body"
            restageable = (
                under_body and s.ndim >= 3 and len(t_shape) == s.ndim and
                s.shape[2:] == t_shape[2:] and
                s.shape[0] * s.shape[1] == t_shape[0] * t_shape[1])
            if not restageable:
                raise ValueError(
                    f"checkpoint {what} leaf {jax.tree_util.keystr(path)} "
                    f"has shape {s.shape}, engine expects {t_shape}: not a "
                    "pipeline restage (only the leading [stages, "
                    "layers/stage] dims may refactor) — checkpoint is from "
                    "a different model")
            log_dist(
                f"restaging {what} leaf {jax.tree_util.keystr(path)}: "
                f"{s.shape} -> {t_shape}", ranks=[0])
            return s.reshape(t_shape)
        return jax.tree_util.tree_map_with_path(fix, saved_tree,
                                                template_tree)

    def load_checkpoint(self, load_dir, tag=None,
                        load_optimizer_states=True,
                        load_lr_scheduler_states=True):
        """Restore engine state from a checkpoint under ``load_dir``.

        ``tag=None`` loads the newest *valid* checkpoint (the ``latest``
        pointer when it validates, else a scan that skips corrupt/partial
        directories). An explicit ``tag`` is strict: a corrupt target
        raises :class:`CheckpointCorruptError` rather than silently
        loading something else.
        """
        load_t0 = time.perf_counter()
        self._ckpt_manager.wait()  # join any in-flight async save first
        resolved = self._ckpt_manager.resolve_tag(load_dir, tag)
        if resolved is None:
            logger.warning(f"no valid checkpoint found at {load_dir}; "
                           "cannot load")
            return None, {}
        # Topology gate: a checkpoint saved under a different data-parallel
        # layout only loads when elasticity is enabled (a reshard-on-load),
        # and raises the typed ElasticResumeError when the change is one no
        # relayout can absorb (tensor-parallel degree, offload toggle).
        manifest = self._ckpt_manager.validate(
            self._ckpt_manager.ckpt_path(load_dir, resolved))
        check = check_topology(
            manifest.get("topology"), self._topology(),
            elastic=bool(self._config.elasticity.enabled))
        if check.kind == "elastic":
            log_dist(
                f"elastic resume: checkpoint topology {check.changed} "
                f"differs from current mesh; resharding on load", ranks=[0])
            if self.telemetry is not None:
                self.telemetry.emit(
                    "elastic_resume", step=self.global_steps,
                    changed=check.changed,
                    dp_world_size=self.dp_world_size)
        # Restore as host numpy arrays (placement happens below on the
        # CURRENT mesh/shardings) — restoring with the saved shardings
        # trips orbax's "unsafe when restoring on a different topology"
        # path, which is exactly the elastic/restage case we support.
        restored, meta, path = self._ckpt_manager.load(load_dir, resolved)
        self._install_restored_state(
            restored, meta,
            load_optimizer_states=load_optimizer_states,
            load_lr_scheduler_states=load_lr_scheduler_states)
        log_dist(f"loaded checkpoint {path} (saved at dp="
                 f"{meta.get('dp_world_size')}, now dp={self.dp_world_size})",
                 ranks=[0])
        if self.telemetry is not None:
            self.telemetry.emit(
                "checkpoint_load", step=self.global_steps, path=str(path),
                duration_s=round(time.perf_counter() - load_t0, 6),
                topology=check.kind,
                saved_dp_world_size=meta.get("dp_world_size"),
                dp_world_size=self.dp_world_size)
        return path, meta.get("client_state", {})

    def _install_restored_state(self, restored, meta,
                                load_optimizer_states=True,
                                load_lr_scheduler_states=True):
        """Install a restored host-numpy state tree + meta sidecar into
        this engine, re-placing every leaf on the *current*
        mesh/shardings. Shared by the disk path (``load_checkpoint``)
        and the hot tier's RAM/mirror restores — the tiers differ only
        in where the bytes come from."""
        # Re-place on the *current* mesh/shardings: the elastic-checkpoint
        # capability (reference stage1.py:1030 re-partitions for a new dp
        # world size) comes for free from resharding on load.
        if self._offload:
            # Masters come from the checkpoint's fp32 "params" entry;
            # the opt_state tree carries moments + step only.
            opt = self.cpu_optimizer
            flat_leaves = jax.tree_util.tree_leaves(restored["params"])
            if len(flat_leaves) != len(opt.sizes):
                raise ValueError(
                    f"checkpoint has {len(flat_leaves)} param leaves but "
                    f"offload optimizer expects {len(opt.sizes)}; "
                    "checkpoint is from a different model")
            for i, (leaf, off, size) in enumerate(
                    zip(flat_leaves, opt.offsets, opt.sizes)):
                if int(np.size(leaf)) != int(size):
                    raise ValueError(
                        f"checkpoint param leaf {i} has {np.size(leaf)} "
                        f"elements, expected {size}; checkpoint is from a "
                        "different model shape")
                opt.master[off:off + size] = np.asarray(
                    leaf, np.float32).reshape(-1)
            if load_optimizer_states:
                saved = restored["opt_state"]
                opt.exp_avg[:] = np.asarray(saved["exp_avg"],
                                            np.float32).reshape(-1)
                opt.exp_avg_sq[:] = np.asarray(saved["exp_avg_sq"],
                                               np.float32).reshape(-1)
                opt._step = int(saved["step"])
            self.params = self._upload_offload_params()
        else:
            # Streaming placement: each leaf is device_put individually and
            # its host copy dropped immediately after, so peak host memory
            # during an (elastic) restore stays ~one full section + one
            # leaf rather than the whole state tree twice.
            self.params = stream_device_put(
                self._reshape_for_restage(restored["params"], self.params,
                                          "param"),
                self._shardings["param"])
            del restored["params"]
            if load_optimizer_states:
                opt_tree = restored.pop("opt_state")
                opt_tree["m"] = self._reshape_for_restage(
                    opt_tree["m"], self.opt_state.m, "opt.m")
                opt_tree["v"] = self._reshape_for_restage(
                    opt_tree["v"], self.opt_state.v, "opt.v")
                self.opt_state = stream_device_put(
                    self._opt_state_from_tree(opt_tree, self.opt_state),
                    self._opt_state_shardings())
        ds = restored["device_state"]
        self.device_state = jax.device_put(
            DeviceState(
                loss_scale=LossScaleState(
                    cur_scale=jnp.asarray(ds["cur_scale"], jnp.float32),
                    cur_iter=jnp.asarray(ds["cur_iter"], jnp.int32),
                    last_overflow_iter=jnp.asarray(ds["last_overflow_iter"],
                                                   jnp.int32),
                    cur_hysteresis=jnp.asarray(ds["cur_hysteresis"],
                                               jnp.int32)),
                global_step=jnp.asarray(ds["global_step"], jnp.int32),
                skipped_steps=jnp.asarray(ds["skipped_steps"], jnp.int32),
                # Absent in checkpoints saved before the resilience PR.
                consecutive_skipped=jnp.asarray(
                    ds.get("consecutive_skipped", 0), jnp.int32)),
            NamedSharding(self.mesh, PartitionSpec()))

        self.global_steps = meta["global_steps"]
        self.micro_steps = meta["micro_steps"]
        if meta.get("rng_base_key") is not None:
            self._rng = jnp.asarray(meta["rng_base_key"],
                                    np.asarray(self._rng).dtype)
        if load_lr_scheduler_states and meta.get("lr_scheduler") and \
                self.lr_scheduler is not None and \
                hasattr(self.lr_scheduler, "load_state_dict"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        if meta.get("dataloader") is not None and self._data_iter is not None:
            self._data_iter.load_state_dict(meta["dataloader"])
        if self._health_monitor is not None:
            # Pre-restore loss history would poison the spike detector.
            self._health_monitor.reset_history()

    def _hot_snapshot(self):
        """One in-RAM hot snapshot (async CRC stamp + optional mirror)."""
        t0 = time.perf_counter()
        tag = f"step{self.global_steps}"
        self._hot_store.snapshot(tag, self._checkpoint_state_tree(),
                                 self._checkpoint_meta(None),
                                 topology=self._topology())
        if self.telemetry is not None:
            self.telemetry.emit(
                "hot_snapshot", step=self.global_steps, tag=tag,
                mirrored=bool(self._hot_store.mirror_dir),
                duration_s=round(time.perf_counter() - t0, 6))

    def _install_hot_restore(self, got, tier):
        """Install a hot-tier ``(state, meta, topology)`` triple; False
        when the snapshot's topology fingerprint no longer matches (a
        restart onto a different mesh must fall through to the disk
        tier, whose elastic reshard-on-load can absorb the change)."""
        state, meta, topology = got
        try:
            check = check_topology(topology, self._topology(),
                                   elastic=False)
        except Exception as e:
            logger.warning("hot restore (%s): topology check failed "
                           "(%s); falling through", tier, e)
            return False
        if check.kind != "same":
            logger.warning(
                "hot restore (%s): snapshot topology %s does not match "
                "the current mesh; falling through to disk", tier,
                check.changed if hasattr(check, "changed") else check.kind)
            return False
        self._install_restored_state(state, meta)
        return True

    def _emit_recovery(self, tier, source, t0, error=None):
        if self.telemetry is not None:
            self.telemetry.emit(
                "recovery_ladder", tier=tier, source=source,
                step=self.global_steps,
                duration_s=round(time.perf_counter() - t0, 6),
                error=error)

    def _auto_resume(self):
        """Resume through the recovery ladder: hot RAM → hot mirror →
        newest valid disk checkpoint (→ older disk, inside
        ``resolve_tag``). Returns a description of what was loaded, or
        None when nothing is loadable (fresh start). Each successful
        rung emits a ``recovery_ladder`` event naming the tier, so
        ``ds_tpu_metrics summary`` shows which tier actually served the
        restart."""
        rz = self._config.resilience
        t0 = time.perf_counter()
        if self._hot_store is not None:
            # Tier 1: hot RAM — survives in-process restarts only (a
            # fresh process starts with an empty store).
            try:
                got = self._hot_store.restore()
            except HotCheckpointCorruptError as e:
                logger.warning("hot RAM restore rejected: %s", e)
                got = None
            if got is not None and self._install_hot_restore(got,
                                                             "hot_ram"):
                self._emit_recovery("hot_ram", "<ram>", t0)
                return "<hot_ram>"
            # Tier 2: hot mirror on local disk — the fast path for a
            # restarted process. Snapshot leaves are keyed by path; the
            # fresh-init state tree supplies the structure.
            if rz.hot_mirror_dir and os.path.isdir(rz.hot_mirror_dir):
                try:
                    got = HotCheckpointStore.load_mirror(
                        rz.hot_mirror_dir, self._checkpoint_state_tree())
                except HotCheckpointCorruptError as e:
                    logger.warning("hot mirror restore rejected: %s", e)
                    got = None
                if got is not None and self._install_hot_restore(
                        got, "hot_mirror"):
                    self._emit_recovery("hot_mirror", rz.hot_mirror_dir,
                                        t0)
                    return f"<hot_mirror:{rz.hot_mirror_dir}>"
        # Tier 3: durable disk checkpoints (resolve_tag already scans
        # past a corrupt newest one, emitting checkpoint_fallback).
        tag = self._ckpt_manager.resolve_tag(rz.save_dir, None)
        if tag is None:
            return None
        path, _ = self.load_checkpoint(rz.save_dir)
        if path is not None:
            self._emit_recovery("disk", str(path), t0)
        return path

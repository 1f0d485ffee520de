"""Activation checkpointing (rematerialization), TPU-native.

Capability parity with the reference's Megatron-compatible checkpointing
(`runtime/activation_checkpointing/checkpointing.py:325-576,579,654`), with
the mechanisms re-designed for XLA:

- ``CheckpointFunction`` (autograd.Function saving inputs, replaying RNG
  states in backward) becomes ``jax.checkpoint``: XLA rematerializes the
  segment inside one compiled backward, and RNG replay is free because JAX
  PRNG keys are explicit values — the same key threads through both the
  forward and the rematerialized forward, so dropout patterns match by
  construction. The whole ``CudaRNGStatesTracker`` / ``_CUDA_RNG_STATE_
  TRACKER`` fork/restore machinery (reference 147-278) collapses into
  :class:`RNGKeyTracker`, a deterministic named-key derivation helper.
- ``partition_activations`` (reference 369-397: each MP rank stores 1/mp of
  every saved activation, allgathered back in backward at 281-322) becomes a
  sharding constraint over the ``model`` mesh axis on the checkpointed
  inputs — GSPMD stores the shard and inserts the all-gather.
- ``cpu_checkpointing`` (reference 410-419) becomes an offload checkpoint
  policy moving saved residuals to pinned host memory when the backend
  supports it.
- ``contiguous_memory_optimization`` (reference 398-409: preallocated
  contiguous checkpoint buffers) is subsumed by XLA's static buffer
  allocation — accepted and recorded for config parity, nothing to do.
- ``number_checkpoints`` feeds :func:`checkpoint_sequential` segmenting.
- PROFILE/SYNCHRONIZE knobs map to named-timer instrumentation around the
  checkpointed call (reference 331-335).

Public surface mirrors the reference module: ``configure``,
``is_configured``, ``checkpoint``, ``model_parallel_seed`` (analog of
``model_parallel_cuda_manual_seed``, reference 223), ``get_rng_tracker``
(analog of ``get_cuda_rng_tracker``, reference 265), ``reset``.
"""

import contextlib
import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from deepspeed_tpu.runtime.activation_checkpointing.config import (
    DeepSpeedActivationCheckpointingConfig)
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.telemetry.timers import SynchronizedWallClockTimer

__all__ = [
    "configure", "is_configured", "reset", "checkpoint",
    "checkpoint_sequential", "make_policy", "RNGKeyTracker",
    "get_rng_tracker", "model_parallel_seed",
]

# ---------------------------------------------------------------------------
# Module-level configuration (the reference keeps the same globals,
# checkpointing.py:90-130).
# ---------------------------------------------------------------------------

_config: Optional[DeepSpeedActivationCheckpointingConfig] = None
_timers: Optional[SynchronizedWallClockTimer] = None


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None):
    """Configure the module, from a DeepSpeedConfig or explicit kwargs
    (reference ``configure``, checkpointing.py:654-734)."""
    import copy
    global _config, _timers
    if deepspeed_config is not None:
        cfg = getattr(deepspeed_config, "activation_checkpointing_config",
                      None)
        if cfg is None:
            cfg = DeepSpeedActivationCheckpointingConfig(
                deepspeed_config if isinstance(deepspeed_config, dict) else {})
        else:
            # Never mutate the caller's DeepSpeedConfig sub-object — kwarg
            # overrides apply to this module's copy only.
            cfg = copy.copy(cfg)
    else:
        cfg = DeepSpeedActivationCheckpointingConfig({})
    if partition_activations is not None:
        cfg.partition_activations = partition_activations
    if contiguous_checkpointing is not None:
        cfg.contiguous_memory_optimization = contiguous_checkpointing
    if num_checkpoints is not None:
        cfg.number_checkpoints = num_checkpoints
    if checkpoint_in_cpu is not None:
        cfg.cpu_checkpointing = checkpoint_in_cpu
    if synchronize is not None:
        cfg.synchronize_checkpoint_boundary = synchronize
    if profile is not None:
        cfg.profile = profile
    _config = cfg
    if cfg.profile and _timers is None:
        _timers = SynchronizedWallClockTimer()
    return cfg


def is_configured():
    """Reference ``is_configured`` (checkpointing.py:744)."""
    return _config is not None


def reset():
    """Drop module configuration and RNG tracker state (reference ``reset``,
    checkpointing.py:246 resets the tracker; here both)."""
    global _config, _timers
    _config = None
    _timers = None
    _RNG_TRACKER.reset()


def _cfg() -> DeepSpeedActivationCheckpointingConfig:
    return _config if _config is not None else \
        DeepSpeedActivationCheckpointingConfig({})


# ---------------------------------------------------------------------------
# Checkpoint policies
# ---------------------------------------------------------------------------

def _offload_policy():
    """Host-offload policy for ``cpu_checkpointing`` — saved residuals go to
    pinned host memory instead of HBM (the reference's explicit
    ``.cpu()`` copies, checkpointing.py:410-419)."""
    policies = jax.checkpoint_policies
    maker = getattr(policies, "offload_dot_with_no_batch_dims", None)
    if maker is None:
        logger.warning(
            "cpu_checkpointing requested but this jax version has no offload "
            "checkpoint policy; falling back to full rematerialization")
        return policies.nothing_saveable
    try:
        return maker("device", "pinned_host")
    except TypeError:
        return policies.nothing_saveable


_NAMED_POLICIES = {
    # Full remat: save only segment inputs — the reference's behaviour.
    "nothing": lambda: jax.checkpoint_policies.nothing_saveable,
    # Save every matmul output (skip recomputing MXU work, re-do the cheap
    # elementwise ops) — the standard TPU selective-remat policy.
    "dots": lambda: jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": lambda: jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "everything": lambda: jax.checkpoint_policies.everything_saveable,
    "offload": _offload_policy,
}


def make_policy(name=None):
    """Resolve a checkpoint policy by name or from the configured state."""
    if callable(name):
        return name
    if name is None:
        name = "offload" if _cfg().cpu_checkpointing else "nothing"
    try:
        return _NAMED_POLICIES[name]()
    except KeyError:
        raise ValueError(
            f"unknown checkpoint policy {name!r}; "
            f"one of {sorted(_NAMED_POLICIES)}")


# ---------------------------------------------------------------------------
# checkpoint()
# ---------------------------------------------------------------------------

def _partition_constraint(tree, axis="model"):
    """Shard checkpointed inputs over the model axis — the
    ``partition_activations`` capability (reference 369-397) as a GSPMD
    sharding constraint. Outside a mesh context this is a no-op."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or axis not in mesh.shape or mesh.shape[axis] == 1:
        return tree

    def constrain(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return x
        # Shard the trailing (feature/hidden) dim — what the reference's
        # flatten-and-split over MP ranks amounts to. Walk backwards so the
        # batch dim (dim 0, owned by the data axis) is only used as a last
        # resort for 1-D values.
        size = mesh.shape[axis]
        for d in range(x.ndim - 1, -1, -1):
            if x.shape[d] % size == 0 and x.shape[d] > 0:
                spec = [None] * x.ndim
                spec[d] = axis
                return jax.lax.with_sharding_constraint(x, P(*spec))
        return x

    return jax.tree_util.tree_map(constrain, tree)


@contextlib.contextmanager
def _profiled(name):
    if _cfg().profile and _timers is not None:
        _timers(name).start()
        try:
            yield
        finally:
            _timers(name).stop()
            _timers.log([name])
    else:
        yield


def checkpoint(function, *args, policy=None, static_argnums=(),
               prevent_cse=False):
    """Checkpoint a model segment: recompute its activations in backward
    instead of storing them (reference ``checkpoint``, checkpointing.py:579).

    Unlike the reference this composes with jit/scan/pjit and needs no RNG
    state capture — pass PRNG keys as explicit ``args`` and dropout is
    bitwise-identical in the rematerialized forward.
    """
    cfg = _cfg()
    ckpt_policy = make_policy(policy)

    fn = function
    if cfg.partition_activations:
        inner = function

        def fn(*inner_args):
            return inner(*_partition_constraint(inner_args))

    wrapped = jax.checkpoint(fn, policy=ckpt_policy,
                             prevent_cse=prevent_cse,
                             static_argnums=static_argnums)
    with _profiled("activation_checkpoint"):
        out = wrapped(*args)
    if cfg.synchronize_checkpoint_boundary:
        # The reference cuda-synchronizes at segment boundaries (331-335);
        # under jit this is a trace-time no-op, but eagerly it makes the
        # profile timers honest.
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, out)
    return out


def checkpoint_sequential(functions: Sequence[Callable], x,
                          num_checkpoints=None, policy=None):
    """Apply ``functions`` in order, checkpointing in ``num_checkpoints``
    equal segments (the reference's Megatron usage pattern: checkpoint every
    ``checkpoint-num-layers`` block; segment count from config
    ``number_checkpoints``)."""
    n = len(functions)
    segs = num_checkpoints or _cfg().number_checkpoints or n
    segs = max(1, min(segs, n))
    bounds = [round(i * n / segs) for i in range(segs + 1)]
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if lo == hi:
            continue

        def segment(y, fns=tuple(functions[lo:hi])):
            for f in fns:
                y = f(y)
            return y

        x = checkpoint(segment, x, policy=policy)
    return x


# ---------------------------------------------------------------------------
# RNG tracking — deterministic named key derivation.
# ---------------------------------------------------------------------------

class RNGKeyTracker:
    """Named PRNG key tracker (the ``CudaRNGStatesTracker`` capability,
    reference checkpointing.py:147-220, without any state capture: JAX keys
    are values, so "restoring the RNG state in backward" is just reusing the
    same key).

    ``add(name, seed)`` registers a stream; ``fork(name)`` yields a fresh
    per-use subkey, advancing the stream deterministically.
    """

    def __init__(self):
        self._keys = {}
        self._counts = {}

    def reset(self):
        self._keys.clear()
        self._counts.clear()

    def get_states(self):
        return dict(self._keys), dict(self._counts)

    def set_states(self, states):
        keys, counts = states
        self._keys = dict(keys)
        self._counts = dict(counts)

    def add(self, name, seed):
        if name in self._keys:
            raise Exception(f"RNG stream {name} already present")
        self._keys[name] = jax.random.PRNGKey(seed)
        self._counts[name] = 0

    @contextlib.contextmanager
    def fork(self, name="model-parallel-rng"):
        """Yield a fresh subkey for the named stream (reference ``fork``,
        checkpointing.py:192-220 swaps global CUDA RNG state; here the
        subkey is handed to the caller explicitly)."""
        if name not in self._keys:
            raise Exception(f"RNG stream {name} not added")
        sub = jax.random.fold_in(self._keys[name], self._counts[name])
        self._counts[name] += 1
        yield sub


_RNG_TRACKER = RNGKeyTracker()
_MODEL_PARALLEL_RNG = "model-parallel-rng"


def get_rng_tracker():
    """Reference ``get_cuda_rng_tracker`` (checkpointing.py:265)."""
    return _RNG_TRACKER


def model_parallel_seed(seed, model_parallel_rank=0, offset=2718):
    """Seed two streams the way Megatron does (reference
    ``model_parallel_cuda_manual_seed``, checkpointing.py:223-262): a
    ``default`` stream identical on all MP ranks (data-parallel dropout)
    and a ``model-parallel-rng`` stream offset per MP rank (different
    dropout on each tensor-parallel shard of an activation)."""
    _RNG_TRACKER.reset()
    _RNG_TRACKER.add("default", seed)
    _RNG_TRACKER.add(_MODEL_PARALLEL_RNG,
                     seed + offset + model_parallel_rank)
    return _RNG_TRACKER

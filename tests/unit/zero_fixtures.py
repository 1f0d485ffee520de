"""Shared model + step-lowering recipe for the ZeRO proof tests.

``test_zero_memory.py`` (per-device bytes) and ``test_zero_comm_volume.py``
(collective bytes) pin different compile-time facts of the SAME programs;
one copy of the model and the lower() argument list keeps their
PARAM_BYTES-based assertions in sync with engine internals.
"""

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from tests.unit.simple_model import base_config

HIDDEN = 512
NLAYERS = 8
PARAM_BYTES = NLAYERS * (HIDDEN * HIDDEN + HIDDEN) * 4  # fp32


def init_params(rng):
    keys = jax.random.split(rng, NLAYERS)
    return {
        f"linear_{i}": {
            "kernel": jax.random.normal(
                k, (HIDDEN, HIDDEN), jnp.float32) * 0.02,
            "bias": jnp.zeros((HIDDEN,), jnp.float32),
        }
        for i, k in enumerate(keys)
    }


def loss_fn(params, batch, rng=None):
    x = batch["x"]
    for i in range(NLAYERS):
        layer = params[f"linear_{i}"]
        x = x @ layer["kernel"] + layer["bias"]
        if i < NLAYERS - 1:
            x = jax.nn.relu(x)
    return jnp.mean(jnp.square(x - batch["y"]))


def build_engine(stage, accum=1, precision=None, **extra):
    """``precision``: the config's precision block, bf16 when None (e.g.
    ``{"fp16": {"enabled": True, "initial_scale_power": 20}}``)."""
    cfg = base_config(train_batch_size=16 * accum,
                      gradient_accumulation_steps=accum,
                      zero_optimization={"stage": stage},
                      **(precision or {"bf16": {"enabled": True}}), **extra)
    params = init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg, loss_fn=loss_fn, params=params)
    return engine


def make_batch(accum=1):
    rng = np.random.default_rng(0)
    bs = 16 * accum
    return {"x": rng.normal(size=(bs, HIDDEN)).astype(np.float32),
            "y": rng.normal(size=(bs, HIDDEN)).astype(np.float32)}


def lowered_train_step(stage, accum=1, compiler_options=None):
    """Build the engine at ``stage``, run one step, and return the
    lowered-compiled train step (callers read .as_text() /
    .memory_analysis(); pass ``compiler_options`` e.g. for an
    xla_dump_to pass dump)."""
    engine = build_engine(stage, accum=accum)
    raw = make_batch(accum=accum)
    engine.train_batch(raw)  # builds the compiled step lazily
    batch = engine._shard_batch(raw)
    lowered = engine._compiled_train_step.lower(
        engine.params, engine.opt_state, engine.device_state, batch,
        jax.random.PRNGKey(1), jnp.asarray(1e-3, jnp.float32))
    if compiler_options:
        # Dump options only take effect if XLA actually COMPILES: the
        # warm-up step above (and same-HLO engines from earlier tests)
        # can otherwise satisfy the compile from an executable cache and
        # produce no dump (observed once under full-suite cache
        # pressure). Clear between the warm-up and the dump compile.
        jax.clear_caches()
        return lowered.compile(compiler_options)
    return lowered.compile()

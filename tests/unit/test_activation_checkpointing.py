"""Activation checkpointing tests — analog of the reference's
`tests/unit/test_activation_checkpointing.py` (grad equivalence of
checkpointed vs plain autograd) plus policy/config/RNG coverage the
reference does via CUDA RNG state capture."""

import jax

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ck
from deepspeed_tpu.runtime.config import DeepSpeedConfig


@pytest.fixture(autouse=True)
def _reset_module():
    ck.reset()
    yield
    ck.reset()


def _mlp(params, x):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return jnp.sum((h @ params["w2"]) ** 2)


def _params(key):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (16, 32)) * 0.1,
        "b1": jnp.zeros((32,)),
        "w2": jax.random.normal(k2, (32, 8)) * 0.1,
    }


def test_checkpoint_grad_matches_plain():
    params = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))

    def plain(p):
        return _mlp(p, x)

    def ckpt(p):
        return ck.checkpoint(_mlp, p, x)

    g_plain = jax.grad(plain)(params)
    g_ckpt = jax.grad(ckpt)(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
        g_plain, g_ckpt)


def test_checkpoint_with_dropout_key_deterministic():
    """Explicit PRNG keys make the rematerialized forward bitwise-identical
    — the property the reference needs the CudaRNGStatesTracker for."""
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 16))
    w = jax.random.normal(jax.random.PRNGKey(3), (16, 16)) * 0.1

    def f(w, x, key):
        h = x @ w
        keep = jax.random.bernoulli(key, 0.5, h.shape)
        return jnp.sum(jnp.where(keep, h, 0.0) ** 2)

    key = jax.random.PRNGKey(4)
    g_plain = jax.grad(f)(w, x, key)
    g_ckpt = jax.grad(lambda w: ck.checkpoint(f, w, x, key))(w)
    np.testing.assert_allclose(g_plain, g_ckpt, rtol=1e-6)


def test_checkpoint_inside_jit():
    params = _params(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 16))

    @jax.jit
    def step(p):
        return jax.grad(lambda q: ck.checkpoint(_mlp, q, x))(p)

    g = step(params)
    g_ref = jax.grad(lambda q: _mlp(q, x))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5),
        g_ref, g)


def test_checkpoint_sequential_segments():
    fns = [lambda y, i=i: jnp.tanh(y) + 0.01 * i for i in range(6)]
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 4))

    def direct(y):
        for f in fns:
            y = f(y)
        return y

    for segs in (1, 2, 3, 6, 99):
        out = ck.checkpoint_sequential(fns, x, num_checkpoints=segs)
        np.testing.assert_allclose(out, direct(x), rtol=1e-6)

    # number_checkpoints flows in from config when not passed explicitly
    ck.configure(num_checkpoints=2)
    out = ck.checkpoint_sequential(fns, x)
    np.testing.assert_allclose(out, direct(x), rtol=1e-6)


def test_policies_resolve():
    assert ck.make_policy("nothing") is jax.checkpoint_policies.nothing_saveable
    assert ck.make_policy("dots") is jax.checkpoint_policies.checkpoint_dots
    assert callable(ck.make_policy("offload"))
    with pytest.raises(ValueError):
        ck.make_policy("no_such_policy")
    # grads still correct under a save-dots policy
    params = _params(jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (4, 16))
    g = jax.grad(lambda p: ck.checkpoint(_mlp, p, x, policy="dots"))(params)
    g_ref = jax.grad(lambda p: _mlp(p, x))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), g_ref, g)


def test_configure_from_deepspeed_config(tmp_path):
    cfg_dict = {
        "train_batch_size": 8,
        "activation_checkpointing": {
            "partition_activations": True,
            "number_checkpoints": 4,
            "cpu_checkpointing": False,
            "profile": False,
        },
    }
    ds_config = DeepSpeedConfig(cfg_dict)
    assert not ck.is_configured()
    got = ck.configure(deepspeed_config=ds_config)
    assert ck.is_configured()
    assert got.partition_activations
    assert got.number_checkpoints == 4
    # kwargs override config
    got = ck.configure(deepspeed_config=ds_config, num_checkpoints=7,
                       partition_activations=False)
    assert got.number_checkpoints == 7
    assert not got.partition_activations
    # kwarg overrides must not leak into the caller's DeepSpeedConfig
    assert ds_config.activation_checkpointing_config.partition_activations
    assert ds_config.activation_checkpointing_config.number_checkpoints == 4


def test_partition_activations_matches_unpartitioned():
    """Under a real model-axis mesh the partitioned checkpoint path must
    be numerically identical (it only changes where residuals live)."""
    devices = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = jax.sharding.Mesh(devices, ("data", "model"))
    params = _params(jax.random.PRNGKey(10))
    x = jax.random.normal(jax.random.PRNGKey(11), (8, 16))

    g_ref = jax.grad(lambda p: _mlp(p, x))(params)

    ck.configure(partition_activations=True)
    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(lambda p: ck.checkpoint(_mlp, p, x)))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-5, atol=1e-6),
        g_ref, g)


def test_rng_tracker():
    tracker = ck.get_rng_tracker()
    tracker.reset()
    tracker.add("default", 123)
    with pytest.raises(Exception):
        tracker.add("default", 123)
    with tracker.fork("default") as k1:
        pass
    with tracker.fork("default") as k2:
        pass
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    with pytest.raises(Exception):
        with tracker.fork("missing"):
            pass
    # replaying from saved state reproduces the same keys
    tracker.reset()
    tracker.add("default", 123)
    state = tracker.get_states()
    with tracker.fork("default") as ka:
        pass
    tracker.set_states(state)
    with tracker.fork("default") as kb:
        pass
    assert np.array_equal(np.asarray(ka), np.asarray(kb))


def test_model_parallel_seed():
    t0 = ck.model_parallel_seed(42, model_parallel_rank=0)
    with t0.fork("default") as d0:
        pass
    with t0.fork(ck._MODEL_PARALLEL_RNG) as m0:
        pass
    t1 = ck.model_parallel_seed(42, model_parallel_rank=1)
    with t1.fork("default") as d1:
        pass
    with t1.fork(ck._MODEL_PARALLEL_RNG) as m1:
        pass
    # default stream identical across MP ranks; model-parallel stream differs
    assert np.array_equal(np.asarray(d0), np.asarray(d1))
    assert not np.array_equal(np.asarray(m0), np.asarray(m1))

"""Unit tests for the int8 chunk-scaled quantized all-reduce
(`deepspeed_tpu/runtime/comm/quantized.py`): codec accuracy, collective
correctness against the exact fp32 mean on the 8-device CPU mesh, bucket
planning, error feedback, and the config-level legality checks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.runtime.comm.quantized import (
    bucket_plan, dequantize_chunks, init_residuals, quantize_chunks,
    quantized_allreduce, quantized_allreduce_sizes,
    quantized_allreduce_tree)
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from jax import shard_map

WORLD = 8
CHUNK = 64


def _mesh():
    return Mesh(np.array(jax.devices()[:WORLD]), ("data",))


# ---------------------------------------------------------------- codec

def test_roundtrip_error_bounded_by_half_step():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8 * CHUNK,)).astype(np.float32))
    q, scales = quantize_chunks(x, CHUNK)
    assert q.dtype == jnp.int8 and scales.dtype == jnp.float32
    back = dequantize_chunks(q, scales)
    # Rounding to the nearest of 255 levels: error <= scale/2 per element.
    err = np.abs(np.asarray(back - x))
    bound = np.repeat(np.asarray(scales), CHUNK) / 2 + 1e-7
    assert (err <= bound).all()


def test_zero_chunks_decode_exactly():
    x = jnp.zeros((4 * CHUNK,), jnp.float32)
    q, scales = quantize_chunks(x, CHUNK)
    assert (np.asarray(scales) == 0).all()
    assert (np.asarray(dequantize_chunks(q, scales)) == 0).all()


def test_absmax_is_representable_exactly_per_chunk():
    # The absmax element of each chunk maps to +-127 and decodes back to
    # itself — the codec is exact at the extremes.
    rng = np.random.default_rng(1)
    x = rng.normal(size=(4, CHUNK)).astype(np.float32)
    flat = jnp.asarray(x.reshape(-1))
    q, scales = quantize_chunks(flat, CHUNK)
    back = np.asarray(dequantize_chunks(q, scales)).reshape(4, CHUNK)
    idx = np.abs(x).argmax(axis=1)
    rows = np.arange(4)
    np.testing.assert_allclose(back[rows, idx], x[rows, idx], rtol=1e-6)


# ----------------------------------------------------------- collective

def _run_allreduce(xs, ef=False):
    """xs: [world, n] per-rank inputs; returns (avg, worker, server)."""
    n = xs.shape[-1]
    mesh = _mesh()
    if ef:
        res_w = jnp.zeros((WORLD, n), jnp.float32)
        res_s = jnp.zeros((WORLD, n // WORLD), jnp.float32)

        def body(x, rw, rs):
            avg, w2, s2 = quantized_allreduce(
                x[0], "data", chunk_size=CHUNK,
                worker_residual=rw[0], server_residual=rs[0])
            return avg[None], w2[None], s2[None]

        f = shard_map(body, mesh=mesh,
                      in_specs=(P("data", None),) * 3,
                      out_specs=(P("data", None),) * 3,
                      check_vma=False)
        return f(xs, res_w, res_s)

    def body(x):
        avg, _, _ = quantized_allreduce(x[0], "data", chunk_size=CHUNK)
        return avg[None]

    f = shard_map(body, mesh=mesh, in_specs=(P("data", None),),
                  out_specs=P("data", None), check_vma=False)
    return f(xs), None, None


def test_allreduce_matches_fp32_mean():
    rng = np.random.default_rng(2)
    n = WORLD * CHUNK * 2
    xs = jnp.asarray(rng.normal(size=(WORLD, n)).astype(np.float32))
    avg, _, _ = _run_allreduce(xs)
    avg = np.asarray(avg)
    exact = np.asarray(xs).mean(axis=0)
    # All ranks agree (the final all-gather replicates the result)...
    assert np.abs(avg - avg[0]).max() == 0.0
    # ...and the double quantization stays within a few quantization steps.
    rel = np.linalg.norm(avg[0] - exact) / np.linalg.norm(exact)
    assert rel < 0.02, rel


def test_allreduce_identical_inputs_near_exact():
    # With identical inputs the mean is the input; the only error is two
    # codec roundtrips.
    rng = np.random.default_rng(3)
    x = rng.normal(size=(WORLD * CHUNK,)).astype(np.float32)
    xs = jnp.asarray(np.broadcast_to(x, (WORLD, x.size)).copy())
    avg, _, _ = _run_allreduce(xs)
    rel = (np.linalg.norm(np.asarray(avg)[0] - x) / np.linalg.norm(x))
    assert rel < 0.01, rel


def test_error_feedback_residual_is_the_codec_error():
    rng = np.random.default_rng(4)
    n = WORLD * CHUNK
    xs = jnp.asarray(rng.normal(size=(WORLD, n)).astype(np.float32))
    avg, worker, server = _run_allreduce(xs, ef=True)
    # First call: residual = input - dequant(quant(input)) per rank.
    q, s = quantize_chunks(xs[0], CHUNK)
    expect = np.asarray(xs[0] - dequantize_chunks(q, s))
    np.testing.assert_allclose(np.asarray(worker)[0], expect, atol=1e-6)
    assert server.shape == (WORLD, n // WORLD)


def test_sizes_alignment():
    padded, shard = quantized_allreduce_sizes(1000, WORLD, CHUNK)
    assert padded % (WORLD * CHUNK) == 0 and padded >= 1000
    assert shard == padded // WORLD
    assert quantized_allreduce_sizes(WORLD * CHUNK, WORLD, CHUNK)[0] \
        == WORLD * CHUNK


# ------------------------------------------------------------- buckets

def test_bucket_plan_covers_all_leaves_in_order():
    sizes = [1000, 50, 2_000_000, 3, 700_000, 12]
    plan = bucket_plan(sizes, WORLD, bucket_bytes=4 * 1024 * 1024,
                       chunk_size=CHUNK)
    covered = []
    for sl, n, padded in plan:
        members = sizes[sl]
        assert sum(members) == n
        assert padded >= n and padded % (WORLD * CHUNK) == 0
        covered.extend(range(sl.start, sl.stop))
    assert covered == list(range(len(sizes)))


def test_bucket_plan_splits_at_byte_limit():
    # 1 MB bucket limit, fp32: 262144 elements per bucket.
    sizes = [200_000, 200_000, 200_000]
    plan = bucket_plan(sizes, WORLD, bucket_bytes=1024 * 1024,
                       chunk_size=CHUNK)
    assert len(plan) == 2  # [0,1] closes the first bucket, [2] trails
    assert plan[0][0] == slice(0, 2) and plan[1][0] == slice(2, 3)


def test_tree_allreduce_matches_tree_mean():
    rng = np.random.default_rng(5)
    def tree_for(rank):
        r = np.random.default_rng(100 + rank)
        return {"w": r.normal(size=(300, 40)).astype(np.float32),
                "b": r.normal(size=(17,)).astype(np.float32)}
    trees = [tree_for(r) for r in range(WORLD)]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.asarray(np.stack(xs)), *trees)
    mesh = _mesh()

    def body(tree):
        local = jax.tree_util.tree_map(lambda v: v[0], tree)
        avg, _ = quantized_allreduce_tree(local, "data", chunk_size=CHUNK,
                                          bucket_bytes=64 * 1024)
        return jax.tree_util.tree_map(lambda v: v[None], avg)

    f = shard_map(body, mesh=mesh,
                  in_specs=({"b": P("data", None),
                             "w": P("data", None, None)},),
                  out_specs={"b": P("data", None),
                             "w": P("data", None, None)},
                  check_vma=False)
    out = f(stacked)
    exact = jax.tree_util.tree_map(
        lambda *xs: np.mean(np.stack(xs), axis=0), *trees)
    for k in ("w", "b"):
        got = np.asarray(out[k])[0]
        rel = (np.linalg.norm(got - exact[k]) /
               np.linalg.norm(exact[k]))
        assert rel < 0.02, (k, rel)


def test_init_residuals_shapes_follow_plan():
    grads = {"a": jnp.zeros((70_000,)), "b": jnp.zeros((128,))}
    res = init_residuals(grads, WORLD, bucket_bytes=128 * 1024,
                         chunk_size=CHUNK)
    plan = bucket_plan([70_000, 128], WORLD, 128 * 1024, CHUNK)
    assert len(res["worker"]) == len(plan)
    for (sl, n, padded), w, s in zip(plan, res["worker"], res["server"]):
        assert w.shape == (WORLD, padded)
        assert s.shape == (WORLD, padded // WORLD)


# -------------------------------------------------------------- config

def _cfg(extra=None, **quant):
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "comm_quantization": {"enabled": True, **quant}}
    cfg.update(extra or {})
    return cfg


def test_config_defaults_and_parse():
    cfg = DeepSpeedConfig(_cfg(chunk_size=256, bucket_mb=2,
                               error_feedback=True), world_size=8)
    cq = cfg.comm_quantization
    assert cq.enabled and cq.bits == 8 and cq.chunk_size == 256
    assert cq.bucket_mb == 2 and cq.error_feedback
    off = DeepSpeedConfig({"train_batch_size": 8}, world_size=8)
    assert not off.comm_quantization.enabled


@pytest.mark.parametrize("bad", [
    _cfg(bits=4),
    _cfg(chunk_size=0),
    _cfg(chunk_size=511),
    _cfg(bucket_mb=0),
    _cfg(extra={"zero_optimization": {"stage": 3},
                "bf16": {"enabled": True}}),
    _cfg(extra={"sparse_gradients": True}),
    _cfg(extra={"optimizer": {"type": "OneBitAdam",
                              "params": {"lr": 1e-3}},
                "fp16": {"enabled": True}}),
])
def test_config_rejects_illegal_combinations(bad):
    with pytest.raises(AssertionError):
        DeepSpeedConfig(bad, world_size=8)

"""Flash-decode kernel unit tests (`ops/pallas/flash_decode.py`).

The kernel is tested in Pallas interpret mode (the CPU path the engine
itself uses off-TPU) against a straight-line dense reference computed
from the same buffers: split-K online softmax across block sizes,
per-row active-length masking (including a fresh row at position 0 and
a row at the last cache slot), in-kernel dequantization for every
codec, and the head-folded layout under a TP ``shard_map``.

The mask-hoist pin: the dense cached path builds its ``[max_batch, 1,
max_seq]`` position mask ONCE per decode step (`models/gpt2.py`
computes it in ``GPT2LMHead`` and threads it to every block), so the
lowered decode program's iota count must not scale with ``n_layer`` —
before the hoist each layer re-emitted the mask iota.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.cache import _quantize
from deepspeed_tpu.ops.pallas.flash_decode import flash_decode

B, S, H, D = 3, 32, 4, 8


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _dense_ref(q, k, v, positions):
    """Straight-line dense decode attention over fp32 buffers."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
    mask = (jnp.arange(S)[None, None, None, :]
            <= positions[:, None, None, None])
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture
def qkv():
    rng = np.random.default_rng(0)
    return (_rand(rng, (B, 1, H, D)), _rand(rng, (B, S, H, D)),
            _rand(rng, (B, S, H, D)))


# positions exercise: mid-block, fresh row (only slot 0 visible), and
# the full buffer (last slot) in one call.
POSITIONS = jnp.asarray([5, 0, S - 1], jnp.int32)


@pytest.mark.parametrize("block_k", [8, 16, 32])
def test_matches_dense_reference(qkv, block_k):
    q, k, v = qkv
    out = flash_decode(q, k, v, POSITIONS, block_k=block_k)
    ref = _dense_ref(q, k, v, POSITIONS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6)


def test_stale_tail_is_invisible(qkv):
    """Slots past a row's position must not influence the output —
    that's where a recycled ring row still holds the previous tenant's
    k/v. Garbage with huge magnitude planted there must change
    nothing."""
    q, k, v = qkv
    base = flash_decode(q, k, v, POSITIONS, block_k=8)
    k2 = k.at[:, 9:].set(1e4)    # rows 0 (pos 5) and 1 (pos 0) masked
    v2 = v.at[:, 9:].set(-1e4)
    poisoned = flash_decode(q, k2, v2,
                            jnp.asarray([5, 0, 8], jnp.int32),
                            block_k=8)
    clean = flash_decode(q, k, v, jnp.asarray([5, 0, 8], jnp.int32),
                         block_k=8)
    np.testing.assert_array_equal(np.asarray(poisoned)[:2],
                                  np.asarray(base)[:2])
    np.testing.assert_array_equal(np.asarray(poisoned),
                                  np.asarray(clean))


@pytest.mark.parametrize("codec", ["int8", "f8e4m3fn", "f8e5m2"])
def test_fused_dequant_matches_dense_dequant(qkv, codec):
    """The in-kernel dequant must reproduce dense attention over the
    EXPLICITLY dequantized buffers (same storage error in both paths,
    so the comparison isolates the fusion, not the codec)."""
    q, k, v = qkv
    k_q, k_s = _quantize(k, codec)
    v_q, v_s = _quantize(v, codec)
    out = flash_decode(q, k_q, v_q, POSITIONS, k_scale=k_s, v_scale=v_s,
                       block_k=8)
    k_deq = k_q.astype(jnp.float32) * k_s[..., None]
    v_deq = v_q.astype(jnp.float32) * v_s[..., None]
    ref = _dense_ref(q, k_deq, v_deq, POSITIONS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6)


def test_tp_shard_map_matches_unsharded(qkv):
    """Head-folding contract: under shard_map over a 4-way head shard
    (the `cache.kv_partition_specs` layout) each kernel instance sees
    only local heads and the stitched result equals the unsharded
    call."""
    from jax.sharding import Mesh, PartitionSpec as P

    q, k, v = qkv
    k_q, k_s = _quantize(k, "int8")
    v_q, v_s = _quantize(v, "int8")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("model",))
    head = P(None, None, "model", None)
    sharded = jax.shard_map(
        lambda q_, k_, v_, p_, ks_, vs_: flash_decode(
            q_, k_, v_, p_, k_scale=ks_, v_scale=vs_, block_k=8),
        mesh=mesh,
        in_specs=(head, head, head, P(None),
                  P(None, None, "model"), P(None, None, "model")),
        out_specs=head, check_vma=False)
    out = sharded(q, k_q, v_q, POSITIONS, k_s, v_s)
    ref = flash_decode(q, k_q, v_q, POSITIONS, k_scale=k_s, v_scale=v_s,
                       block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6)


def test_input_validation():
    rng = np.random.default_rng(1)
    q = _rand(rng, (B, 1, H, D))
    k = _rand(rng, (B, S, H, D))
    v = _rand(rng, (B, S, H, D))
    pos = jnp.zeros((B,), jnp.int32)
    with pytest.raises(ValueError, match="one query token"):
        flash_decode(_rand(rng, (B, 2, H, D)), k, v, pos)
    with pytest.raises(ValueError, match="multiple"):
        flash_decode(q, k, v, pos, block_k=12)
    with pytest.raises(ValueError, match="both k_scale and v_scale"):
        flash_decode(q, k, v, pos,
                     k_scale=jnp.ones((B, S, H), jnp.float32))


def _decode_stablehlo_iotas(n_layer, scan_layers=False):
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=n_layer, n_head=4, dtype=jnp.float32,
                     scan_layers=scan_layers)
    model = GPT2LMHead(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(model, params, config={
        "max_batch": 2, "seq_buckets": (16, 32), "prefill_chunk": 4})
    text = eng._decode.lower(*eng.decode_lowering_args()).as_text()
    return text.count("stablehlo.iota")


@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["unrolled", "scan"])
def test_dense_mask_is_hoisted_out_of_layers(scan_layers):
    """The traced decode step emits the position-mask iota ONCE however
    deep the model is: 2- and 4-layer engines lower to the same iota
    count (pre-hoist, unrolled models emitted one mask iota per
    layer)."""
    two = _decode_stablehlo_iotas(2, scan_layers)
    four = _decode_stablehlo_iotas(4, scan_layers)
    assert two == four == 2

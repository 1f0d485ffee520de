"""Flash attention parity vs dense reference (the analog of the reference's
kernel-parity tests `test_cuda_forward.py`/`test_cuda_backward.py`)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.pallas.flash_attention import (
    dense_attention,
    flash_attention,
)


def qkv(seed=0, B=2, T=64, H=4, D=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, T, H, D)
    return (jax.random.normal(ks[0], shape, dtype),
            jax.random.normal(ks[1], shape, dtype),
            jax.random.normal(ks[2], shape, dtype))


@pytest.mark.parametrize("causal", [True, False])
def test_xla_blockwise_matches_dense(causal):
    q, k, v = qkv()
    ref = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, implementation="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_xla_blockwise_small_block():
    q, k, v = qkv(T=100)
    ref = dense_attention(q, k, v, causal=True)
    from deepspeed_tpu.ops.pallas.flash_attention import _blockwise_attention
    got = _blockwise_attention(q, k, v, True, 1.0 / 4.0, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_dense(causal):
    q, k, v = qkv(T=32)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       implementation="xla") ** 2)

    g_ref = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_bf16_inputs():
    q, k, v = qkv(dtype=jnp.bfloat16)
    ref = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, implementation="xla")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_forward_matches_dense(causal):
    # Interpreter mode on CPU runs the literal TPU kernel.
    q, k, v = qkv(T=64)
    ref = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, implementation="pallas",
                          block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_backward_matches_dense(causal):
    # The FlashAttention-2 dQ/dKV Pallas kernels, in interpreter mode.
    q, k, v = qkv(T=64)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       implementation="pallas",
                                       block_q=32, block_k=32) ** 2)

    g_ref = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_pallas_backward_uneven_blocks():
    # block_q != block_k exercises the causal tile-skip logic off-diagonal.
    q, k, v = qkv(T=64)

    def loss(impl):
        def f(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, implementation=impl,
                block_q=16, block_k=32) ** 2)
        return f

    g_ref = jax.grad(loss("dense"), argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_gpt2_with_flash_attention():
    from deepspeed_tpu.models.gpt2 import (
        GPT2LMHead, gpt2_tiny, init_gpt2_params, make_gpt2_loss_fn)
    cfg = gpt2_tiny(use_flash_attention=True)
    model = GPT2LMHead(cfg)
    params = init_gpt2_params(model, jax.random.PRNGKey(0))
    loss_fn = make_gpt2_loss_fn(model)
    batch = {"input_ids": jnp.ones((2, 32), jnp.int32)}
    loss = loss_fn(params, batch, None)
    assert np.isfinite(float(loss))

    # parity with the dense-attention model
    cfg_d = gpt2_tiny(use_flash_attention=False)
    loss_d = make_gpt2_loss_fn(GPT2LMHead(cfg_d))(params, batch, None)
    np.testing.assert_allclose(float(loss), float(loss_d), rtol=1e-4)


# --- key-padding mask (round 3: the BERT padded-batch path) ---------------
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("causal", [False, True])
def test_key_padding_mask_matches_dense(impl, causal):
    rng = np.random.default_rng(5)
    B, T, H, D = 2, 256, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    kpm = np.ones((B, T), bool)
    kpm[0, 200:] = False          # padded tail, batch row 0
    kpm[1, 64:128] = False        # hole mid-sequence, row 1
    kpm = jnp.asarray(kpm)

    def f(impl_name):
        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=causal,
                                  implementation=impl_name,
                                  block_q=128, block_k=128,
                                  key_padding_mask=kpm)
            # only valid QUERY positions contribute (padded-query outputs
            # are unspecified by contract; causal row 0 of batch 1 only
            # sees masked keys after the hole starts — also excluded)
            q_ok = kpm[:, :, None, None]
            return (out * q_ok).astype(jnp.float32).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    vd, gd = f("dense")
    vi, gi = f(impl)
    np.testing.assert_allclose(float(vi), float(vd), rtol=2e-4)
    for a, b in zip(gd, gi):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-3, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_soft_key_bias_matches_dense(impl):
    """Soft additive penalties (not just hard masks) are honored exactly
    (the transformer layer passes collapsed additive masks through)."""
    rng = np.random.default_rng(7)
    B, T, H, D = 2, 256, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    bias = jnp.asarray(rng.uniform(-2.0, 0.0, (B, T)), jnp.float32)

    out_d = flash_attention(q, k, v, causal=False, implementation="dense",
                            key_bias=bias)
    out_i = flash_attention(q, k, v, causal=False, implementation=impl,
                            block_q=128, block_k=128, key_bias=bias)
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_d),
                               rtol=2e-4, atol=2e-5)


# --- in-kernel attention-prob dropout (round 4) ---------------------------
# The counter-based mask (dropout_multiplier) computes identically in the
# Pallas kernels (interpret mode here = the literal TPU kernel), the
# blockwise-XLA path and the dense reference, so "same seed ⇒ flash ==
# dense-with-the-same-mask" holds exactly — the parity contract the
# reference's in-kernel cuRAND dropout (dropout_kernels.cu) can't even
# offer its own dense fallback.

def test_dropout_multiplier_statistics():
    from deepspeed_tpu.ops.pallas.flash_attention import dropout_multiplier
    rate = 0.25
    T = S = 256
    m = dropout_multiplier(jnp.int32(1234), jnp.int32(3),
                           jnp.arange(T)[:, None], jnp.arange(S)[None, :],
                           rate)
    vals = np.unique(np.asarray(m))
    np.testing.assert_allclose(vals, [0.0, 1.0 / (1 - rate)], rtol=1e-6)
    keep_frac = float((np.asarray(m) > 0).mean())
    assert abs(keep_frac - 0.75) < 0.02, keep_frac
    # deterministic in the seed, different across seeds / heads
    m2 = dropout_multiplier(jnp.int32(1234), jnp.int32(3),
                            jnp.arange(T)[:, None], jnp.arange(S)[None, :],
                            rate)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m2))
    m3 = dropout_multiplier(jnp.int32(1235), jnp.int32(3),
                            jnp.arange(T)[:, None], jnp.arange(S)[None, :],
                            rate)
    assert (np.asarray(m) != np.asarray(m3)).mean() > 0.2


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("causal", [True, False])
def test_dropout_matches_dense_same_seed(impl, causal):
    q, k, v = qkv(T=64)
    seed = jnp.int32(42)

    def loss(impl_name):
        def f(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, implementation=impl_name,
                block_q=32, block_k=32,
                dropout_rate=0.2, dropout_seed=seed) ** 2)
        return f

    vd, gd = jax.value_and_grad(loss("dense"), argnums=(0, 1, 2))(q, k, v)
    vi, gi = jax.value_and_grad(loss(impl), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(vi), float(vd), rtol=1e-4)
    for a, b in zip(gi, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_dropout_seed_changes_output():
    q, k, v = qkv(T=64)
    o1 = flash_attention(q, k, v, implementation="pallas", block_q=32,
                         block_k=32, dropout_rate=0.3,
                         dropout_seed=jnp.int32(1))
    o2 = flash_attention(q, k, v, implementation="pallas", block_q=32,
                         block_k=32, dropout_rate=0.3,
                         dropout_seed=jnp.int32(2))
    assert not np.allclose(np.asarray(o1), np.asarray(o2))


def test_dropout_requires_seed():
    q, k, v = qkv(T=32)
    with pytest.raises(ValueError, match="dropout_seed"):
        flash_attention(q, k, v, dropout_rate=0.1)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("dropout", [0.0, 0.2])
def test_key_bias_gradient_matches_dense(impl, dropout):
    """d(key_bias) must be the true gradient on every implementation —
    the pallas backward emits per-head dbias partials from the dK/dV
    kernel (round 4; previously the pallas path returned zeros)."""
    rng = np.random.default_rng(11)
    B, T, H, D = 2, 64, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    bias = jnp.asarray(rng.uniform(-2.0, 0.0, (B, T)), jnp.float32)
    seed = jnp.int32(7) if dropout else None

    def loss(impl_name):
        def f(bias):
            return jnp.sum(flash_attention(
                q, k, v, causal=False, implementation=impl_name,
                block_q=32, block_k=32, key_bias=bias,
                dropout_rate=dropout, dropout_seed=seed) ** 2)
        return f

    g_ref = jax.grad(loss("dense"))(bias)
    g_got = jax.grad(loss(impl))(bias)
    assert float(jnp.abs(g_ref).max()) > 1e-3   # non-trivial gradient
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


def test_gpt2_flash_trains_with_dropout():
    """The round-3 gate (dense fallback whenever attention dropout was
    active) is gone: the flash path takes dropout natively."""
    from deepspeed_tpu.models.gpt2 import (
        GPT2LMHead, gpt2_tiny, init_gpt2_params, make_gpt2_loss_fn)
    cfg = gpt2_tiny(use_flash_attention=True, dropout=0.1)
    model = GPT2LMHead(cfg)
    params = init_gpt2_params(model, jax.random.PRNGKey(0))
    loss_fn = make_gpt2_loss_fn(model)
    batch = {"input_ids": jnp.ones((2, 32), jnp.int32)}
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, jax.random.PRNGKey(1)))(params)
    assert np.isfinite(float(loss))
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.parametrize("impl", ["dense", "xla", "pallas"])
def test_dropout_head_offset_matches_global_slice(impl):
    """Tensor-parallel head shards: running each half of the heads with
    (dropout_head_offset, dropout_num_heads) must reproduce the
    replicated full-head run's dropout EXACTLY — the mask hashes global
    coordinates, so the sharding is invisible (round 5; this is what
    lets TP blocks keep the fused attention path under dropout)."""
    q, k, v = qkv(T=64, H=4)
    seed = jnp.int32(7)
    kw = dict(causal=True, implementation=impl, block_q=32, block_k=32,
              dropout_rate=0.3, dropout_seed=seed)
    full = flash_attention(q, k, v, **kw)
    parts = [flash_attention(q[:, :, lo:lo + 2], k[:, :, lo:lo + 2],
                             v[:, :, lo:lo + 2], dropout_head_offset=lo,
                             dropout_num_heads=4, **kw)
             for lo in (0, 2)]
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(parts, axis=2)), np.asarray(full))


def test_dropout_head_offset_gradients_match_global_slice():
    """Same invariance through the backward (the bwd kernels regenerate
    the mask from the same globalized coordinates)."""
    q, k, v = qkv(T=64, H=4)
    seed = jnp.int32(11)

    def loss_full(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, implementation="pallas", block_q=32,
            block_k=32, dropout_rate=0.3, dropout_seed=seed) ** 2)

    def loss_shard(lo):
        def f(q, k, v):
            return jnp.sum(flash_attention(
                q[:, :, lo:lo + 2], k[:, :, lo:lo + 2], v[:, :, lo:lo + 2],
                causal=True, implementation="pallas", block_q=32,
                block_k=32, dropout_rate=0.3, dropout_seed=seed,
                dropout_head_offset=lo, dropout_num_heads=4) ** 2)
        return f

    _, g_full = jax.value_and_grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for lo in (0, 2):
        _, g_sh = jax.value_and_grad(loss_shard(lo),
                                     argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_sh, g_full):
            # the shard's grad is the full grad restricted to its heads
            np.testing.assert_allclose(
                np.asarray(a)[:, :, lo:lo + 2],
                np.asarray(b)[:, :, lo:lo + 2], rtol=1e-5, atol=1e-5)
            assert np.all(np.asarray(a)[:, :, :lo] == 0)


@pytest.mark.parametrize("dropout", [0.0, 0.3])
def test_pallas_under_mesh_shard_maps_itself(dropout):
    """GSPMD cannot partition a Mosaic kernel, so inside `placed_on_mesh`
    the pallas path wraps itself in shard_map over the axes the caller
    named. Values, gradients and — through the global head offset —
    dropout bits equal the call without a placement."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.ops.pallas.flash_attention import placed_on_mesh

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    q, k, v = qkv(B=2, T=64, H=4)
    kw = dict(causal=True, implementation="pallas", block_q=32,
              block_k=32, dropout_rate=dropout,
              dropout_seed=jnp.int32(5) if dropout else None)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, **kw) ** 2)

    def on_mesh(q, k, v):
        with placed_on_mesh(mesh, rows="dp", heads="tp"):
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    placed = [jax.device_put(x, NamedSharding(mesh, P("dp")))
              for x in (q, k, v)]
    assert "shard_map" in str(jax.make_jaxpr(on_mesh)(*placed))
    assert "shard_map" not in str(jax.make_jaxpr(loss)(q, k, v))
    got, g_got = jax.jit(on_mesh)(*placed)
    ref, g_ref = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    # rows or heads that do not divide their axis are refused, never
    # silently replicated
    with placed_on_mesh(mesh, rows="dp", heads="tp"), \
            pytest.raises(ValueError, match="do not divide"):
        flash_attention(q[:, :, :3], k[:, :, :3], v[:, :, :3], **kw)

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


# --- the kernels on the model's own layout (PR 30) -------------------------
# q, k, v are read as [B, T, H*D] as the projections wrote them: a
# 128-lane group of heads a grid step, the live tiles of the causal
# triangle only, and inside a tile the live sub-tiles. Every layout the
# lane grouping can take runs here in interpret mode (the literal
# kernels) against the float32 dense reference: values and all three
# gradients.

import importlib

_fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

# (heads, head size): what `_lane_groups` makes of each
LAYOUTS = {
    "d64-h2-one-group": (2, 64),
    "d64-h4-two-groups": (4, 64),
    "d64-h25-odd-last-group-half-full": (25, 64),    # GPT-2 XL
    "d64-h1-half-group": (1, 64),                    # a TP head shard
    "d128-h3-a-head-a-group": (3, 128),              # OLMoE's head size
    "d256-h1-two-lane-tiles-a-head": (1, 256),
    "d32-h5-four-a-group-ragged": (5, 32),
    "d16-h4-narrower-than-128-lanes": (4, 16),
    "d80-h2-folded": (2, 80),                        # GPT-2 2.7B's head
}
# (T, block): 1, 2 and 8 tiles a side
TILES = {"1-tile": (32, 32), "2-tiles": (64, 32), "8-tiles": (256, 32)}


def _lane_grouping_of(name):
    return _fa._lane_groups(*LAYOUTS[name])


def test_lane_groups():
    """(lanes a block, heads a block, blocks a row); None = folded."""
    assert _lane_grouping_of("d64-h2-one-group") == (128, 2, 1)
    assert _lane_grouping_of("d64-h25-odd-last-group-half-full") == \
        (128, 2, 13)
    assert _lane_grouping_of("d64-h1-half-group") == (64, 1, 1)
    assert _lane_grouping_of("d128-h3-a-head-a-group") == (128, 1, 3)
    assert _lane_grouping_of("d256-h1-two-lane-tiles-a-head") == (256, 1, 1)
    assert _lane_grouping_of("d32-h5-four-a-group-ragged") == (128, 4, 2)
    assert _lane_grouping_of("d16-h4-narrower-than-128-lanes") == (64, 4, 1)
    assert _lane_grouping_of("d80-h2-folded") is None


def _against_dense(shape, causal, block_q, block_k, bias=False,
                   dropout=0.0, offset=None, seed=0, tol=2e-5):
    """Values and every gradient of the pallas path (interpret mode)
    against the dense reference, both in float32, on one random
    cotangent. Returns the largest absolute differences."""
    B, T, H, D = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks[:3])
    ct = jax.random.normal(ks[3], shape, jnp.float32)
    kw = dict(causal=causal, block_q=block_q, block_k=block_k)
    args = [q, k, v]
    if bias:
        args.append(jax.random.uniform(ks[4], (B, T), jnp.float32, -2., 0.))
    if dropout:
        kw.update(dropout_rate=dropout, dropout_seed=jnp.int32(1234 + seed))
    if offset is not None:
        kw.update(dropout_head_offset=offset[0],
                  dropout_num_heads=offset[1])

    def run(impl):
        def f(q, k, v, *b):
            out = flash_attention(q, k, v, implementation=impl,
                                  key_bias=b[0] if b else None, **kw)
            return jnp.sum(out * ct), out
        (_, out), grads = jax.value_and_grad(
            f, argnums=tuple(range(len(args))), has_aux=True)(*args)
        return (out,) + grads

    worst = []
    for got, ref in zip(run("pallas"), run("dense")):
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=tol * 5, atol=tol)
        worst.append(float(jnp.abs(got - ref).max()))
    return worst


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("tiles", list(TILES))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_layouts_match_dense(layout, tiles, causal):
    H, D = LAYOUTS[layout]
    T, block = TILES[tiles]
    _against_dense((2 if H < 8 else 1, T, H, D), causal, block, block)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("layout", [
    "d64-h2-one-group", "d64-h25-odd-last-group-half-full",
    "d128-h3-a-head-a-group", "d32-h5-four-a-group-ragged",
    "d80-h2-folded"])
def test_layouts_key_bias_and_its_gradient(layout, causal):
    H, D = LAYOUTS[layout]
    worst = _against_dense((2, 64, H, D), causal, 32, 32, bias=True)
    assert len(worst) == 5          # out, dq, dk, dv, dbias


@pytest.mark.parametrize("offset", [None, (3, 40)], ids=["whole", "shard"])
@pytest.mark.parametrize("layout", [
    "d64-h2-one-group", "d64-h25-odd-last-group-half-full",
    "d64-h1-half-group", "d128-h3-a-head-a-group",
    "d16-h4-narrower-than-128-lanes", "d80-h2-folded"])
def test_layouts_dropout_mask_is_the_dense_one(layout, offset):
    """The kernels draw `dropout_multiplier` on the global (head, query,
    key) coordinates, as the dense reference does with the same seed: one
    differing bit of the mask would move an output by a whole
    probability, far over the tolerance. With `dropout_head_offset` the
    local heads are a shard of 40."""
    H, D = LAYOUTS[layout]
    _against_dense((2, 64, H, D), True, 32, 32, dropout=0.25,
                   offset=offset, bias=(offset is None))


@pytest.mark.parametrize("blocks", [(128, 128), (64, 64), (32, 64), (64, 32),
                                    (16, 16)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("form", ["causal", "full", "causal-bias-dropout"])
def test_tile_kinds_match_dense(form, blocks):
    """A tile under the diagonal runs unmasked; a square one on it as a
    staircase of strips that leaves out what lies over the diagonal
    (128 x 128: 2 strips for the forward, 4 for dQ, 8 for dK/dV; 16 x 16
    one: whole and masked); any other that crosses it whole and masked
    (uneven blocks)."""
    rich = form == "causal-bias-dropout"
    _against_dense((2, 256, 3, 64), form != "full", *blocks, bias=rich,
                   dropout=0.2 if rich else 0.0)


def test_the_medium_cells_row():
    """One row of the GPT-2 medium cell, two heads of it: T 1024 is one
    tile on the diagonal, in strips of 512, 256 and 128."""
    lay = _fa._Layout.of((1, 1024, 2, 64), 1024, 1024, 1024)
    assert (lay.width, lay.heads, lay.groups) == (128, 2, 1)
    assert [_fa._strips(k, 1024) for k in ("fwd", "dq", "dkv")] == [2, 4, 8]
    _against_dense((1, 1024, 2, 64), True, 1024, 1024, tol=5e-5)


def test_tile_walk_visits_live_tiles_only():
    """q-major for the forward and dQ, kv-major for dK/dV; the flags mark
    each run's first and last step, the kind what the mask does to the
    tile."""
    U, X, D = _fa.UNDER, _fa.CROSSING, _fa.ON_DIAGONAL
    qi, ki, first, last, kind = _fa._tile_walk(3, 3, 32, 32, True,
                                               kv_major=False)
    assert list(zip(qi, ki)) == [(0, 0), (1, 0), (1, 1), (2, 0), (2, 1),
                                 (2, 2)]
    assert list(first) == [1, 1, 0, 1, 0, 0]
    assert list(last) == [1, 0, 1, 0, 0, 1]
    assert list(kind) == [D, U, D, U, U, D]
    qi, ki, first, last, kind = _fa._tile_walk(3, 3, 32, 32, True,
                                               kv_major=True)
    assert list(zip(ki, qi)) == [(0, 0), (0, 1), (0, 2), (1, 1), (1, 2),
                                 (2, 2)]
    assert list(first) == [1, 0, 0, 1, 0, 1]
    assert list(last) == [0, 0, 1, 0, 1, 1]
    assert list(kind) == [D, U, U, D, U, D]
    # uneven blocks: no tile is square, so none is taken in strips
    kind = _fa._tile_walk(4, 2, 16, 32, True, False)[4]
    assert set(kind) == {U, X}
    # not causal: the whole rectangle, nothing to mask
    full = _fa._tile_walk(2, 4, 32, 32, False, False)
    assert full[0].size == 8 and set(full[4]) == {U}
    # blocks of 512: 3 of 4 tiles at T 1024, 36 of 64 at T 4096
    assert _fa._tile_walk(2, 2, 512, 512, True, False)[0].size == 3
    assert _fa._tile_walk(8, 8, 512, 512, True, True)[0].size == 36
    # the cells, in blocks of 1024: T 1024 is one tile, on the diagonal;
    # T 4096 is 10 of 16, and with 4 of the 10 on the diagonal it takes
    # those whole (strips would be traced for 0.3 % of that step)
    assert list(_fa._tile_walk(1, 1, 1024, 1024, True, False)[4]) == [D]
    long = _fa._tile_walk(4, 4, 1024, 1024, True, False)
    assert long[0].size == 10 and set(long[4]) == {U, X}
    assert set(_fa._tile_walk(3, 3, 1024, 1024, True, True)[4]) == {U, D}
    # keys past the last query (S > T): each such kv tile is still
    # visited once, masked empty, so its zero gradient is written
    qi, ki, _, _, kind = _fa._tile_walk(1, 2, 32, 32, True, True)
    assert list(zip(ki, qi)) == [(0, 0), (1, 0)] and kind[1] == X


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("kind", ["UNDER", "CROSSING", "ON_DIAGONAL"])
def test_pieces_cover_what_the_mask_leaves(kind, kernel):
    """The pieces of a tile never overlap and hold every live score of
    it; those of a tile on the diagonal are a staircase that leaves out
    all but half a strip's width of the dead part."""
    lay = _fa._Layout(False, 128, 2, 1, 1, 2, 128, 128)
    pieces = _fa._pieces(getattr(_fa, kind), lay, kernel)
    seen = np.zeros((128, 128), int)
    for rows, cols, masked in pieces:
        seen[rows, cols] += 1
        assert masked == (kind != "UNDER")
    assert seen.max() == 1
    live = np.tril(np.ones((128, 128), bool))
    if kind == "ON_DIAGONAL":
        n = {"fwd": 2, "dq": 4, "dkv": 8}[kernel]
        assert len(pieces) == n == _fa._strips(kernel, 128)
        assert (seen[live] == 1).all()
        assert seen.sum() == 128 * 128 * (n + 1) // (2 * n)
        # forward and dQ: a row's columns are all in one piece (its
        # statistics see them together); dK/dV: a column's rows are
        owner = np.zeros(128, int)
        for rows, cols, _ in pieces:
            owner[cols if kernel == "dkv" else rows] += 1
        assert (owner == 1).all()
    else:
        assert seen.sum() == 128 * 128


def test_strips_stay_whole_tiles_of_rows():
    """A strip is never cut finer than 16 rows (a bf16 tile)."""
    assert [_fa._strips("dkv", b) for b in (1024, 128, 64, 32, 16, 48)] \
        == [8, 8, 4, 2, 1, 1]
    assert _fa._strips("fwd", 1024) == 2 and _fa._strips("dq", 512) == 4


def test_narrow_head_shard_equals_its_slice_of_the_whole():
    """One head of 64 (a 64-lane row: a tensor-parallel shard narrower
    than a lane group) with its global offset, against the same head
    inside the 4-head call that pairs it with its neighbour."""
    q, k, v = qkv(T=64, H=4, D=64)
    kw = dict(causal=True, implementation="pallas", block_q=32, block_k=32,
              dropout_rate=0.3, dropout_seed=jnp.int32(9))
    full = flash_attention(q, k, v, **kw)
    for lo in range(4):
        part = flash_attention(q[:, :, lo:lo + 1], k[:, :, lo:lo + 1],
                               v[:, :, lo:lo + 1], dropout_head_offset=lo,
                               dropout_num_heads=4, **kw)
        np.testing.assert_array_equal(np.asarray(part),
                                      np.asarray(full[:, :, lo:lo + 1]))


@pytest.mark.parametrize("layout", ["d64-h25-odd-last-group-half-full",
                                    "d128-h3-a-head-a-group"])
def test_layouts_under_shard_map_over_data(layout):
    """The four-chip cell's placement: batch rows over `data`, every
    head on every chip (GPT-2 XL's 25 among them), the kernel inside the
    `shard_map` that `placed_on_mesh` asks for."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.ops.pallas.flash_attention import placed_on_mesh

    H, D = LAYOUTS[layout]
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1),
                ("data", "model"))
    q, k, v = qkv(B=4, T=64, H=H, D=D)
    kw = dict(causal=True, implementation="pallas", block_q=32, block_k=32,
              dropout_rate=0.2, dropout_seed=jnp.int32(5))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, **kw) ** 2)

    def on_mesh(q, k, v):
        with placed_on_mesh(mesh, rows="data", heads="model"):
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    placed = [jax.device_put(x, NamedSharding(mesh, P("data")))
              for x in (q, k, v)]
    got, g_got = jax.jit(on_mesh)(*placed)
    ref, g_ref = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    # a sum of 4 x 64 x H x D squares, added up chip by chip
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-4)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def _kernel_bodies(shape):
    """{kernel name: its body's jaxpr} for forward + backward at
    ``shape``, traced on abstract values (nothing runs)."""
    from deepspeed_tpu.analysis.kernels import extract_pallas_calls

    def loss(q, k, v):
        return _fa._flash_pallas(q, k, v, None, None, 0, True,
                                 shape[-1] ** -0.5, 1024, 1024, 0.0, None,
                                 True).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    from deepspeed_tpu.analysis import kernels as ka
    hits = []
    ka._walk_static(closed.jaxpr, hits, set())
    return {eqn.params["name"]: eqn.params["jaxpr"] for eqn, _ in hits}


def _count_eqns(jaxpr):
    from deepspeed_tpu.analysis.kernels import _param_jaxprs
    return sum(1 + sum(_count_eqns(sub) for sub in _param_jaxprs(e.params))
               for e in jaxpr.eqns)


@pytest.mark.parametrize("heads,head_dim", [(16, 64), (16, 128)],
                         ids=["gpt2-d64", "olmoe-d128"])
def test_kernel_bodies_do_not_grow_with_the_tiles(heads, head_dim):
    """The set-up budget: a kernel's traced body at T 4096 (4 x 4 tiles
    of 1024) is no larger than at T 1024 (one tile), and the same at
    T 8192 (8 x 8). Tiles are grid steps read from prefetched tables;
    nothing is unrolled over them. (Tracing is paid in every process,
    cached compile or not: in the OLMoE cell each traced equation of the
    train step cost ~5 ms of set-up, `PERF.md` section 6, PR 30.)"""
    short = _kernel_bodies((1, 1024, heads, head_dim))
    long = _kernel_bodies((1, 4096, heads, head_dim))
    longer = _kernel_bodies((1, 8192, heads, head_dim))
    assert set(short) == {"ds_flash_fwd", "ds_flash_dq", "ds_flash_dkv"}
    for name in short:
        assert _count_eqns(long[name]) <= _count_eqns(short[name]), name
        assert _count_eqns(long[name]) == _count_eqns(longer[name]), name
    # a long walk's body: two kinds of tile (under the diagonal, and
    # crossing it), each once a head of the lane group; the parent's one
    # body was 73 / 56 / 65 equations
    per_head = 2 if head_dim == 64 else 1
    assert sum(_count_eqns(b) for b in long.values()) < 350 * per_head

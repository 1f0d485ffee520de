"""DeepSpeedTransformerLayer parity tests — the analog of the reference's
`tests/unit/test_cuda_forward.py`/`test_cuda_backward.py` (339+330 LoC):
the fused layer is checked against an independent plain-JAX BERT layer
across shapes and config flags, forward and backward, tolerance-based."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import (
    DeepSpeedTransformerConfig, DeepSpeedTransformerLayer,
    init_transformer_layer)


def _plain_reference(params, x, mask, cfg):
    """Straight-line BERT encoder block (the `tests/unit/modeling.py`
    fixture role): no fusion tricks, fp32, same weight layout."""
    H, heads = cfg.hidden_size, cfg.heads
    B, T, _ = x.shape

    def ln(y, w, b):
        mu = y.mean(-1, keepdims=True)
        var = y.var(-1, keepdims=True)
        return (y - mu) / jnp.sqrt(var + 1e-12) * w + b

    def attention(y):
        qkv = y @ params["attn_qkvw"] + params["attn_qkvb"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        hd = H // heads
        q = q.reshape(B, T, heads, hd)
        k = k.reshape(B, T, heads, hd)
        v = v.reshape(B, T, heads, hd)
        att = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(hd)
        if mask is not None:
            att = att + mask
        att = jax.nn.softmax(att, axis=-1)
        ctx = jnp.einsum("bhts,bshd->bthd", att, v).reshape(B, T, H)
        return ctx @ params["attn_ow"] + params["attn_ob"]

    def ffn(y):
        h = jax.nn.gelu(y @ params["inter_w"] + params["inter_b"],
                        approximate=False)
        return h @ params["output_w"] + params["output_b"]

    if cfg.pre_layer_norm:
        x = x + attention(ln(x, params["attn_nw"], params["attn_nb"]))
        x = x + ffn(ln(x, params["norm_w"], params["norm_b"]))
    else:
        x = ln(x + attention(x), params["attn_nw"], params["attn_nb"])
        x = ln(x + ffn(x), params["norm_w"], params["norm_b"])
    return x


def _make(cfg_kwargs, B=3, T=16):
    cfg = DeepSpeedTransformerConfig(
        batch_size=B, max_seq_length=T, hidden_size=64,
        intermediate_size=256, heads=4, attn_dropout_ratio=0.0,
        hidden_dropout_ratio=0.0, num_hidden_layers=2,
        initializer_range=0.02, **cfg_kwargs)
    layer = DeepSpeedTransformerLayer(cfg)
    params = init_transformer_layer(layer, jax.random.PRNGKey(0),
                                    batch_size=B, seq_len=T)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, 64), jnp.float32)
    return cfg, layer, params, x


@pytest.mark.parametrize("pre_ln", [True, False])
@pytest.mark.parametrize("use_mask", [False, True])
def test_forward_parity(pre_ln, use_mask):
    cfg, layer, params, x = _make({"pre_layer_norm": pre_ln})
    mask = None
    if use_mask:
        keep = jnp.asarray(
            np.random.default_rng(2).random((3, 16)) > 0.25)
        mask = jnp.where(keep, 0.0, -10000.0)[:, None, None, :]
    out = layer.apply({"params": params}, x, mask, True)
    ref = _plain_reference(params, x, mask, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("pre_ln", [True, False])
def test_backward_parity(pre_ln):
    cfg, layer, params, x = _make({"pre_layer_norm": pre_ln})

    def fused_loss(p):
        return jnp.sum(layer.apply({"params": p}, x, None, True) ** 2)

    def ref_loss(p):
        return jnp.sum(_plain_reference(p, x, None, cfg) ** 2)

    g_fused = jax.grad(fused_loss)(params)
    g_ref = jax.grad(ref_loss)(params)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g_fused[k]), np.asarray(g_ref[k]),
            rtol=5e-4, atol=5e-5, err_msg=f"grad mismatch in {k}")


# rtol 1e-5 between two programs: as the optimizing compiler rounds them
@pytest.mark.full_compile
@pytest.mark.parametrize("knob", ["normalize_invertible", "gelu_checkpoint",
                                  "attn_dropout_checkpoint"])
def test_memory_knobs_preserve_values(knob):
    """The remat memory knobs must be numerically invisible, fwd and bwd
    (the reference's knob matrix in test_cuda_backward.py)."""
    cfg0, layer0, params, x = _make({})
    cfg1, layer1, _, _ = _make({knob: True})

    out0 = layer0.apply({"params": params}, x, None, True)
    out1 = layer1.apply({"params": params}, x, None, True)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1),
                               rtol=1e-6)

    g0 = jax.grad(lambda p: jnp.sum(
        layer0.apply({"params": p}, x, None, True) ** 2))(params)
    g1 = jax.grad(lambda p: jnp.sum(
        layer1.apply({"params": p}, x, None, True) ** 2))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                np.asarray(b), rtol=1e-5,
                                                atol=1e-6),
        g0, g1)


def test_dropout_deterministic_with_key():
    cfg = DeepSpeedTransformerConfig(
        hidden_size=32, intermediate_size=128, heads=4,
        attn_dropout_ratio=0.1, hidden_dropout_ratio=0.1,
        num_hidden_layers=1)
    layer = DeepSpeedTransformerLayer(cfg)
    params = init_transformer_layer(layer, jax.random.PRNGKey(0),
                                    batch_size=2, seq_len=8)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    key = jax.random.PRNGKey(3)
    a = layer.apply({"params": params}, x, None, False,
                    rngs={"dropout": key})
    b = layer.apply({"params": params}, x, None, False,
                    rngs={"dropout": key})
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = layer.apply({"params": params}, x, None, False,
                    rngs={"dropout": jax.random.PRNGKey(4)})
    assert not np.allclose(np.asarray(a), np.asarray(c))


def test_config_from_dict_and_json(tmp_path):
    d = {"hidden_size": 128, "heads": 8, "pre_layer_norm": False,
         "stochastic_mode": True}
    cfg = DeepSpeedTransformerConfig.from_dict(d)
    assert cfg.hidden_size == 128 and not cfg.pre_layer_norm
    import json
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps(d))
    cfg2 = DeepSpeedTransformerConfig.from_json_file(str(p))
    assert cfg2.heads == 8 and cfg2.stochastic_mode


@pytest.mark.slow
def test_jit_and_seq_scaling():
    """Layer compiles under jit and handles the reference's shape matrix
    (a slice of test_cuda_forward's (batch, seq, hidden, heads) grid)."""
    for B, T, H, heads in [(1, 8, 32, 4), (4, 32, 64, 8), (2, 25, 48, 3)]:
        cfg = DeepSpeedTransformerConfig(
            hidden_size=H, intermediate_size=4 * H, heads=heads,
            num_hidden_layers=1)
        layer = DeepSpeedTransformerLayer(cfg)
        params = init_transformer_layer(layer, jax.random.PRNGKey(0),
                                        batch_size=B, seq_len=T)
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, H))
        f = jax.jit(lambda p, y: layer.apply({"params": p}, y, None, True))
        out = f(params, x)
        assert out.shape == (B, T, H)
        assert np.isfinite(np.asarray(out)).all()

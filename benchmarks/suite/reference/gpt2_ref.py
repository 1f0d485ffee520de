"""Plain reference for the GPT-2 configurations: the published forward
pass and next-token loss in straightforward ``jax.numpy`` and float32.
No kernels, no cache, no batching tricks, no engine. Matrix products run
at ``highest`` precision (on a TPU a float32 product is otherwise done
in bf16 passes).

It follows Radford et al. 2019 / the public ``GPT2LMHeadModel``: learned
position embeddings, pre-LayerNorm blocks, fused QKV, causal softmax
attention scaled by 1/sqrt(head size), tanh-approximated GELU
(``gelu_new``), final LayerNorm, output head tied to the token
embedding. Departure, noted in the configuration files: LayerNorm's
epsilon is the configuration's ``layer_norm_epsilon`` as run (1e-6, the
program's; published 1e-5).

``params`` is the program's parameter tree (``wte``, ``wpe``,
``h_<i>/{ln_1, attn/{c_attn, c_proj}, ln_2, mlp/{c_fc, c_proj}}``,
``ln_f``), read in float32 whatever type it is stored in.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(p["scale"]) + \
        _f32(p["bias"])


def _dense(x, p):
    return jnp.matmul(x, _f32(p["kernel"]), precision=HIGHEST) + \
        _f32(p["bias"])


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _attention(x, p, n_head):
    B, T, C = x.shape
    q, k, v = jnp.split(_dense(x, p["c_attn"]), 3, axis=-1)
    q, k, v = (a.reshape(B, T, n_head, C // n_head) for a in (q, k, v))
    att = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST) / \
        jnp.sqrt(jnp.float32(C // n_head))
    causal = jnp.tril(jnp.ones((T, T), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    y = jnp.einsum("bhts,bshd->bthd", att, v, precision=HIGHEST)
    return _dense(y.reshape(B, T, C), p["c_proj"])


def logits(params, input_ids, n_head, eps):
    """``[B, T, vocab]`` float32 logits of ``input_ids`` ``[B, T]``."""
    with jax.default_matmul_precision("highest"):
        T = input_ids.shape[1]
        x = _f32(params["wte"])[input_ids] + _f32(params["wpe"])[None, :T]
        n_layer = sum(1 for k in params if str(k).startswith("h_"))
        for i in range(n_layer):
            p = params[f"h_{i}"]
            x = x + _attention(_layer_norm(x, p["ln_1"], eps), p["attn"],
                               n_head)
            h = _gelu_new(_dense(_layer_norm(x, p["ln_2"], eps),
                                 p["mlp"]["c_fc"]))
            x = x + _dense(h, p["mlp"]["c_proj"])
        x = _layer_norm(x, params["ln_f"], eps)
        return jnp.matmul(x, _f32(params["wte"]).T, precision=HIGHEST)


def loss(params, input_ids, n_head, eps):
    """Mean next-token cross entropy over all but the last position."""
    lg = logits(params, input_ids, n_head, eps)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)
    return -picked.mean()

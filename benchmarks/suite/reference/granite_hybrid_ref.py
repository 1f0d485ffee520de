"""Plain reference for the Granite 4.0-H (``granitemoehybrid``)
configurations: the published forward pass in straightforward
``jax.numpy`` and float32, one sequence at a time. No kernel, no cache,
no pages, no batching, no chunks: the state-space recurrence is written
as the recurrence, **one token at a time** under ``lax.scan``, so
nothing here is shared with the program's chunked scan or its one-step
decode update. Matrix products run at ``highest`` precision (on a TPU a
float32 product is otherwise done in bf16 passes).

It follows ``transformers/models/granitemoehybrid/
modeling_granitemoehybrid.py`` (whose Mamba layer is Bamba's; Dao & Gu
2024, arXiv:2405.21060). With ``n = RMSNorm(h)`` (float32 statistics,
learned weight, ``rms_norm_eps``):

- stream: ``h = embed[tokens] * embedding_multiplier``; a layer is
  ``h = h + residual_multiplier * mixer(RMSNorm(h))`` and then
  ``h = h + residual_multiplier * mlp(RMSNorm(h))``; after the last,
  ``logits = RMSNorm(h) @ embed^T / logits_scaling`` (tied head).
- MLP: ``[g, u] = n W_in``; ``(silu(g) * u) W_out``.
- attention (``layer_types[i] == "attention"``): ``q = n W_q`` as
  ``num_attention_heads`` heads, ``k = n W_k`` and ``v = n W_v`` as
  ``num_key_value_heads`` heads, each key head repeated for its group of
  query heads (query head ``h`` reads key head ``h // group``); no
  positional encoding; ``softmax(q k^T * attention_multiplier)`` under
  the causal mask; ``W_o``.
- Mamba-2 (``"mamba"``): ``[z, xBC, dt] = n W_in``; ``xBC_t = silu(b +
  sum_k w_k xBC_{t-K+1+k})`` (depthwise, zeros before the sequence);
  ``xBC`` splits into ``x`` (``mamba_n_heads`` heads of
  ``mamba_d_head``), ``B`` and ``C`` (``mamba_d_state``, one group);
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per head
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` from ``S = 0``,
  ``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y * silu(z)) * w`` over the
  whole inner width (the gate before the norm); ``y W_out``.

What the published ``config.json`` does not give, taken from that file's
conventions and listed under the configuration's ``assumed``: the order
of gate and norm; no clamp on ``dt`` (``time_step_limit`` (0, inf));
one norm group.

``params`` is the program's parameter tree (``embed``,
``layers_<i>/{input_norm, mixer/{in_proj, conv_weight [K, channels],
conv_bias, dt_bias, A_log, D, norm_weight, out_proj} | attn/{q_proj,
k_proj, v_proj, o_proj}, post_norm, mlp/{w_in, w_out}}``,
``final_norm``; a norm holds its ``weight``; a product is ``x @ W``
with ``W`` stored ``[in, out]``), read in float32 whatever type it is
stored in, **a layer at a time**: :func:`forward` is a Python loop over
jitted layer functions, so that beside a 12 GB engine only one layer's
float32 copy exists at a time.

``cfg`` is a configuration file's dict (the published keys).
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MAMBA, ATTENTION = "mamba", "attention"


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * \
        _f32(weight)


def mlp(n, p):
    gu = _mm(n, p["w_in"])
    half = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :half]) * gu[..., half:], p["w_out"])


def attention(n, p, cfg):
    """``n`` ``[T, C]`` -> ``[T, C]``."""
    T = n.shape[0]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = _mm(n, p["q_proj"]).reshape(T, Hq, -1)
    k = _mm(n, p["k_proj"]).reshape(T, Hkv, -1)
    v = _mm(n, p["v_proj"]).reshape(T, Hkv, -1)
    k, v = (jnp.repeat(a, Hq // Hkv, axis=1) for a in (k, v))
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(qh, kh, vh):       # one head at a time: [T, T] scores
        s = jnp.matmul(qh, kh.T, precision=HIGHEST) * \
            cfg["attention_multiplier"]
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(s, axis=-1), vh,
                          precision=HIGHEST)

    y = jax.lax.map(lambda a: head(*a), tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    return _mm(jnp.moveaxis(y, 0, 1).reshape(T, -1), p["o_proj"])


def mamba(n, p, cfg, state_at=None):
    """``n`` ``[T, C]`` -> ``(out [T, C], S)``: ``S`` ``[H, P, N]`` is
    the state after token ``state_at`` (``None``: after the last)."""
    T = n.shape[0]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"]
    K = cfg["mamba_d_conv"]
    d_in = H * P
    zxd = _mm(n, p["in_proj"])
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:d_in + d_in + 2 * N], \
        zxd[:, 2 * d_in + 2 * N:]
    w = _f32(p["conv_weight"])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = _f32(p["conv_bias"]) + sum(
        w[k] * padded[k:k + T] for k in range(K))
    u = jax.nn.silu(conv)
    x, B, C = u[:, :d_in].reshape(T, H, P), u[:, d_in:d_in + N], \
        u[:, d_in + N:]
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))
    A = -jnp.exp(_f32(p["A_log"]))
    at = T - 1 if state_at is None else state_at

    def step(carry, inp):
        S, kept = carry
        t, x_t, dt_t, B_t, C_t = inp
        S = jnp.exp(dt_t * A)[:, None, None] * S + \
            (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        kept = jnp.where(t == at, S, kept)
        return (S, kept), (S * C_t[None, None, :]).sum(-1)

    zero = jnp.zeros((H, P, N), jnp.float32)
    (_, kept), y = jax.lax.scan(
        step, (zero, zero), (jnp.arange(T), x, dt, B, C))
    y = y + _f32(p["D"])[None, :, None] * x
    y = y.reshape(T, d_in) * jax.nn.silu(z)
    y = _rms_norm(y, p["norm_weight"], cfg["rms_norm_eps"])
    return _mm(y, p["out_proj"]), kept


@functools.partial(jax.jit, static_argnames=("kind", "cfg"))
def _layer(h, p, state_at, kind, cfg):
    cfg = dict(cfg)
    n = _rms_norm(h, p["input_norm"]["weight"], cfg["rms_norm_eps"])
    if kind == ATTENTION:
        y, state = attention(n, p["attn"], cfg), None
    else:
        y, state = mamba(n, p["mixer"], cfg, state_at)
    h = h + cfg["residual_multiplier"] * y
    n = _rms_norm(h, p["post_norm"]["weight"], cfg["rms_norm_eps"])
    return h + cfg["residual_multiplier"] * mlp(n, p["mlp"]), state


@functools.partial(jax.jit, static_argnames=("cfg",))
def _embed(embed, tokens, cfg):
    return _f32(embed[tokens]) * dict(cfg)["embedding_multiplier"]


@functools.partial(jax.jit, static_argnames=("cfg",))
def _head(h, final_norm, embed, rows, cfg):
    cfg = dict(cfg)
    n = _rms_norm(h[rows], final_norm["weight"], cfg["rms_norm_eps"])
    return jnp.matmul(n, _f32(embed).T, precision=HIGHEST) / \
        cfg["logits_scaling"]


def _static(cfg):
    """The numbers the layer functions read, hashable."""
    keys = ("num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_d_conv",
            "rms_norm_eps", "attention_multiplier", "residual_multiplier",
            "embedding_multiplier", "logits_scaling")
    return tuple((k, cfg[k]) for k in keys)


def forward(params, tokens, cfg, rows=None, state_at=None, layers=None):
    """One sequence ``tokens`` ``[T]`` through the model. Returns
    ``(logits [len(rows), vocab], {layer name: S})``: the logits at the
    positions ``rows`` (default: all) and every Mamba layer's state
    after token ``state_at`` (default: the last). ``layers`` stops
    after that many layers (then the logits are ``None``): a layer's
    state needs only the layers before it."""
    static = _static(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    at = jnp.asarray(len(tokens) - 1 if state_at is None else state_at,
                     jnp.int32)
    kinds = cfg["layer_types"]
    h = _embed(params["embed"], tokens, static)
    states = {}
    for i, kind in enumerate(kinds[:layers]):
        name = f"layers_{i}"
        h, state = _layer(h, params[name], at, kind, static)
        if state is not None:
            states[name] = state
    if layers is not None and layers < len(kinds):
        return None, states
    rows = jnp.arange(len(tokens)) if rows is None else jnp.asarray(rows)
    return _head(h, params["final_norm"], params["embed"], rows,
                 static), states

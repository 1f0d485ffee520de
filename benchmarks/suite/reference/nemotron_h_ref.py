"""Plain reference for the Nemotron-H (``nemotron_h``) configurations:
the published forward pass in straightforward ``jax.numpy`` and float32,
one sequence at a time, written from the equations below and not from
the program. No kernel, no cache, no pages, no chunks, no duality form,
no sort, no grouped matmul: the state-space recurrence is the
recurrence, **one token at a time** under ``lax.scan``; attention is
every query over the whole prefix; every held expert is computed on
every token and weighted (by zero where the token did not choose it).
Matrix products run at ``highest`` precision (on a TPU a float32
product is otherwise done in bf16 passes).

It follows ``modeling_nemotron_h.py`` beside the ``config.json`` of
``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``. With ``n =
RMSNorm(h)`` (float32 statistics, a learned weight,
``layer_norm_epsilon``), no bias in any product, no multipliers:

- stream: ``h = embed[tokens]``; block ``i`` is ``h = h +
  f_i(RMSNorm(h))`` with ONE ``f_i``, by letter ``i`` of
  ``hybrid_override_pattern``; after the last, ``logits = RMSNorm(h)
  @ lm_head`` (untied).
- ``M`` (Mamba-2; Dao & Gu 2024, arXiv:2405.21060): ``[z, xBC, dt] = n
  W_in``; ``xBC_t = silu(b + sum_k w_k xBC_{t-K+1+k})`` (depthwise,
  ``conv_kernel`` taps, zeros before the sequence); ``xBC`` splits into
  ``x`` (``mamba_num_heads`` heads of ``mamba_head_dim``), ``B`` and
  ``C`` (``n_groups`` groups of ``ssm_state_size``; head ``h`` reads
  group ``h // (heads / groups)``); ``dt = softplus(dt + dt_bias)``;
  ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  (x) B_t`` from ``S = 0``, ``y_t = S_t C_t + D x_t``; ``y = y *
  silu(z)``, then RMS-normed over each group's ``d_inner / n_groups``
  channels and times the weight; ``y W_out``.
- ``*``: ``q = n W_q`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = n W_k`` and ``v = n W_v`` as
  ``num_key_value_heads`` heads (query head ``h`` reads key head ``h //
  group``); no positional encoding; ``softmax(q k^T head_dim^-0.5)``
  under the causal mask; ``W_o``.
- ``E``: ``s = sigmoid(n W_r)`` over ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` =
  ``e_score_correction_bias``) are chosen; their weights are ``s`` over
  the chosen ``s``'s sum (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``l = n W_down`` (``moe_latent_size``
  wide); ``r = sum_e w_e relu(l W1_e)^2 W2_e``; ``y = r W_up +
  relu(n V1)^2 V2`` (the shared expert reads ``n``, not ``l``).

**The share.** ``params`` may hold only some experts' banks (their
leading size) and some rows of the vocabulary: ``first_expert`` says
which expert the banks start at, and the routed sum runs over the held
experts only. Token ids are taken within the held rows.

Departures from the published code, none of which a random
initialisation can see or which are this reference's whole point:
(1) ``n_group`` = ``topk_group`` = 1, so the router's group stage is
the identity and is not written; (2) the router's product is float32 at
``highest`` on float32 copies of input and weight; (3) experts are
evaluated densely and masked, not dispatched; (4) no clamp on ``dt``
(``time_step_limit`` (0, inf)); (5) ``rope_theta`` /
``partial_rotary_factor`` are keys of the file that the published
attention does not read either: no rotary here; (6) the multi-token
prediction head (``num_nextn_predict_layers``) drafts tokens for
speculative decoding and is not part of this pass; (7)
``residual_in_fp32`` false and ``rescale_prenorm_residual`` (an
initialiser's scale of the out-projections) do not change the
equations; (8) no dropout, no mask but the causal one, one sequence.

``params`` is the program's parameter tree (``embed``, ``lm_head``
``[hidden, vocab]``, ``final_norm``, ``layers_<i>/{norm, mixer/{in_proj,
conv_weight [K, channels], conv_bias, dt_bias, A_log, D, norm_weight,
out_proj} | attn/{q_proj, k_proj, v_proj, o_proj} | experts/{router,
e_score_correction_bias, w_up, w_down, latent_down, latent_up,
shared_up, shared_down}}``; a norm holds its ``weight``; a product is
``x @ W`` with ``W`` stored ``[in, out]``), read in float32 whatever
type it is stored in, **a layer at a time and a block of tokens at a
time within it** (the token-local products; an expert at a time within
that; a head at a time in attention): :func:`forward` is a Python loop
over jitted layer functions, so that 5,120 tokens at the published
widths stand beside a 12 GB engine.

``cfg`` is a configuration file's dict (the published keys).
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MIXER, ATTENTION, EXPERTS = "M", "*", "E"
TOKEN_BLOCK = 1024


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


def _rms_norm(x, weight, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * \
        _f32(weight)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _blocks(fn, x, block=TOKEN_BLOCK):
    """``fn`` over the rows of ``x`` (an array, or a tuple of arrays
    with the same rows) in blocks (rows padded up to a whole number of
    them): the token-local parts."""
    n = jax.tree_util.tree_leaves(x)[0].shape[0]
    block = min(block, n)
    pad = -n % block
    xs = jax.tree_util.tree_map(
        lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
            (-1, block) + a.shape[1:]), x)
    out = jax.lax.map(fn, xs)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:])[:n], out)


def keys_values(n, p, cfg):
    """What a cache holds of the normed input ``n`` ``[T, hidden]``:
    ``(k, v)`` each ``[T, key heads, head_dim]``."""
    Hkv = cfg["num_key_value_heads"]
    k, v = _blocks(lambda x: (_mm(x, p["k_proj"]), _mm(x, p["v_proj"])),
                   _f32(n))
    return k.reshape(len(k), Hkv, -1), v.reshape(len(v), Hkv, -1)


def attention(n, p, cfg, scale=None, kv=None):
    """``n`` ``[T, C]`` -> ``[T, C]`` (``kv``: :func:`keys_values` of
    ``n``, where the caller has them)."""
    n = _f32(n)
    T = n.shape[0]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    scale = cfg["head_dim"] ** -0.5 if scale is None else scale
    q = _blocks(lambda x: _mm(x, p["q_proj"]), n).reshape(T, Hq, -1)
    k, v = kv or keys_values(n, p, cfg)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(i):                # one head at a time: [T, T] scores
        s = jnp.matmul(q[:, i], k[:, i // (Hq // Hkv)].T,
                       precision=HIGHEST) * scale
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(s, axis=-1),
                          v[:, i // (Hq // Hkv)], precision=HIGHEST)

    y = jax.lax.map(head, jnp.arange(Hq))
    return _blocks(lambda x: _mm(x, p["o_proj"]),
                   jnp.moveaxis(y, 0, 1).reshape(T, -1))


def gated_scan(n, p, cfg, state_at=None):
    """The mixer up to its gate: ``n`` ``[T, C]`` -> ``(y * silu(z)
    [T, d_inner], S)``: ``S`` ``[H, P, N]`` is the state after token
    ``state_at`` (``None``: after the last)."""
    n = _f32(n)
    T = n.shape[0]
    H, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
        cfg["ssm_state_size"]
    G, K = cfg["n_groups"], cfg["conv_kernel"]
    d_in = H * P
    zxd = _blocks(lambda x: _mm(x, p["in_proj"]), n)
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:2 * d_in + 2 * G * N], \
        zxd[:, 2 * d_in + 2 * G * N:]
    w = _f32(p["conv_weight"])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    u = jax.nn.silu(_f32(p["conv_bias"]) + sum(
        w[k] * padded[k:k + T] for k in range(K)))
    x = u[:, :d_in].reshape(T, H, P)
    B = u[:, d_in:d_in + G * N].reshape(T, G, N)
    C = u[:, d_in + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))
    A = -jnp.exp(_f32(p["A_log"]))
    at = T - 1 if state_at is None else state_at

    def step(carry, inp):
        S, kept = carry
        t, x_t, dt_t, B_t, C_t = inp
        B_h = jnp.repeat(B_t, H // G, axis=0)           # a head's group
        C_h = jnp.repeat(C_t, H // G, axis=0)
        S = jnp.exp(dt_t * A)[:, None, None] * S + \
            (dt_t[:, None] * x_t)[:, :, None] * B_h[:, None, :]
        kept = jnp.where(t == at, S, kept)
        return (S, kept), (S * C_h[:, None, :]).sum(-1)

    zero = jnp.zeros((H, P, N), jnp.float32)
    (_, kept), y = jax.lax.scan(
        step, (zero, zero), (jnp.arange(T), x, dt, B, C))
    y = y + _f32(p["D"])[None, :, None] * x
    return y.reshape(T, d_in) * jax.nn.silu(z), kept


def group_norm(y, weight, groups, eps):
    """RMS norm of ``y`` ``[T, d]`` over each of ``groups`` runs of
    ``d / groups`` channels, times ``weight`` ``[d]``."""
    g = y.reshape(len(y), groups, -1)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
    return g.reshape(y.shape) * _f32(weight)


def mamba(n, p, cfg, state_at=None):
    """``n`` ``[T, C]`` -> ``(out [T, C], S)`` (:func:`gated_scan`, the
    gated norm by group, the out-projection)."""
    y, kept = gated_scan(n, p, cfg, state_at)
    y = group_norm(y, p["norm_weight"], cfg["n_groups"],
                   cfg["layer_norm_epsilon"])
    return _blocks(lambda x: _mm(x, p["out_proj"]), y), kept


def route(n, p, cfg):
    """``(weights [T, k] float32, experts [T, k])`` of the normed input
    under the sigmoid router."""
    s = jax.nn.sigmoid(_mm(_f32(n), p["router"]))
    _, chosen = jax.lax.top_k(s + _f32(p["e_score_correction_bias"]),
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], chosen


def routed(n, p, cfg, first_expert=0):
    """The held experts' part of the routed sum, in the latent ``[T,
    moe_latent_size]``: the banks of ``p`` hold the experts from
    ``first_expert`` on; expert ``e`` is computed on every token's
    latent and weighted by the token's weight for it (0 where it was
    not chosen)."""
    n = _f32(n)
    w, chosen = route(n, p, cfg)
    held = p["w_up"].shape[0]

    def block(xwc):
        x, w, chosen = xwc
        lat = _mm(x, p["latent_down"])

        def expert(y, e_bank):
            e, up, down = e_bank
            mine = jnp.sum(jnp.where(chosen == e + first_expert, w, 0.0),
                           -1, keepdims=True)
            return y + mine * _mm(relu2(_mm(lat, up)), down), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(lat),
                            (jnp.arange(held), p["w_up"], p["w_down"]))
        return y

    return _blocks(block, (n, w, chosen))


def shared(n, p):
    return _blocks(lambda x: _mm(relu2(_mm(x, p["shared_up"])),
                                 p["shared_down"]), _f32(n))


def experts(n, p, cfg, first_expert=0):
    """An expert layer's ``f`` on the share: the held experts' part of
    the routed sum, projected up, and the shared expert."""
    r = routed(n, p, cfg, first_expert)
    return _blocks(lambda x: _mm(x, p["latent_up"]), r) + shared(n, p)


# --- the forward pass --------------------------------------------------------

def _static(cfg):
    """The numbers the layer functions read, hashable."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
            "n_groups", "conv_kernel", "layer_norm_epsilon",
            "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("kind", "cfg", "first_expert"))
def _layer(h, p, state_at, kind, cfg, first_expert):
    cfg = dict(cfg)
    n = _rms_norm(h, p["norm"]["weight"], cfg["layer_norm_epsilon"])
    kept = None
    if kind == MIXER:
        y, kept = mamba(n, p["mixer"], cfg, state_at)
    elif kind == ATTENTION:
        kept = keys_values(n, p["attn"], cfg)
        y = attention(n, p["attn"], cfg, kv=kept)
    else:
        y = experts(n, p["experts"], cfg, first_expert)
    return h + y, kept


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, final_norm, lm_head, rows, eps):
    return _mm(_rms_norm(h[rows], final_norm["weight"], eps), lm_head)


def pattern_of(cfg):
    return cfg["hybrid_override_pattern"][:cfg["n_layer"]]


def first_expert_of(cfg):
    return cfg.get("assumed", {}).get("experts_held", [0])[0]


def forward(params, tokens, cfg, rows=None, state_at=None, layers=None):
    """One sequence ``tokens`` ``[T]`` through the model. Returns
    ``(logits [len(rows), vocab], {layer name: S}, {layer name: (k,
    v)})``: the logits at the positions ``rows`` (default: all), every
    mixer's state after token ``state_at`` (default: the last) and
    every attention layer's keys and values ``[T, key heads,
    head_dim]``. ``layers`` stops after that many layers (then the
    logits are ``None``): a layer's state needs only the layers before
    it."""
    static, first = _static(cfg), first_expert_of(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    at = jnp.asarray(len(tokens) - 1 if state_at is None else state_at,
                     jnp.int32)
    kinds = pattern_of(cfg)
    h = _f32(params["embed"][tokens])
    states, kv = {}, {}
    for i, kind in enumerate(kinds[:layers]):
        name = f"layers_{i}"
        h, kept = _layer(h, params[name], at, kind, static, first)
        if kind == MIXER:
            states[name] = kept
        elif kind == ATTENTION:
            kv[name] = kept
    if layers is not None and layers < len(kinds):
        return None, states, kv
    rows = jnp.arange(len(tokens)) if rows is None else jnp.asarray(rows)
    return _head(h, params["final_norm"], params["lm_head"], rows,
                 cfg["layer_norm_epsilon"]), states, kv

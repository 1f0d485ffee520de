"""Plain reference for the Qwen3-Next (``qwen3_next``) configurations:
the published forward pass in straightforward ``jax.numpy`` and float32,
one sequence at a time, written from the equations below and not from
the program. No kernel, no cache, no pages, no chunks, no triangular
system, no sort, no grouped matmul: the delta rule is the recurrence,
**one token at a time** under ``lax.scan``; attention is every query
over the whole prefix, a head at a time; every held expert is computed
on every token and weighted (by zero where the token did not choose
it). Matrix products run at ``highest`` precision (on a TPU a float32
product is otherwise done in bf16 passes).

It follows ``modeling_qwen3_next.py`` beside the ``config.json`` of
``Qwen/Qwen3-Next-80B-A3B-Instruct``. ``norm(x) = x rsqrt(mean(x^2) +
rms_norm_eps) (1 + w)`` (zero-centred weight), no bias in any product:

- stream: ``h = embed[tokens]``; block ``i`` is ``h = h +
  mixer_i(norm(h))``, ``h = h + moe(norm(h))``; the mixer is full
  attention where ``(i + 1) % full_attention_interval == 0`` and Gated
  DeltaNet otherwise; after the last, ``logits = norm(h) @ lm_head``
  (untied).
- Gated DeltaNet (Yang, Kautz & Hatamizadeh 2024, arXiv:2412.06464):
  ``[q, k, v, z] = n W_qkvz``, ``[b, a] = n W_ba``; ``[q; k; v]_t =
  silu(sum_j w_j [q; k; v]_{t-K+1+j})`` (depthwise, ``linear_conv_
  kernel_dim`` taps, no bias, zeros before the sequence); ``q``, ``k``
  as ``linear_num_key_heads`` heads of ``linear_key_head_dim``, each
  ``x rsqrt(sum x^2 + 1e-6)``, ``q`` times ``key_head_dim^-0.5``; value
  head ``h`` of ``linear_num_value_heads`` reads key head ``h // (value
  heads / key heads)``; ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)``; per value head from ``S = 0`` ``[key, value]``:
  ``S <- exp(g_t) S``; ``d = beta_t (v_t - S^T k_t)``; ``S <- S + k_t
  (x) d``; ``o_t = S^T q_t``; ``y = o rsqrt(mean(o^2) + eps) w silu(z)``
  a head (``w`` plain, ``linear_value_head_dim`` wide, shared by the
  heads); ``y W_out``.
- attention: ``q_proj`` gives every one of ``num_attention_heads``
  heads ``2 head_dim`` numbers, the first ``head_dim`` the query and
  the rest its gate; ``k``, ``v`` as ``num_key_value_heads`` heads
  (query head ``h`` reads key head ``h // group``); ``norm`` over
  ``head_dim`` on every query and key head; rotary (``rope_theta``,
  rotate-half: entry ``i`` pairs with ``i + r/2``) on the first ``r =
  partial_rotary_factor head_dim`` entries; ``softmax(q k^T
  head_dim^-0.5)`` under the causal mask; ``o sigmoid(gate)``; ``W_o``.
- experts: ``p = softmax(n W_r)`` over ``num_experts``; the
  ``num_experts_per_tok`` largest, over their sum (``norm_topk_prob``);
  ``r = sum_e p_e W_d[e] (silu(W_g[e] n) * W_u[e] n)``; ``y = r +
  sigmoid(n w_sg) shared(n)``, ``shared`` a SwiGLU of
  ``shared_expert_intermediate_size``.

**The share.** ``params`` may hold only some experts' banks (their
leading size) and some rows of the vocabulary: ``first_expert`` says
which expert the banks start at, and the routed sum runs over the held
experts only; the weights stay what the whole router gave (they sum to
1 over all chosen experts, held or not). Token ids are taken within
the held rows.

Departures from the published code, none of which a random
initialisation can see or which are this reference's whole point:
(1) the published first projection lies a key head at a time (``q, k,
v, v, z, z`` and ``b, b, a, a``); here ``q | k | v | z`` and ``b | a``
lie whole: with seeded weights the order is a relabelling of columns;
(2) the published code runs the recurrence in chunks of 64 on a GPU
kernel (`chunk_gated_delta_rule`) for a prompt; this is the recurrence
it equals; (3) the router's product is float32 at ``highest`` on
float32 copies of input and weight; (4) experts are evaluated densely
and masked, not dispatched; (5) ``rope_scaling`` null: plain rotary;
(6) the multi-token prediction block is a draft head for speculative
decoding (no key of the catalog's ``config`` describes it) and is not
part of this pass; (7) ``decoder_sparse_step`` 1 and ``mlp_only_layers``
empty: every block's feed-forward is the expert layer, and the dense
``intermediate_size`` is read by nothing; (8) no dropout, no mask but
the causal one, one sequence.

``params`` is the program's parameter tree (``embed``, ``lm_head``
``[hidden, vocab]``, ``final_norm``, ``layers_<i>/{input_norm,
post_norm, mixer/{in_proj_qkvz, in_proj_ba, conv_weight [K, channels],
dt_bias, A_log, norm_weight, out_proj} | attn/{q_proj, k_proj, v_proj,
q_norm, k_norm, o_proj}, experts/{router, w_gate, w_up, w_down,
shared_gate, shared_up, shared_down, shared_expert_gate}}``; a block's
norm holds its ``weight``; a product is ``x @ W`` with ``W`` stored
``[in, out]``), read in float32 whatever type it is stored in, **a layer
at a time and a block of tokens at a time within it**: :func:`forward`
is a Python loop over jitted layer functions, so that 5,120 tokens at
the published widths stand beside a 12 GB engine.

``cfg`` is a configuration file's dict (the published keys).
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
DELTA, ATTENTION = "linear_attention", "full_attention"
TOKEN_BLOCK = 1024


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


def norm(x, w, eps):
    """The zero-centred RMS norm over the last axis."""
    x = _f32(x)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * \
        (1.0 + _f32(w))


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


def layer_types(cfg):
    return tuple(
        ATTENTION if (i + 1) % cfg["full_attention_interval"] == 0
        else DELTA for i in range(cfg["n_layer"]))


# --- attention ---------------------------------------------------------------

def rotary(x, positions, cfg):
    """``x`` ``[T, heads, head_dim]`` with its first ``r`` entries a
    head rotated by the angles of ``positions`` ``[T]``, by the direct
    formula: entry ``i < r/2`` pairs with entry ``i + r/2`` under the
    angle ``position theta^(-2i/r)``."""
    r = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    i = jnp.arange(r // 2, dtype=jnp.float32)
    ang = _f32(positions)[:, None, None] * \
        cfg["rope_theta"] ** (-2.0 * i / r)
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [a * jnp.cos(ang) - b * jnp.sin(ang),
         b * jnp.cos(ang) + a * jnp.sin(ang), x[..., r:]], axis=-1)


def keys_values(n, p, cfg):
    """What a cache holds of the normed input ``n`` ``[T, hidden]``:
    ``(k, v)`` each ``[T, key heads, head_dim]``, the keys normed and
    rotated."""
    Hkv, eps = cfg["num_key_value_heads"], cfg["rms_norm_eps"]
    k, v = _blocks(lambda x: (_mm(x, p["k_proj"]), _mm(x, p["v_proj"])),
                   _f32(n))
    k = norm(k.reshape(len(k), Hkv, -1), p["k_norm"], eps)
    return rotary(k, jnp.arange(len(k)), cfg), v.reshape(len(v), Hkv, -1)


def attention(n, p, cfg, scale=None, kv=None):
    """``n`` ``[T, C]`` -> ``[T, C]`` (``kv``: :func:`keys_values` of
    ``n``, where the caller has them)."""
    n = _f32(n)
    T = n.shape[0]
    Hq, Hkv, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    scale = D ** -0.5 if scale is None else scale
    qg = _blocks(lambda x: _mm(x, p["q_proj"]), n).reshape(T, Hq, 2 * D)
    gate = qg[..., D:]
    q = rotary(norm(qg[..., :D], p["q_norm"], cfg["rms_norm_eps"]),
               jnp.arange(T), cfg)
    k, v = kv or keys_values(n, p, cfg)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(i):                # one head at a time: [T, T] scores
        s = jnp.matmul(q[:, i], k[:, i // (Hq // Hkv)].T,
                       precision=HIGHEST) * scale
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(s, axis=-1),
                          v[:, i // (Hq // Hkv)], precision=HIGHEST)

    y = jnp.moveaxis(jax.lax.map(head, jnp.arange(Hq)), 0, 1)
    y = y * jax.nn.sigmoid(gate)
    return _blocks(lambda x: _mm(x, p["o_proj"]), y.reshape(T, -1))


# --- Gated DeltaNet ----------------------------------------------------------

def delta_inputs(n, p, cfg):
    """What the recurrence takes of ``n`` ``[T, C]``: ``(q, k [T, Hv,
    K], v [T, Hv, V], g, beta [T, Hv], z [T, Hv, V], the projected
    [q; k; v] before the convolution [T, channels])``."""
    n = _f32(n)
    T = n.shape[0]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    K, V = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    d_k, d_v = Hk * K, Hv * V
    qkvz, ba = _blocks(lambda x: (_mm(x, p["in_proj_qkvz"]),
                                  _mm(x, p["in_proj_ba"])), n)
    qkv, z = qkvz[:, :2 * d_k + d_v], qkvz[:, 2 * d_k + d_v:]
    w = _f32(p["conv_weight"])
    padded = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv])
    u = jax.nn.silu(sum(w[j] * padded[j:j + T] for j in range(taps)))

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(u[:, :d_k].reshape(T, Hk, K)) * K ** -0.5
    k = unit(u[:, d_k:2 * d_k].reshape(T, Hk, K))
    q, k = (jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))
    v = u[:, 2 * d_k:].reshape(T, Hv, V)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(_f32(p["A_log"])) * \
        jax.nn.softplus(ba[:, Hv:] + _f32(p["dt_bias"]))
    return q, k, v, g, beta, z.reshape(T, Hv, V), qkv


def delta_rule(q, k, v, g, beta, state_at=None):
    """The recurrence, a token at a time: ``(o [T, Hv, V], S [Hv, K,
    V])``, ``S`` the state after token ``state_at`` (``None``: after the
    last)."""
    T, Hv, K = q.shape
    at = T - 1 if state_at is None else state_at

    def step(carry, inp):
        S, kept = carry
        t, q_t, k_t, v_t, g_t, b_t = inp
        S = jnp.exp(g_t)[:, None, None] * S
        d = b_t[:, None] * (v_t - (S * k_t[:, :, None]).sum(1))
        S = S + k_t[:, :, None] * d[:, None, :]
        kept = jnp.where(t == at, S, kept)
        return (S, kept), (S * q_t[:, :, None]).sum(1)

    zero = jnp.zeros((Hv, K, v.shape[-1]), jnp.float32)
    (_, kept), o = jax.lax.scan(
        step, (zero, zero), (jnp.arange(T), q, k, v, g, beta))
    return o, kept


def gated_norm(o, z, weight, eps):
    """``o rsqrt(mean(o^2) + eps) w silu(z)`` a head."""
    return o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) * \
        _f32(weight) * jax.nn.silu(z)


def delta_net(n, p, cfg, state_at=None, rule=delta_rule):
    """``n`` ``[T, C]`` -> ``(out [T, C], (S, window))``: ``S`` the
    state after token ``state_at`` and ``window`` the ``taps - 1``
    projected ``[q; k; v]`` rows up to it (zeros before the sequence),
    which is what a slot keeps of a prompt. (``rule``: a fault's way
    in.)"""
    q, k, v, g, beta, z, qkv = delta_inputs(n, p, cfg)
    o, kept = rule(q, k, v, g, beta, state_at)
    y = gated_norm(o, z, p["norm_weight"], cfg["rms_norm_eps"])
    taps = cfg["linear_conv_kernel_dim"]
    at = len(qkv) - 1 if state_at is None else state_at
    padded = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv])
    window = jax.lax.dynamic_slice_in_dim(padded, at + 1, taps - 1, 0)
    return _blocks(lambda x: _mm(x, p["out_proj"]),
                   y.reshape(len(y), -1)), (kept, window)


# --- experts -----------------------------------------------------------------

def route(n, p, cfg):
    """``(weights [T, k] float32, experts [T, k])`` of the normed input:
    softmax over all experts, the largest ``k``, over their sum."""
    probs = jax.nn.softmax(_mm(_f32(n), p["router"]), axis=-1)
    w, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    return w / w.sum(-1, keepdims=True), chosen


def routed(n, p, cfg, first_expert=0):
    """The held experts' part of the routed sum: the banks of ``p`` hold
    the experts from ``first_expert`` on; expert ``e`` is computed on
    every token and weighted by the token's weight for it (0 where it
    was not chosen)."""
    n = _f32(n)
    w, chosen = route(n, p, cfg)
    held = p["w_up"].shape[0]

    def block(xwc):
        x, w, chosen = xwc

        def expert(y, e_bank):
            e, gate, up, down = e_bank
            mine = jnp.sum(jnp.where(chosen == e + first_expert, w, 0.0),
                           -1, keepdims=True)
            return y + mine * _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up),
                                  down), None

        y, _ = jax.lax.scan(
            expert, jnp.zeros_like(x),
            (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]))
        return y

    return _blocks(block, (n, w, chosen))


def shared(n, p):
    """``sigmoid(n w_sg) * W_d (silu(W_g n) * W_u n)``."""
    def block(x):
        y = _mm(jax.nn.silu(_mm(x, p["shared_gate"])) *
                _mm(x, p["shared_up"]), p["shared_down"])
        return jax.nn.sigmoid(_mm(x, p["shared_expert_gate"])) * y
    return _blocks(block, _f32(n))


def experts(n, p, cfg, first_expert=0):
    """An expert layer on the share: the held experts' part of the
    routed sum and the gated shared expert."""
    return routed(n, p, cfg, first_expert) + shared(n, p)


# --- the forward pass --------------------------------------------------------

def _static(cfg):
    """The numbers the layer functions read, hashable."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
            "linear_key_head_dim", "linear_num_value_heads",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "rms_norm_eps", "num_experts_per_tok")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("kind", "cfg", "first_expert"))
def _layer(h, p, state_at, kind, cfg, first_expert):
    cfg = dict(cfg)
    eps = cfg["rms_norm_eps"]
    n = norm(h, p["input_norm"]["weight"], eps)
    if kind == ATTENTION:
        kept = keys_values(n, p["attn"], cfg)
        y = attention(n, p["attn"], cfg, kv=kept)
    else:
        y, kept = delta_net(n, p["mixer"], cfg, state_at)
    h = h + y
    return h + experts(norm(h, p["post_norm"]["weight"], eps),
                       p["experts"], cfg, first_expert), kept


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, final_norm, lm_head, rows, eps):
    return _mm(norm(h[rows], final_norm["weight"], eps), lm_head)


def first_expert_of(cfg):
    return cfg.get("assumed", {}).get("experts_held", [0])[0]


def forward(params, tokens, cfg, rows=None, state_at=None, layers=None):
    """One sequence ``tokens`` ``[T]`` through the model. Returns
    ``(logits [len(rows), vocab], {layer name: (S, window)}, {layer
    name: (k, v)})``: the logits at the positions ``rows`` (default:
    all), every Gated DeltaNet layer's state and convolution window
    after token ``state_at`` (default: the last) and every attention
    layer's keys and values ``[T, key heads, head_dim]``. ``layers``
    stops after that many layers (then the logits are ``None``): a
    layer's state needs only the layers before it."""
    static, first = _static(cfg), first_expert_of(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    at = jnp.asarray(len(tokens) - 1 if state_at is None else state_at,
                     jnp.int32)
    kinds = layer_types(cfg)
    h = _f32(params["embed"][tokens])
    states, kv = {}, {}
    for i, kind in enumerate(kinds[:layers]):
        name = f"layers_{i}"
        h, kept = _layer(h, params[name], at, kind, static, first)
        (kv if kind == ATTENTION else states)[name] = kept
    if layers is not None and layers < len(kinds):
        return None, states, kv
    rows = jnp.arange(len(tokens)) if rows is None else jnp.asarray(rows)
    return _head(h, params["final_norm"], params["lm_head"], rows,
                 cfg["rms_norm_eps"]), states, kv

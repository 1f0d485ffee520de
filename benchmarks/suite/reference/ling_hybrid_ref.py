"""Plain reference for the Ling-3.0 (``bailing_hybrid``) configurations:
the published forward pass in straightforward ``jax.numpy`` and float32,
one sequence at a time, written from the equations below and not from
the program. No kernel, no cache, no pages, no chunks, no triangular
system, no absorbed form, no sort, no grouped matmul: the delta rule is
the recurrence, **one token at a time** under ``lax.scan`` with
``diag(e^g)`` applied to the state's rows; the convolutions are an
explicit sum over taps; latent attention is **unabsorbed**, a head at a
time, every query over the whole prefix; the group choice is a loop over
groups; every held expert is computed on every token and weighted (by
zero where the token did not choose it). Matrix products run at
``highest`` precision. It imports nothing of ``deepspeed_tpu``.

It follows the ``config.json`` of ``inclusionAI/Ling-3.0-flash``
(``model_type: bailing_hybrid``), Kimi Linear (arXiv:2510.26692;
``fla/layers/kda.py``, ``fla/ops/kda``) for the KDA layer and
DeepSeek-V3 (arXiv:2412.19437) for latent attention and ``noaux_tc``.
``norm(x) = x rsqrt(mean(x^2) + rms_norm_eps) w`` (plain weight), no
bias in any product:

- stream: ``h = embed[tokens]``; layer ``i`` is ``h = h +
  mixer_i(norm(h))``, ``h = h + ffn_i(norm(h))``; the mixer is latent
  attention where ``(i + 1) % layer_group_size == 0`` and KDA
  otherwise; ``ffn_i`` is a SwiGLU of ``intermediate_size`` for ``i <
  first_k_dense_replace`` and the expert layer otherwise; after the
  last, ``logits = norm(h) @ lm_head`` (untied).
- KDA, ``H = num_attention_heads`` heads of ``K = V = head_dim``: ``q =
  n W_q``, ``k = n W_k``, ``v = n W_v``; each ``x_t <- silu(sum_j w_j
  x_{t-taps+1+j})`` (depthwise, ``short_conv_kernel_size`` taps, no
  bias, zeros before the sequence); ``q``, ``k`` a head ``x rsqrt(sum
  x^2 + 1e-6)``, ``q`` times ``K^-0.5``; a channel ``g =
  kda_lower_bound sigmoid(exp(A_log_h) (n W_f + dt_bias))``; a head
  ``beta = sigmoid(n W_beta)``; per head from ``S = 0`` ``[K, V]``: ``S
  <- diag(exp(g_t)) S``; ``d = beta_t (v_t - S^T k_t)``; ``S <- S + k_t
  (x) d``; ``o_t = S^T q_t``; ``y = o rsqrt(mean(o^2) + eps) w
  sigmoid(n W_g)`` a head (``w`` ``head_dim`` wide, shared by the
  heads); ``y W_o``.
- latent attention: ``q = n W_q`` (``qk_nope_head_dim +
  qk_rope_head_dim`` a head); ``[c_kv | k_rope] = n W_dkv``; ``c_kv <-
  norm(c_kv)``; ``[k_nope | v] = c_kv W_ukv`` a head; rotary
  (``rope_theta``, rotate-half: entry ``i`` pairs with ``i + r/2``) on
  ``q``'s last ``r = qk_rope_head_dim`` entries and on ``k_rope``,
  which every head uses; ``softmax((q_nope . k_nope + q_rope . k_rope)
  (nope + rope)^-0.5)`` under the causal mask; head ``h``'s output
  times ``sigmoid((n W_a)_h)``; ``W_o``.
- experts: ``s = sigmoid(n W_r)`` over ``num_experts``, ``c = s +
  bias``; the experts lie in ``n_group`` groups of consecutive experts;
  a group's score is the sum of its two largest ``c``; the
  ``topk_group`` best groups are kept; the ``num_experts_per_tok``
  largest ``c`` inside them are chosen; weights ``s_e / sum(chosen s)
  routed_scaling_factor``; ``y = sum_e w_e W_d[e] (silu(W_g[e] n) *
  W_u[e] n) + Shared(n)``, ``Shared`` one ungated SwiGLU.

**The share.** ``params`` may hold only some experts' banks and some
rows of the vocabulary: ``first_expert`` says which expert the banks
start at, and the routed sum runs over the held experts only; the
weights stay what the whole router gave (they sum to
``routed_scaling_factor`` over all chosen experts, held or not).

**Assumed readings** (each also in ``configs/ling-3.0-flash.json``'s
``assumed``; a departure wherever the published code reads otherwise):
(1) the bounded gate's formula above for ``kda_safe_gate: true``,
``kda_lower_bound: -5`` (``fla/ops/kda``'s gate with a lower bound;
unbounded it is ``-exp(A_log) softplus(a + dt_bias)``, which this model
does not run); (2) ``linear_silu: true`` = SiLU after each of the three
convolutions; (3) ``use_qk_norm: true`` = the KDA heads' L2 norm and,
in latent attention, the latent's norm alone (no norm on ``q``); (4)
``num_kv_heads_for_linear_attn: 0`` = as many as query heads; (5)
``no_kda_lora: true`` / ``use_kda_lora: false`` = ``W_f`` and ``W_g``
full rank; (6) ``group_norm_size: 1`` = the output norm over each
head's own ``head_dim``; (7) group score = sum of a group's two largest
``c`` (DeepSeek-V3's ``noaux_tc``), experts outside the kept groups
excluded (not scored 0); (8) ``rope_interleave: true`` is run
rotate-half: with seeded weights a fixed relabelling of the rope
columns of ``W_q`` and ``W_dkv``; ``use_mla_nope: false`` = rotary is
applied; ``partial_rotary_factor`` / ``rotary_dim`` 64 = the rope part
of a head; (9) ``gated_attention_proj_granularity_type: head_wise`` =
one sigmoid gate a head on the attention's output, from the layer's
normed input; (10) ``expert_swiglu_limit_list`` /
``share_expert_swiglu_limit_list`` are 0 (no clamp) in every layer
built; (11) the multi-token-prediction layer is a draft head, no part
of this pass; ``max_window_layers``, ``mtp_*``, ``seq_aux``, ``use_nGPT:
false``, ``value_norm: false``, ``up_proj_norm: false`` change nothing
here; (12) the router's product is float32 at ``highest``
(``scale_router_input: false``: the input is not scaled); (13) experts
are evaluated densely and masked, not dispatched; (14) no dropout, no
mask but the causal one, one sequence. The published code runs KDA in
chunks on a GPU kernel; this is the recurrence it equals.

``params`` is the program's parameter tree (``embed``, ``lm_head``
``[hidden, vocab]``, ``final_norm``, ``layers_<i>/{input_norm,
post_norm, mixer/{q_proj, k_proj, v_proj, q_conv, k_conv, v_conv [taps,
channels], f_proj, dt_bias, A_log, b_proj, g_proj, norm_weight, o_proj}
| attn/{q_proj, kv_a_proj, kv_a_norm, kv_b_proj, gate_proj, o_proj},
mlp/{w_in, w_out} | experts/{router, expert_bias, w_gate, w_up, w_down,
shared/{w_in, w_out}}}``; a norm holds its ``weight``; a product is ``x
@ W`` with ``W`` stored ``[in, out]``), read in float32 whatever type
it is stored in, **a layer at a time and a block of tokens at a time
within it** (the recurrence carries its state from block to block; the
attention takes a head at a time and a block of queries at a time
within it): :func:`forward` is a Python loop over jitted layer
functions, so that 34 k tokens at the published widths stand beside an
11 GB engine.

``cfg`` is a configuration file's dict (the published keys).
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
KDA, MLA = "kda", "mla"
TOKEN_BLOCK = 1024


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


def norm(x, w, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def _in_blocks(x, block=TOKEN_BLOCK):
    """The rows of ``x`` (an array or a tuple of arrays with the same
    rows) as ``[blocks, block, ...]``, padded with zeros up to a whole
    number of them; and the block's size."""
    n = jax.tree_util.tree_leaves(x)[0].shape[0]
    block = min(block, n)
    pad = -n % block
    return jax.tree_util.tree_map(
        lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
            (-1, block) + a.shape[1:]), x), block


def _blocks(fn, x, block=TOKEN_BLOCK):
    """``fn`` over the rows of ``x`` in blocks: the token-local parts."""
    n = jax.tree_util.tree_leaves(x)[0].shape[0]
    xs, _ = _in_blocks(x, block)
    out = jax.lax.map(fn, xs)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:])[:n], out)


def layer_types(cfg):
    return tuple(MLA if (i + 1) % cfg["layer_group_size"] == 0 else KDA
                 for i in range(cfg["n_layer"]))


# --- KDA ----------------------------------------------------------------------

def kda_gate(a, p, cfg):
    """``g`` ``[T, H, K]`` of the gate projection's output ``a`` ``[T, H
    K]``: the bounded gate."""
    H = cfg["num_attention_heads"]
    x = (a + _f32(p["dt_bias"])).reshape(len(a), H, -1)
    return cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(_f32(p["A_log"]))[:, None] * x)


def unit(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def convolve(padded, w, T):
    """``silu(sum_j w_j x_{t - taps + 1 + j})``: ``padded`` holds the
    ``taps - 1`` rows before the ``T`` it convolves."""
    return jax.nn.silu(sum(w[j] * padded[j:j + T] for j in range(len(w))))


def delta_step(S, q_t, k_t, v_t, g_t, b_t):
    """One token of every head: ``(S, o_t)``."""
    S = jnp.exp(g_t)[:, :, None] * S
    d = b_t[:, None] * (v_t - (S * k_t[:, :, None]).sum(1))
    S = S + k_t[:, :, None] * d[:, None, :]
    return S, (S * q_t[:, :, None]).sum(1)


def kda_block_inputs(x, tail, p, cfg):
    """What the recurrence takes of a block ``x`` ``[T, C]`` of the
    normed input whose convolutions continue ``tail`` (the ``taps - 1``
    projected ``[q | k | v]`` rows before it): ``(q, k, v, g [T, H, K],
    beta [T, H], the projected rows with their tail [taps - 1 + T, 3 H
    K])``."""
    T = x.shape[0]
    H, K = cfg["num_attention_heads"], cfg["head_dim"]
    d = H * K
    qkv = jnp.concatenate([_mm(x, p[n]) for n in
                           ("q_proj", "k_proj", "v_proj")], axis=-1)
    w = jnp.concatenate([_f32(p[n]) for n in
                         ("q_conv", "k_conv", "v_conv")], axis=-1)
    padded = jnp.concatenate([tail, qkv])
    u = convolve(padded, w, T)
    q = unit(u[:, :d].reshape(T, H, K)) * K ** -0.5
    k = unit(u[:, d:2 * d].reshape(T, H, K))
    v = u[:, 2 * d:].reshape(T, H, K)
    g = kda_gate(_mm(x, p["f_proj"]), p, cfg)
    beta = jax.nn.sigmoid(_mm(x, p["b_proj"]))
    return q, k, v, g, beta, padded


def output_gate(o, x, p, cfg):
    """``o rsqrt(mean(o^2) + eps) w sigmoid(x W_g)`` a head."""
    gate = jax.nn.sigmoid(_mm(x, p["g_proj"])).reshape(o.shape)
    return o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                             + cfg["rms_norm_eps"]) * \
        _f32(p["norm_weight"]) * gate


def kda(n, p, cfg, state_at=None):
    """``n`` ``[T, C]`` -> ``(out [T, C], (S, window))``: ``S`` ``[H, K,
    V]`` the state after token ``state_at`` (default: the last) and
    ``window`` the ``taps - 1`` projected ``[q | k | v]`` rows up to it
    (zeros before the sequence): what a slot keeps of a prompt."""
    n = _f32(n)
    T = n.shape[0]
    H, K = cfg["num_attention_heads"], cfg["head_dim"]
    taps = cfg["short_conv_kernel_size"]
    at = T - 1 if state_at is None else state_at
    xs, block = _in_blocks(n)

    def one(carry, inp):
        S, tail, kept_S, kept_w = carry
        b, x = inp
        q, k, v, g, beta, padded = kda_block_inputs(x, tail, p, cfg)
        t0 = b * block

        def step(c, tok):
            S, kept = c
            t, q_t, k_t, v_t, g_t, b_t = tok
            S, o_t = delta_step(S, q_t, k_t, v_t, g_t, b_t)
            return (S, jnp.where(t0 + t == at, S, kept)), o_t

        (S, kept_S), o = jax.lax.scan(
            step, (S, kept_S), (jnp.arange(block), q, k, v, g, beta))
        # token t of the block sits at padded[t + taps - 1]
        here = (at >= t0) & (at < t0 + block)
        w = jax.lax.dynamic_slice_in_dim(
            padded, jnp.clip(at - t0, 0, block - 1) + 1, taps - 1, 0)
        y = _mm(output_gate(o, x, p, cfg).reshape(block, H * K),
                p["o_proj"])
        return (S, padded[block:], kept_S, jnp.where(here, w, kept_w)), y

    zero = jnp.zeros((H, K, K), jnp.float32)
    tail = jnp.zeros((taps - 1, 3 * H * K), jnp.float32)
    (_, _, S, window), y = jax.lax.scan(
        one, (zero, tail, zero, tail), (jnp.arange(len(xs)), xs))
    return y.reshape(-1, y.shape[-1])[:T], (S, window)


# --- latent attention ---------------------------------------------------------

def rotary(x, positions, theta):
    """``x`` ``[T, ..., r]`` rotated by the angles of ``positions``
    ``[T]``: entry ``i < r/2`` pairs with ``i + r/2`` under ``position
    theta^(-2i/r)``."""
    r = x.shape[-1]
    i = jnp.arange(r // 2, dtype=jnp.float32)
    ang = _f32(positions).reshape((-1,) + (1,) * (x.ndim - 1)) * \
        theta ** (-2.0 * i / r)
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def latents(n, p, cfg):
    """What a pool holds of the normed input ``n`` ``[T, C]``: ``[c_kv |
    k_rope]`` ``[T, kv_lora_rank + rope]``, the latent normed, the key
    rotated."""
    r = cfg["kv_lora_rank"]
    ckv = _blocks(lambda x: _mm(x, p["kv_a_proj"]), _f32(n))
    c = norm(ckv[:, :r], p["kv_a_norm"]["weight"], cfg["rms_norm_eps"])
    return jnp.concatenate(
        [c, rotary(ckv[:, r:], jnp.arange(len(ckv)), cfg["rope_theta"])], -1)


def attention(n, p, cfg, lat=None, scale=None, gated=True):
    """``n`` ``[T, C]`` -> ``[T, C]``: a head at a time, its keys and
    values expanded from the latents over the whole prefix, its queries
    a block at a time. (``lat``: :func:`latents` of ``n``.)"""
    n = _f32(n)
    T, C = n.shape
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    scale = (dn + dr) ** -0.5 if scale is None else scale
    lat = latents(n, p, cfg) if lat is None else lat
    c_kv, k_rope = lat[:, :r], lat[:, r:]
    pos = jnp.arange(T)
    w_q = _f32(p["q_proj"]).reshape(C, H, dn + dr).transpose(1, 0, 2)
    w_ukv = _f32(p["kv_b_proj"]).reshape(r, H, dn + dv).transpose(1, 0, 2)
    w_a = _f32(p["gate_proj"]).T                        # [H, C]
    w_o = _f32(p["o_proj"]).reshape(H, dv, C)

    def head(out, w):
        wq, wukv, wa, wo = w
        q = _blocks(lambda x: jnp.matmul(x, wq, precision=HIGHEST), n)
        q = jnp.concatenate(
            [q[:, :dn], rotary(q[:, dn:], pos, cfg["rope_theta"])], -1)
        kv = _blocks(lambda x: jnp.matmul(x, wukv, precision=HIGHEST), c_kv)
        keys = jnp.concatenate([kv[:, :dn], k_rope], -1)    # [T, dn + dr]
        values = kv[:, dn:]

        def queries(qp):
            q_b, p_b = qp
            s = jnp.matmul(q_b, keys.T, precision=HIGHEST) * scale
            s = jnp.where(pos[None, :] <= p_b[:, None], s, -jnp.inf)
            return jnp.matmul(jax.nn.softmax(s, axis=-1), values,
                              precision=HIGHEST)

        o = _blocks(queries, (q, pos))
        if gated:
            o = o * jax.nn.sigmoid(_blocks(
                lambda x: jnp.matmul(x, wa[:, None], precision=HIGHEST), n))
        return out + _blocks(
            lambda x: jnp.matmul(x, wo, precision=HIGHEST), o), None

    out, _ = jax.lax.scan(head, jnp.zeros((T, C), jnp.float32),
                          (w_q, w_ukv, w_a, w_o))
    return out


# --- feed-forward ---------------------------------------------------------------

def swiglu(x, p):
    """``(silu(g) * u) W_out`` with ``[g, u] = x W_in``."""
    gu = _mm(x, p["w_in"])
    half = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :half]) * gu[..., half:], p["w_out"])


def group_scores(c, cfg):
    """``[T, n_group]``: each group's two largest ``c`` summed, a group
    at a time."""
    per = c.shape[1] // cfg["n_group"]
    out = []
    for gi in range(cfg["n_group"]):
        part = jnp.sort(c[:, gi * per:(gi + 1) * per], axis=-1)
        out.append(part[:, -min(2, per):].sum(-1))
    return jnp.stack(out, axis=1)


def route(n, p, cfg):
    """``(weights [T, k] float32, experts [T, k], kept groups [T,
    topk_group])`` of the normed input."""
    s = jax.nn.sigmoid(_mm(_f32(n), p["router"]))
    c = s + _f32(p["expert_bias"])
    G, per = cfg["n_group"], c.shape[1] // cfg["n_group"]
    # the best groups first (a stable sort: the lower group on a tie)
    kept = jnp.argsort(-group_scores(c, cfg), axis=1,
                       stable=True)[:, :cfg["topk_group"]]
    keep = (kept[:, :, None] == jnp.arange(G)).any(1)       # [T, G]
    inside = jnp.where(jnp.repeat(keep, per, axis=1), c, -jnp.inf)
    chosen = jnp.argsort(-inside, axis=1,
                         stable=True)[:, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, chosen, axis=1)
    return w / w.sum(-1, keepdims=True) * cfg["routed_scaling_factor"], \
        chosen, kept


def routed(n, p, cfg, first_expert=0):
    """The held experts' part of the routed sum: expert ``e`` of the
    banks is computed on every token and weighted by the token's weight
    for it (0 where it was not chosen)."""
    n = _f32(n)
    w, chosen, _ = route(n, p, cfg)
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


def experts(n, p, cfg, first_expert=0):
    """An expert layer on the share: the held experts' part of the
    routed sum and the shared expert."""
    return routed(n, p, cfg, first_expert) + \
        _blocks(lambda x: swiglu(x, p["shared"]), _f32(n))


# --- the forward pass ----------------------------------------------------------

def _static(cfg):
    """The numbers the layer functions read, hashable."""
    keys = ("num_attention_heads", "head_dim", "short_conv_kernel_size",
            "kda_lower_bound", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_norm_eps",
            "num_experts_per_tok", "n_group", "topk_group",
            "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("kind", "dense", "cfg",
                                             "first_expert"))
def _layer(h, p, state_at, kind, dense, cfg, first_expert):
    cfg = dict(cfg)
    eps = cfg["rms_norm_eps"]
    n = norm(h, p["input_norm"]["weight"], eps)
    if kind == MLA:
        kept = latents(n, p["attn"], cfg)
        y = attention(n, p["attn"], cfg, lat=kept)
    else:
        y, kept = kda(n, p["mixer"], cfg, state_at)
    h = h + y
    n = norm(h, p["post_norm"]["weight"], eps)
    if dense:
        return h + _blocks(lambda x: swiglu(x, p["mlp"]), n), kept
    return h + experts(n, p["experts"], cfg, first_expert), kept


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, final_norm, lm_head, rows, eps):
    return _mm(norm(h[rows], final_norm["weight"], eps), lm_head)


def first_expert_of(cfg):
    return cfg.get("assumed", {}).get("experts_held", [0])[0]


def forward(params, tokens, cfg, rows=None, state_at=None, layers=None):
    """One sequence ``tokens`` ``[T]`` through the model. Returns
    ``(logits [len(rows), vocab], {KDA layer: (S, window)}, {latent
    layer: latents [T, kv_lora_rank + rope]})``: the logits at the
    positions ``rows`` (default: all), every KDA layer's state and
    convolution window after token ``state_at`` (default: the last) and
    every latent layer's pooled vectors. ``layers`` stops after that
    many layers (then the logits are ``None``)."""
    static, first = _static(cfg), first_expert_of(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    at = jnp.asarray(len(tokens) - 1 if state_at is None else state_at,
                     jnp.int32)
    kinds = layer_types(cfg)
    h = _f32(params["embed"][tokens])
    states, pooled = {}, {}
    for i, kind in enumerate(kinds[:layers]):
        name = f"layers_{i}"
        h, kept = _layer(h, params[name], at, kind,
                         i < cfg["first_k_dense_replace"], static, first)
        (pooled if kind == MLA else states)[name] = kept
    if layers is not None and layers < len(kinds):
        return None, states, pooled
    rows = jnp.arange(len(tokens)) if rows is None else jnp.asarray(rows)
    return _head(h, params["final_norm"], params["lm_head"], rows,
                 cfg["rms_norm_eps"]), states, pooled

"""Plain reference for the DeepSeek-V3 block (``kimi_k2`` reuses it):
latent attention **unabsorbed**, sigmoid-routed experts as a loop over
experts, YaRN from its definition, in straightforward ``jax.numpy`` and
float32, one sequence at a time. No kernel, no cache, no pages, no
chunks, no grouped matmul, no sort: every key and value is expanded
through ``W_ukv`` and every held expert is computed on every token and
weighted (by zero where the token did not choose it), so nothing here
is shared with the program's absorbed decode, its block walk or its
dropless dispatch. Matrix products run at ``highest`` precision (on a
TPU a float32 product is otherwise done in bf16 passes).

It follows ``transformers/models/deepseek_v3/modeling_deepseek_v3.py``
(DeepSeek-V3 technical report, arXiv:2412.19437; ``config.json`` of
``moonshotai/Kimi-K2.7-Code``). With ``n = RMSNorm(h)`` (float32
statistics, learned weight, ``rms_norm_eps``) and no bias anywhere:

- stream: ``h = embed[tokens]``; a layer is ``h = h + attn(RMSNorm(h))``
  then ``h = h + ffn(RMSNorm(h))``; after the last, ``logits =
  RMSNorm(h) @ lm_head`` (untied).
- attention, ``num_attention_heads`` heads: ``c_q = RMSNorm(n W_dq)``;
  ``[q_nope | q_rope] = c_q W_uq`` a head (``qk_nope_head_dim``,
  ``qk_rope_head_dim``); ``[c_kv | k_rope] = n W_dkv``; ``c_kv <-
  RMSNorm(c_kv)``; ``[k_nope | v] = c_kv W_ukv`` a head
  (``v_head_dim``); rotary on ``q_rope`` of each head and on the one
  ``k_rope``, which every head uses; ``softmax((q_nope . k_nope + q_rope
  . k_rope) s)`` under the causal mask; ``concat(p v) W_o``.
- YaRN (:func:`yarn_inv_freq`): frequency ``i`` of ``d / 2`` is
  ``theta^(-2i/d)`` divided by ``factor`` where it turns fewer than
  ``beta_slow`` times in ``original_max_position_embeddings`` tokens,
  undivided where it turns more than ``beta_fast`` times, a linear
  blend between (the ramp runs between the two correction dimensions
  ``d ln(L / (2 pi beta)) / (2 ln theta)``, floored and ceiled);
  ``cos`` and ``sin`` are scaled by ``mscale(factor, mscale) /
  mscale(factor, mscale_all_dim)`` (1 here) and ``s = (nope +
  rope)^-0.5 mscale(factor, mscale_all_dim)^2`` with ``mscale(f, m) =
  0.1 m ln f + 1``.
- dense FFN (layer ``i < first_k_dense_replace``): ``[g, u] = n W_in``;
  ``(silu(g) * u) W_out``, width ``intermediate_size``.
- expert FFN: ``s = sigmoid(n W_r)`` over ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` =
  ``e_score_correction_bias``) are chosen; their weights are ``s`` over
  the chosen ``s``'s sum (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``y = sum_e w_e E_e(n) + Shared(n)``,
  every expert and the shared one a SiLU-gated MLP of width
  ``moe_intermediate_size`` (the shared one ``n_shared_experts`` times
  that).

**The share.** ``params`` may hold only some experts' banks (their
leading size) and some rows of the vocabulary, as one chip of an
expert-parallel deployment does: ``first_expert`` says which expert the
banks start at, and the routed sum runs over the held experts only (a
pair whose expert is held elsewhere adds nothing here). Token ids are
taken within the held rows.

Departures from the published code, none of which a random
initialisation can see or which are this reference's whole point:
(1) rotary uses the rotate-half convention on the rope entries as
stored; the published code first de-interleaves them (a fixed
permutation of the 64 rope columns of ``W_uq`` and ``W_dkv``);
(2) ``n_group`` = ``topk_group`` = 1, so the group stage of
``noaux_tc`` is the identity and is not written; (3) the router's
product is float32 at ``highest`` on float32 copies of input and
weight (the published code does the same cast); (4) experts are
evaluated densely and masked, not dispatched; (5) no dropout, no
attention mask but the causal one, one sequence.

``params`` is the program's parameter tree (``embed``, ``lm_head``
``[hidden, vocab]``, ``final_norm``, ``layers_<i>/{input_norm,
post_attn_norm, attn/{q_a_proj, q_a_norm, q_b_proj, kv_a_proj,
kv_a_norm, kv_b_proj, o_proj}, mlp/{w_in, w_out} | experts/{router,
e_score_correction_bias, w_gate, w_up, w_down, shared/{w_in,
w_out}}}``; a norm holds its ``weight``; a product is ``x @ W`` with
``W`` stored ``[in, out]``), read in float32 whatever type it is stored
in, **a layer at a time, a block of tokens at a time within it, and a
head and an expert at a time within that**. Beside every token's
latents (576 numbers) all of a layer is its own token's, so
:func:`forward` is a Python loop over jitted layer functions that walk
the stream in blocks and give it back in the buffer it came in: 17,408
tokens at the published widths hold the stream once (0.5 GB), their
latents and 0.15 GB of a block's temporaries (compiled for a described
v5e), so that the engine the reference stands beside, not the
reference, sets the process's peak.

``cfg`` is a configuration file's dict (the published keys).
"""

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# tokens to a block: of the token-local parts, of a layer's walk over
# the stream, of the queries that meet every key at once
TOKEN_BLOCK = 1024


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


def _rms_norm(x, weight, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        _f32(weight)


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


# --- YaRN -------------------------------------------------------------------

def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """The ``qk_rope_head_dim / 2`` rotary frequencies, float64."""
    rs, d, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    length = rs["original_max_position_embeddings"]
    plain = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def correction_dim(turns):
        return d * math.log(length / (turns * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    high = high + 0.001 if low == high else high
    # 0 below ``low`` (keep the plain frequency), 1 above ``high``
    # (divide it by ``factor``)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return plain / rs["factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return d ** -0.5 * yarn_mscale(rs["factor"],
                                   rs.get("mscale_all_dim", 0)) ** 2


def rope(x, positions, cfg):
    """Rotary embedding of ``x`` ``[T, ..., d]`` at ``positions``
    ``[T]``, rotate-half, float32."""
    rs = cfg["rope_scaling"]
    ang = _f32(positions)[:, None] * jnp.asarray(yarn_inv_freq(cfg),
                                                 jnp.float32)
    m = yarn_mscale(rs["factor"], rs.get("mscale", 1)) / \
        yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), \
        (jnp.sin(ang) * m).reshape(shape)
    d = x.shape[-1] // 2
    x1, x2 = x[..., :d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# --- one layer's parts, each on its own input -------------------------------

def latents(n, p, cfg, positions=None):
    """What a cache of latents holds of the normed input ``n`` ``[T,
    hidden]``: ``[RMSNorm(c_kv) | rotary(k_rope)]`` ``[T, kv_lora_rank
    + qk_rope_head_dim]``."""
    r = cfg["kv_lora_rank"]
    positions = jnp.arange(n.shape[0]) if positions is None else positions
    ckv = _blocks(lambda x: _mm(x, p["kv_a_proj"]), _f32(n))
    c_kv = _rms_norm(ckv[:, :r], p["kv_a_norm"]["weight"],
                     cfg["rms_norm_eps"])
    return jnp.concatenate([c_kv, rope(ckv[:, r:], positions, cfg)], -1)


def attend(n, positions, lat, p, cfg, scale=None):
    """Causal latent attention of the queries of a block of tokens
    (normed input ``n`` ``[t, hidden]`` at ``positions`` ``[t]``) over
    every token's latents ``lat`` ``[T, 576]`` (:func:`latents`), every
    key and value expanded from them, a head at a time."""
    H, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    scale = softmax_scale(cfg) if scale is None else scale
    n = _f32(n)
    k_pos = jnp.arange(lat.shape[0])
    c_q = _rms_norm(_mm(n, p["q_a_proj"]), p["q_a_norm"]["weight"],
                    cfg["rms_norm_eps"])
    c_kv, k_rope = lat[:, :rkv], lat[:, rkv:]
    w_uq = p["q_b_proj"].reshape(-1, H, dn + dr)
    w_ukv = p["kv_b_proj"].reshape(rkv, H, dn + dv)
    w_o = p["o_proj"].reshape(H, dv, -1)

    def head(y, w):
        uq, ukv, o = w
        q = _mm(c_q, uq)                                    # [t, dn+dr]
        q = jnp.concatenate([q[:, :dn], rope(q[:, dn:], positions, cfg)],
                            -1)
        kv = _mm(c_kv, ukv)                                 # [T, dn+dv]
        k = jnp.concatenate([kv[:, :dn], k_rope], -1)
        s = jnp.matmul(q, k.T, precision=HIGHEST) * scale
        s = jnp.where(k_pos[None, :] <= positions[:, None], s, -jnp.inf)
        out = jnp.matmul(jax.nn.softmax(s, -1), kv[:, dn:],
                         precision=HIGHEST)
        return y + _mm(out, o), None

    y, _ = jax.lax.scan(
        head, jnp.zeros(n.shape, jnp.float32),
        (jnp.moveaxis(w_uq, 1, 0), jnp.moveaxis(w_ukv, 1, 0), w_o))
    return y


def attention(n, p, cfg, scale=None):
    """Causal latent attention of the normed input ``n`` ``[T,
    hidden]``: every token's latents, then the queries in blocks of
    tokens over them (:func:`attend`)."""
    n = _f32(n)
    lat = latents(n, p, cfg)
    return _blocks(lambda xp: attend(*xp, lat, p, cfg, scale),
                   (n, jnp.arange(n.shape[0])))


def mlp(n, p):
    """``(silu(g) * u) W_out`` with ``[g, u] = n W_in``."""
    half = p["w_in"].shape[1] // 2

    def block(x):
        g, u = _mm(x, p["w_in"][:, :half]), _mm(x, p["w_in"][:, half:])
        return _mm(jax.nn.silu(g) * u, p["w_out"])

    return _blocks(block, _f32(n))


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
    """The held experts' part of the routed sum: the banks of ``p``
    hold the experts from ``first_expert`` on; expert ``e`` is computed
    on every token and weighted by the token's weight for it (0 where
    it was not chosen)."""
    n = _f32(n)
    w, chosen = route(n, p, cfg)
    held = p["w_gate"].shape[0]

    def block(xwc):
        x, w, chosen = xwc

        def expert(y, e_bank):
            e, gate, up, down = e_bank
            mine = jnp.sum(jnp.where(chosen == e + first_expert, w, 0.0),
                           -1, keepdims=True)
            h = jax.nn.silu(_mm(x, gate)) * _mm(x, up)
            return y + mine * _mm(h, down), None

        y, _ = jax.lax.scan(
            expert, jnp.zeros_like(x),
            (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]))
        return y

    return _blocks(block, (n, w, chosen))


def experts(n, p, cfg, first_expert=0):
    """An expert layer's feed-forward part on the share: the held
    experts' part of the routed sum, and the shared expert."""
    return routed(n, p, cfg, first_expert) + mlp(n, p["shared"])


# --- the forward pass --------------------------------------------------------

def _static(cfg):
    """The numbers the layer functions read, hashable."""
    keys = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rms_norm_eps", "rope_theta",
            "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys) + (
        ("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),)


def _dict(static):
    cfg = dict(static)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


@functools.partial(jax.jit, static_argnames=("cfg", "first_expert"),
                   donate_argnums=0)
def _layer(h, p, cfg, first_expert):
    """A layer on the stream ``h`` ``[T, hidden]`` (a whole number of
    blocks: :func:`forward` pads it), which it gives back in the same
    buffer: every token's latents first, then the layer a block of
    tokens at a time (beside the latents everything in it is its own
    token's), so that nothing else of the stream's size is ever held."""
    cfg = _dict(cfg)
    block = min(TOKEN_BLOCK, h.shape[0])

    def norm(x, w):
        return _rms_norm(x, w["weight"], cfg["rms_norm_eps"])

    lat = _blocks(lambda xp: latents(norm(xp[0], p["input_norm"]),
                                     p["attn"], cfg, xp[1]),
                  (h, jnp.arange(h.shape[0])))

    def step(i, h):
        x = jax.lax.dynamic_slice_in_dim(h, i * block, block)
        at = i * block + jnp.arange(block)
        x = x + attend(norm(x, p["input_norm"]), at, lat, p["attn"], cfg)
        n = norm(x, p["post_attn_norm"])
        x = x + (mlp(n, p["mlp"]) if "mlp" in p else
                 experts(n, p["experts"], cfg, first_expert))
        return jax.lax.dynamic_update_slice_in_dim(h, x, i * block, 0)

    return jax.lax.fori_loop(0, h.shape[0] // block, step, h), lat


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, final_norm, lm_head, rows, eps):
    return _mm(_rms_norm(h[rows], final_norm["weight"], eps), lm_head)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _first_latents(embed, p, tokens, cfg):
    cfg = _dict(cfg)

    def block(tp):
        n = _rms_norm(embed[tp[0]], p["input_norm"]["weight"],
                      cfg["rms_norm_eps"])
        return latents(n, p["attn"], cfg, tp[1])

    return _blocks(block, (tokens, jnp.arange(len(tokens))))


def first_layer_latents(params, tokens, cfg):
    """What a cache of latents holds of ``tokens`` ``[T]`` in the first
    layer, whose input is the embedding alone: ``[T, 576]``. A latent
    is its own token's and position's: no other token is in it."""
    return _first_latents(params["embed"], params["layers_0"],
                          jnp.asarray(tokens, jnp.int32), _static(cfg))


def first_expert_of(cfg):
    return cfg.get("assumed", {}).get("experts_held", [0])[0]


def forward(params, tokens, cfg, rows=None, layers=None):
    """One sequence ``tokens`` ``[T]`` through the model. Returns
    ``(logits [len(rows), vocab], {layer name: latents [T, 576]})``:
    the logits at the positions ``rows`` (default: all) and what a
    cache of latents would hold of every layer run. ``layers`` stops
    after that many layers (then the logits are ``None``)."""
    static = _static(cfg)
    first = first_expert_of(cfg)
    T = len(tokens)
    # a whole number of blocks: under the causal mask the padding is
    # seen by nothing before it
    tokens = jnp.pad(jnp.asarray(tokens, jnp.int32),
                     (0, -T % min(TOKEN_BLOCK, T)))
    names = [f"layers_{i}" for i in range(cfg["n_layer"])]
    h = _f32(params["embed"][tokens])
    lats = {}
    for name in names[:layers]:
        h, lat = _layer(h, params[name], static, first)
        lats[name] = lat[:T]
    if layers is not None and layers < len(names):
        return None, lats
    rows = jnp.arange(T) if rows is None else jnp.asarray(rows)
    return _head(h, params["final_norm"], params["lm_head"], rows,
                 cfg["rms_norm_eps"]), lats

"""Plain reference for the MiMo-V2 (``mimo_v2``) configurations: the
published forward pass of the language model in straightforward
``jax.numpy`` and float32, one sequence at a time, written from the
equations below and not from the program. No kernel, no cache, no pages,
no ring, no chunks, no online softmax, no sort, no grouped matmul:
attention is every query over the whole prefix under an explicit mask,
a head at a time, the sink an extra column of the scores that is
dropped after the softmax; every held expert is computed on every token
and weighted (by zero where the token did not choose it). Matrix
products run at ``highest`` precision (on a TPU a float32 product is
otherwise done in bf16 passes).

It follows the ``config.json`` of ``XiaomiMiMo/MiMo-V2.5``
(``model_type: mimo_v2``). ``norm(x) = x rsqrt(mean(x^2) +
layernorm_epsilon) w``, no bias in any product, no QK-norm:

- stream: ``h = embed[tokens]``; layer ``i`` is ``h = h +
  attn_i(norm(h))``, ``h = h + ffn_i(norm(h))``; after the last,
  ``logits = norm(h) @ lm_head`` (untied).
- attention, ``hybrid_layer_pattern[i]`` 0 (full): ``q`` as
  ``num_attention_heads`` heads of ``head_dim``, ``k`` as
  ``num_key_value_heads`` heads of ``head_dim``, ``v`` as that many of
  ``v_head_dim`` (query head ``h`` reads key head ``h // group``);
  rotary (rotate-half: entry ``i`` pairs with ``i + r/2``) at
  ``rope_theta`` on the first ``r = int(partial_rotary_factor
  head_dim)`` entries of each query and key head; ``softmax(q k^T
  head_dim^-0.5)`` under the causal mask; times ``v
  attention_value_scale``; ``W_o``.
- ``hybrid_layer_pattern[i]`` 1 (window): the ``swa_*`` keys for heads
  and widths, rotary at ``swa_rope_theta``; the mask admits ``j`` for
  ``t`` iff ``0 <= t - j < sliding_window``; with
  ``add_swa_attention_sink_bias`` a learned ``b`` ``[heads]``: the
  scores of head ``h`` get a column ``b_h``, the softmax runs over
  ``[scores | b_h]`` and the column is dropped: the sink takes weight
  and gives no value.
- ``moe_layer_freq[i]`` 0: ``W_out (silu(g) * u)`` with ``[g, u] = n
  W_in`` at ``intermediate_size``. 1: ``s = sigmoid(n W_r)`` over
  ``n_routed_experts``; the ``num_experts_per_tok`` largest of ``s +
  e_score_correction_bias`` are chosen; their weights are ``s`` over
  their sum (``norm_topk_prob``) times ``routed_scaling_factor`` (null:
  1); ``y = sum_e w_e W_d[e] (silu(W_g[e] n) * W_u[e] n)``. No shared
  expert.

**The share.** ``params`` may hold only some experts' banks (their
leading size) and some rows of the vocabulary: ``first_expert`` says
which expert the banks start at, and the routed sum runs over the held
experts only; the weights stay what the whole router gave (they sum to
1 over all chosen experts, held or not). Token ids are taken within
the held rows.

Departures from the published description, none of which a random
initialisation can see: (1) ``attention_projection_layout: fused_qkv``
lays ``q | k | v`` in one matrix; here they are three: with seeded
weights a relabelling of columns; (2) ``attention_chunk_size`` 128 is
read by nothing: the mask is the window's, as the model card describes
it ("SWA(128) with learnable sink bias"); (3) the dense layer's ``W_in``
holds ``gate | up`` side by side; (4) the vision and audio encoders and
the three multi-token-prediction layers are not part of the pass that
serves a token from tokens. Queries go through the attention in blocks
of rows (``QUERY_BLOCK``) and tokens through the matrices in blocks
(``TOKEN_BLOCK``) so that a 33 k sequence fits beside the weights; a
block's scores are still ``[rows, T]`` over the whole sequence under
the explicit mask.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
TOKEN_BLOCK = 2048      # tokens to a block through the matrices
QUERY_BLOCK = 1024      # query rows to a block of one head's scores
FULL, WINDOW = "full", "window"


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


def norm(x, w, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def _blocks(fn, x, block=TOKEN_BLOCK):
    """``fn`` over ``x``'s rows in blocks (the same numbers as at once)."""
    T = x.shape[0]
    if T <= block:
        return fn(x)
    pad = -T % block
    xs = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)).reshape(
        (-1, block) + x.shape[1:])
    out = jax.lax.map(fn, xs)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:])[:T], out)


def layer_kinds(cfg):
    return [WINDOW if p else FULL
            for p in cfg["hybrid_layer_pattern"][:cfg["n_layer"]]]


def kind_of(cfg, which):
    """``(heads, key heads, head_dim, v_head_dim, theta, sink, window)``
    of a full or a window layer."""
    pre = "swa_" if which == WINDOW else ""
    return (cfg[pre + "num_attention_heads"], cfg[pre + "num_key_value_heads"],
            cfg[pre + "head_dim"], cfg[pre + "v_head_dim"],
            cfg["swa_rope_theta" if which == WINDOW else "rope_theta"],
            cfg["add_swa_attention_sink_bias" if which == WINDOW
                else "add_full_attention_sink_bias"],
            cfg["sliding_window"] if which == WINDOW else 0)


def rotary(x, positions, r, theta):
    """Rotate-half rotary of the first ``r`` entries of each head of
    ``x`` ``[T, H, D]`` at ``positions`` ``[T]``; the rest pass."""
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = _f32(positions)[:, None] * inv                    # [T, r/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], -1)


def keys_values(n, p, cfg, which):
    """What a layer keeps of ``n`` ``[T, C]``: ``(k [T, key heads,
    head_dim]`` rotated, ``v [T, key heads, v_head_dim]`` scaled)."""
    n = _f32(n)
    T = n.shape[0]
    _, Hkv, D, Dv, theta, _, _ = kind_of(cfg, which)
    r = int(cfg["partial_rotary_factor"] * D)
    k = _blocks(lambda x: _mm(x, p["k_proj"]), n).reshape(T, Hkv, D)
    v = _blocks(lambda x: _mm(x, p["v_proj"]), n).reshape(T, Hkv, Dv)
    return (rotary(k, jnp.arange(T), r, theta),
            v * cfg["attention_value_scale"])


def attention(n, p, cfg, which, kv=None):
    """``n`` ``[T, C]`` -> ``[T, C]``: one attention layer of kind
    ``which`` over the whole sequence."""
    n = _f32(n)
    T = n.shape[0]
    Hq, Hkv, D, Dv, theta, sink, window = kind_of(cfg, which)
    r = int(cfg["partial_rotary_factor"] * D)
    k, v = kv if kv is not None else keys_values(n, p, cfg, which)
    b = _f32(p["sink"]) if sink else None
    rows = min(QUERY_BLOCK, T)
    pad = -T % rows
    j = jnp.arange(T)[None, :]

    def head(i):                # one head at a time, its queries too
        kh, vh = k[:, i // (Hq // Hkv)], v[:, i // (Hq // Hkv)]
        w_q = _f32(jax.lax.dynamic_slice_in_dim(p["q_proj"], i * D, D, 1))
        q = _blocks(lambda x: jnp.matmul(x, w_q, precision=HIGHEST), n)
        q = rotary(q[:, None], jnp.arange(T), r, theta)[:, 0]
        q = jnp.pad(q, [(0, pad), (0, 0)])

        def block(c):           # [rows, T] scores under the mask
            t = (c * rows + jnp.arange(rows))[:, None]
            qb = jax.lax.dynamic_slice_in_dim(q, c * rows, rows)
            s = jnp.matmul(qb, kh.T, precision=HIGHEST) * D ** -0.5
            seen = j <= t
            if window:
                seen = seen & (t - j < window)
            s = jnp.where(seen, s, -1e30)
            if b is not None:   # the sink: a column that is dropped
                s = jnp.concatenate(
                    [s, jnp.full((rows, 1), b[i], jnp.float32)], 1)
            w = jax.nn.softmax(s, axis=-1)[:, :T]
            return jnp.matmul(w, vh, precision=HIGHEST)

        return jax.lax.map(block, jnp.arange((T + pad) // rows)).reshape(
            T + pad, Dv)[:T]

    y = jnp.moveaxis(jax.lax.map(head, jnp.arange(Hq)), 0, 1)
    return _blocks(lambda x: _mm(x, p["o_proj"]), y.reshape(T, Hq * Dv))


def mlp(n, p):
    def block(x):
        gu = _mm(x, p["w_in"])
        i = gu.shape[-1] // 2
        return _mm(jax.nn.silu(gu[:, :i]) * gu[:, i:], p["w_out"])
    return _blocks(block, _f32(n))


def route(n, p, cfg):
    """``(weights [T, k], experts [T, k])`` of the whole router."""
    s = jax.nn.sigmoid(_mm(_f32(n), p["router"]))
    _, chosen = jax.lax.top_k(s + _f32(p["e_score_correction_bias"]),
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w * (cfg["routed_scaling_factor"] or 1.0), chosen


def experts(n, p, cfg, first_expert=0):
    """An expert layer on the share: the held experts' part of the
    routed sum, a loop over them."""
    n = _f32(n)
    held = p["w_gate"].shape[0]

    def block(x):
        w, chosen = route(x, p, cfg)

        def one(e, y):
            mine = (w * (chosen == first_expert + e)).sum(-1)   # [T]
            h = jax.nn.silu(_mm(x, p["w_gate"][e])) * _mm(x, p["w_up"][e])
            return y + mine[:, None] * _mm(h, p["w_down"][e])

        return jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))

    return _blocks(block, n)


# --- the forward pass --------------------------------------------------------

def _static(cfg):
    """The numbers the layer functions read, hashable."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "v_head_dim", "swa_num_attention_heads",
            "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim",
            "partial_rotary_factor", "rope_theta", "swa_rope_theta",
            "sliding_window", "add_swa_attention_sink_bias",
            "add_full_attention_sink_bias", "attention_value_scale",
            "layernorm_epsilon", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("which", "dense", "cfg",
                                             "first_expert"))
def _layer(h, p, which, dense, cfg, first_expert):
    cfg = dict(cfg)
    eps = cfg["layernorm_epsilon"]
    n = norm(h, p["input_norm"]["weight"], eps)
    kept = keys_values(n, p["attn"], cfg, which)
    h = h + attention(n, p["attn"], cfg, which, kv=kept)
    n = norm(h, p["post_attn_norm"]["weight"], eps)
    y = mlp(n, p["mlp"]) if dense else experts(n, p["experts"], cfg,
                                               first_expert)
    return h + y, kept


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, final_norm, lm_head, rows, eps):
    return _mm(norm(h[rows], final_norm["weight"], eps), lm_head)


def first_expert_of(cfg):
    return cfg.get("assumed", {}).get("experts_held", [0])[0]


def forward(params, tokens, cfg, rows=None, layers=None):
    """One sequence ``tokens`` ``[T]`` through the model. Returns
    ``(logits [len(rows), vocab], {layer name: (k, v)})``: the logits at
    the positions ``rows`` (default: all) and every layer's keys and
    values ``[T, key heads, head_dim | v_head_dim]`` as a cache would
    keep them (rotated; scaled). ``layers`` stops after that many layers
    (then the logits are ``None``)."""
    static, first = _static(cfg), first_expert_of(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    kinds = layer_kinds(cfg)
    h = _f32(params["embed"][tokens])
    kv = {}
    for i, which in enumerate(kinds[:layers]):
        name = f"layers_{i}"
        h, kv[name] = _layer(h, params[name], which,
                             not cfg["moe_layer_freq"][i], static, first)
    if layers is not None and layers < len(kinds):
        return None, kv
    rows = jnp.arange(len(tokens)) if rows is None else jnp.asarray(rows)
    return _head(h, params["final_norm"], params["lm_head"], rows,
                 cfg["layernorm_epsilon"]), kv

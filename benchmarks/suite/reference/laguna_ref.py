"""Plain reference for the Laguna (``laguna``) configurations: the
forward pass in straightforward ``jax.numpy`` and float32, one sequence
at a time, written from the equations below and not from the program.
No kernel, no cache, no pages, no ring, no chunks, no online softmax, no
sort, no grouped matmul: attention is every query over the whole prefix
under an explicit mask, a head at a time (its gated output through its
own rows of the output projection, summed over the heads); YaRN's
frequencies come from a direct formula; every held expert is computed on
every token and weighted (by zero where the token did not choose it).
Matrix products run at ``highest`` precision (on a TPU a float32 product
is otherwise done in bf16 passes).

It follows the ``config.json`` of ``poolside/Laguna-S-2.1``
(``model_type: laguna``). ``norm(x) = x rsqrt(mean(x^2) + rms_norm_eps)
w``, no bias in any product:

- stream: ``h = embed[tokens]``; layer ``i`` is ``h = h +
  attn_i(norm(h))``, ``h = h + ffn_i(norm(h))``; after the last,
  ``logits = norm(h) @ lm_head`` (untied).
- attention of layer ``i``, kind ``layer_types[i]``, ``Hq =
  num_attention_heads_per_layer[i]`` query heads over
  ``num_key_value_heads`` key heads of ``head_dim`` (query head ``h``
  reads key head ``h // (Hq / num_key_value_heads)``); rotary
  (rotate-half: entry ``i`` pairs with ``i + r/2``) on the first ``r =
  int(partial_rotary_factor head_dim)`` entries of each query and key
  head by the kind's ``rope_parameters``; ``softmax(q k^T
  head_dim^-0.5)`` under the causal mask, which in a
  ``sliding_attention`` layer admits ``j`` for ``t`` iff ``0 <= t - j <
  sliding_window``; head ``h``'s output times ``sigmoid(n W_g)_h``;
  ``W_o``.
- rotary frequencies, ``rope_type`` ``default``: ``theta^(-2i/r)``.
  ``yarn``: frequency ``i`` is ``f_i (1 - ramp_i) + f_i / factor
  ramp_i`` with ``f_i = theta^(-2i/r)`` and ``ramp_i = clip((i - low) /
  (high - low), 0, 1)``, ``low = floor(d(beta_fast))``, ``high =
  ceil(d(beta_slow))``, ``d(turns) = r ln(original_max_position_
  embeddings / (2 pi turns)) / (2 ln theta)``; ``cos`` and ``sin`` times
  ``attention_factor``.
- ``mlp_layer_types[i]`` ``dense``: ``W_out (silu(g) * u)`` with ``[g,
  u] = n W_in`` at ``intermediate_size``. ``sparse``: ``p = softmax(n
  W_r)`` over ``num_experts``; the ``num_experts_per_tok`` largest are
  chosen; their weights are ``p`` over their sum (``norm_topk_prob``)
  times ``moe_routed_scaling_factor``; ``y = sum_e w_e W_d[e]
  (silu(W_g[e] n) * W_u[e] n)``; plus the shared expert, the same
  SwiGLU at ``shared_expert_intermediate_size``, ungated and unscaled.

**The share.** ``params`` may hold only some experts' banks (their
leading size) and some rows of the vocabulary: ``first_expert`` says
which expert the banks start at, and the routed sum runs over the held
experts only; the weights stay what the whole router gave. Token ids
are taken within the held rows.

Departures from the published description: each is a reading that no
key states outright (``assumed`` in `configs/laguna-s-2.1.json` holds
them in words). (1) ``gating: per-head`` is read as the head-wise form
of "Gated Attention for Large Language Models" (arXiv:2505.06708): a
sigmoid of a product of the layer's normed input, one logit a query
head, times that head's attention output before ``W_o``, no bias. (2)
No QK-norm: no key names one. (3) The router's scores are a softmax: no
key names the score, and the keys' names (``num_experts``,
``norm_topk_prob``, ``decoder_sparse_step``, ``mlp_only_layers``,
``shared_expert_intermediate_size``) are letter for letter those of the
family whose router is a softmax. (4) The shared expert is ungated and
unscaled: no key names a gate (that family gates it with a sigmoid of
a product of its input). (5) ``moe_router_logit_softcapping`` 0 = none;
``decoder_sparse_step`` 1 and ``mlp_only_layers`` [0] say what
``mlp_layer_types`` says, which is what is read. (6) The dense layer's
``W_in`` holds ``gate | up`` side by side. Queries go through the
attention in blocks of rows (``QUERY_BLOCK``) and tokens through the
matrices in blocks (``TOKEN_BLOCK``) so that a 33 k sequence fits beside
the weights; a block's scores are still ``[rows, T]`` over the whole
sequence under the explicit mask.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
TOKEN_BLOCK = 2048      # tokens to a block through the matrices
QUERY_BLOCK = 1024      # query rows to a block of one head's scores
FULL, WINDOW = "full", "window"
PUBLISHED = {FULL: "full_attention", WINDOW: "sliding_attention"}


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
    return [FULL if t == PUBLISHED[FULL] else WINDOW
            for t in cfg["layer_types"][:cfg["n_layer"]]]


def kind_of(cfg, which):
    """``(query heads, key heads, head_dim, window, the kind's
    rope_parameters)`` of a full or a window layer."""
    heads = [h for h, t in zip(cfg["num_attention_heads_per_layer"],
                               cfg["layer_types"]) if t == PUBLISHED[which]]
    return (heads[0], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"] if which == WINDOW else 0,
            cfg["rope_parameters"][PUBLISHED[which]])


def frequencies(rope, r):
    """``(the r / 2 rotary frequencies, the factor on cos and sin)`` of
    a kind's ``rope_parameters``, by the direct formulas above."""
    theta = rope["rope_theta"]
    i = np.arange(r // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / r)
    if rope["rope_type"] != "yarn":
        return plain, 1.0

    def dim_of(turns):
        return r * math.log(rope["original_max_position_embeddings"] /
                            (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), r - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain * (1.0 - ramp) + plain / rope["factor"] * ramp,
            rope.get("attention_factor", 1.0))


def rotary(x, positions, rope, head_dim):
    """Rotate-half rotary of the first ``r`` entries of each head of
    ``x`` ``[T, H, D]`` at ``positions`` ``[T]``; the rest pass."""
    r = int(rope["partial_rotary_factor"] * head_dim)
    freq, factor = frequencies(rope, r)
    ang = _f32(positions)[:, None] * _f32(freq)             # [T, r/2]
    cos, sin = (jnp.cos(ang) * factor)[:, None], \
        (jnp.sin(ang) * factor)[:, None]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], -1)


def keys_values(n, p, cfg, which):
    """What a layer keeps of ``n`` ``[T, C]``: ``(k [T, key heads,
    head_dim]`` rotated, ``v`` alike)."""
    n = _f32(n)
    T = n.shape[0]
    _, Hkv, D, _, rope = kind_of(cfg, which)
    k = _blocks(lambda x: _mm(x, p["k_proj"]), n).reshape(T, Hkv, D)
    v = _blocks(lambda x: _mm(x, p["v_proj"]), n).reshape(T, Hkv, D)
    return rotary(k, jnp.arange(T), rope, D), v


def attention(n, p, cfg, which, kv=None):
    """``n`` ``[T, C]`` -> ``[T, C]``: one attention layer of kind
    ``which`` over the whole sequence, gated a head: ``sum_h (y_h
    g_h) W_o[h]``, a head at a time."""
    n = _f32(n)
    T = n.shape[0]
    Hq, Hkv, D, window, rope = kind_of(cfg, which)
    k, v = kv if kv is not None else keys_values(n, p, cfg, which)
    gate = jax.nn.sigmoid(_blocks(lambda x: _mm(x, p["g_proj"]), n))
    rows = min(QUERY_BLOCK, T)
    pad = -T % rows
    j = jnp.arange(T)[None, :]

    def head(i, out):           # one head at a time, its queries too
        kh, vh = k[:, i // (Hq // Hkv)], v[:, i // (Hq // Hkv)]
        w_q = _f32(jax.lax.dynamic_slice_in_dim(p["q_proj"], i * D, D, 1))
        q = _blocks(lambda x: jnp.matmul(x, w_q, precision=HIGHEST), n)
        q = rotary(q[:, None], jnp.arange(T), rope, D)[:, 0]
        q = jnp.pad(q, [(0, pad), (0, 0)])

        def block(c):           # [rows, T] scores under the mask
            t = (c * rows + jnp.arange(rows))[:, None]
            qb = jax.lax.dynamic_slice_in_dim(q, c * rows, rows)
            s = jnp.matmul(qb, kh.T, precision=HIGHEST) * D ** -0.5
            seen = j <= t
            if window:
                seen = seen & (t - j < window)
            w = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            return jnp.matmul(w, vh, precision=HIGHEST)

        y = jax.lax.map(block, jnp.arange((T + pad) // rows)).reshape(
            T + pad, D)[:T]
        # the head's rows of W_o, on its gated output
        w_o = _f32(jax.lax.dynamic_slice_in_dim(p["o_proj"], i * D, D, 0))
        g = jax.lax.dynamic_slice_in_dim(gate, i, 1, 1)         # [T, 1]
        return out + _blocks(
            lambda x: jnp.matmul(x, w_o, precision=HIGHEST), y * g)

    return jax.lax.fori_loop(0, Hq, head, jnp.zeros_like(n))


def mlp(n, p):
    def block(x):
        gu = _mm(x, p["w_in"])
        i = gu.shape[-1] // 2
        return _mm(jax.nn.silu(gu[:, :i]) * gu[:, i:], p["w_out"])
    return _blocks(block, _f32(n))


def route(n, p, cfg):
    """``(weights [T, k], experts [T, k])`` of the whole router."""
    probs = jax.nn.softmax(_mm(_f32(n), p["router"]), axis=-1)
    w, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w * cfg["moe_routed_scaling_factor"], chosen


def shared_expert(x, p):
    return _mm(jax.nn.silu(_mm(x, p["shared_gate"])) *
               _mm(x, p["shared_up"]), p["shared_down"])


def experts(n, p, cfg, first_expert=0, shared=True):
    """An expert layer on the share: the held experts' part of the
    routed sum, a loop over them, and (``shared``) the shared expert."""
    n = _f32(n)
    held = p["w_gate"].shape[0]

    def block(x):
        w, chosen = route(x, p, cfg)

        def one(e, y):
            mine = (w * (chosen == first_expert + e)).sum(-1)   # [T]
            h = jax.nn.silu(_mm(x, p["w_gate"][e])) * _mm(x, p["w_up"][e])
            return y + mine[:, None] * _mm(h, p["w_down"][e])

        y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
        return y + shared_expert(x, p) if shared else y

    return _blocks(block, n)


# --- the forward pass --------------------------------------------------------

KEYS = ("num_key_value_heads", "head_dim", "sliding_window", "rms_norm_eps",
        "rope_parameters", "layer_types", "num_attention_heads_per_layer",
        "num_experts_per_tok", "norm_topk_prob", "moe_routed_scaling_factor")


def _static(cfg):
    """The numbers the layer functions read, hashable."""
    return json.dumps({k: cfg[k] for k in KEYS}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("which", "dense", "cfg",
                                             "first_expert"))
def _layer(h, p, which, dense, cfg, first_expert):
    cfg = json.loads(cfg)
    eps = cfg["rms_norm_eps"]
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
    values ``[T, key heads, head_dim]`` as a cache would keep them
    (rotated). ``layers`` stops after that many layers (then the logits
    are ``None``)."""
    static, first = _static(cfg), first_expert_of(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    kinds = layer_kinds(cfg)
    h = _f32(params["embed"][tokens])
    kv = {}
    for i, which in enumerate(kinds[:layers]):
        name = f"layers_{i}"
        h, kv[name] = _layer(h, params[name], which,
                             cfg["mlp_layer_types"][i] == "dense", static,
                             first)
    if layers is not None and layers < len(kinds):
        return None, kv
    rows = jnp.arange(len(tokens)) if rows is None else jnp.asarray(rows)
    return _head(h, params["final_norm"], params["lm_head"], rows,
                 cfg["rms_norm_eps"]), kv

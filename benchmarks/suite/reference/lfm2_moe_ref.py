"""Plain reference for the LFM2 configurations with experts
(``lfm2_moe``): the published forward pass in straightforward
``jax.numpy`` and float32, one sequence at a time, written from the
equations below and not from the program. No kernel, no cache, no pages,
no chunks, no window, no sort, no grouped matmul: the convolution is an
**explicit sum over taps** of ``b * x`` over the whole sequence;
attention is a head at a time, every query over the whole prefix under
the explicit causal mask (queries in blocks of 1,024); the routing is a
plain top-k of ``s + bias``; every held expert is computed on every
token and weighted (by zero where the token did not choose it). Matrix
products run at ``highest`` precision. It imports nothing of
``deepspeed_tpu``.

It follows the ``config.json`` of ``LiquidAI/LFM2-8B-A1B``
(``model_type: lfm2_moe``). ``norm(x) = x rsqrt(mean(x^2) + norm_eps)
w`` (plain weight), no bias in any product:

- stream: ``h = embed[tokens]``; layer ``i`` is ``h = h +
  mixer_i(norm(h))``, ``h = h + ffn_i(norm(h))``; the mixer is attention
  where ``layer_types[i] == "full_attention"`` and the short convolution
  where it is ``"conv"``; ``ffn_i`` is a SwiGLU of ``intermediate_size``
  for ``i < num_dense_layers`` and the expert layer otherwise; after the
  last, ``logits = norm(h) @ embed^T`` (tied).
- short convolution, ``L = conv_L_cache``: ``[b | c | x] = n W_in``
  (``hidden_size`` each); ``u_t = b_t * x_t``; ``z_t = sum_j w_j u_(t -
  (L - 1) + j)`` (depthwise, ``u`` before the sequence 0; ``w_(L-1)``
  multiplies the current token); ``y_t = (c_t * z_t) W_out``. No
  activation.
- attention, ``Hq`` query heads over ``Hkv`` key heads of ``D =
  hidden_size / Hq``: ``q = n W_q``, ``k = n W_k``, ``v = n W_v``; ``q``,
  ``k`` a head ``x rsqrt(mean(x^2) + norm_eps) w`` (``w`` ``[D]``, one
  for queries, one for keys); rotary (``rope_theta``, rotate-half: entry
  ``i`` pairs with ``i + D/2``) over all ``D``; ``softmax(q . k
  D^-0.5)`` under the causal mask, query head ``h`` against key head ``h
  // (Hq / Hkv)``; ``W_o``.
- experts: ``s = sigmoid(n W_r)`` over ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + bias`` are chosen; weights
  ``s_e / (sum of the chosen s + 1e-6) routed_scaling_factor``; ``y =
  sum_e w_e W_2[e] (silu(W_1[e] n) * W_3[e] n)``; no shared expert.

**The share.** ``params`` may hold only some experts' banks:
``first_expert`` says which expert the banks start at, and the routed
sum runs over the held experts only; the weights stay what the whole
router gave (they sum to ``routed_scaling_factor`` over all chosen
experts, held or not).

**Assumed readings** (each also in ``configs/lfm2-8b-a1b.json``'s
``assumed``; a departure wherever the published code reads otherwise):
(1) the head is tied to the embedding (``tie_embedding`` is no key of
the catalog's ``config``; the family ties it, and 8.34 G parameters
against 8.47 G untied is the count the model's name states); (2)
``head_dim`` = ``hidden_size / num_attention_heads`` = 64; (3) an RMS
norm a head on queries and keys, before the rotary (the family's
``q_layernorm`` / ``k_layernorm``; no key says so); (4) rotate-half
over the whole head, no scaling; (5) the input projection's columns are
``b | c | x`` in that order; (6) what a row keeps is the last ``L - 1``
values of ``u`` (the published cache keeps ``L`` columns, of which the
oldest is never read again: a departure that changes nothing); (7) the
choice bias moves the choice and no weight; (8) the renormaliser is
``sum + 1e-6``; (9) the router's product is float32 at ``highest``;
(10) experts are evaluated densely and masked, not dispatched; (11) no
dropout, no mask but the causal one, one sequence.

``params`` is the program's parameter tree (``embed`` ``[vocab,
hidden]``, ``embedding_norm``, ``layers_<i>/{operator_norm, ffn_norm,
mixer/{in_proj, conv_weight [taps, hidden], out_proj} | attn/{q_proj,
k_proj, v_proj, q_layernorm, k_layernorm, o_proj}, mlp/{w_in, w_out} |
experts/{router, expert_bias, w_gate, w_up, w_down}}``; a norm holds its
``weight``; a product is ``x @ W`` with ``W`` stored ``[in, out]``),
read in float32 whatever type it is stored in, **a layer at a time and
a block of tokens at a time within it**: :func:`forward` is a Python
loop over jitted layer functions, so that 9 k tokens at the published
widths stand beside a 5 GB engine.

``cfg`` is a configuration file's dict (the published keys, ``n_layer``).
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
CONV, ATTENTION = "conv", "full_attention"
TOKEN_BLOCK = 1024
ROUTE_EPS = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


def norm(x, w, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def _blocks(fn, x, block=TOKEN_BLOCK):
    """``fn`` over the rows of ``x`` (an array or a tuple of arrays with
    the same rows) in blocks: the token-local parts."""
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
    return list(cfg["layer_types"][:cfg.get("n_layer",
                                            cfg["num_hidden_layers"])])


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


# --- the short convolution ----------------------------------------------------

def gated_input(n, p):
    """``(u = b * x, c)`` ``[T, C]`` each of the normed input."""
    bcx = _blocks(lambda x: _mm(x, p["in_proj"]), _f32(n))
    C = bcx.shape[1] // 3
    return bcx[:, :C] * bcx[:, 2 * C:], bcx[:, C:2 * C]


def taps(padded, w, T):
    """``z_t = sum_j w_j padded_(t + j)``: ``padded`` is the sequence
    behind ``len(w) - 1`` rows of what came before it."""
    return sum(w[j][None, :] * padded[j:j + T] for j in range(len(w)))


def gate_out(c, z):
    return c * z


def short_conv(n, p, cfg, state_at=None):
    """``n`` ``[T, C]`` -> ``(y [T, C], the window after token
    ``state_at`` [L - 1, C])``: the taps an explicit sum over the whole
    sequence, zeros before it."""
    L = cfg["conv_L_cache"]
    u, c = gated_input(n, p)
    T = u.shape[0]
    padded = jnp.concatenate([jnp.zeros((L - 1, u.shape[1])), u])
    z = taps(padded, _f32(p["conv_weight"]), T)
    at = T - 1 if state_at is None else state_at
    # tokens at - (L - 2) .. at: padded rows at + 1 .. at + L - 1
    window = jax.lax.dynamic_slice_in_dim(padded, at + 1, L - 1, 0)
    return _blocks(lambda x: _mm(x, p["out_proj"]), gate_out(c, z)), window


# --- attention ------------------------------------------------------------------

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


def keys_values(n, p, cfg):
    """What a pool holds of the normed input ``n`` ``[T, C]``: the keys
    normed a head and rotated, and the values, ``[T, Hkv, D]`` each."""
    n = _f32(n)
    D, Hkv = head_dim(cfg), cfg["num_key_value_heads"]
    k = _blocks(lambda x: _mm(x, p["k_proj"]), n).reshape(-1, Hkv, D)
    v = _blocks(lambda x: _mm(x, p["v_proj"]), n).reshape(-1, Hkv, D)
    k = norm(k, p["k_layernorm"], cfg["norm_eps"])
    return rotary(k, jnp.arange(len(n)), cfg["rope_theta"]), v


def attention(n, p, cfg, kv=None, scale=None):
    """``n`` ``[T, C]`` -> ``[T, C]``: a query head at a time over the
    whole prefix, its queries a block at a time. (``kv``:
    :func:`keys_values` of ``n``.)"""
    n = _f32(n)
    T, C = n.shape
    Hq, Hkv, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    scale = D ** -0.5 if scale is None else scale
    keys, values = keys_values(n, p, cfg) if kv is None else kv
    pos = jnp.arange(T)
    w_q = _f32(p["q_proj"]).reshape(C, Hq, D).transpose(1, 0, 2)
    w_o = _f32(p["o_proj"]).reshape(Hq, D, C)
    group = Hq // Hkv

    def head(out, w):
        h, wq, wo = w
        q = _blocks(lambda x: jnp.matmul(x, wq, precision=HIGHEST), n)
        q = rotary(norm(q, p["q_layernorm"], cfg["norm_eps"]), pos,
                   cfg["rope_theta"])
        k_h = jax.lax.dynamic_index_in_dim(keys, h // group, 1, False)
        v_h = jax.lax.dynamic_index_in_dim(values, h // group, 1, False)

        def queries(qp):
            q_b, p_b = qp
            s = jnp.matmul(q_b, k_h.T, precision=HIGHEST) * scale
            s = jnp.where(pos[None, :] <= p_b[:, None], s, -jnp.inf)
            return jnp.matmul(jax.nn.softmax(s, axis=-1), v_h,
                              precision=HIGHEST)

        o = _blocks(queries, (q, pos))
        return out + _blocks(
            lambda x: jnp.matmul(x, wo, precision=HIGHEST), o), None

    out, _ = jax.lax.scan(head, jnp.zeros((T, C), jnp.float32),
                          (jnp.arange(Hq), w_q, w_o))
    return out


# --- feed-forward ---------------------------------------------------------------

def swiglu(x, p):
    """``(silu(g) * u) W_out`` with ``[g, u] = x W_in``."""
    gu = _mm(x, p["w_in"])
    half = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :half]) * gu[..., half:], p["w_out"])


def route(n, p, cfg):
    """``(weights [T, k] float32, experts [T, k])`` of the normed input:
    the ``k`` largest of ``s + bias`` (a stable sort: the lower expert
    on a tie), weighted by ``s`` alone."""
    s = jax.nn.sigmoid(_mm(_f32(n), p["router"]))
    chosen = jnp.argsort(-(s + _f32(p["expert_bias"])), axis=1,
                         stable=True)[:, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, chosen, axis=1)
    return w / (w.sum(-1, keepdims=True) + ROUTE_EPS) * \
        cfg["routed_scaling_factor"], chosen


def experts(n, p, cfg, first_expert=0):
    """An expert layer on the share: expert ``e`` of the banks is
    computed on every token and weighted by the token's weight for it (0
    where it was not chosen)."""
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


# --- the forward pass ----------------------------------------------------------

def _static(cfg):
    """The numbers the layer functions read, hashable."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "conv_L_cache", "rope_theta", "norm_eps", "num_experts_per_tok",
            "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("kind", "dense", "cfg",
                                             "first_expert"))
def _layer(h, p, state_at, kind, dense, cfg, first_expert):
    cfg = dict(cfg)
    eps = cfg["norm_eps"]
    n = norm(h, p["operator_norm"]["weight"], eps)
    if kind == ATTENTION:
        kept = keys_values(n, p["attn"], cfg)
        y = attention(n, p["attn"], cfg, kv=kept)
    else:
        y, kept = short_conv(n, p["mixer"], cfg, state_at)
    h = h + y
    n = norm(h, p["ffn_norm"]["weight"], eps)
    if dense:
        return h + _blocks(lambda x: swiglu(x, p["mlp"]), n), kept
    return h + experts(n, p["experts"], cfg, first_expert), kept


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, final_norm, embed, rows, eps):
    return jnp.matmul(norm(h[rows], final_norm["weight"], eps),
                      _f32(embed).T, precision=HIGHEST)


def first_expert_of(cfg):
    return cfg.get("assumed", {}).get("experts_held", [0])[0]


def forward(params, tokens, cfg, rows=None, state_at=None, layers=None):
    """One sequence ``tokens`` ``[T]`` through the model. Returns
    ``(logits [len(rows), vocab], {convolution layer: window [L - 1,
    C]}, {attention layer: (keys, values) [T, Hkv, D]})``: the logits at
    the positions ``rows`` (default: all), every convolution layer's
    window after token ``state_at`` (default: the last) and every
    attention layer's pooled keys and values. ``layers`` stops after
    that many layers (then the logits are ``None``)."""
    static, first = _static(cfg), first_expert_of(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    at = jnp.asarray(len(tokens) - 1 if state_at is None else state_at,
                     jnp.int32)
    kinds = layer_types(cfg)
    h = _f32(params["embed"][tokens])
    windows, pooled = {}, {}
    for i, kind in enumerate(kinds[:layers]):
        name = f"layers_{i}"
        h, kept = _layer(h, params[name], at, kind,
                         i < cfg["num_dense_layers"], static, first)
        (pooled if kind == ATTENTION else windows)[name] = kept
    if layers is not None and layers < len(kinds):
        return None, windows, pooled
    rows = jnp.arange(len(tokens)) if rows is None else jnp.asarray(rows)
    return _head(h, params["embedding_norm"], params["embed"], rows,
                 cfg["norm_eps"]), windows, pooled

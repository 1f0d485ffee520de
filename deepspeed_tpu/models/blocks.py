"""The parts the served decoders share.

`granite_hybrid`, `mla_moe`, `nemotron_h`, `qwen3_next`, `mimo_v2`,
`laguna`, `ling_hybrid` and `lfm2_moe` are built from these and import
nothing of one another (`tests/unit/test_model_blocks.py` holds that);
`olmoe` takes its norm and its initialiser from here too. This module
sits below the model files and above `moe/`, `ops/` and
`inference/cache.py`. What is here is used by more than one model as it
is; what differs between models in more than its parameters (an
attention's norms, gates and rotary scheme, a mixer, how the experts
route) stays in the model's file.

- initialisers and parameter helpers (`normal`, `param`, `linear`, the
  Mamba-2 and convolution initialisers, the router bias's two draws);
- layers: `RMSNorm`, `GatedMLP`, `GroupedQueryAttention`, `Mamba2Mixer`;
- rotary: `rotate`, `rope_cos_sin`, `partial_rotary`, `yarn_inv_freq`;
- the experts' counters by name (`expert_counters`, `summed_counters`)
  and the one jitted expert layer two models share
  (`sigmoid_held_experts`);
- the frame of a served decoder: `ServedLM` (the engine's protocol,
  `inference/engine.py`), `token_mask`, `last_token`, `head_logits`;
- random weights that a check can use: `centred`, `init_served_params`.
"""

import functools
import math
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.moe.dropless import dropless_moe, sigmoid_top_k
from deepspeed_tpu.ops import ssm


# --- initialisers and parameter helpers --------------------------------------

def normal(cfg):
    return nn.initializers.normal(cfg.initializer_range)


def param(mod, name, cfg, shape):
    return mod.param(name, normal(cfg), shape,
                     cfg.param_dtype).astype(cfg.dtype)


def linear(mod, name, cfg, shape, x):
    return jnp.dot(x, param(mod, name, cfg, shape))


def dt_bias_init(key, shape, dtype):
    """``dt = softplus(dt_bias)`` log-uniform over [0.001, 0.1] (Mamba-2's
    ``dt_min`` / ``dt_max``, floor 1e-4), stored through the inverse of
    softplus."""
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    dt = jnp.maximum(dt, 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def a_log_init(key, shape, dtype):
    """``A = -exp(A_log)`` uniform over [-16, -1] (Mamba-2's
    ``A_init_range``): with the ``dt`` above a step's decay ``exp(dt A)``
    runs from ~0.07 to ~0.9994 over heads and tokens: some heads forget
    within a token or two, some keep a thousand."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


def _in_proj_init(cfg):
    """``[z, xBC]`` columns at ``initializer_range``; the ``dt`` columns
    small enough that the projection moves ``dt`` by about ``e^(+-0.5)``
    round ``softplus(dt_bias)`` (a unit-RMS input times ``0.5 /
    sqrt(hidden)``), as a trained model's does: drawn at the range of
    the rest, the projection would swamp ``dt_bias`` and most tokens
    would wipe the state."""
    wide = normal(cfg)
    dt_std = 0.5 / math.sqrt(cfg.hidden_size)

    def init(key, shape, dtype):
        k1, k2 = jax.random.split(key)
        H = cfg.mamba_n_heads
        return jnp.concatenate(
            [wide(k1, (shape[0], shape[1] - H), jnp.float32),
             dt_std * jax.random.normal(k2, (shape[0], H), jnp.float32)],
            axis=1).astype(dtype)
    return init


def conv_init(taps):
    """torch's Conv1d default: uniform within 1 / sqrt(taps)."""
    bound = 1.0 / math.sqrt(taps)

    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    return init

def uniform_bias_init(cfg):
    """A router's choice bias uniform within ``router_bias_range``
    (DeepSeek-V3's ``e_score_correction_bias`` and its kin)."""
    def init(key, shape, dtype):
        r = cfg.router_bias_range
        return jax.random.uniform(key, shape, dtype, -r, r)
    return init


def normal_bias_init(cfg):
    """A router's choice bias normal at ``router_bias_range`` (LFM2's
    ``expert_bias``)."""
    def init(key, shape, dtype):
        return cfg.router_bias_range * jax.random.normal(key, shape, dtype)
    return init


def l2_normalised(x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + eps)


# --- rotary ------------------------------------------------------------------

def rotate(x, cos, sin):
    """Rotary embedding of ``x`` ``[..., d]`` (rotate-half convention)
    by ``cos`` / ``sin`` ``[..., d / 2]``, in float32."""
    d = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d], x32[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def rope_cos_sin(positions, dim, theta):
    """``cos`` and ``sin`` ``[..., dim / 2]`` float32 of the plain
    rotary angles of a width ``dim`` at ``positions`` ``[...]``; a head
    axis is the caller's, an axis more on ``positions``."""
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def partial_rotary(x, positions, cfg):
    """Rotary embedding (rotate-half) of the first ``rotary_dim``
    entries of each head of ``x`` ``[B, T, H, D]`` at ``positions``
    ``[B, T]``; the rest pass. Angles in float32."""
    r = cfg.rotary_dim
    inv = 1.0 / cfg.rope_theta ** (
        jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[..., None] * inv     # [B, T, r/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    return jnp.concatenate([rotate(x[..., :r], cos, sin), x[..., r:]], -1)


def yarn_inv_freq(dim, theta, rope_scaling):
    """The ``dim // 2`` rotary frequencies under YaRN, float64 numpy:
    ``theta^(-2i/dim)`` where a dimension turns more than ``beta_fast``
    times over the original context, that over ``factor`` where it
    turns fewer than ``beta_slow`` times, a linear blend between."""
    rs = dict(rope_scaling)
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]
    extra = theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) /
                   (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


# --- layers ------------------------------------------------------------------

class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * weight``, statistics in float32."""
    config: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],),
                       cfg.param_dtype)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        return (x32 * w.astype(jnp.float32)).astype(cfg.dtype)


class GatedMLP(nn.Module):
    """``(silu(g) * u) W_out`` with ``[g, u] = x W_in``; no bias.
    ``width``: the inner width where the configuration has several
    (Kimi's dense and shared MLPs); 0 is ``shared_intermediate_size``."""
    config: Any
    width: int = 0

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        C = cfg.hidden_size
        I = self.width or cfg.shared_intermediate_size
        gu = linear(self, "w_in", cfg, (C, 2 * I), x)
        y = jax.nn.silu(gu[..., :I]) * gu[..., I:]
        return linear(self, "w_out", cfg, (I, C), y)


class GroupedQueryAttention(nn.Module):
    """Causal attention through the page pool: grouped queries, no
    positional encoding, the configuration's own score scale."""
    config: Any

    @nn.compact
    def __call__(self, x, layer_cache, positions, page_table, attn):
        from deepspeed_tpu.inference.cache import cached_attention
        cfg = self.config
        B, T, C = x.shape
        Hq, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        with jax.named_scope("ds_attn_qkv"):
            q = linear(self, "q_proj", cfg, (C, Hq * D), x)
            k = linear(self, "k_proj", cfg, (C, Hkv * D), x)
            v = linear(self, "v_proj", cfg, (C, Hkv * D), x)
        y, layer_cache = cached_attention(
            q.reshape(B, T, Hq, D), k.reshape(B, T, Hkv, D),
            v.reshape(B, T, Hkv, D), layer_cache, positions, cfg.dtype,
            page_table, scale=cfg.attention_multiplier, **attn)
        with jax.named_scope("ds_attn_out"):
            y = linear(self, "o_proj", cfg, (Hq * D, C),
                        y.reshape(B, T, Hq * D))
        return y, layer_cache


class Mamba2Mixer(nn.Module):
    """The state-space mixer through its slot's recurrent leaves
    (``ssm`` ``[rows, H, P, N]`` float32, ``conv`` ``[K-1, rows,
    channels]``). Two shapes, as the page pool's writes have:

    - a prefill chunk (one row, ``T`` tokens of which ``n_valid`` are
      real): the row's slot's leaves are read (zeros where the chunk
      starts the prompt), the chunked scan runs from them with the
      padded tail's ``dt`` zeroed, and the state after the last real
      token and the window at that token go back into the slot;
    - a decode step (``T == 1``, row ``i`` in slot ``i``): one step of
      the recurrence for every row; a row with ``n_valid`` 0 holds no
      request and keeps its leaves.
    """
    config: Any     # one with Granite's mamba_* names

    @nn.compact
    def __call__(self, x, leaves, positions, slots, n_valid):
        cfg = self.config
        B, T, C = x.shape
        H, P, N, K = cfg.mamba_n_heads, cfg.mamba_d_head, \
            cfg.mamba_d_state, cfg.mamba_d_conv
        G = cfg.mamba_n_groups
        d_in, d_conv = cfg.d_inner, cfg.conv_dim

        def maps(u):
            """``B`` and ``C`` of the convolved channels ``u`` ``[rows,
            d_conv]``: ``[rows, N]``, with groups ``[rows, G, N]``."""
            b, c = u[:, d_in:d_in + G * N], u[:, d_in + G * N:]
            if G > 1:
                b, c = (m.reshape(-1, G, N) for m in (b, c))
            return b, c

        pd = cfg.param_dtype
        with jax.named_scope("ds_ssm_in_proj"):
            w_in = self.param("in_proj", _in_proj_init(cfg),
                              (C, d_in + d_conv + H), pd)
            zxd = jnp.dot(x, w_in.astype(cfg.dtype))
        z, xbc, dt = (zxd[..., :d_in], zxd[..., d_in:d_in + d_conv],
                      zxd[..., d_in + d_conv:])
        conv_w = self.param("conv_weight", conv_init(K), (K, d_conv), pd)
        conv_b = self.param("conv_bias", conv_init(K), (d_conv,), pd)
        dt_bias = self.param("dt_bias", dt_bias_init, (H,), pd)
        A = -jnp.exp(self.param("A_log", a_log_init, (H,),
                                pd).astype(jnp.float32))
        D = self.param("D", nn.initializers.ones, (H,), pd)
        dt = jax.nn.softplus(dt.astype(jnp.float32) +
                             dt_bias.astype(jnp.float32))
        state, window = leaves["ssm"], leaves["conv"]

        if T == 1:
            live = n_valid > 0
            with jax.named_scope("ds_ssm_conv"):
                u, window = ssm.causal_conv_step(xbc[:, 0], window, conv_w,
                                                 conv_b, live)
                u = jax.nn.silu(u).astype(cfg.dtype)
            xs = u[:, :d_in].reshape(B, H, P)
            with jax.named_scope("ds_ssm_scan"):
                y, state = ssm.ssm_decode_step(
                    xs, dt[:, 0], A, *maps(u), state, live)
            y = y[:, None]                              # [B, 1, H, P]
            xs = xs[:, None]
        elif B == 1:
            slot, n = slots[0], n_valid[0]
            fresh = positions[0, 0] == 0
            with jax.named_scope("ds_ssm_conv"):
                win = jax.lax.dynamic_slice_in_dim(window, slot, 1, 1)[:, 0]
                win = jnp.where(fresh, jnp.zeros_like(win), win)
                u, win = ssm.causal_conv_prefill(xbc[0], win, conv_w,
                                                 conv_b, n)
                u = jax.nn.silu(u).astype(cfg.dtype)
                window = jax.lax.dynamic_update_slice_in_dim(
                    window, win[:, None], slot, 1)
            xs = u[:, :d_in].reshape(T, H, P)
            with jax.named_scope("ds_ssm_scan"):
                s0 = jax.lax.dynamic_index_in_dim(state, slot, 0, False)
                s0 = jnp.where(fresh, jnp.zeros_like(s0), s0)
                # the ragged tail: dt = 0 decays nothing, adds nothing
                dt_row = jnp.where(jnp.arange(T)[:, None] < n, dt[0], 0.0)
                y, s1 = ssm.ssd_chunked_scan(
                    xs, dt_row, A, *maps(u), s0, cfg.mamba_chunk_size)
                state = jax.lax.dynamic_update_index_in_dim(
                    state, s1, slot, 0)
            y, xs = y[None], xs[None]                   # [1, T, H, P]
        else:
            raise ValueError(
                f"a mixer serves one prompt's chunk or one token of "
                f"every row; got {B} rows of {T} tokens")

        with jax.named_scope("ds_ssm_gate_norm"):
            y = y + D.astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
            y = y.reshape(B, T, d_in) * jax.nn.silu(z.astype(jnp.float32))
            w = self.param("norm_weight", nn.initializers.ones, (d_in,), pd)
            if G > 1:       # each group's channels have their own statistics
                y = y.reshape(B, T, G, d_in // G)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                                  + cfg.rms_norm_eps)
            y = (y.reshape(B, T, d_in) *
                 w.astype(jnp.float32)).astype(cfg.dtype)
        with jax.named_scope("ds_ssm_out_proj"):
            y = linear(self, "out_proj", cfg, (d_in, C), y)
        return y, {"ssm": state, "conv": window}


# --- the experts' counters ---------------------------------------------------

class ExpertCounters(NamedTuple):
    """What one expert layer counts of one call, int32 scalars: the
    pairs the router made of the real tokens, those that fell on an
    expert held here, the held experts that took any, the fullest held
    expert's pairs, and the sorted rows the dispatch filled (whole
    tiles, `moe/dropless.py`)."""
    pairs_routed: Any
    pairs_held: Any
    experts_touched: Any
    pairs_max: Any
    rows_visited: Any


def expert_counters(mask, top_k, stats):
    """A layer's `ExpertCounters` from the tokens' ``mask``, the
    router's ``top_k`` and `dropless_moe`'s ``stats``."""
    sizes = stats["tokens_per_expert"]
    return ExpertCounters(
        pairs_routed=mask.sum().astype(jnp.int32) * top_k,
        pairs_held=sizes.sum(),
        experts_touched=(sizes > 0).sum().astype(jnp.int32),
        pairs_max=sizes.max(), rows_visited=stats["rows_visited"])


def summed_counters(names, layers, **values):
    """A model's ``serve_counters`` values, a dict in the order of
    ``names``. ``moe_<field>`` is the field of the expert ``layers``'
    counters (`ExpertCounters`, or a model's own tuple with more fields)
    summed over the layers, but ``moe_pairs_max`` their largest; any
    other name, and a ``moe_`` name that no layer counts, is one of
    ``values``. No expert layer counts zeros."""
    out = {}
    for name in names:
        if name in values:
            out[name] = values[name]
            continue
        mine = [getattr(c, name[len("moe_"):]) for c in layers]
        if not mine:
            out[name] = jnp.int32(0)
        elif name == "moe_pairs_max":
            out[name] = jnp.stack(mine).max()
        else:
            out[name] = sum(mine[1:], mine[0])
    return out


# jitted, so that a model's expert layers share one trace of the routing
# and of the three grouped matmuls (`PERF.md`, PR 30: every traced
# equation of a kernel body costs set-up time in a process that holds an
# engine). A model whose experts route or are shaped otherwise has its
# own `_held_experts`: its ``route`` closes over what it is given, and
# one function for all would branch on its caller.
@functools.partial(jax.jit, static_argnames=(
    "top_k", "scaling", "renormalise", "first_expert"))
def sigmoid_held_experts(x, mask, router, bias, w_gate, w_up, w_down, *,
                         top_k, scaling, renormalise, first_expert):
    """The held experts of a router that chooses by ``sigmoid + bias``
    in one group (Kimi's, MiMo's). Returns ``(y, ExpertCounters)``."""
    y, stats = dropless_moe(
        x, router, w_gate, w_up, w_down, top_k,
        route=sigmoid_top_k(bias, scaling, renormalise),
        first_expert=first_expert, token_mask=mask)
    return y, expert_counters(mask, top_k, stats)


# --- the frame of a served decoder -------------------------------------------

def token_mask(n_valid, T):
    """``[B, T]``: which tokens are real (not a decode row without a
    request, not a chunk's padded tail)."""
    return jnp.arange(T)[None, :] < n_valid[:, None]


def last_token(h, n_valid):
    """``h`` ``[B, T, C]`` at each row's last real token: ``[B, C]``."""
    last = jnp.maximum(n_valid - 1, 0)[:, None, None]
    return jnp.take_along_axis(h, last, axis=1)[:, 0]


def head_logits(h, head, dtype):
    """``h @ head`` (``head`` ``[C, vocab]``: an untied head, or the
    embedding transposed) in ``dtype``, accumulated and returned in
    float32."""
    return jnp.dot(h, head.astype(dtype),
                   preferred_element_type=jnp.float32)


class ServedLM:
    """The model's side of the serving engine's protocol
    (`inference/engine.py`), mixed into a decoder whose ``__call__``
    takes ``(tokens, cache, positions, page_table, slots, n_valid,
    attn)``. A decoder whose call differs (no slots where pages are the
    whole cache, no mesh) overrides `serve_args` alone."""

    @nn.nowrap
    def cache_spec(self, *args, **kwargs):
        return self.config.cache_spec(*args, **kwargs)

    @nn.nowrap
    def serve_args(self, cache, tokens, positions, page_table, slots,
                   n_valid, attn_impl, attn_block_k, attn_mesh):
        """The protocol's arguments as ``__call__`` takes them."""
        return (tokens, cache, positions, page_table, slots, n_valid,
                {"impl": attn_impl, "block_k": attn_block_k,
                 "mesh": attn_mesh})

    @nn.nowrap
    def serve_apply(self, params, cache, tokens, positions, page_table,
                    slots, n_valid, attn_impl="dense", attn_block_k=128,
                    attn_mesh=None):
        return self.apply({"params": params}, *self.serve_args(
            cache, tokens, positions, page_table, slots, n_valid,
            attn_impl, attn_block_k, attn_mesh))


# --- random weights that a check can use -------------------------------------

def centred(params, writers):
    """``params`` with each writer's weights less their mean over its
    input axis, so that each output's weights sum to zero. ``writers``
    names the matrices that write to the stream by their leaf's name,
    with the input axis (1 for a bank of experts).

    Random weights give every token the same positive mean activation
    (``relu^2``, ``silu``), which an uncentred writer turns into one
    token-independent vector in the stream; every block adds to it and
    reads it back through its norm (at Nemotron's published widths,
    eleven blocks: 78 % of the stream's energy, and every token then
    chooses the same experts). A trained model's router bias balances
    its experts' load; random weights have had no such training, and
    this is what stands in for it
    (`configs/nemotron-3-super-120b-a12b.json`, ``centred_why``)."""
    def one(path, leaf):
        axis = writers.get(path[-1].key)
        if axis is None:
            return leaf
        w = leaf.astype(jnp.float32)
        return (w - w.mean(axis, keepdims=True)).astype(leaf.dtype)
    return jax.tree_util.tree_map_with_path(one, params)


def init_served_params(model, rng, writers, page=8):
    """A `ServedLM`'s weights from ``rng``, in ``param_dtype`` (a
    router's bias and a sink float32), the ``writers`` centred
    (`centred`), made on the device in one jitted call: a prefill chunk
    of one ``page`` over a 2-row toy cache gives the shapes."""
    from deepspeed_tpu.inference.cache import init_kv_cache
    spec = model.cache_spec(2, page, page_size=page)

    def init(key):
        args = model.serve_args(
            init_kv_cache(spec), jnp.zeros((1, page), jnp.int32),
            jnp.arange(page, dtype=jnp.int32)[None],
            jnp.ones((1, spec.table_width), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.full((1,), page, jnp.int32),
            "dense", page, None)
        return centred(model.init({"params": key}, *args)["params"],
                       writers)

    return jax.jit(init)(rng)

"""Granite 4.0-H: a hybrid decoder of state-space and attention layers.

Most layers are Mamba-2 mixers (a short causal convolution, then a
selective state-space recurrence with one scalar decay a head) and a few
are grouped-query attention without any positional encoding; every
layer ends in the same SiLU-gated MLP. Four constant multipliers shape
the stream: the embedding is scaled up, each branch is scaled down
before it joins the residual, the attention scores have their own scale
(not ``1 / sqrt(head)``), and the tied head's logits are divided.

Layer equations as published (``config.json`` of
``ibm-granite/granite-4.0-h-micro``, ``model_type: granitemoehybrid``;
``transformers/models/granitemoehybrid/modeling_granitemoehybrid.py``,
whose Mamba layer is Bamba's; Dao & Gu 2024, arXiv:2405.21060), with
``n = RMSNorm(h)``:

- stream: ``h = embed[tokens] * embedding_multiplier``; a layer is
  ``h = h + residual_multiplier * mixer(RMSNorm(h))`` then
  ``h = h + residual_multiplier * mlp(RMSNorm(h))``; after the last,
  ``logits = RMSNorm(h) @ embed^T / logits_scaling``.
- MLP: ``[g, u] = n W_in``; ``y = (silu(g) * u) W_out``.
- attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads (query head ``h`` reads key
  head ``h // group``); no positions; ``softmax(q . k *
  attention_multiplier)`` under the causal mask.
- Mamba-2: ``[z, xBC, dt] = n W_in``; ``xBC = silu(conv(xBC) + b)``
  (depthwise, causal, ``mamba_d_conv`` taps), split into ``x`` (heads of
  ``mamba_d_head``), ``B`` and ``C`` (``mamba_n_groups`` groups of
  ``mamba_d_state``: head ``h`` reads group ``h // (heads / groups)``;
  Granite has one group, which every head reads); ``dt = softplus(dt +
  dt_bias)``,
  ``A = -exp(A_log)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y * silu(z)) * w`` (the gate
  before the norm; the statistics over each group's ``d_inner /
  mamba_n_groups`` channels, with one group over the whole inner
  width); ``out = y W_out``.

This file is the serving model: it runs through a cache
(`inference/cache.py`: page pools for the attention layers, per-slot
recurrent leaves for the mixers) under the engine's protocol
(`inference/engine.py`), a prefill chunk of one prompt or a decode step
of every row. `benchmarks/suite/reference/granite_hybrid_ref.py` is the
plain float32, token-by-token statement of the same mathematics.
Training (the scan's backward, packed documents) is not here.

Precision, part of the configuration and not a tuning knob: weights,
activations, the KV pool and the convolution window in ``dtype``
(bfloat16 as published); products accumulate in float32; ``dt``,
``exp(dt A)``, the state ``S`` and the statistics of every norm float32.
"""

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.olmoe import RMSNorm
from deepspeed_tpu.ops import ssm

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The published ``config.json`` keys under their published names,
    and how it is run. ``layer_types`` is the model's pattern: a later
    hybrid is other numbers."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    initializer_range: float = 0.1
    dtype: Any = jnp.bfloat16           # activations, pool, conv window
    param_dtype: Any = jnp.bfloat16     # weights as served

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each '{MAMBA}' or '{ATTENTION}'; got {self.layer_types}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_groups must divide mamba_n_heads")
        if self.mamba_n_heads * self.mamba_d_head != \
                self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads x mamba_d_head must be "
                             "mamba_expand x hidden_size")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def names(self, kind):
        return tuple(f"layers_{i}" for i, t in enumerate(self.layer_types)
                     if t == kind)

    def cache_spec(self, max_batch, max_seq, kv_cache_dtype=None,
                   page_size=0, n_pages=0):
        """Page pools for the attention layers (the key/value heads),
        and for every mixer a float32 state ``[rows, heads, d_head,
        d_state]`` and a convolution window ``[d_conv - 1, rows,
        channels]`` in the activations' type."""
        from deepspeed_tpu.inference.cache import page_pool_spec
        att = self.names(ATTENTION)
        return page_pool_spec(
            max_batch, max_seq, n_layer=len(att),
            n_head=self.num_key_value_heads, head_dim=self.head_dim,
            compute_dtype=self.dtype,
            n_positions=self.max_position_embeddings,
            kv_cache_dtype=kv_cache_dtype, page_size=page_size,
            n_pages=n_pages, layers=att,
            recurrent_layers=self.names(MAMBA),
            recurrent_leaves=(
                ("ssm", (max_batch, self.mamba_n_heads, self.mamba_d_head,
                         self.mamba_d_state), jnp.float32),
                ("conv", (self.mamba_d_conv - 1, max_batch, self.conv_dim),
                 self.dtype)))


def _pattern(n_layer, attention_at):
    return tuple(ATTENTION if i in attention_at else MAMBA
                 for i in range(n_layer))


def granite_4_0_h_micro(**kw):
    """ibm-granite/granite-4.0-h-micro as published: 40 layers,
    attention at 5, 15, 25 and 35."""
    kw.setdefault("layer_types", _pattern(40, (5, 15, 25, 35)))
    return GraniteHybridConfig(**kw)


def granite_hybrid_tiny(**kw):
    """Test-size model: two periods of (mamba, mamba, attention), two
    query heads to a key head."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("shared_intermediate_size", 96)
    kw.setdefault("num_hidden_layers", 6)
    kw.setdefault("layer_types", _pattern(6, (2, 5)))
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("mamba_n_heads", 8)
    kw.setdefault("mamba_d_head", 16)
    kw.setdefault("mamba_d_state", 16)
    kw.setdefault("mamba_chunk_size", 8)
    kw.setdefault("max_position_embeddings", 128)
    return GraniteHybridConfig(**kw)


def _normal(cfg):
    return nn.initializers.normal(cfg.initializer_range)


def _linear(mod, name, cfg, shape, x):
    w = mod.param(name, _normal(cfg), shape, cfg.param_dtype)
    return jnp.dot(x, w.astype(cfg.dtype))


def _dt_bias_init(key, shape, dtype):
    """``dt = softplus(dt_bias)`` log-uniform over [0.001, 0.1] (Mamba-2's
    ``dt_min`` / ``dt_max``, floor 1e-4), stored through the inverse of
    softplus."""
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    dt = jnp.maximum(dt, 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype):
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
    wide = _normal(cfg)
    dt_std = 0.5 / math.sqrt(cfg.hidden_size)

    def init(key, shape, dtype):
        k1, k2 = jax.random.split(key)
        H = cfg.mamba_n_heads
        return jnp.concatenate(
            [wide(k1, (shape[0], shape[1] - H), jnp.float32),
             dt_std * jax.random.normal(k2, (shape[0], H), jnp.float32)],
            axis=1).astype(dtype)
    return init


def _conv_init(taps):
    """torch's Conv1d default: uniform within 1 / sqrt(taps)."""
    bound = 1.0 / math.sqrt(taps)

    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    return init


class GatedMLP(nn.Module):
    """``(silu(g) * u) W_out`` with ``[g, u] = x W_in``; no bias.
    ``width``: the inner width where the configuration has several
    (`models/mla_moe.py`); 0 is ``shared_intermediate_size``."""
    config: Any
    width: int = 0

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        C = cfg.hidden_size
        I = self.width or cfg.shared_intermediate_size
        gu = _linear(self, "w_in", cfg, (C, 2 * I), x)
        y = jax.nn.silu(gu[..., :I]) * gu[..., I:]
        return _linear(self, "w_out", cfg, (I, C), y)


class GroupedQueryAttention(nn.Module):
    """Causal attention through the page pool: grouped queries, no
    positional encoding, the configuration's own score scale."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, x, layer_cache, positions, page_table, attn):
        from deepspeed_tpu.inference.cache import cached_attention
        cfg = self.config
        B, T, C = x.shape
        Hq, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        with jax.named_scope("ds_attn_qkv"):
            q = _linear(self, "q_proj", cfg, (C, Hq * D), x)
            k = _linear(self, "k_proj", cfg, (C, Hkv * D), x)
            v = _linear(self, "v_proj", cfg, (C, Hkv * D), x)
        y, layer_cache = cached_attention(
            q.reshape(B, T, Hq, D), k.reshape(B, T, Hkv, D),
            v.reshape(B, T, Hkv, D), layer_cache, positions, cfg.dtype,
            page_table, scale=cfg.attention_multiplier, **attn)
        with jax.named_scope("ds_attn_out"):
            y = _linear(self, "o_proj", cfg, (Hq * D, C),
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
    config: Any     # a GraniteHybridConfig, or one with its mamba_* names

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
        conv_w = self.param("conv_weight", _conv_init(K), (K, d_conv), pd)
        conv_b = self.param("conv_bias", _conv_init(K), (d_conv,), pd)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), pd)
        A = -jnp.exp(self.param("A_log", _a_log_init, (H,),
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
            y = _linear(self, "out_proj", cfg, (d_in, C), y)
        return y, {"ssm": state, "conv": window}


class HybridLayer(nn.Module):
    """Pre-norm residual layer: the mixer of its kind, then the MLP,
    each scaled by ``residual_multiplier``."""
    config: GraniteHybridConfig
    kind: str

    @nn.compact
    def __call__(self, h, layer_cache, positions, page_table, slots,
                 n_valid, attn):
        cfg = self.config
        scale = cfg.residual_multiplier
        if self.kind == ATTENTION:
            # the norm under the scope of the projections it feeds, the
            # residual add under that of the one it follows
            with jax.named_scope("ds_attn_qkv"):
                n = RMSNorm(cfg, name="input_norm")(h)
            y, layer_cache = GroupedQueryAttention(cfg, name="attn")(
                n, layer_cache, positions, page_table, attn)
            with jax.named_scope("ds_attn_out"):
                h = h + jnp.asarray(scale, cfg.dtype) * y
        else:   # the mixer whole, round its older inner scopes
            with jax.named_scope("ds_ssm_mixer"):
                y, layer_cache = Mamba2Mixer(cfg, name="mixer")(
                    RMSNorm(cfg, name="input_norm")(h), layer_cache,
                    positions, slots, n_valid)
                h = h + jnp.asarray(scale, cfg.dtype) * y
        with jax.named_scope("ds_mlp"):
            y = GatedMLP(cfg, name="mlp")(RMSNorm(cfg, name="post_norm")(h))
            h = h + jnp.asarray(scale, cfg.dtype) * y
        return h, layer_cache


class GraniteHybridLM(nn.Module):
    """The decoder with its tied head, through the serving cache.
    Returns ``(logits [B, vocab] float32 at each row's last real token,
    the cache)``."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, tokens, cache, positions, page_table, slots,
                 n_valid, attn):
        cfg = self.config
        embed = self.param("embed", _normal(cfg),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with jax.named_scope("ds_embed"):
            h = embed.astype(cfg.dtype)[tokens] * \
                jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
        new_cache = {}
        for i, kind in enumerate(cfg.layer_types):
            name = f"layers_{i}"
            h, new_cache[name] = HybridLayer(cfg, kind, name=name)(
                h, cache[name], positions, page_table, slots, n_valid,
                attn)
        # the head reads each row's last real token only
        with jax.named_scope("ds_head"):
            last = jnp.maximum(n_valid - 1, 0)[:, None, None]
            h = jnp.take_along_axis(h, last, axis=1)[:, 0]
            h = RMSNorm(cfg, name="final_norm")(h)
            logits = jnp.dot(h, embed.T.astype(cfg.dtype),
                             preferred_element_type=jnp.float32)
            return logits / cfg.logits_scaling, new_cache

    # -- the serving engine's protocol (`inference/engine.py`) -------------

    @nn.nowrap
    def cache_spec(self, *args, **kwargs):
        return self.config.cache_spec(*args, **kwargs)

    @nn.nowrap
    def serve_apply(self, params, cache, tokens, positions, page_table,
                    slots, n_valid, attn_impl="dense", attn_block_k=128,
                    attn_mesh=None):
        return self.apply(
            {"params": params}, tokens, cache, positions, page_table,
            slots, n_valid,
            {"impl": attn_impl, "block_k": attn_block_k,
             "mesh": attn_mesh})


def init_granite_hybrid_params(model, rng):
    """The model's weights from ``rng``, in ``param_dtype``, made on the
    device in one jitted call (a 2-row toy cache gives the shapes)."""
    cfg = model.config
    spec = cfg.cache_spec(2, 8, page_size=8)

    def init(key):
        from deepspeed_tpu.inference.cache import init_kv_cache
        return model.init(
            {"params": key}, jnp.zeros((1, 8), jnp.int32),
            init_kv_cache(spec), jnp.arange(8, dtype=jnp.int32)[None],
            jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.full((1,), 8, jnp.int32),
            {"impl": "dense", "block_k": 8, "mesh": None})["params"]

    return jax.jit(init)(rng)

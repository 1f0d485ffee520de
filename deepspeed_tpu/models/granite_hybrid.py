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
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.blocks import (GatedMLP, GroupedQueryAttention,
                                         Mamba2Mixer, RMSNorm, ServedLM,
                                         head_logits, init_served_params,
                                         last_token, normal)

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


class GraniteHybridLM(ServedLM, nn.Module):
    """The decoder with its tied head, through the serving cache.
    Returns ``(logits [B, vocab] float32 at each row's last real token,
    the cache)``."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, tokens, cache, positions, page_table, slots,
                 n_valid, attn):
        cfg = self.config
        embed = self.param("embed", normal(cfg),
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
        with jax.named_scope("ds_head"):
            h = RMSNorm(cfg, name="final_norm")(last_token(h, n_valid))
            logits = head_logits(h, embed.T, cfg.dtype)
            return logits / cfg.logits_scaling, new_cache


def init_granite_hybrid_params(model, rng):
    """The model's weights from ``rng`` (`blocks.init_served_params`);
    no writer is centred."""
    return init_served_params(model, rng, {})

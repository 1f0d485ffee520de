"""Qwen3-Next: a decoder whose mixers are Gated DeltaNet layers (a
recurrent state that is a matrix a head, written under a delta rule)
with a gated softmax attention every fourth block, every block's
feed-forward a layer of routed experts; served as one chip's share of
an expert-parallel group.

What no other model here has: a **delta-rule state** (`ops/gated_delta.py`:
the write reads the state through the key; `ops/ssm.py`'s Mamba-2 writes
an outer product whatever the state holds); **rotary keys in a per-head
page pool** (Granite's and Nemotron-H's attention has no positions,
Kimi's rotary key is one shared vector in a latent), on the first
``partial_rotary_factor`` of a **256-wide head**; an attention whose
output is **gated by the query's own projection**; **zero-centred**
norms (``1 + w``); softmax routing whose chosen probabilities are
**renormalised**; a shared expert behind a **sigmoid gate**.

Layer equations as published (``config.json`` of
``Qwen/Qwen3-Next-80B-A3B-Instruct``, ``model_type: qwen3_next``;
``transformers/models/qwen3_next/modeling_qwen3_next.py``), ``norm(x) =
x rsqrt(mean(x^2) + rms_norm_eps) (1 + w)``, no bias in any product,
untied head. Block ``i``: ``h = h + mixer_i(norm(h))``; ``h = h +
moe(norm(h))``; the mixer is full attention where ``(i + 1) %
full_attention_interval == 0``, Gated DeltaNet otherwise.

- Gated DeltaNet: ``[q, k, v, z] = n W_qkvz`` (``linear_num_key_heads``
  heads of ``linear_key_head_dim`` for ``q`` and ``k``,
  ``linear_num_value_heads`` of ``linear_value_head_dim`` for ``v`` and
  ``z``), ``[b, a] = n W_ba`` (one each a value head); ``[q; k; v]``
  through a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps without bias, then SiLU; ``q``, ``k``
  L2-normalised a head (eps 1e-6), ``q`` times ``key_dim^-0.5``, a key
  head repeated for its ``value heads / key heads`` value heads; in
  float32 ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)`` and the recurrence of `ops/gated_delta.py`; ``y =
  RMSNorm(o) w silu(z)`` a head (this norm's weight is plain, not
  ``1 + w``); ``y W_out``.
- attention: ``q_proj`` gives every head ``2 head_dim`` numbers, the
  first ``head_dim`` its query and the rest its gate; ``k_proj``,
  ``v_proj`` ``num_key_value_heads`` heads; ``norm`` over ``head_dim``
  on each query and key head; rotary (``rope_theta``, rotate-half) on
  the first ``partial_rotary_factor head_dim`` entries; causal softmax
  at ``head_dim^-0.5``; ``o sigmoid(gate)``; ``o_proj``.
- experts: ``p = softmax(n W_r)`` float32 over ``num_experts``; the
  ``num_experts_per_tok`` largest over their sum (``norm_topk_prob``);
  expert ``e`` is ``W_d[e] (silu(W_g[e] n) * W_u[e] n)``; plus
  ``sigmoid(n w_sg) shared(n)``, the shared expert a SwiGLU of
  ``shared_expert_intermediate_size``.
- after the last block ``logits = norm(h) W_head``.
- the published first projection lies a key head at a time (``q, k, v,
  v, z, z``); here it lies ``q | k | v | z`` whole: with seeded weights
  a relabelling of columns. Multi-token prediction is a draft head, no
  part of the pass that serves a token: not built.

**The share** (as `models/nemotron_h.py`): ``experts_held = (first,
count)`` of the router's ``num_experts`` are held, routing runs over
all of them and pairs of experts held elsewhere add nothing here
(`moe/dropless.py`); the first ``vocab_size`` rows of embedding and
head are held. Mixers, attention, router and the shared expert are
whole. Nothing stands in for the other chips.

Precision, part of the configuration: weights, activations, the page
pool and the convolution window in ``dtype`` (bfloat16 as published);
products accumulate in float32; the delta rule's ``g``, ``beta``,
decays, system and state, the L2 norms, every norm's statistics, the
rotary angles, the attention's softmax, the router's product (at the
highest precision), softmax, choice and weights float32.
`benchmarks/suite/reference/qwen3_next_ref.py` is the plain float32
statement of the same mathematics. Serving only.
"""

import dataclasses
import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.blocks import (ServedLM, a_log_init, conv_init,
                                         dt_bias_init, expert_counters,
                                         head_logits,
                                         init_served_params, l2_normalised,
                                         last_token, normal, param,
                                         partial_rotary, summed_counters,
                                         token_mask)
from deepspeed_tpu.moe.dropless import dropless_moe, softmax_top_k_renorm
from deepspeed_tpu.ops import gated_delta, ssm

DELTA, ATTENTION = "linear_attention", "full_attention"
# what a decode step's span carries (`inference/engine.py` reads the
# names): the expert layers' five as `models/nemotron_h.py`'s, the
# rows whose delta-rule state the step moved on against those it read
# and wrote back, and last the sorted rows the expert layers' dispatch
# filled (`moe/dropless.py`: whole tiles, summed over the layers)
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_pairs_max", "moe_experts_held", "gdn_rows_live",
            "gdn_rows_touched", "moe_rows_visited")


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The published ``config.json`` keys under their published names,
    the share that is held, and how it is run."""
    vocab_size: int = 151936            # rows held of embedding and head
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_key_head_dim: int = 128
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    delta_chunk_size: int = 64          # the chunked form's; not published
    initializer_range: float = 0.02
    norm_weight_range: float = 0.1      # a zero-centred norm's w: normal
    experts_held: Tuple[int, int] = (0, 512)    # (first, count)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and
                first + count <= self.num_experts):
            raise ValueError(
                f"experts_held {self.experts_held} must lie within the "
                f"{self.num_experts} routed experts")
        if self.decoder_sparse_step != 1 or self.mlp_only_layers or \
                not self.norm_topk_prob:
            raise ValueError(
                "every block's feed-forward is the expert layer "
                "(decoder_sparse_step 1, no mlp_only_layers) and the "
                "router renormalises (norm_topk_prob)")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                "num_key_value_heads must divide num_attention_heads and "
                "linear_num_key_heads linear_num_value_heads")
        if self.rotary_dim % 2:
            raise ValueError(
                f"partial_rotary_factor x head_dim must be even, got "
                f"{self.rotary_dim}")

    @property
    def layer_types(self):
        return tuple(
            ATTENTION if (i + 1) % self.full_attention_interval == 0
            else DELTA for i in range(self.num_hidden_layers))

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self):
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self):
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self):
        return 2 * self.key_dim + self.value_dim

    def names(self, kind):
        return tuple(f"layers_{i}" for i, t in enumerate(self.layer_types)
                     if t == kind)

    def cache_spec(self, max_batch, max_seq, kv_cache_dtype=None,
                   page_size=0, n_pages=0):
        """A page pool for the attention layers (the key/value heads at
        ``head_dim``); for every Gated DeltaNet layer a float32 state
        ``[rows, value heads, key_head_dim, value_head_dim]`` and a
        convolution window ``[taps - 1, rows, q + k + v channels]``."""
        from deepspeed_tpu.inference.cache import page_pool_spec
        att = self.names(ATTENTION)
        return page_pool_spec(
            max_batch, max_seq, n_layer=len(att),
            n_head=self.num_key_value_heads, head_dim=self.head_dim,
            compute_dtype=self.dtype,
            n_positions=self.max_position_embeddings,
            kv_cache_dtype=kv_cache_dtype, page_size=page_size,
            n_pages=n_pages, layers=att,
            recurrent_layers=self.names(DELTA),
            recurrent_leaves=(
                ("gdn", (max_batch, self.linear_num_value_heads,
                         self.linear_key_head_dim,
                         self.linear_value_head_dim), jnp.float32),
                ("conv", (self.linear_conv_kernel_dim - 1, max_batch,
                          self.conv_dim), self.dtype)))


def qwen3_next_80b_share(n_layer=8, experts_held=(0, 128),
                         vocab_size=37984, **kw):
    """Qwen3-Next-80B-A3B-Instruct at its published widths, as one chip
    of 4 that share each block holds it: the first ``n_layer`` blocks
    (eight are two periods of three Gated DeltaNet layers and an
    attention), 128 of the 512 experts, a quarter of the vocabulary's
    rows."""
    return Qwen3NextConfig(
        num_hidden_layers=n_layer, experts_held=tuple(experts_held),
        vocab_size=vocab_size, **kw)


def qwen3_next_tiny(**kw):
    """Test-size model: two periods of (delta, delta, delta, attention),
    4 of 8 experts held, top 3, two value heads to a key head, four
    query heads to a key head, rotary on half of a head."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_hidden_layers", 8)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 1)
    kw.setdefault("head_dim", 16)
    kw.setdefault("partial_rotary_factor", 0.5)
    kw.setdefault("linear_num_key_heads", 2)
    kw.setdefault("linear_key_head_dim", 16)
    kw.setdefault("linear_num_value_heads", 4)
    kw.setdefault("linear_value_head_dim", 8)
    kw.setdefault("moe_intermediate_size", 48)
    kw.setdefault("shared_expert_intermediate_size", 32)
    kw.setdefault("num_experts", 8)
    kw.setdefault("num_experts_per_tok", 3)
    kw.setdefault("experts_held", (2, 4))
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("delta_chunk_size", 8)
    kw.setdefault("initializer_range", 0.1)
    return Qwen3NextConfig(**kw)


def _norm_weight(mod, name, cfg, width):
    """A zero-centred norm's ``w`` (the norm multiplies by ``1 + w``):
    drawn, so that ``1 + w`` differs from 1 and from ``w``."""
    return mod.param(name, nn.initializers.normal(cfg.norm_weight_range),
                     (width,), cfg.param_dtype)


def zero_centred_norm(x, w, eps):
    """``x rsqrt(mean(x^2) + eps) (1 + w)`` over the last axis, in
    float32; returns float32."""
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return x32 * (1.0 + w.astype(jnp.float32))


class ZeroCentredRMSNorm(nn.Module):
    config: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        w = _norm_weight(self, "weight", cfg, x.shape[-1])
        return zero_centred_norm(x, w, cfg.rms_norm_eps).astype(cfg.dtype)


def _in_proj_ba_init(cfg):
    """The ``b`` columns at ``initializer_range`` (``beta = sigmoid(b)``
    then covers about (0.1, 0.9)); the ``a`` columns small enough that
    the projection moves ``softplus(a + dt_bias)`` by about ``e^(+-0.5)``
    (as `models/blocks.py:_in_proj_init`'s ``dt`` columns, and
    for its reason: drawn wider, most tokens wipe the state)."""
    wide = normal(cfg)
    a_std = 0.5 / math.sqrt(cfg.hidden_size)

    def init(key, shape, dtype):
        k1, k2 = jax.random.split(key)
        H = cfg.linear_num_value_heads
        return jnp.concatenate(
            [wide(k1, (shape[0], H), jnp.float32),
             a_std * jax.random.normal(k2, (shape[0], H), jnp.float32)],
            axis=1).astype(dtype)
    return init


class GatedDeltaNet(nn.Module):
    """The delta-rule mixer through its slot's recurrent leaves (``gdn``
    ``[rows, Hv, K, V]`` float32, ``conv`` ``[taps - 1, rows, channels]``).
    Two shapes, as `models/blocks.py:Mamba2Mixer`'s: a prefill
    chunk (one row, ``n_valid`` of ``T`` tokens real: the slot's leaves
    are read, zeros where the chunk starts the prompt; the padded tail's
    ``g`` and ``beta`` are zeroed; the state after the last real token
    and the window at it go back) and a decode step (``T == 1``, row
    ``i`` in slot ``i``; a row with ``n_valid`` 0 keeps its leaves)."""
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, leaves, positions, slots, n_valid):
        cfg = self.config
        B, T, C = x.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        K, V = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        taps, d_k, d_v = cfg.linear_conv_kernel_dim, cfg.key_dim, \
            cfg.value_dim
        pd = cfg.param_dtype
        qkvz = jnp.dot(x, param(self, "in_proj_qkvz", cfg,
                                 (C, cfg.conv_dim + d_v)))
        ba = jnp.dot(x, self.param(
            "in_proj_ba", _in_proj_ba_init(cfg), (C, 2 * Hv),
            pd).astype(cfg.dtype))
        qkv, z = qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:]
        conv_w = self.param("conv_weight", conv_init(taps),
                            (taps, cfg.conv_dim), pd)
        no_bias = jnp.zeros((cfg.conv_dim,), jnp.float32)
        dt_bias = self.param("dt_bias", dt_bias_init, (Hv,), pd)
        A = jnp.exp(self.param("A_log", a_log_init, (Hv,),
                               pd).astype(jnp.float32))
        ba = ba.astype(jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = -A * jax.nn.softplus(ba[..., Hv:] + dt_bias.astype(jnp.float32))
        state, window = leaves["gdn"], leaves["conv"]

        def heads(u):
            """The convolved channels ``u`` ``[rows, conv_dim]`` as the
            recurrence takes them: ``q``, ``k`` ``[rows, Hk, K]`` (unit
            length, ``q`` scaled; a key head serves ``Hv / Hk`` value
            heads), ``v`` ``[rows, Hv, V]``."""
            q = l2_normalised(u[:, :d_k].reshape(-1, Hk, K)) * K ** -0.5
            k = l2_normalised(u[:, d_k:2 * d_k].reshape(-1, Hk, K))
            return q.astype(cfg.dtype), k.astype(cfg.dtype), \
                u[:, 2 * d_k:].reshape(-1, Hv, V)

        if T == 1:
            live = n_valid > 0
            with jax.named_scope("ds_gdn_conv"):
                u, window = ssm.causal_conv_step(qkv[:, 0], window, conv_w,
                                                 no_bias, live)
                u = jax.nn.silu(u).astype(cfg.dtype)
            with jax.named_scope("ds_gdn_step"):
                o, state = gated_delta.gated_delta_step(
                    *heads(u), g[:, 0], beta[:, 0], state, live)
            o = o[:, None]                              # [B, 1, Hv, V]
        elif B == 1:
            slot, n = slots[0], n_valid[0]
            fresh = positions[0, 0] == 0
            with jax.named_scope("ds_gdn_conv"):
                win = jax.lax.dynamic_slice_in_dim(window, slot, 1, 1)[:, 0]
                win = jnp.where(fresh, jnp.zeros_like(win), win)
                u, win = ssm.causal_conv_prefill(qkv[0], win, conv_w,
                                                 no_bias, n)
                u = jax.nn.silu(u).astype(cfg.dtype)
                window = jax.lax.dynamic_update_slice_in_dim(
                    window, win[:, None], slot, 1)
            with jax.named_scope("ds_gdn_scan"):
                s0 = jax.lax.dynamic_index_in_dim(state, slot, 0, False)
                s0 = jnp.where(fresh, jnp.zeros_like(s0), s0)
                # the ragged tail: g = 0 decays nothing, beta = 0 writes
                # nothing
                real = jnp.arange(T)[:, None] < n
                o, s1 = gated_delta.gated_delta_chunked(
                    *heads(u), jnp.where(real, g[0], 0.0),
                    jnp.where(real, beta[0], 0.0), s0,
                    cfg.delta_chunk_size)
                state = jax.lax.dynamic_update_index_in_dim(
                    state, s1, slot, 0)
            o = o[None]                                 # [1, T, Hv, V]
        else:
            raise ValueError(
                f"a mixer serves one prompt's chunk or one token of "
                f"every row; got {B} rows of {T} tokens")

        w = self.param("norm_weight", nn.initializers.ones, (V,), pd)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.rms_norm_eps)
        y = o * w.astype(jnp.float32) * jax.nn.silu(
            z.astype(jnp.float32).reshape(B, T, Hv, V))
        y = y.reshape(B, T, d_v).astype(cfg.dtype)
        y = jnp.dot(y, param(self, "out_proj", cfg, (d_v, C)))
        return y, {"gdn": state, "conv": window}


class GatedAttention(nn.Module):
    """Causal grouped-query attention through the page pool with
    normed, partly rotated queries and keys, its output gated by the
    query projection's second half."""
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, layer_cache, positions, page_table, attn):
        from deepspeed_tpu.inference.cache import cached_attention
        cfg = self.config
        B, T, C = x.shape
        Hq, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        with jax.named_scope("ds_attn_qkv"):
            qg = jnp.dot(x, param(self, "q_proj", cfg, (C, Hq * 2 * D)))
            qg = qg.reshape(B, T, Hq, 2 * D)
            q, gate = qg[..., :D], qg[..., D:]
            k = jnp.dot(x, param(self, "k_proj", cfg, (C, Hkv * D)))
            v = jnp.dot(x, param(self, "v_proj", cfg, (C, Hkv * D)))
            q = zero_centred_norm(q, _norm_weight(self, "q_norm", cfg, D),
                                  cfg.rms_norm_eps)
            k = zero_centred_norm(k.reshape(B, T, Hkv, D),
                                  _norm_weight(self, "k_norm", cfg, D),
                                  cfg.rms_norm_eps)
            q = partial_rotary(q, positions, cfg).astype(cfg.dtype)
            k = partial_rotary(k, positions, cfg).astype(cfg.dtype)
            v = v.reshape(B, T, Hkv, D)
        y, layer_cache = cached_attention(
            q, k, v, layer_cache, positions, cfg.dtype, page_table,
            scale=D ** -0.5, **attn)
        with jax.named_scope("ds_attn_gate"):
            y = (y.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(cfg.dtype)
        with jax.named_scope("ds_attn_out"):
            y = jnp.dot(y.reshape(B, T, Hq * D),
                        param(self, "o_proj", cfg, (Hq * D, C)))
        return y, layer_cache


# jitted, so that the eight expert layers share one trace of the routing
# and of the three grouped matmuls (as `models/nemotron_h.py`'s)
@functools.partial(jax.jit, static_argnames=("top_k", "first_expert"))
def _held_experts(x, mask, router, w_gate, w_up, w_down, *, top_k,
                  first_expert):
    y, stats = dropless_moe(
        x, router, w_gate, w_up, w_down, top_k, route=softmax_top_k_renorm,
        first_expert=first_expert, token_mask=mask)
    return y, expert_counters(mask, top_k, stats)


class SparseExperts(nn.Module):
    """The routed experts this chip holds and the gated shared expert.
    Returns ``(y, the layer's `blocks.ExpertCounters`)``; ``mask`` ``[B,
    T]`` says which tokens are real."""
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.config
        B, T, C = x.shape
        E, I, S = cfg.num_experts, cfg.moe_intermediate_size, \
            cfg.shared_expert_intermediate_size
        first, held = cfg.experts_held
        init, pd = normal(cfg), cfg.param_dtype
        router = self.param("router", init, (C, E), pd)
        w_gate = self.param("w_gate", init, (held, C, I), pd)
        w_up = self.param("w_up", init, (held, C, I), pd)
        w_down = self.param("w_down", init, (held, I, C), pd)
        y, counters = _held_experts(
            x.reshape(B * T, C), mask.reshape(B * T), router, w_gate, w_up,
            w_down, top_k=cfg.num_experts_per_tok, first_expert=first)
        with jax.named_scope("ds_moe_shared"):
            hidden = jax.nn.silu(
                jnp.dot(x, param(self, "shared_gate", cfg, (C, S)))) * \
                jnp.dot(x, param(self, "shared_up", cfg, (C, S)))
            shared = jnp.dot(hidden, param(self, "shared_down", cfg,
                                            (S, C)))
            opened = jax.nn.sigmoid(jnp.dot(
                x, param(self, "shared_expert_gate", cfg, (C, 1)),
                preferred_element_type=jnp.float32))
            shared = (opened * shared.astype(jnp.float32)).astype(cfg.dtype)
        return y.reshape(B, T, C) + shared, counters


class Qwen3NextBlock(nn.Module):
    """``h + mixer(norm(h))`` then ``h + moe(norm(h))``. Returns ``(h,
    the mixer's cache, the expert layer's counters)``."""
    config: Qwen3NextConfig
    kind: str

    @nn.compact
    def __call__(self, h, layer_cache, positions, page_table, slots,
                 n_valid, mask, attn):
        cfg = self.config
        if self.kind == ATTENTION:
            # the norm under the scope of the projections it feeds, the
            # residual add under that of the one it follows
            with jax.named_scope("ds_attn_qkv"):
                n = ZeroCentredRMSNorm(cfg, name="input_norm")(h)
            y, layer_cache = GatedAttention(cfg, name="attn")(
                n, layer_cache, positions, page_table, attn)
            with jax.named_scope("ds_attn_out"):
                h = h + y
        else:   # the mixer whole, round its older inner scopes
            with jax.named_scope("ds_gdn_mixer"):
                y, layer_cache = GatedDeltaNet(cfg, name="mixer")(
                    ZeroCentredRMSNorm(cfg, name="input_norm")(h),
                    layer_cache, positions, slots, n_valid)
                h = h + y
        with jax.named_scope("ds_experts"):
            y, counters = SparseExperts(cfg, name="experts")(
                ZeroCentredRMSNorm(cfg, name="post_norm")(h), mask)
            return h + y, layer_cache, counters


class Qwen3NextLM(ServedLM, nn.Module):
    """The decoder with its untied head, through the serving cache.
    Returns ``(logits [B, vocab_size] float32 at each row's last real
    token, the cache, the counters of `COUNTERS`)``."""
    config: Qwen3NextConfig
    # the names of what `serve_apply` returns third, for the engine
    serve_counters = COUNTERS

    @nn.compact
    def __call__(self, tokens, cache, positions, page_table, slots,
                 n_valid, attn):
        cfg = self.config
        B, T = tokens.shape
        embed = self.param("embed", normal(cfg),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with jax.named_scope("ds_embed"):
            h = embed.astype(cfg.dtype)[tokens]
            mask = token_mask(n_valid, T)
        new_cache, counted = {}, []
        for i, kind in enumerate(cfg.layer_types):
            name = f"layers_{i}"
            h, new_cache[name], counters = Qwen3NextBlock(
                cfg, kind, name=name)(
                    h, cache[name], positions, page_table, slots, n_valid,
                    mask, attn)
            counted.append(counters)
        with jax.named_scope("ds_head"):
            h = ZeroCentredRMSNorm(cfg, name="final_norm")(
                last_token(h, n_valid))
            head = self.param("lm_head", normal(cfg),
                              (cfg.hidden_size, cfg.vocab_size),
                              cfg.param_dtype)
            logits = head_logits(h, head, cfg.dtype)
        with jax.named_scope("ds_sample"):
            # the state update visits the rows that hold a request and no
            # other (`ops/pallas/gated_delta.py`'s list of live rows)
            live = (n_valid > 0).sum().astype(jnp.int32)
            counters = summed_counters(
                COUNTERS, counted, gdn_rows_live=live, gdn_rows_touched=live,
                moe_experts_held=jnp.int32(
                    cfg.experts_held[1] * len(cfg.layer_types)))
        return logits, new_cache, counters


# the matrices that write to the stream (out of a mixer, the attention,
# the shared expert), and the experts' third banks
_WRITERS = {"out_proj": 0, "o_proj": 0, "shared_down": 0, "w_down": 1}


def init_qwen3_next_params(model, rng):
    """The model's weights from ``rng``, the writers centred
    (`blocks.init_served_params`)."""
    return init_served_params(model, rng, _WRITERS)

"""Ling-3.0 (``bailing_hybrid``): a decoder whose mixers are Kimi Delta
Attention layers (KDA: a delta-rule state a head that forgets **a
channel at a time**) with a gated latent attention (MLA) every
``layer_group_size``-th layer, two leading dense layers and sigmoid
routing through **groups** of experts after them; served as one chip's
share of an expert-parallel group.

What no other model here has: a delta rule whose decay is a vector
(`ops/kda.py`; Qwen3-Next's is one scalar a head), bounded below by the
configuration (``kda_safe_gate``, ``kda_lower_bound``); **a latent pool
beside per-slot recurrent leaves in one cache**; latent attention
without a query latent, under plain rotary, with a gate a head; the
group stage of ``noaux_tc`` (`moe/dropless.py:sigmoid_group_top_k`).

Layer equations (``config.json`` of ``inclusionAI/Ling-3.0-flash``,
``model_type: bailing_hybrid``; KDA: Kimi Linear, arXiv:2510.26692,
``fla/layers/kda.py``; every reading that is not a key's plain value is
listed as *assumed* in `benchmarks/suite/configs/ling-3.0-flash.json`
and in the reference's head), ``n = RMSNorm(h)`` (plain weight), no
bias anywhere, untied head. Layer ``i``: ``h = h + mixer_i(n)``; ``h =
h + ffn_i(RMSNorm(h))``; the mixer is MLA where ``(i + 1) %
layer_group_size == 0`` and KDA otherwise; ``ffn_i`` is a SwiGLU of
``intermediate_size`` for ``i < first_k_dense_replace``, the expert
layer otherwise; after the last layer ``logits = RMSNorm(h) W_head``.

- KDA (``H = num_attention_heads`` heads, keys and values ``head_dim``
  wide): ``q = n W_q``, ``k = n W_k``, ``v = n W_v``, each through its
  own causal depthwise convolution of ``short_conv_kernel_size`` taps
  without bias, then SiLU; ``q``, ``k`` L2-normalised a head (eps
  1e-6), ``q`` times ``head_dim^-0.5``; in float32, a channel, ``g =
  kda_lower_bound sigmoid(exp(A_log_h) (n W_f + dt_bias))`` (so
  ``kda_lower_bound < g < 0``) and ``beta = sigmoid(n W_beta)`` a head;
  the recurrence of `ops/kda.py`; ``y = (RMSNorm_head(o) sigmoid(n
  W_g)) W_o`` (the norm over each head's ``head_dim``, one weight shared
  by the heads; ``W_f``, ``W_g`` full rank: ``no_kda_lora``).
- MLA: ``q = n W_q`` (``qk_nope_head_dim + qk_rope_head_dim`` a head,
  no latent, no norm); ``[c_kv | k_rope] = n W_dkv``, ``c_kv <-
  RMSNorm(c_kv)``; ``[k_nope | v] = c_kv W_ukv`` a head; plain rotary
  (``rope_theta``, rotate-half) on ``q``'s rope part and on ``k_rope``;
  causal softmax at ``qk_head_dim^-0.5``; ``y = concat_h(o_h sigmoid((n
  W_a)_h)) W_o`` (``W_a`` one column a head: ``head_wise``).
- experts: `moe/dropless.py:sigmoid_group_top_k` over ``num_experts``
  in ``n_group`` groups of which ``topk_group`` are kept, weights
  renormalised times ``routed_scaling_factor``; expert ``e`` is ``W_d[e]
  (silu(W_g[e] n) * W_u[e] n)``; plus one ungated shared expert.
  ``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``: a
  non-zero entry of a layer that is built **raises** (the clamp's form
  is not in the config and is not guessed); the published lists are 0
  in layers 0-33.

**What is stored.** A KDA layer keeps, a batch row, a float32 state
``kda`` ``[rows, H, head_dim, head_dim]`` and the convolutions' window
``conv`` ``[taps - 1, rows, 3 H head_dim]`` (``q | k | v`` channels), in
the row's slot. The MLA layers keep ``[c_kv | k_rope]`` a token in a
latent pool (`inference/cache.py`); a decode step runs absorbed through
`flash_decode_paged`, a prefill chunk walks the row's prefix through
`cache.latent_prefill_attention` (`models/mla_moe.py` says why).

**The share** (as `models/qwen3_next.py`): ``experts_held = (first,
count)`` of the router's ``num_experts`` are held, routing runs over all
of them through all groups and pairs of experts held elsewhere add
nothing here; the first ``vocab_size`` rows of embedding and head are
held. Mixers, the dense layers, router and shared expert are whole.
Nothing stands in for the other chips.

Precision, part of the configuration: weights, activations, the latent
pool and the convolution window in ``dtype`` (bfloat16 as published);
products accumulate in float32; the delta rule's ``g``, ``beta``,
decays, system and state, the L2 norms, every norm's statistics, the
rotary angles, the attention's softmax, both sigmoid gates, the
router's product (at the highest precision), sigmoid, group scores,
choice and weights float32.
`benchmarks/suite/reference/ling_hybrid_ref.py` is the plain float32
statement of the same mathematics. Serving only.
"""

import collections
import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models import blocks
from deepspeed_tpu.models.blocks import (ExpertCounters, GatedMLP, RMSNorm,
                                         ServedLM, conv_init,
                                         expert_counters, head_logits,
                                         init_served_params, l2_normalised,
                                         last_token, normal, param, rotate,
                                         summed_counters, token_mask,
                                         uniform_bias_init)
from deepspeed_tpu.moe.dropless import dropless_moe, sigmoid_group_top_k
from deepspeed_tpu.ops import kda, ssm

KDA, MLA = "kda", "mla"
# what a decode step's span carries (`inference/engine.py` reads the
# names): the expert layers' five as `models/qwen3_next.py`'s, the rows
# whose delta-rule state the step moved on against those it read and
# wrote back, the sorted rows the dispatch filled, and last the real
# tokens the expert layers routed against those of them one of whose
# kept groups is held here
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_pairs_max", "moe_experts_held", "kda_rows_live",
            "kda_rows_touched", "moe_rows_visited", "moe_tokens_routed",
            "moe_tokens_held_group")


@dataclasses.dataclass(frozen=True)
class LingHybridConfig:
    """The published ``config.json`` keys under their published names,
    the share that is held, and how it is run."""
    vocab_size: int = 157184            # rows held of embedding and head
    hidden_size: int = 2560
    intermediate_size: int = 6144       # the leading dense layers' MLP
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    num_attention_heads: int = 32
    head_dim: int = 128                 # a KDA head's keys and values
    num_kv_heads_for_linear_attn: int = 0   # 0: as many as query heads
    short_conv_kernel_size: int = 4
    kda_safe_gate: bool = True
    kda_lower_bound: float = -5.0
    no_kda_lora: bool = True
    use_kda_lora: bool = False
    linear_silu: bool = True
    group_norm_size: int = 1
    use_qk_norm: bool = True
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    rope_scaling: Optional[Tuple] = None
    gated_attention_proj_granularity_type: str = "head_wise"
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_experts: int = 512
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    score_function: str = "sigmoid"
    topk_method: str = "noaux_tc"
    moe_router_enable_expert_bias: bool = True
    scale_router_input: bool = False
    expert_swiglu_limit_list: Tuple = ()
    share_expert_swiglu_limit_list: Tuple = ()
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    kda_chunk_size: int = 64            # the chunked form's; not published
    initializer_range: float = 0.02
    router_bias_range: float = 0.005    # the expert bias: +-
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
        if (self.score_function, self.topk_method) != \
                ("sigmoid", "noaux_tc") or not \
                self.moe_router_enable_expert_bias or \
                self.scale_router_input or self.num_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                "sigmoid scores with an expert bias chosen by noaux_tc "
                "through n_group groups that divide num_experts, the "
                "router's input unscaled")
        if not self.kda_safe_gate or self.use_kda_lora or \
                not self.no_kda_lora or not self.linear_silu or \
                self.num_kv_heads_for_linear_attn not in \
                (0, self.num_attention_heads) or self.group_norm_size != 1 \
                or not self.use_qk_norm:
            raise ValueError(
                "KDA as Ling-3.0 publishes it only: the bounded gate "
                "(kda_safe_gate; without it g has no lower bound and "
                "ops/kda.py's kernel may not run), full-rank gate "
                "projections, SiLU after the convolutions, as many key "
                "heads as query heads, a norm a head")
        if self.q_lora_rank is not None or self.rope_scaling is not None \
                or self.gated_attention_proj_granularity_type != "head_wise":
            raise ValueError(
                "latent attention without a query latent, under plain "
                "rotary, with a gate a head only (models/mla_moe.py has "
                "the query latent and YaRN)")
        for name in ("expert_swiglu_limit_list",
                     "share_expert_swiglu_limit_list"):
            limits = getattr(self, name)
            if any(limits[:self.num_hidden_layers]):
                raise ValueError(
                    f"{name} is non-zero in a layer that is built "
                    f"({list(limits[:self.num_hidden_layers])}): the "
                    "clamp's form is not in the config and is not guessed")

    @property
    def layer_types(self):
        return tuple(MLA if (i + 1) % self.layer_group_size == 0 else KDA
                     for i in range(self.num_hidden_layers))

    @property
    def latent_dim(self):
        """What the pool keeps a token a layer: ``[c_kv | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def key_dim(self):
        return self.num_attention_heads * self.head_dim

    def is_dense(self, i):
        return i < self.first_k_dense_replace

    def names(self, kind):
        return tuple(f"layers_{i}" for i, t in enumerate(self.layer_types)
                     if t == kind)

    def cache_spec(self, max_batch, max_seq, kv_cache_dtype=None,
                   page_size=0, n_pages=0):
        """A latent pool for the MLA layers (one head of ``kv_lora_rank
        + qk_rope_head_dim``, whose first ``kv_lora_rank`` are the
        value) beside, for every KDA layer, a float32 state ``[rows, H,
        head_dim, head_dim]`` and a convolution window ``[taps - 1,
        rows, q + k + v channels]``."""
        from deepspeed_tpu.inference.cache import page_pool_spec
        latent = self.names(MLA)
        return page_pool_spec(
            max_batch, max_seq, n_layer=len(latent), n_head=1,
            head_dim=self.latent_dim, compute_dtype=self.dtype,
            n_positions=self.max_position_embeddings,
            kv_cache_dtype=kv_cache_dtype, page_size=page_size,
            n_pages=n_pages, latent_v_dim=self.kv_lora_rank, layers=latent,
            recurrent_layers=self.names(KDA),
            recurrent_leaves=(
                ("kda", (max_batch, self.num_attention_heads, self.head_dim,
                         self.head_dim), jnp.float32),
                ("conv", (self.short_conv_kernel_size - 1, max_batch,
                          3 * self.key_dim), self.dtype)))


def ling_3_flash_share(n_layer=8, experts_held=(0, 128), vocab_size=39296,
                       **kw):
    """Ling-3.0-flash at its published widths, as one chip of 4 that
    share each layer holds it: the first ``n_layer`` layers (eight are
    the two dense layers and one whole period ``K K | K K K M K K``),
    128 of the 512 experts (groups 0 and 1 of 8), a quarter of the
    vocabulary's rows."""
    return LingHybridConfig(
        num_hidden_layers=n_layer, experts_held=tuple(experts_held),
        vocab_size=vocab_size, **kw)


def ling_hybrid_tiny(**kw):
    """Test-size model: a dense layer and one period of three (KDA, KDA,
    MLA, KDA), 4 of 16 experts held (one group of four whole), top 3
    out of 2 kept groups."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 96)
    kw.setdefault("num_hidden_layers", 4)
    kw.setdefault("first_k_dense_replace", 1)
    kw.setdefault("layer_group_size", 3)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("head_dim", 16)
    kw.setdefault("kv_lora_rank", 32)
    kw.setdefault("qk_nope_head_dim", 16)
    kw.setdefault("qk_rope_head_dim", 8)
    kw.setdefault("v_head_dim", 16)
    kw.setdefault("moe_intermediate_size", 32)
    kw.setdefault("moe_shared_expert_intermediate_size", 32)
    kw.setdefault("num_experts", 16)
    kw.setdefault("num_experts_per_tok", 3)
    kw.setdefault("n_group", 4)
    kw.setdefault("topk_group", 2)
    kw.setdefault("experts_held", (4, 4))
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("kda_chunk_size", 8)
    kw.setdefault("initializer_range", 0.1)
    kw.setdefault("router_bias_range", 0.05)
    return LingHybridConfig(**kw)


# --- the KDA mixer ------------------------------------------------------------

def _gate_bias_init(key, shape, dtype):
    """``dt_bias`` uniform over [-6, -1]: with ``A`` in [1, 2] a
    channel's resting decay ``e^g`` = ``exp(-5 sigmoid(A dt_bias))``
    runs from ~0.99997 to ~0.55: some channels keep thousands of
    tokens, some a few."""
    return jax.random.uniform(key, shape, jnp.float32, -6.0,
                              -1.0).astype(dtype)


def _gate_a_log_init(key, shape, dtype):
    """``A = exp(A_log)`` uniform over [1, 2] a head."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      2.0)).astype(dtype)


def _gate_proj_init(cfg):
    """``W_f`` at ``1 / sqrt(hidden_size)``: a unit-RMS input moves ``a``
    by about +-1 round ``dt_bias``, so ``g`` covers its range (a channel
    at ``dt_bias`` -1 under a token at +2 reads ``g`` about -3.7) and
    most tokens leave most channels nearly whole; drawn at
    ``initializer_range`` x 5 and more, most tokens wipe the state and
    the state checks nothing (`models/qwen3_next.py:_in_proj_ba_init`)."""
    std = 1.0 / math.sqrt(cfg.hidden_size)

    def init(key, shape, dtype):
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    return init


class KimiDeltaAttention(nn.Module):
    """The KDA mixer through its slot's recurrent leaves (``kda`` ``[rows,
    H, K, V]`` float32, ``conv`` ``[taps - 1, rows, channels]``). Two
    shapes, as `models/qwen3_next.py:GatedDeltaNet`'s: a prefill chunk
    (one row, ``n_valid`` of ``T`` tokens real: the slot's leaves are
    read, zeros where the chunk starts the prompt; the padded tail's
    ``g`` and ``beta`` are zeroed) and a decode step (``T == 1``, row
    ``i`` in slot ``i``; a row with ``n_valid`` 0 keeps its leaves)."""
    config: LingHybridConfig

    @nn.compact
    def __call__(self, x, leaves, positions, slots, n_valid):
        cfg = self.config
        B, T, C = x.shape
        H, K, taps = cfg.num_attention_heads, cfg.head_dim, \
            cfg.short_conv_kernel_size
        d, pd = cfg.key_dim, cfg.param_dtype
        qkv = jnp.concatenate(
            [jnp.dot(x, param(self, name, cfg, (C, d)))
             for name in ("q_proj", "k_proj", "v_proj")], axis=-1)
        conv_w = jnp.concatenate(
            [self.param(name, conv_init(taps), (taps, d), pd)
             for name in ("q_conv", "k_conv", "v_conv")], axis=-1)
        no_bias = jnp.zeros((3 * d,), jnp.float32)
        with jax.named_scope("ds_kda_gate"):
            a = jnp.dot(x, self.param("f_proj", _gate_proj_init(cfg),
                                      (C, d), pd).astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
            dt_bias = self.param("dt_bias", _gate_bias_init, (d,), pd)
            A = jnp.exp(self.param("A_log", _gate_a_log_init, (H,),
                                   pd).astype(jnp.float32))
            g = cfg.kda_lower_bound * jax.nn.sigmoid(
                A[:, None] * (a + dt_bias.astype(jnp.float32)
                              ).reshape(B, T, H, K))
            beta = jax.nn.sigmoid(jnp.dot(
                x, param(self, "b_proj", cfg, (C, H)),
                preferred_element_type=jnp.float32))
            gate = jax.nn.sigmoid(jnp.dot(
                x, param(self, "g_proj", cfg, (C, d)),
                preferred_element_type=jnp.float32))
        state, window = leaves["kda"], leaves["conv"]

        def heads(u):
            """The convolved channels ``u`` ``[rows, 3 H K]`` as the
            recurrence takes them: ``q``, ``k`` (unit length, ``q``
            scaled) and ``v``, each ``[rows, H, K]``."""
            q = l2_normalised(u[:, :d].reshape(-1, H, K)) * K ** -0.5
            k = l2_normalised(u[:, d:2 * d].reshape(-1, H, K))
            return q.astype(cfg.dtype), k.astype(cfg.dtype), \
                u[:, 2 * d:].reshape(-1, H, K)

        if T == 1:
            live = n_valid > 0
            u, window = ssm.causal_conv_step(qkv[:, 0], window, conv_w,
                                             no_bias, live)
            u = jax.nn.silu(u).astype(cfg.dtype)
            with jax.named_scope("ds_kda_step"):
                o, state = kda.kda_step(*heads(u), g[:, 0], beta[:, 0],
                                        state, live)
            o = o[:, None]                              # [B, 1, H, K]
        elif B == 1:
            slot, n = slots[0], n_valid[0]
            fresh = positions[0, 0] == 0
            win = jax.lax.dynamic_slice_in_dim(window, slot, 1, 1)[:, 0]
            win = jnp.where(fresh, jnp.zeros_like(win), win)
            u, win = ssm.causal_conv_prefill(qkv[0], win, conv_w, no_bias,
                                             n)
            u = jax.nn.silu(u).astype(cfg.dtype)
            window = jax.lax.dynamic_update_slice_in_dim(
                window, win[:, None], slot, 1)
            with jax.named_scope("ds_kda_scan"):
                s0 = jax.lax.dynamic_index_in_dim(state, slot, 0, False)
                s0 = jnp.where(fresh, jnp.zeros_like(s0), s0)
                # the ragged tail: g = 0 decays nothing, beta = 0 writes
                # nothing
                real = jnp.arange(T) < n
                o, s1 = kda.kda_chunked(
                    *heads(u), jnp.where(real[:, None, None], g[0], 0.0),
                    jnp.where(real[:, None], beta[0], 0.0), s0,
                    cfg.kda_chunk_size)
                state = jax.lax.dynamic_update_index_in_dim(
                    state, s1, slot, 0)
            o = o[None]                                 # [1, T, H, K]
        else:
            raise ValueError(
                f"a mixer serves one prompt's chunk or one token of "
                f"every row; got {B} rows of {T} tokens")

        w = self.param("norm_weight", nn.initializers.ones, (K,), pd)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.rms_norm_eps)
        y = o * w.astype(jnp.float32) * gate.reshape(B, T, H, K)
        y = jnp.dot(y.reshape(B, T, d).astype(cfg.dtype),
                    param(self, "o_proj", cfg, (d, C)))
        return y, {"kda": state, "conv": window}


# --- latent attention ---------------------------------------------------------

def rope_cos_sin(cfg, positions):
    """``cos`` and ``sin`` ``[B, T, rope / 2]`` float32 of the plain
    rotary angles at ``positions``."""
    return blocks.rope_cos_sin(positions, cfg.qk_rope_head_dim,
                               cfg.rope_theta)


class GatedLatentAttention(nn.Module):
    """`models/mla_moe.py:LatentAttention` without the query latent,
    under plain rotary, its heads' outputs gated a head: a decode step
    absorbed through the flash kernel or the dense oracle, one prompt's
    chunk over the row's live prefix."""
    config: LingHybridConfig

    @nn.compact
    def __call__(self, x, layer_cache, positions, page_table, rope, attn):
        from deepspeed_tpu.inference.cache import cached_attention
        cfg = self.config
        B, T, C = x.shape
        H, rkv = cfg.num_attention_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        cos, sin = rope
        absorbed = T == 1       # a decode step; a chunk expands its blocks
        with jax.named_scope("ds_mla_project"):
            q = jnp.dot(x, param(self, "q_proj", cfg, (C, H * (dn + dr))))
            q = q.reshape(B, T, H, dn + dr)
            ckv = jnp.dot(x, param(self, "kv_a_proj", cfg, (C, rkv + dr)))
            c_kv = RMSNorm(cfg, name="kv_a_norm")(ckv[..., :rkv])
            q_rope = rotate(q[..., dn:], cos[:, :, None], sin[:, :, None])
            k_rope = rotate(ckv[..., rkv:], cos, sin)
            latent = jnp.concatenate([c_kv, k_rope], -1)[:, :, None]
            w_ukv = param(self, "kv_b_proj", cfg,
                           (rkv, H * (dn + dv))).reshape(rkv, H, dn + dv)
            expand = None
            if absorbed:
                q_abs = jnp.einsum("bthn,chn->bthc", q[..., :dn],
                                   w_ukv[..., :dn])
                q_in = jnp.concatenate([q_abs, q_rope], -1)
            else:
                q_in = jnp.concatenate([q[..., :dn], q_rope], -1)

                def expand(lat):
                    c = lat[:, :rkv]
                    return (jnp.einsum("sc,chn->shn", c, w_ukv[..., :dn]),
                            lat[:, rkv:],
                            jnp.einsum("chv,sc->hvs", w_ukv[..., dn:], c))
        with jax.named_scope("ds_mla_prefill_attn" if T > 1
                             else "ds_mla_decode_attn"):
            y, layer_cache = cached_attention(
                q_in, latent, None, layer_cache, positions, cfg.dtype,
                page_table, scale=cfg.qk_head_dim ** -0.5, v_dim=rkv,
                expand=expand, **attn)
        with jax.named_scope("ds_mla_project"):
            if absorbed:
                y = jnp.einsum("bthc,chv->bthv", y, w_ukv[..., dn:])
        with jax.named_scope("ds_attn_gate"):
            opened = jax.nn.sigmoid(jnp.dot(
                x, param(self, "gate_proj", cfg, (C, H)),
                preferred_element_type=jnp.float32))
            y = (y.astype(jnp.float32) * opened[..., None]).astype(cfg.dtype)
        with jax.named_scope("ds_mla_project"):
            y = jnp.dot(y.reshape(B, T, H * dv),
                        param(self, "o_proj", cfg, (H * dv, C)))
        return y, layer_cache


# --- experts ------------------------------------------------------------------

# a layer's `blocks.ExpertCounters` and, behind them, the real tokens
# the layer routed and those of them one of whose kept groups is held
# here
GroupedExpertCounters = collections.namedtuple(
    "GroupedExpertCounters",
    ExpertCounters._fields + ("tokens_routed", "tokens_held_group"))


# jitted, so that the expert layers share one trace of the routing and of
# the three grouped matmuls (as `models/qwen3_next.py`'s)
@functools.partial(jax.jit, static_argnames=(
    "top_k", "scaling", "n_group", "topk_group", "renormalise",
    "first_expert"))
def _held_experts(x, mask, router, bias, w_gate, w_up, w_down, *, top_k,
                  scaling, n_group, topk_group, renormalise, first_expert):
    y, stats = dropless_moe(
        x, router, w_gate, w_up, w_down, top_k,
        route=sigmoid_group_top_k(bias, scaling, n_group, topk_group,
                                  renormalise),
        first_expert=first_expert, token_mask=mask)
    with jax.named_scope("ds_moe_route"):
        # the groups that hold a held expert, and the real tokens one of
        # whose kept groups is such a group
        per = router.shape[1] // n_group
        groups = jnp.arange(n_group)
        held = (groups >= first_expert // per) & \
            (groups <= (first_expert + w_up.shape[0] - 1) // per)
        here = mask & held[stats["kept_groups"]].any(-1)
    return y, GroupedExpertCounters(
        *expert_counters(mask, top_k, stats),
        tokens_routed=mask.sum().astype(jnp.int32),
        tokens_held_group=here.sum().astype(jnp.int32))


class GroupedExperts(nn.Module):
    """The routed experts this chip holds and the shared expert.
    Returns ``(y, the layer's `GroupedExpertCounters`)``; ``mask`` ``[B,
    T]`` says which tokens are real."""
    config: LingHybridConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.config
        B, T, C = x.shape
        E, I = cfg.num_experts, cfg.moe_intermediate_size
        first, held = cfg.experts_held
        init, pd = normal(cfg), cfg.param_dtype
        router = self.param("router", init, (C, E), pd)
        bias = self.param("expert_bias", uniform_bias_init(cfg), (E,), jnp.float32)
        w_gate = self.param("w_gate", init, (held, C, I), pd)
        w_up = self.param("w_up", init, (held, C, I), pd)
        w_down = self.param("w_down", init, (held, I, C), pd)
        y, counters = _held_experts(
            x.reshape(B * T, C), mask.reshape(B * T), router, bias, w_gate,
            w_up, w_down, top_k=cfg.num_experts_per_tok,
            scaling=cfg.routed_scaling_factor, n_group=cfg.n_group,
            topk_group=cfg.topk_group, renormalise=cfg.norm_topk_prob,
            first_expert=first)
        with jax.named_scope("ds_moe_shared"):
            shared = GatedMLP(
                cfg, cfg.moe_shared_expert_intermediate_size *
                cfg.num_shared_experts, name="shared")(x)
        return y.reshape(B, T, C) + shared, counters


class LingHybridLayer(nn.Module):
    """``h + mixer(norm(h))`` then ``h + ffn(norm(h))``. Returns ``(h,
    the mixer's cache, the feed-forward's counters)``."""
    config: LingHybridConfig
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, h, layer_cache, positions, page_table, slots,
                 n_valid, rope, mask, attn):
        cfg = self.config
        if self.kind == MLA:
            # the norm under the scope of the projections it feeds, the
            # residual add under that of the one it follows
            with jax.named_scope("ds_attn_qkv"):
                n = RMSNorm(cfg, name="input_norm")(h)
            y, layer_cache = GatedLatentAttention(cfg, name="attn")(
                n, layer_cache, positions, page_table, rope, attn)
            with jax.named_scope("ds_attn_out"):
                h = h + y
        else:   # the mixer whole, round its inner scopes
            with jax.named_scope("ds_kda_mixer"):
                y, layer_cache = KimiDeltaAttention(cfg, name="mixer")(
                    RMSNorm(cfg, name="input_norm")(h), layer_cache,
                    positions, slots, n_valid)
                h = h + y
        with jax.named_scope("ds_mlp" if self.dense else "ds_experts"):
            n = RMSNorm(cfg, name="post_norm")(h)
            if self.dense:
                y = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(n)
                counters = None
            else:
                y, counters = GroupedExperts(cfg, name="experts")(n, mask)
            return h + y, layer_cache, counters


class LingHybridLM(ServedLM, nn.Module):
    """The decoder with its untied head, through the serving cache.
    Returns ``(logits [B, vocab_size] float32 at each row's last real
    token, the cache, the counters of `COUNTERS`)``."""
    config: LingHybridConfig
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
            rope = rope_cos_sin(cfg, positions)
            mask = token_mask(n_valid, T)
        new_cache, counted = {}, []
        for i, kind in enumerate(cfg.layer_types):
            name = f"layers_{i}"
            h, new_cache[name], counters = LingHybridLayer(
                cfg, kind, bool(cfg.is_dense(i)), name=name)(
                    h, cache[name], positions, page_table, slots, n_valid,
                    rope, mask, attn)
            if counters is not None:
                counted.append(counters)
        with jax.named_scope("ds_head"):
            h = RMSNorm(cfg, name="final_norm")(last_token(h, n_valid))
            head = self.param("lm_head", normal(cfg),
                              (cfg.hidden_size, cfg.vocab_size),
                              cfg.param_dtype)
            logits = head_logits(h, head, cfg.dtype)
        with jax.named_scope("ds_sample"):
            # the state update visits the rows that hold a request and no
            # other (`ops/pallas/kda.py`'s list of live rows)
            live = (n_valid > 0).sum().astype(jnp.int32)
            counters = summed_counters(
                COUNTERS, counted, kda_rows_live=live, kda_rows_touched=live,
                moe_experts_held=jnp.int32(
                    cfg.experts_held[1] * len(counted)))
        return logits, new_cache, counters


# the matrices that write to the stream (out of a mixer, the attention,
# a dense MLP, the shared expert), and the experts' third banks
_WRITERS = {"o_proj": 0, "w_out": 0, "w_down": 1}


def init_ling_hybrid_params(model, rng):
    """The model's weights from ``rng``, the writers centred
    (`blocks.init_served_params`)."""
    return init_served_params(model, rng, _WRITERS)

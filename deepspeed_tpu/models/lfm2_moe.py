"""LFM2 with experts (``lfm2_moe``): a decoder most of whose mixers are
**short convolutions** (a depthwise causal convolution of
``conv_L_cache`` taps between two multiplicative gates, and nothing
else: no recurrence, no activation, no bias), with grouped-query
attention in the layers ``layer_types`` names, leading dense layers and
sigmoid routing with a choice bias after them; served as one chip's
share of an expert-parallel host.

What no other model here has: a mixer whose whole state is the
convolution's window (every other recurrent leaf is a float32 state
beside a window: `models/granite_hybrid.py`, `models/qwen3_next.py`,
`models/ling_hybrid.py`), written **after** the input gate (the window
holds ``b * x``); grouped-query attention whose heads are normed and
then rotated over their whole width; layer kinds read off a published
list whose last period is not the others'; a head tied to the embedding
beside held experts.

Layer equations (``config.json`` of ``LiquidAI/LFM2-8B-A1B``,
``model_type: lfm2_moe``; every reading that is not a key's plain value
is listed as *assumed* in `benchmarks/suite/configs/lfm2-8b-a1b.json`
and in the reference's head), ``n = RMSNorm(h)`` (plain weight, eps
``norm_eps``), no bias anywhere. Layer ``i``: ``h = h + mixer_i(n)``;
``h = h + ffn_i(RMSNorm(h))``; the mixer is the attention where
``layer_types[i] == "full_attention"`` and the short convolution where
it is ``"conv"``; ``ffn_i`` is a SwiGLU of ``intermediate_size`` for ``i
< num_dense_layers``, the expert layer otherwise; after the last layer
``logits = RMSNorm(h) E^T`` with ``E`` the embedding (tied).

- short convolution (``L = conv_L_cache``): ``[b | c | x] = n W_in``
  (``hidden_size`` each, in that order); ``u_t = b_t * x_t``; ``z_t =
  sum_k w_k * u_(t - (L - 1) + k)`` (``w`` ``[L, hidden_size]``,
  depthwise; ``w_(L-1)`` multiplies the current token, as `ops/ssm.py`
  orders its taps; ``u`` before the prompt's first token is 0); ``y_t =
  (c_t * z_t) W_out``.
- attention (``Hq = num_attention_heads`` query heads over ``Hkv =
  num_key_value_heads`` key heads of ``head_dim = hidden_size / Hq``):
  ``q = n W_q``, ``k = n W_k``, ``v = n W_v``; ``q`` and ``k`` through an
  RMS norm over each head's ``head_dim`` (a weight ``[head_dim]`` each:
  ``q_layernorm``, ``k_layernorm``); rotary at ``rope_theta`` over the
  whole head (rotate-half), no scaling; causal softmax at
  ``head_dim^-0.5``; ``y = concat_h(o_h) W_o``.
- experts: `moe/dropless.py:sigmoid_top_k` over ``num_experts`` (``s =
  sigmoid(n W_r)``, the ``num_experts_per_tok`` largest of ``s + bias``
  chosen, weights ``s_e / (sum of the chosen s + 1e-6)`` times
  ``routed_scaling_factor``); expert ``e`` is ``W_2[e] (silu(W_1[e] n) *
  W_3[e] n)``; no shared expert.

**What is stored.** A convolution layer keeps, a batch row, the window
``conv`` ``[L - 1, rows, hidden_size]`` of ``u = b * x`` in ``dtype``,
in the row's slot, and nothing else (the published cache keeps ``L``
columns, of which the oldest is never read again). The attention layers
keep rotated, normed keys and values in the per-head page pool
(`inference/cache.py`): a decode step runs through `flash_decode_paged`,
a prefill chunk reads the row's bucket.

**The share** (as `models/qwen3_next.py`): ``experts_held = (first,
count)`` of the router's ``num_experts`` are held, routing runs over all
of them and pairs of experts held elsewhere add nothing here. Mixers,
the dense layers, the router, the embedding and the head are whole.
Nothing stands in for the other chips.

Precision, part of the configuration: weights, activations, the page
pool and the window in ``dtype`` (bfloat16 as published); products
accumulate in float32; both gates' products and the taps' sum, every
norm's statistics (the heads' too), the rotary angles, the attention's
softmax, the router's product (at the highest precision), sigmoid,
choice and weights float32.
`benchmarks/suite/reference/lfm2_moe_ref.py` is the plain float32
statement of the same mathematics. Serving only.
"""

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models import blocks
from deepspeed_tpu.models.blocks import (GatedMLP, RMSNorm, ServedLM,
                                         conv_init, expert_counters,
                                         head_logits, init_served_params,
                                         last_token, normal,
                                         normal_bias_init, param, rotate,
                                         summed_counters, token_mask)
from deepspeed_tpu.moe.dropless import dropless_moe, sigmoid_top_k
from deepspeed_tpu.ops import ssm

CONV, ATTENTION = "conv", "full_attention"
# the published list of LFM2-8B-A1B: a period of four, ``c c A c``, five
# times, then ``c A c c``
LFM2_8B_A1B_LAYERS = tuple(
    ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))
# the renormaliser of the chosen experts' weights (``sum + 1e-6``)
ROUTE_EPS = 1e-6
# what a decode step's span carries (`inference/engine.py` reads the
# names): the expert layers' five and the dispatch's sorted rows as
# `models/qwen3_next.py`'s, and the rows that hold a request against the
# rows whose window the step read and wrote back (every row: the step is
# one select over the leaf)
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_pairs_max", "moe_experts_held", "sconv_rows_live",
            "sconv_rows_touched", "moe_rows_visited")


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published ``config.json`` keys under their published names,
    the share that is held, and how it is run."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168       # the leading dense layers' MLP
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = LFM2_8B_A1B_LAYERS
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    moe_intermediate_size: int = 1792
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    norm_weight_range: float = 0.1      # the heads' norms: 1 +- this
    router_bias_range: float = 0.1      # the choice bias: normal, sigma
    experts_held: Tuple[int, int] = (0, 32)     # (first, count)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and
                first + count <= self.num_experts):
            raise ValueError(
                f"experts_held {self.experts_held} must lie within the "
                f"{self.num_experts} routed experts")
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {CONV, ATTENTION}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each '{CONV}' or '{ATTENTION}'; got {self.layer_types}")
        if self.conv_bias or not self.use_expert_bias or \
                self.conv_L_cache < 2:
            raise ValueError(
                "the short convolution as LFM2 publishes it only: no "
                "bias, at least two taps; the router with its choice "
                "bias")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.hidden_size % self.num_attention_heads:
            raise ValueError(
                "num_key_value_heads must divide num_attention_heads, "
                "which must divide hidden_size")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def rms_norm_eps(self):     # `models/blocks.py:RMSNorm` reads this name
        return self.norm_eps

    def is_dense(self, i):
        return i < self.num_dense_layers

    def names(self, kind):
        return tuple(f"layers_{i}" for i, t in enumerate(self.layer_types)
                     if t == kind)

    def cache_spec(self, max_batch, max_seq, kv_cache_dtype=None,
                   page_size=0, n_pages=0):
        """Page pools for the attention layers (the key heads) and, for
        every convolution layer, its window ``[conv_L_cache - 1, rows,
        hidden_size]`` in the activations' type: the one recurrent leaf."""
        from deepspeed_tpu.inference.cache import page_pool_spec
        att = self.names(ATTENTION)
        return page_pool_spec(
            max_batch, max_seq, n_layer=len(att),
            n_head=self.num_key_value_heads, head_dim=self.head_dim,
            compute_dtype=self.dtype,
            n_positions=self.max_position_embeddings,
            kv_cache_dtype=kv_cache_dtype, page_size=page_size,
            n_pages=n_pages, layers=att,
            recurrent_layers=self.names(CONV),
            recurrent_leaves=(
                ("conv", (self.conv_L_cache - 1, max_batch,
                          self.hidden_size), self.dtype),))


def lfm2_8b_a1b_share(experts_held=(0, 8), **kw):
    """LFM2-8B-A1B at its published widths and its whole depth, as one
    chip of the 4 that share each layer's experts holds it: all 24
    layers, 8 of the 32 experts, the whole vocabulary."""
    return Lfm2MoeConfig(experts_held=tuple(experts_held), **kw)


def lfm2_moe_tiny(**kw):
    """Test-size model: one dense layer, then an irregular list (``c A c
    c A c``: the two attention layers three and not four apart from the
    list's ends), 4 of 8 experts held, top 2."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 96)
    kw.setdefault("num_hidden_layers", 6)
    kw.setdefault("layer_types", (CONV, ATTENTION, CONV, CONV, ATTENTION,
                                  CONV))
    kw.setdefault("num_dense_layers", 1)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("moe_intermediate_size", 32)
    kw.setdefault("num_experts", 8)
    kw.setdefault("num_experts_per_tok", 2)
    kw.setdefault("experts_held", (2, 4))
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("initializer_range", 0.1)
    return Lfm2MoeConfig(**kw)


# --- the short convolution ----------------------------------------------------

class ShortConv(nn.Module):
    """The short-convolution mixer through its slot's one recurrent leaf
    (``conv`` ``[L - 1, rows, hidden_size]``: the last ``L - 1`` gated
    inputs ``u = b * x`` of the row). Two shapes, as the other mixers
    here have: a prefill chunk (one row, ``n_valid`` of ``T`` tokens
    real: the slot's window is read, zeros where the chunk starts the
    prompt, and the window after the last real token goes back: the
    padded tail never enters it) and a decode step (``T == 1``, row
    ``i`` in slot ``i``; a row with ``n_valid`` 0 holds no request and
    keeps its window to the bit)."""
    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, leaves, positions, slots, n_valid):
        cfg = self.config
        B, T, C = x.shape
        L = cfg.conv_L_cache
        with jax.named_scope("ds_sconv_in_proj"):
            bcx = jnp.dot(x, param(self, "in_proj", cfg, (C, 3 * C)))
        taps = self.param("conv_weight", conv_init(L), (L, C),
                          cfg.param_dtype)
        window = leaves["conv"]
        with jax.named_scope("ds_sconv_taps"):
            b, c, xs = (bcx[..., i * C:(i + 1) * C].astype(jnp.float32)
                        for i in range(3))
            u = (b * xs).astype(cfg.dtype)      # what the window holds
            if T == 1:
                z, window = ssm.causal_conv_step(u[:, 0], window, taps,
                                                 None, n_valid > 0)
                z = z[:, None]
            elif B == 1:
                slot = slots[0]
                win = jax.lax.dynamic_slice_in_dim(window, slot, 1, 1)[:, 0]
                win = jnp.where(positions[0, 0] == 0, jnp.zeros_like(win),
                                win)
                z, win = ssm.causal_conv_prefill(u[0], win, taps, None,
                                                 n_valid[0])
                window = jax.lax.dynamic_update_slice_in_dim(
                    window, win[:, None], slot, 1)
                z = z[None]
            else:
                raise ValueError(
                    f"a mixer serves one prompt's chunk or one token of "
                    f"every row; got {B} rows of {T} tokens")
            y = (c * z).astype(cfg.dtype)
        with jax.named_scope("ds_sconv_out_proj"):
            y = jnp.dot(y, param(self, "out_proj", cfg, (C, C)))
        return y, {"conv": window}


# --- attention ------------------------------------------------------------------

def _head_norm_weight(mod, name, cfg):
    """A head norm's plain weight, drawn round 1 so that it differs from
    1 and the queries' from the keys'."""
    def init(key, shape, dtype):
        return (1.0 + cfg.norm_weight_range * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)
    return mod.param(name, init, (cfg.head_dim,), cfg.param_dtype)


def head_norm(x, w, eps):
    """``x rsqrt(mean(x^2) + eps) w`` over the last axis (a head), in
    float32; returns float32."""
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return x32 * w.astype(jnp.float32)


def rope_cos_sin(cfg, positions):
    """``cos`` and ``sin`` ``[B, T, 1, head_dim / 2]`` float32 of the
    plain rotary angles at ``positions``, over the whole head."""
    return blocks.rope_cos_sin(positions[..., None], cfg.head_dim,
                               cfg.rope_theta)


class NormedGroupedQueryAttention(nn.Module):
    """Causal grouped-query attention through the page pool with queries
    and keys normed a head and then rotated over the whole head."""
    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, layer_cache, positions, page_table, rope, attn):
        from deepspeed_tpu.inference.cache import cached_attention
        cfg = self.config
        B, T, C = x.shape
        Hq, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        with jax.named_scope("ds_attn_qkv"):
            q = jnp.dot(x, param(self, "q_proj", cfg, (C, Hq * D)))
            k = jnp.dot(x, param(self, "k_proj", cfg, (C, Hkv * D)))
            v = jnp.dot(x, param(self, "v_proj", cfg, (C, Hkv * D)))
            with jax.named_scope("ds_attn_qk_norm"):
                q = head_norm(q.reshape(B, T, Hq, D),
                              _head_norm_weight(self, "q_layernorm", cfg),
                              cfg.norm_eps)
                k = head_norm(k.reshape(B, T, Hkv, D),
                              _head_norm_weight(self, "k_layernorm", cfg),
                              cfg.norm_eps)
            q = rotate(q, *rope).astype(cfg.dtype)
            k = rotate(k, *rope).astype(cfg.dtype)
        y, layer_cache = cached_attention(
            q, k, v.reshape(B, T, Hkv, D), layer_cache, positions,
            cfg.dtype, page_table, scale=D ** -0.5, **attn)
        with jax.named_scope("ds_attn_out"):
            y = jnp.dot(y.reshape(B, T, Hq * D),
                        param(self, "o_proj", cfg, (Hq * D, C)))
        return y, layer_cache


# --- experts --------------------------------------------------------------------

# jitted, so that the expert layers share one trace of the routing and of
# the three grouped matmuls (as `models/qwen3_next.py`'s)
@functools.partial(jax.jit, static_argnames=(
    "top_k", "scaling", "renormalise", "first_expert"))
def _held_experts(x, mask, router, bias, w_gate, w_up, w_down, *, top_k,
                  scaling, renormalise, first_expert):
    y, stats = dropless_moe(
        x, router, w_gate, w_up, w_down, top_k,
        route=sigmoid_top_k(bias, scaling, renormalise, eps=ROUTE_EPS),
        first_expert=first_expert, token_mask=mask)
    return y, expert_counters(mask, top_k, stats)


class HeldExperts(nn.Module):
    """The routed experts this chip holds; no shared expert. Returns
    ``(y, the layer's `blocks.ExpertCounters`)``; ``mask`` ``[B, T]``
    says which tokens are real."""
    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.config
        B, T, C = x.shape
        E, I = cfg.num_experts, cfg.moe_intermediate_size
        first, held = cfg.experts_held
        init, pd = normal(cfg), cfg.param_dtype
        router = self.param("router", init, (C, E), pd)
        bias = self.param("expert_bias", normal_bias_init(cfg), (E,),
                          jnp.float32)
        w_gate = self.param("w_gate", init, (held, C, I), pd)
        w_up = self.param("w_up", init, (held, C, I), pd)
        w_down = self.param("w_down", init, (held, I, C), pd)
        y, counters = _held_experts(
            x.reshape(B * T, C), mask.reshape(B * T), router, bias, w_gate,
            w_up, w_down, top_k=cfg.num_experts_per_tok,
            scaling=cfg.routed_scaling_factor,
            renormalise=cfg.norm_topk_prob, first_expert=first)
        return y.reshape(B, T, C), counters


class Lfm2MoeLayer(nn.Module):
    """``h + mixer(norm(h))`` then ``h + ffn(norm(h))``. Returns ``(h,
    the mixer's cache, the feed-forward's counters)``."""
    config: Lfm2MoeConfig
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, h, layer_cache, positions, page_table, slots,
                 n_valid, rope, mask, attn):
        cfg = self.config
        if self.kind == ATTENTION:
            # the norm under the scope of the projections it feeds, the
            # residual add under that of the one it follows
            with jax.named_scope("ds_attn_qkv"):
                n = RMSNorm(cfg, name="operator_norm")(h)
            y, layer_cache = NormedGroupedQueryAttention(cfg, name="attn")(
                n, layer_cache, positions, page_table, rope, attn)
            with jax.named_scope("ds_attn_out"):
                h = h + y
        else:   # the mixer whole, round its inner scopes
            with jax.named_scope("ds_sconv_mixer"):
                y, layer_cache = ShortConv(cfg, name="mixer")(
                    RMSNorm(cfg, name="operator_norm")(h), layer_cache,
                    positions, slots, n_valid)
                h = h + y
        with jax.named_scope("ds_mlp" if self.dense else "ds_experts"):
            n = RMSNorm(cfg, name="ffn_norm")(h)
            if self.dense:
                y = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(n)
                counters = None
            else:
                y, counters = HeldExperts(cfg, name="experts")(n, mask)
            return h + y, layer_cache, counters


class Lfm2MoeLM(ServedLM, nn.Module):
    """The decoder with its tied head, through the serving cache.
    Returns ``(logits [B, vocab_size] float32 at each row's last real
    token, the cache, the counters of `COUNTERS`)``."""
    config: Lfm2MoeConfig
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
            h, new_cache[name], counters = Lfm2MoeLayer(
                cfg, kind, bool(cfg.is_dense(i)), name=name)(
                    h, cache[name], positions, page_table, slots, n_valid,
                    rope, mask, attn)
            if counters is not None:
                counted.append(counters)
        with jax.named_scope("ds_head"):
            h = RMSNorm(cfg, name="embedding_norm")(last_token(h, n_valid))
            logits = head_logits(h, embed.T, cfg.dtype)
        with jax.named_scope("ds_sample"):
            counters = summed_counters(
                COUNTERS, counted,
                sconv_rows_live=(n_valid > 0).sum().astype(jnp.int32),
                sconv_rows_touched=jnp.int32(B),
                moe_experts_held=jnp.int32(
                    cfg.experts_held[1] * len(counted)))
        return logits, new_cache, counters


# the matrices that write to the stream (out of a mixer, the attention,
# a dense MLP), and the experts' third banks
_WRITERS = {"out_proj": 0, "o_proj": 0, "w_out": 0, "w_down": 1}


def init_lfm2_moe_params(model, rng):
    """The model's weights from ``rng``, the writers centred
    (`blocks.init_served_params`)."""
    return init_served_params(model, rng, _WRITERS)

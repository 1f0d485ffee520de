"""MiMo-V2: a decoder of window-attention layers with a learned sink
beside full-attention layers (five to one), keys wider than values,
each kind with key heads of its own, the first layer's feed-forward
dense and every later one a layer of sigmoid-routed experts; served as
one chip's share of an expert-parallel group.

What no other model here has: **two groups of page layers**
(`inference/cache.py:PageGroup`): a full layer keeps every page of a
row, a window layer a ring of ``sliding_window // page_size + 1`` pages
whatever the row's length; the groups differ in key heads (4 and 8);
**keys of 192 over values of 128**; **a learned sink**, one logit a
query head of a window layer, which takes weight in the softmax and
gives no value; **two rotary bases** (1e7 full, 1e4 window) on the
first ``int(partial_rotary_factor x head_dim)`` entries of a head.

Layer equations as published (``config.json`` of ``XiaomiMiMo/MiMo-V2.5``,
``model_type: mimo_v2``; the language model alone: the vision and audio
encoders and the multi-token-prediction layers are not built), ``norm(x)
= x rsqrt(mean(x^2) + layernorm_epsilon) w``, no bias in any product, no
QK-norm, untied head. Layer ``i``: ``h = h + attn_i(norm(h))``; ``h = h
+ ffn_i(norm(h))``.

- ``hybrid_layer_pattern[i]`` 0, full attention: ``num_attention_heads``
  queries over ``num_key_value_heads`` keys at ``head_dim`` and values at
  ``v_head_dim``, causal, scores times ``head_dim^-0.5``, rotary
  (rotate-half) at ``rope_theta``.
- ``hybrid_layer_pattern[i]`` 1, window attention: ``swa_*`` heads and
  widths, rotary at ``swa_rope_theta``; position ``t`` sees ``j`` iff ``0
  <= t - j < sliding_window``; with ``add_swa_attention_sink_bias`` a
  parameter ``b`` ``[heads]`` float32: ``p_j = exp(s_j - m) / (exp(b_h -
  m) + sum_j exp(s_j - m))``.
- both: values times ``attention_value_scale`` (on the values as they
  are projected, so the pool keeps them scaled); ``o_proj`` from ``heads
  x v_head_dim``.
- ``moe_layer_freq[i]`` 0: SwiGLU at ``intermediate_size``; 1: ``scores =
  sigmoid(x W_r)`` float32, the ``num_experts_per_tok`` largest of
  ``scores + e_score_correction_bias`` (``noaux_tc``, one group), weights
  ``scores`` over their sum, times ``routed_scaling_factor`` (null: 1);
  expert ``e`` is ``W_d[e] (silu(W_g[e] x) * W_u[e] x)``; no shared
  expert (`moe/dropless.py:sigmoid_top_k`, Kimi-K2's routing).
- ``attention_chunk_size`` and ``attention_projection_layout`` are read
  by nothing here: the mask is the window's, and the layout is a
  relabelling of columns under seeded weights.

**The share** (as `models/mla_moe.py`): ``experts_held = (first,
count)`` of the router's ``n_routed_experts`` are held, routing runs over
all of them and pairs of experts held elsewhere add nothing here; the
first ``vocab_size`` rows of embedding and head are held. Attention,
router and norms are whole. Nothing stands in for the other chips.

Precision, part of the configuration: weights, activations and both
pools in ``dtype`` (bfloat16 as published); products accumulate in
float32; norm statistics, rotary angles, scores, softmax, its sums and
the sink float32; the router's product (at the highest precision),
sigmoid, choice and weights float32.
`benchmarks/suite/reference/mimo_v2_ref.py` is the plain float32
statement of the same mathematics. Serving only.
"""

import collections
import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.blocks import (GatedMLP, RMSNorm, ServedLM,
                                         head_logits, init_served_params,
                                         last_token, normal, param,
                                         partial_rotary,
                                         sigmoid_held_experts,
                                         summed_counters, token_mask,
                                         uniform_bias_init)

FULL, WINDOW = "full", "window"
# what a decode step's span carries of the expert layers, summed over
# them (`inference/engine.py` reads the names): `models/mla_moe.py`'s
# four and the experts held over the expert layers
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_rows_visited", "moe_experts_held")
# the published pattern: a full layer first and then every sixth
PATTERN_48 = tuple(int(not (i == 0 or i % 6 == 5)) for i in range(48))

# one kind of attention layer: its heads, widths, rotary base and sink
Kind = collections.namedtuple(
    "Kind", "heads kv_heads head_dim v_dim rope_theta sink window "
            "rotary_dim")


class MimoV2Unsupported(ValueError):
    """A published key asks for what `models/mimo_v2.py` does not
    build."""


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    """The published ``config.json`` keys under their published names,
    the share that is held, and how it is run."""
    vocab_size: int = 152576            # rows held of embedding and head
    hidden_size: int = 4096
    intermediate_size: int = 16384      # the dense layers' MLP
    moe_intermediate_size: int = 2048   # one expert
    num_hidden_layers: int = 48
    hybrid_layer_pattern: Tuple[int, ...] = PATTERN_48  # 1: window
    moe_layer_freq: Tuple[int, ...] = (0,) + (1,) * 47  # 1: experts
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    sliding_window: int = 128
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    attention_value_scale: float = 0.707
    attention_bias: bool = False
    n_routed_experts: int = 256
    n_shared_experts: Optional[int] = None
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: Optional[float] = None
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    router_bias_range: float = 0.005    # e_score_correction_bias: +-
    sink_bias_mean: float = 5.0         # a sink's logit: normal(mean,
    sink_bias_range: float = 1.0        # range)
    experts_held: Tuple[int, int] = (0, 256)    # (first, count)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and
                first + count <= self.n_routed_experts):
            raise ValueError(
                f"experts_held {self.experts_held} must lie within the "
                f"{self.n_routed_experts} routed experts")
        if (self.scoring_func, self.topk_method, self.n_group,
                self.topk_group) != ("sigmoid", "noaux_tc", 1, 1):
            raise MimoV2Unsupported(
                "sigmoid scores chosen by noaux_tc in one group only "
                f"(got {self.scoring_func}, {self.topk_method}, n_group "
                f"{self.n_group}, topk_group {self.topk_group}; the group "
                "stage lives in moe/dropless.py:sigmoid_group_top_k, which "
                "this model does not route through)")
        if self.n_shared_experts:
            raise MimoV2Unsupported(
                f"no shared expert is built (n_shared_experts "
                f"{self.n_shared_experts})")
        if self.attention_bias:
            raise MimoV2Unsupported("no bias in the attention's products")
        n = self.num_hidden_layers
        if len(self.hybrid_layer_pattern) < n or \
                len(self.moe_layer_freq) < n:
            raise ValueError(
                f"hybrid_layer_pattern and moe_layer_freq say what each "
                f"of the {n} layers is")
        for kind in (self.kind(FULL), self.kind(WINDOW)):
            if kind.heads % kind.kv_heads or kind.rotary_dim % 2 or \
                    kind.v_dim > kind.head_dim:
                raise ValueError(
                    f"key heads divide query heads, the rotary part of a "
                    f"head is even, values are no wider than keys: {kind}")

    # the norms' epsilon under the name `models/blocks.py:RMSNorm` reads
    @property
    def rms_norm_eps(self):
        return self.layernorm_epsilon

    def kind(self, which):
        """The numbers of a full or a window layer."""
        if which == WINDOW:
            d = self.swa_head_dim
            return Kind(self.swa_num_attention_heads,
                        self.swa_num_key_value_heads, d, self.swa_v_head_dim,
                        self.swa_rope_theta,
                        self.add_swa_attention_sink_bias,
                        self.sliding_window,
                        int(self.partial_rotary_factor * d))
        d = self.head_dim
        return Kind(self.num_attention_heads, self.num_key_value_heads, d,
                    self.v_head_dim, self.rope_theta,
                    self.add_full_attention_sink_bias, 0,
                    int(self.partial_rotary_factor * d))

    @property
    def layer_kinds(self):
        return tuple(WINDOW if p else FULL for p in
                     self.hybrid_layer_pattern[:self.num_hidden_layers])

    def is_dense(self, i):
        return not self.moe_layer_freq[i]

    def names(self, which):
        return tuple(f"layers_{i}" for i, k in enumerate(self.layer_kinds)
                     if k == which)

    def cache_spec(self, max_batch, max_seq, kv_cache_dtype=None,
                   page_size=0, n_pages=0):
        """Two groups of page layers: the full layers (every page of a
        row) and the window layers (a ring a row), each with its own key
        heads, keys of ``head_dim`` over values of ``v_head_dim``."""
        from deepspeed_tpu.inference.cache import page_pool_spec
        groups = [(which, self.names(which), k.kv_heads, k.head_dim, k.v_dim,
                   k.window)
                  for which in (FULL, WINDOW)
                  for k in (self.kind(which),) if self.names(which)]
        return page_pool_spec(
            max_batch, max_seq, n_layer=self.num_hidden_layers,
            n_head=groups[0][2], head_dim=groups[0][3],
            compute_dtype=self.dtype,
            n_positions=self.max_position_embeddings,
            kv_cache_dtype=kv_cache_dtype, page_size=page_size,
            n_pages=n_pages, groups=groups)


def mimo_v2_5_share(n_layer=7, experts_held=(0, 16), vocab_size=19072, **kw):
    """XiaomiMiMo/MiMo-V2.5 at its published widths, as one chip of the
    16 that share each layer of a pipeline stage holds it: the first
    ``n_layer`` of the 48 layers (seven are the dense layer and one
    whole period ``F | W W W W F W``), 16 of the 256 experts, an eighth
    of the vocabulary's rows."""
    return MimoV2Config(num_hidden_layers=n_layer,
                        experts_held=tuple(experts_held),
                        vocab_size=vocab_size, **kw)


def mimo_v2_tiny(**kw):
    """Test-size model: ``F | W W F W``, a dense layer and four expert
    layers, 4 of 16 experts held, top 2; two key heads in a full layer
    and four in a window layer, keys of 24 over values of 16, rotary on
    8, a window of 8."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 96)
    kw.setdefault("moe_intermediate_size", 32)
    kw.setdefault("num_hidden_layers", 5)
    kw.setdefault("hybrid_layer_pattern", (0, 1, 1, 0, 1))
    kw.setdefault("moe_layer_freq", (0, 1, 1, 1, 1))
    for heads in ("num_attention_heads", "swa_num_attention_heads"):
        kw.setdefault(heads, 8)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("swa_num_key_value_heads", 4)
    for d in ("head_dim", "swa_head_dim"):
        kw.setdefault(d, 24)
    for d in ("v_head_dim", "swa_v_head_dim"):
        kw.setdefault(d, 16)
    kw.setdefault("sliding_window", 8)
    kw.setdefault("n_routed_experts", 16)
    kw.setdefault("num_experts_per_tok", 2)
    kw.setdefault("experts_held", (4, 4))
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("initializer_range", 0.1)
    kw.setdefault("router_bias_range", 0.1)
    kw.setdefault("sink_bias_mean", 1.0)
    return MimoV2Config(**kw)


def _sink_init(cfg):
    """A sink's logit: about the logarithm of a full window's summed
    ``exp(score)``, so that the sink takes a share of the weight that a
    check cannot miss (`configs/mimo-v2.5.json`, ``sink_bias_why``)."""
    def init(key, shape, dtype):
        return cfg.sink_bias_mean + cfg.sink_bias_range * \
            jax.random.normal(key, shape, dtype)
    return init


class MimoAttention(nn.Module):
    """Causal grouped-query attention of one kind through its group's
    pages: a full layer over every page of the row, a window layer over
    its ring, with a sink where the kind has one."""
    config: MimoV2Config
    which: str

    @nn.compact
    def __call__(self, x, layer_cache, positions, page_table, n_valid,
                 attn):
        from deepspeed_tpu.inference.cache import cached_attention
        cfg = self.config
        kind = cfg.kind(self.which)
        B, T, C = x.shape
        Hq, H, D, Dv = kind.heads, kind.kv_heads, kind.head_dim, kind.v_dim
        with jax.named_scope("ds_attn_qkv"):
            q = jnp.dot(x, param(self, "q_proj", cfg, (C, Hq * D)))
            k = jnp.dot(x, param(self, "k_proj", cfg, (C, H * D)))
            v = jnp.dot(x, param(self, "v_proj", cfg, (C, H * Dv)))
            q = partial_rotary(q.reshape(B, T, Hq, D), positions, kind)
            k = partial_rotary(k.reshape(B, T, H, D), positions, kind)
            v = (v.astype(jnp.float32) * cfg.attention_value_scale).astype(
                cfg.dtype).reshape(B, T, H, Dv)
        sink = self.param("sink", _sink_init(cfg), (Hq,), jnp.float32) \
            if kind.sink else None
        y, layer_cache = cached_attention(
            q, k, v, layer_cache, positions, cfg.dtype, page_table,
            scale=D ** -0.5, window=kind.window, sink=sink, n_valid=n_valid,
            walk=True, **attn)
        with jax.named_scope("ds_attn_out"):
            y = jnp.dot(y.reshape(B, T, Hq * Dv),
                        param(self, "o_proj", cfg, (Hq * Dv, C)))
        return y, layer_cache


class RoutedExperts(nn.Module):
    """The routed experts this chip holds; there is no shared one.
    Returns ``(y, the layer's `blocks.ExpertCounters`)``: ``mask``
    ``[B, T]`` says which tokens are real."""
    config: MimoV2Config

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.config
        B, T, C = x.shape
        E, I = cfg.n_routed_experts, cfg.moe_intermediate_size
        first, held = cfg.experts_held
        init, pd = normal(cfg), cfg.param_dtype
        router = self.param("router", init, (C, E), pd)
        bias = self.param("e_score_correction_bias", uniform_bias_init(cfg), (E,),
                          jnp.float32)
        w_gate = self.param("w_gate", init, (held, C, I), pd)
        w_up = self.param("w_up", init, (held, C, I), pd)
        w_down = self.param("w_down", init, (held, I, C), pd)
        y, counters = sigmoid_held_experts(
            x.reshape(B * T, C), mask.reshape(B * T), router, bias, w_gate,
            w_up, w_down, top_k=cfg.num_experts_per_tok,
            scaling=float(cfg.routed_scaling_factor or 1.0),
            renormalise=cfg.norm_topk_prob, first_expert=first)
        return y.reshape(B, T, C), counters


class MimoV2Layer(nn.Module):
    """Pre-norm residual layer: attention of its kind, then the dense
    MLP or the experts."""
    config: MimoV2Config
    which: str
    dense: bool

    @nn.compact
    def __call__(self, h, layer_cache, positions, page_table, n_valid, mask,
                 attn):
        cfg = self.config
        # each norm under the scope of what it feeds, each residual add
        # under that of what it follows (`telemetry/scopes.py`)
        with jax.named_scope("ds_attn_qkv"):
            n = RMSNorm(cfg, name="input_norm")(h)
        y, layer_cache = MimoAttention(cfg, self.which, name="attn")(
            n, layer_cache, positions, page_table, n_valid, attn)
        with jax.named_scope("ds_attn_out"):
            h = h + y
        with jax.named_scope("ds_mlp" if self.dense else "ds_experts"):
            n = RMSNorm(cfg, name="post_attn_norm")(h)
            if self.dense:
                y = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(n)
                counters = None
            else:
                y, counters = RoutedExperts(cfg, name="experts")(n, mask)
            return h + y, layer_cache, counters


class MimoV2LM(ServedLM, nn.Module):
    """The decoder with its untied head, through the serving cache.
    Returns ``(logits [B, vocab_size] float32 at each row's last real
    token, the cache, the counters of `COUNTERS`)``."""
    config: MimoV2Config
    # the names of what `serve_apply` returns third, for the engine
    serve_counters = COUNTERS

    @nn.compact
    def __call__(self, tokens, cache, positions, page_table, n_valid, attn):
        from deepspeed_tpu.inference.cache import split_table
        cfg = self.config
        B, T = tokens.shape
        embed = self.param("embed", normal(cfg),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with jax.named_scope("ds_embed"):
            h = embed.astype(cfg.dtype)[tokens]
            mask = token_mask(n_valid, T)
        # the table's last entries are the row's ring, where there is one
        page_size = next(iter(cache.values()))["k"].shape[-1]
        ring = cfg.sliding_window // page_size + 1 \
            if cfg.names(WINDOW) else 0
        with jax.named_scope("ds_embed"):
            tables = dict(zip((FULL, WINDOW),
                              split_table(page_table, ring)))
        new_cache, counted = {}, []
        for i, which in enumerate(cfg.layer_kinds):
            name = f"layers_{i}"
            h, new_cache[name], c = MimoV2Layer(
                cfg, which, bool(cfg.is_dense(i)), name=name)(
                    h, cache[name], positions, tables[which], n_valid, mask,
                    attn)
            if c is not None:
                counted.append(c)
        with jax.named_scope("ds_head"):
            h = RMSNorm(cfg, name="final_norm")(last_token(h, n_valid))
            head = self.param("lm_head", normal(cfg),
                              (cfg.hidden_size, cfg.vocab_size),
                              cfg.param_dtype)
            logits = head_logits(h, head, cfg.dtype)
        return logits, new_cache, summed_counters(
            COUNTERS, counted,
            moe_experts_held=jnp.int32(cfg.experts_held[1] * len(counted)))

    @nn.nowrap
    def serve_args(self, cache, tokens, positions, page_table, slots,
                   n_valid, attn_impl, attn_block_k, attn_mesh):
        del slots       # pages are the cache: a row's slot owns nothing
        if attn_mesh is not None:
            raise MimoV2Unsupported("a 'model' mesh axis is not built")
        return (tokens, cache, positions, page_table, n_valid,
                {"impl": attn_impl, "block_k": attn_block_k})


# the matrices that write to the stream: out of an attention, a dense
# MLP, and the experts' third banks
_WRITERS = {"o_proj": 0, "w_out": 0, "w_down": 1}


def init_mimo_v2_params(model, rng):
    """The model's weights from ``rng``, the writers centred
    (`blocks.init_served_params`), over a page that holds a window."""
    return init_served_params(model, rng, _WRITERS,
                              page=max(model.config.sliding_window, 8))

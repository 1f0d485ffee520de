"""Nemotron-H: a decoder whose every block is ONE sub-layer (a Mamba-2
mixer, an attention or an expert layer, by a pattern string), served as
one chip's share of an expert-parallel group.

What no other model here has: a block that is a single pre-norm
residual step, ``h = h + f(RMSNorm(h))``, with ``f`` read off
``hybrid_override_pattern`` (``M`` mixer, ``*`` attention, ``E``
experts; a ``-``, the family's dense MLP, is refused: the served model
has none); **experts that work in a latent** (the token is projected
from ``hidden_size`` down to ``moe_latent_size``, the routed experts
run there, their weighted sum is projected back up; the router and the
shared expert read the full-width token); experts of **two matrices
under ``relu^2``** (no gate matrix); a scan with **several B/C groups**;
and a recurrent state beside routed experts in one program.

Layer equations as published (``config.json`` of
``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``, ``model_type:
nemotron_h``; ``modeling_nemotron_h.py`` beside it), ``n = RMSNorm(h)``
(``layer_norm_epsilon``, a weight and no bias), no bias in any product,
no multipliers, untied head:

- ``M``: Mamba-2 as `models/granite_hybrid.py:Mamba2Mixer` states it,
  with ``n_groups`` B/C groups (head ``h`` reads group ``h // (heads /
  groups)``) and the gated norm by group.
- ``*``: ``num_attention_heads`` query heads of ``head_dim`` over
  ``num_key_value_heads`` key/value heads, scores times ``head_dim ^
  -0.5``, causal, **no positional encoding** (the published attention
  applies none; ``rope_theta`` is a key of the file that nothing reads).
- ``E``: ``s = sigmoid(n W_r)`` over all ``n_routed_experts``, float32;
  the ``num_experts_per_tok`` largest of ``s + b`` are chosen (``b`` the
  ``e_score_correction_bias``; one group, so no group limit); weights
  ``s`` over their sum, times ``routed_scaling_factor``; ``l = n
  W_down`` (the latent); a chosen expert gives ``relu(l W1_e)^2 W2_e``;
  their weighted sum goes through ``W_up``; the shared expert
  ``relu(n V1)^2 V2`` is added.
- after the last block ``logits = RMSNorm(h) W_head``.
- multi-token prediction (``num_nextn_predict_layers``) is a draft head
  for speculative decoding, no part of the pass that serves a token: not
  built.

**The share** (as `models/mla_moe.py`): ``experts_held = (first,
count)`` of the router's ``n_routed_experts`` are held, routing runs
over all of them and pairs of experts held elsewhere add nothing here
(`moe/dropless.py`); the first ``vocab_size`` rows of embedding and head
are held. Mixers, attention, router, latent projections and the shared
expert are whole. Nothing stands in for the other chips.

Precision, part of the configuration: weights, activations, the page
pool and the convolution window in ``dtype`` (bfloat16 as published);
products accumulate in float32; the mixer's ``dt``, decays and state,
every norm's statistics, the attention's softmax, the router's product
(at the highest precision), sigmoid, choice and weights float32.
`benchmarks/suite/reference/nemotron_h_ref.py` is the plain float32
statement of the same mathematics. Serving only.
"""

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.blocks import (GroupedQueryAttention, Mamba2Mixer,
                                         RMSNorm, ServedLM, head_logits,
                                         init_served_params, last_token,
                                         expert_counters, normal,
                                         summed_counters, token_mask,
                                         uniform_bias_init)
from deepspeed_tpu.moe.dropless import dropless_moe, sigmoid_top_k

MIXER, ATTENTION, EXPERTS = "M", "*", "E"
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
# what a decode step's span carries of the expert layers
# (`inference/engine.py` reads the names): the first three summed over
# the layers as `models/mla_moe.py`'s, the fullest held expert's pairs
# (largest over the layers), held experts x expert layers, and the
# sorted rows the layers' dispatch filled (whole tiles, summed)
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_pairs_max", "moe_experts_held", "moe_rows_visited")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published ``config.json`` keys under their published names,
    the share that is held, and how it is run."""
    vocab_size: int = 131072            # rows held of embedding and head
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8                   # the scan's B/C groups
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2
    use_conv_bias: bool = True
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_routed_experts: int = 512
    n_shared_experts: int = 1
    num_experts_per_tok: int = 22
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    n_group: int = 1                    # the router's groups
    topk_group: int = 1
    mlp_hidden_act: str = "relu2"
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    router_bias_range: float = 0.1      # e_score_correction_bias: +-
    experts_held: Tuple[int, int] = (0, 512)    # (first, count)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or \
                set(pattern) - {MIXER, ATTENTION, EXPERTS}:
            raise ValueError(
                f"hybrid_override_pattern must name "
                f"{self.num_hidden_layers} blocks, each '{MIXER}' (mixer), "
                f"'{ATTENTION}' (attention) or '{EXPERTS}' (experts); a "
                f"'-' (dense MLP) is not served; got {pattern!r}")
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and
                first + count <= self.n_routed_experts):
            raise ValueError(
                f"experts_held {self.experts_held} must lie within the "
                f"{self.n_routed_experts} routed experts")
        if (self.n_group, self.topk_group) != (1, 1):
            raise ValueError("the router chooses in one group only "
                             f"(n_group {self.n_group}, topk_group "
                             f"{self.topk_group}; the group stage lives in "
                             "moe/dropless.py:sigmoid_group_top_k, which "
                             "this model does not route through)")
        if self.mlp_hidden_act != "relu2" or not self.use_conv_bias:
            raise ValueError("experts under relu2 and a convolution with "
                             "its bias only")
        if self.mamba_num_heads * self.mamba_head_dim != \
                self.expand * self.hidden_size or \
                self.mamba_num_heads % self.n_groups:
            raise ValueError(
                "mamba_num_heads x mamba_head_dim must be expand x "
                "hidden_size, and n_groups must divide mamba_num_heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")

    # `Mamba2Mixer`, `GroupedQueryAttention` and `RMSNorm` read a
    # configuration by granite's names
    mamba_n_heads = property(lambda self: self.mamba_num_heads)
    mamba_d_head = property(lambda self: self.mamba_head_dim)
    mamba_d_state = property(lambda self: self.ssm_state_size)
    mamba_n_groups = property(lambda self: self.n_groups)
    mamba_d_conv = property(lambda self: self.conv_kernel)
    mamba_chunk_size = property(lambda self: self.chunk_size)
    rms_norm_eps = property(lambda self: self.layer_norm_epsilon)
    attention_multiplier = property(lambda self: self.head_dim ** -0.5)

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def names(self, kind):
        return tuple(f"layers_{i}" for i, t in enumerate(
            self.hybrid_override_pattern) if t == kind)

    def cache_spec(self, max_batch, max_seq, kv_cache_dtype=None,
                   page_size=0, n_pages=0):
        """A page pool for the ``*`` layers; for every ``M`` layer a
        float32 state ``[rows, heads, head_dim, state]`` and a
        convolution window ``[taps - 1, rows, channels]``. An ``E``
        layer keeps nothing."""
        from deepspeed_tpu.inference.cache import page_pool_spec
        att = self.names(ATTENTION)
        return page_pool_spec(
            max_batch, max_seq, n_layer=len(att),
            n_head=self.num_key_value_heads, head_dim=self.head_dim,
            compute_dtype=self.dtype,
            n_positions=self.max_position_embeddings,
            kv_cache_dtype=kv_cache_dtype, page_size=page_size,
            n_pages=n_pages, layers=att,
            recurrent_layers=self.names(MIXER),
            recurrent_leaves=(
                ("ssm", (max_batch, self.mamba_num_heads,
                         self.mamba_head_dim, self.ssm_state_size),
                 jnp.float32),
                ("conv", (self.conv_kernel - 1, max_batch, self.conv_dim),
                 self.dtype)))


def nemotron_3_super_share(n_layer=11, experts_held=(0, 128),
                           vocab_size=32768, **kw):
    """NVIDIA-Nemotron-3-Super-120B-A12B at its published widths, as one
    chip of 4 that share each layer holds it: the pattern's first
    ``n_layer`` blocks (eleven are one period: 5 ``M``, 5 ``E``, 1
    ``*``), 128 of the 512 experts, a quarter of the vocabulary's rows.
    The router's bias is drawn narrow
    (`configs/nemotron-3-super-120b-a12b.json`, ``router_bias_why``)."""
    kw.setdefault("router_bias_range", 0.005)
    kw.setdefault("hybrid_override_pattern", PATTERN[:n_layer])
    return NemotronHConfig(
        num_hidden_layers=n_layer, experts_held=tuple(experts_held),
        vocab_size=vocab_size, **kw)


def nemotron_h_tiny(**kw):
    """Test-size model: two periods of (mixer, experts, attention,
    experts), 4 of 8 experts held, top 3, two B/C groups, two query
    heads to a key head."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_hidden_layers", 8)
    kw.setdefault("hybrid_override_pattern", "ME*E" * 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("mamba_num_heads", 8)
    kw.setdefault("mamba_head_dim", 16)
    kw.setdefault("ssm_state_size", 16)
    kw.setdefault("n_groups", 2)
    kw.setdefault("chunk_size", 8)
    kw.setdefault("moe_intermediate_size", 48)
    kw.setdefault("moe_latent_size", 32)
    kw.setdefault("moe_shared_expert_intermediate_size", 96)
    kw.setdefault("n_routed_experts", 8)
    kw.setdefault("num_experts_per_tok", 3)
    kw.setdefault("experts_held", (2, 4))
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("initializer_range", 0.1)
    return NemotronHConfig(**kw)


# jitted, so that the expert layers share one trace of the routing and
# of the two grouped matmuls (as `models/mla_moe.py:_held_experts`)
@functools.partial(jax.jit, static_argnames=(
    "top_k", "scaling", "renormalise", "first_expert"))
def _held_experts(x, latent, mask, router, bias, w_up, w_down, *, top_k,
                  scaling, renormalise, first_expert):
    y, stats = dropless_moe(
        x, router, None, w_up, w_down, top_k,
        route=sigmoid_top_k(bias, scaling, renormalise),
        first_expert=first_expert, token_mask=mask, rows=latent)
    return y, expert_counters(mask, top_k, stats)


class LatentExperts(nn.Module):
    """The routed experts this chip holds, in their latent, and the
    shared expert at full width. Returns ``(y, the layer's
    `blocks.ExpertCounters`)``; ``mask`` ``[B, T]`` says which tokens are
    real."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.config
        B, T, C = x.shape
        E, I, L = cfg.n_routed_experts, cfg.moe_intermediate_size, \
            cfg.moe_latent_size
        S = cfg.moe_shared_expert_intermediate_size * cfg.n_shared_experts
        first, held = cfg.experts_held
        init, pd, dt = normal(cfg), cfg.param_dtype, cfg.dtype
        router = self.param("router", init, (C, E), pd)
        bias = self.param("e_score_correction_bias", uniform_bias_init(cfg), (E,),
                          jnp.float32)
        w_up = self.param("w_up", init, (held, L, I), pd)
        w_down = self.param("w_down", init, (held, I, L), pd)
        with jax.named_scope("ds_moe_latent_down"):
            latent = jnp.dot(x, self.param("latent_down", init, (C, L),
                                           pd).astype(dt))
        y, counters = _held_experts(
            x.reshape(B * T, C), latent.reshape(B * T, L),
            mask.reshape(B * T), router, bias, w_up, w_down,
            top_k=cfg.num_experts_per_tok,
            scaling=cfg.routed_scaling_factor,
            renormalise=cfg.norm_topk_prob, first_expert=first)
        with jax.named_scope("ds_moe_latent_up"):
            y = jnp.dot(y.reshape(B, T, L),
                        self.param("latent_up", init, (L, C), pd).astype(dt))
        with jax.named_scope("ds_moe_shared"):
            v1 = self.param("shared_up", init, (C, S), pd).astype(dt)
            v2 = self.param("shared_down", init, (S, C), pd).astype(dt)
            shared = jnp.dot(jnp.square(jax.nn.relu(jnp.dot(x, v1))), v2)
        return y + shared, counters


class NemotronHBlock(nn.Module):
    """``h + f(RMSNorm(h))`` with the one ``f`` of its kind. Returns
    ``(h, the layer's cache or None, the layer's counters or None)``."""
    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, h, layer_cache, positions, page_table, slots,
                 n_valid, mask, attn):
        cfg = self.config
        if self.kind == ATTENTION:
            # the norm under the scope of the projections it feeds, the
            # residual add under that of the one it follows
            with jax.named_scope("ds_attn_qkv"):
                n = RMSNorm(cfg, name="norm")(h)
            y, layer_cache = GroupedQueryAttention(cfg, name="attn")(
                n, layer_cache, positions, page_table, attn)
            with jax.named_scope("ds_attn_out"):
                return h + y, layer_cache, None
        # a mixer or an expert layer whole, round its older inner scopes
        with jax.named_scope("ds_ssm_mixer" if self.kind == MIXER
                             else "ds_experts"):
            n = RMSNorm(cfg, name="norm")(h)
            counters = None
            if self.kind == MIXER:
                y, layer_cache = Mamba2Mixer(cfg, name="mixer")(
                    n, layer_cache, positions, slots, n_valid)
            else:
                y, counters = LatentExperts(cfg, name="experts")(n, mask)
            return h + y, layer_cache, counters


class NemotronHLM(ServedLM, nn.Module):
    """The decoder with its untied head, through the serving cache.
    Returns ``(logits [B, vocab_size] float32 at each row's last real
    token, the cache, the expert layers' counters)``."""
    config: NemotronHConfig
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
        for i, kind in enumerate(cfg.hybrid_override_pattern):
            name = f"layers_{i}"
            h, layer_cache, counters = NemotronHBlock(cfg, kind, name=name)(
                h, cache.get(name), positions, page_table, slots, n_valid,
                mask, attn)
            if layer_cache is not None:
                new_cache[name] = layer_cache
            if counters is not None:
                counted.append(counters)
        with jax.named_scope("ds_head"):
            h = RMSNorm(cfg, name="final_norm")(last_token(h, n_valid))
            head = self.param("lm_head", normal(cfg),
                              (cfg.hidden_size, cfg.vocab_size),
                              cfg.param_dtype)
            logits = head_logits(h, head, cfg.dtype)
        with jax.named_scope("ds_sample"):
            counters = summed_counters(
                COUNTERS, counted, moe_experts_held=jnp.int32(
                    cfg.experts_held[1] * len(cfg.names(EXPERTS))))
        return logits, new_cache, counters


# the matrices that write to the stream (out of a mixer, the attention,
# the latent, the shared expert), and the experts' second banks
_WRITERS = {"out_proj": 0, "o_proj": 0, "latent_up": 0, "shared_down": 0,
            "w_down": 1}


def init_nemotron_h_params(model, rng):
    """The model's weights from ``rng``, the writers centred
    (`blocks.init_served_params`)."""
    return init_served_params(model, rng, _WRITERS)

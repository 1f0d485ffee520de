"""Latent attention over sigmoid-routed experts: the DeepSeek-V3 block
(``kimi_k2`` reuses it), served as one chip's share of an
expert-parallel deployment.

Two mechanisms no other model here has. **Latent attention (MLA)**: a
token's keys and values for all heads are one compressed vector
``c_kv`` (``kv_lora_rank`` wide, normed) that each head up-projects,
and beside it one rotary key ``k_rope`` that all heads share; queries
are compressed and up-projected likewise, and rotary (YaRN-scaled) acts
on ``qk_rope_head_dim`` entries of a head only. **Sigmoid routing with
a shared expert**: a token's scores over ``n_routed_experts`` are
sigmoids, the ``num_experts_per_tok`` largest of ``score + bias`` are
chosen (the bias moves the choice and nothing else), the chosen scores
are renormalised and scaled, and one shared expert sees every token.
The first ``first_k_dense_replace`` layers have a dense gated MLP
instead.

Layer equations as published (``config.json`` of
``moonshotai/Kimi-K2.7-Code``, ``model_type: kimi_k2``;
``transformers/models/deepseek_v3/modeling_deepseek_v3.py``; DeepSeek-V3
technical report, arXiv:2412.19437), with ``n = RMSNorm(h)``, no bias
anywhere, ``h += Attn(n); h += FFN(RMSNorm(h))``:

- queries: ``c_q = RMSNorm(n W_dq)``; ``[q_nope | q_rope] = c_q W_uq``
  a head; keys and values: ``[c_kv | k_rope] = n W_dkv``, ``c_kv <-
  RMSNorm(c_kv)``, ``[k_nope | v] = c_kv W_ukv`` a head; rotary on
  ``q_rope`` (each head) and the one ``k_rope``; scores ``(q_nope .
  k_nope + q_rope . k_rope) s`` under the causal mask; ``y =
  concat(softmax v) W_o``.
- YaRN: each rotary frequency is a blend of ``theta^(-2i/d)`` and the
  same over ``factor``, by a linear ramp between the correction
  dimensions of ``beta_fast`` and ``beta_slow``; ``s = (nope +
  rope)^-0.5 (0.1 mscale_all_dim ln factor + 1)^2`` (`yarn_inv_freq`,
  `MlaMoeConfig.softmax_scale`).
- experts: `moe/dropless.py:sigmoid_top_k`; ``y = sum_e w_e E_e(n) +
  Shared(n)``, every expert a SiLU-gated MLP.

**What is stored and what a decode step runs.** The pool keeps, a token
a layer, ``[c_kv | k_rope]`` (after the norm, after rotary): one
latent leaf, one "head" (`inference/cache.py`, *a latent pool*). The
scores do not need the keys expanded: ``q_nope . (c_kv W_uk) = (q_nope
W_uk^T) . c_kv``, so with ``q_abs = q_nope W_uk^T`` a head (the
*absorbed* form) every head's query ``[q_abs | q_rope]`` meets the
stored vector as it lies, the values are its first ``kv_lora_rank``
entries, and ``W_uv`` is applied to the heads' outputs. A decode step
runs that through `flash_decode_paged`. A prefill chunk walks the
row's live prefix in blocks (`cache.latent_prefill_attention`),
expanding each block through ``W_ukv``: fewer operations a query-key
pair at a chunk of 1024 than scoring it absorbed, and faster on the
chip at every prefix (`PERF.md` section 6 (PR 34) has both readings);
under ``attention_impl: "flash"`` a block's scores are the prefill
kernel's (`ops/pallas/latent_prefill.py`).

**The share.** ``experts_held = (first, count)``: the banks hold
``count`` of the router's ``n_routed_experts``; routing runs over all
of them and the pairs of experts held elsewhere add nothing here
(`moe/dropless.py`). The first ``vocab_size`` rows of the embedding
and of the head are held; token ids are taken within that slice.
Attention, the dense MLP, the shared expert and the router are whole.
Nothing stands in for the other chips: the output is this chip's part
of the sum.

Precision, part of the configuration: weights, activations and latents
in ``dtype`` (bfloat16 as published); products accumulate in float32;
norm statistics, rotary angles, scores, softmax and its sums float32;
the router's product, sigmoid, choice and weights float32 at the
highest precision. `benchmarks/suite/reference/mla_moe_ref.py` is the
plain float32, unabsorbed statement of the same mathematics. Serving
only: no loss, no backward.
"""

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.blocks import (GatedMLP, RMSNorm, ServedLM,
                                         head_logits, init_served_params,
                                         last_token, normal, param, rotate,
                                         sigmoid_held_experts,
                                         summed_counters, token_mask,
                                         uniform_bias_init, yarn_inv_freq)

YARN = (("beta_fast", 32), ("beta_slow", 1), ("factor", 64), ("mscale", 1),
        ("mscale_all_dim", 1), ("original_max_position_embeddings", 4096),
        ("type", "yarn"))
# what a decode step's span carries of the expert layers, summed over
# them (`inference/engine.py` reads the names); the last: the sorted
# rows the dispatch filled, whole tiles (`moe/dropless.py`)
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_rows_visited")


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    """The published ``config.json`` keys under their published names,
    the share that is held, and how it is run."""
    vocab_size: int = 163840            # rows held of embedding and head
    hidden_size: int = 7168
    intermediate_size: int = 18432      # the dense layers' MLP
    moe_intermediate_size: int = 2048   # one expert, and the shared one
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 384
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.827
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_scaling: Tuple = YARN          # the published dict's items
    initializer_range: float = 0.02
    router_bias_range: float = 0.1      # e_score_correction_bias: +-
    experts_held: Tuple[int, int] = (0, 384)    # (first, count)
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
            raise ValueError(
                "sigmoid scores chosen by noaux_tc in one group only "
                f"(got {self.scoring_func}, {self.topk_method}, n_group "
                f"{self.n_group}, topk_group {self.topk_group}; the group "
                "stage lives in moe/dropless.py:sigmoid_group_top_k, which "
                "this model does not route through)")
        if dict(self.rope_scaling).get("type") != "yarn":
            raise ValueError("rope_scaling must be the YaRN kind")

    @property
    def latent_dim(self):
        """What the pool keeps a token a layer: ``[c_kv | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        """``(nope + rope)^-0.5`` times YaRN's ``mscale^2``."""
        rs = dict(self.rope_scaling)
        d = self.qk_nope_head_dim + self.qk_rope_head_dim
        return d ** -0.5 * yarn_mscale(rs["factor"],
                                       rs.get("mscale_all_dim", 0)) ** 2

    def is_dense(self, i):
        return i < self.first_k_dense_replace or i % self.moe_layer_freq

    def layer_names(self):
        return tuple(f"layers_{i}" for i in range(self.num_hidden_layers))

    def cache_spec(self, max_batch, max_seq, kv_cache_dtype=None,
                   page_size=0, n_pages=0):
        """One latent leaf a layer: one head of ``kv_lora_rank +
        qk_rope_head_dim``, whose first ``kv_lora_rank`` are the value."""
        from deepspeed_tpu.inference.cache import page_pool_spec
        return page_pool_spec(
            max_batch, max_seq, n_layer=self.num_hidden_layers, n_head=1,
            head_dim=self.latent_dim, compute_dtype=self.dtype,
            n_positions=self.max_position_embeddings,
            kv_cache_dtype=kv_cache_dtype, page_size=page_size,
            n_pages=n_pages, latent_v_dim=self.kv_lora_rank,
            layers=self.layer_names())


def kimi_k2_share(n_layer=7, experts_held=(0, 12), vocab_size=20480, **kw):
    """moonshotai/Kimi-K2.7-Code at its published widths, as one chip of
    32 that share each layer holds it: ``n_layer`` of the 61 layers (the
    dense one and the expert layers after it), 12 of the 384 experts,
    an eighth of the vocabulary's rows. The router's bias is drawn
    narrow (`configs/kimi-k2.7-code.json`, ``router_bias_why``)."""
    kw.setdefault("router_bias_range", 0.005)
    return MlaMoeConfig(num_hidden_layers=n_layer,
                        experts_held=tuple(experts_held),
                        vocab_size=vocab_size, **kw)


def mla_moe_tiny(**kw):
    """Test-size model: a dense layer and two expert layers, 4 of 16
    experts held, top 2."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 96)
    kw.setdefault("moe_intermediate_size", 32)
    kw.setdefault("num_hidden_layers", 3)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("q_lora_rank", 24)
    kw.setdefault("kv_lora_rank", 32)
    kw.setdefault("qk_nope_head_dim", 16)
    kw.setdefault("qk_rope_head_dim", 8)
    kw.setdefault("v_head_dim", 16)
    kw.setdefault("n_routed_experts", 16)
    kw.setdefault("num_experts_per_tok", 2)
    kw.setdefault("experts_held", (4, 4))
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("initializer_range", 0.1)
    kw.setdefault("rope_scaling", tuple(sorted(dict(
        YARN, factor=4, original_max_position_embeddings=64).items())))
    return MlaMoeConfig(**kw)


# --- YaRN -------------------------------------------------------------------

def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_cos_sin(cfg, positions):
    """``cos`` and ``sin`` ``[B, T, rope / 2]`` float32 of the rotary
    angles at ``positions``, times YaRN's factor on the embedding (1
    where ``mscale == mscale_all_dim``)."""
    rs = dict(cfg.rope_scaling)
    inv = jnp.asarray(yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                                    cfg.rope_scaling), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv
    m = yarn_mscale(rs["factor"], rs.get("mscale", 1)) / \
        yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


# --- modules ----------------------------------------------------------------

class LatentAttention(nn.Module):
    """Causal latent attention through a latent pool (the module
    docstring): a decode step absorbed through the flash kernel or the
    dense oracle, one prompt's chunk over the row's live prefix."""
    config: MlaMoeConfig

    @nn.compact
    def __call__(self, x, layer_cache, positions, page_table, rope, attn):
        from deepspeed_tpu.inference.cache import cached_attention
        cfg = self.config
        B, T, C = x.shape
        H, rq, rkv = cfg.num_attention_heads, cfg.q_lora_rank, \
            cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        cos, sin = rope
        absorbed = T == 1       # a decode step; a chunk expands its blocks
        with jax.named_scope("ds_mla_project"):
            c_q = RMSNorm(cfg, name="q_a_norm")(
                jnp.dot(x, param(self, "q_a_proj", cfg, (C, rq))))
            q = jnp.dot(c_q, param(self, "q_b_proj", cfg,
                                    (rq, H * (dn + dr))))
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
                    # each as its product lies (keys a position a row,
                    # values a position a lane), the rotary key once:
                    # what the prefill kernel takes
                    c = lat[:, :rkv]
                    return (jnp.einsum("sc,chn->shn", c, w_ukv[..., :dn]),
                            lat[:, rkv:],
                            jnp.einsum("chv,sc->hvs", w_ukv[..., dn:], c))
        with jax.named_scope("ds_mla_prefill_attn" if T > 1
                             else "ds_mla_decode_attn"):
            y, layer_cache = cached_attention(
                q_in, latent, None, layer_cache, positions, cfg.dtype,
                page_table, scale=cfg.softmax_scale, v_dim=rkv,
                expand=expand, **attn)
        with jax.named_scope("ds_mla_project"):
            if absorbed:
                y = jnp.einsum("bthc,chv->bthv", y, w_ukv[..., dn:])
            y = jnp.dot(y.reshape(B, T, H * dv),
                        param(self, "o_proj", cfg, (H * dv, C)))
        return y, layer_cache


class HeldExperts(nn.Module):
    """The routed experts this chip holds, and the shared expert.
    Returns ``(y, the layer's `blocks.ExpertCounters`)``: ``mask``
    ``[B, T]`` says which tokens are real."""
    config: MlaMoeConfig

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
            scaling=cfg.routed_scaling_factor,
            renormalise=cfg.norm_topk_prob, first_expert=first)
        with jax.named_scope("ds_moe_shared"):
            shared = GatedMLP(cfg, I * cfg.n_shared_experts,
                              name="shared")(x)
        return y.reshape(B, T, C) + shared, counters


class MlaMoeLayer(nn.Module):
    """Pre-norm residual layer: latent attention, then the dense MLP or
    the experts."""
    config: MlaMoeConfig
    dense: bool

    @nn.compact
    def __call__(self, h, layer_cache, positions, page_table, rope, mask,
                 attn):
        cfg = self.config
        # each norm under the scope of what it feeds, each residual add
        # under that of what it follows (`telemetry/scopes.py`)
        with jax.named_scope("ds_attn_qkv"):
            n = RMSNorm(cfg, name="input_norm")(h)
        y, layer_cache = LatentAttention(cfg, name="attn")(
            n, layer_cache, positions, page_table, rope, attn)
        with jax.named_scope("ds_attn_out"):
            h = h + y
        with jax.named_scope("ds_mlp" if self.dense else "ds_experts"):
            n = RMSNorm(cfg, name="post_attn_norm")(h)
            if self.dense:
                y = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(n)
                counters = None
            else:
                y, counters = HeldExperts(cfg, name="experts")(n, mask)
            return h + y, layer_cache, counters


class MlaMoeLM(ServedLM, nn.Module):
    """The decoder with its untied head, through the serving cache.
    Returns ``(logits [B, vocab_size] float32 at each row's last real
    token, the cache, the expert layers' counters summed)``."""
    config: MlaMoeConfig
    # the names of what `serve_apply` returns third, for the engine
    serve_counters = COUNTERS

    @nn.compact
    def __call__(self, tokens, cache, positions, page_table, n_valid, attn):
        cfg = self.config
        B, T = tokens.shape
        embed = self.param("embed", normal(cfg),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with jax.named_scope("ds_embed"):
            h = embed.astype(cfg.dtype)[tokens]
            rope = yarn_cos_sin(cfg, positions)
            mask = token_mask(n_valid, T)
        new_cache, counted = {}, []
        for i, name in enumerate(cfg.layer_names()):
            h, new_cache[name], c = MlaMoeLayer(
                cfg, bool(cfg.is_dense(i)), name=name)(
                    h, cache[name], positions, page_table, rope, mask, attn)
            if c is not None:
                counted.append(c)
        with jax.named_scope("ds_head"):
            h = RMSNorm(cfg, name="final_norm")(last_token(h, n_valid))
            head = self.param("lm_head", normal(cfg),
                              (cfg.hidden_size, cfg.vocab_size),
                              cfg.param_dtype)
            logits = head_logits(h, head, cfg.dtype)
        return logits, new_cache, summed_counters(COUNTERS, counted)

    @nn.nowrap
    def serve_args(self, cache, tokens, positions, page_table, slots,
                   n_valid, attn_impl, attn_block_k, attn_mesh):
        del slots       # pages are the cache: a row's slot owns nothing
        return (tokens, cache, positions, page_table, n_valid,
                {"impl": attn_impl, "block_k": attn_block_k,
                 "mesh": attn_mesh})


def init_mla_moe_params(model, rng):
    """The model's weights from ``rng`` (`blocks.init_served_params`);
    no writer is centred."""
    return init_served_params(model, rng, {})

"""OLMoE: a modern decoder block with dropless sparse experts.

The first model here that is not a 2019 block: RMSNorm instead of
LayerNorm, rotary positions instead of learned ones, QK-norm, a gated
SiLU feed-forward, no bias anywhere, an untied head, and in place of the
dense feed-forward 64 experts of which each token takes 8 with nothing
dropped (`moe/dropless.py`). Layer equations as published
(``transformers/models/olmoe/modeling_olmoe.py``; Muennighoff et al.
2024, arXiv:2409.02060); `benchmarks/suite/reference/olmoe_ref.py` is
the plain float32 statement of the same mathematics that the tests and
the benchmark hold this module to.

Attention and the feed-forward part are separate modules
(`RotaryAttention`, `SparseExperts`) joined by `DecoderLayer`, so that a
later architecture swaps one and keeps the other.
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.blocks import RMSNorm, normal
# the training losses are GPT-2's, as `models/bert.py`'s are
from deepspeed_tpu.models.gpt2 import cross_entropy_loss, next_token_labels
from deepspeed_tpu.moe.dropless import dropless_moe


@dataclasses.dataclass(frozen=True)
class OlmoeConfig:
    """The published ``config.json`` keys under their published names,
    and how it is run."""
    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 1024       # width of one expert
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    router_aux_loss_coef: float = 0.01  # load-balancing loss
    router_z_loss_coef: float = 0.001
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16           # compute dtype
    param_dtype: Any = jnp.float32      # master parameters
    use_flash_attention: bool = False   # Pallas flash-attention kernel


def olmoe_1b_7b(n_layer=16, **kw):
    """allenai/OLMoE-1B-7B at its published widths; ``n_layer`` of its
    16 layers (all alike)."""
    return OlmoeConfig(num_hidden_layers=n_layer, **kw)


def olmoe_tiny(**kw):
    """Test-size model."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 32)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_experts", 8)
    kw.setdefault("num_experts_per_tok", 2)
    kw.setdefault("max_position_embeddings", 64)
    return OlmoeConfig(**kw)


def rotary(x, theta):
    """Rotary embedding of ``x`` ``[B, T, H, D]`` at positions 0..T-1,
    the rotate-half convention, angles in float32."""
    T, D = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :D // 2], x32[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class RotaryAttention(nn.Module):
    """Causal attention with QK-norm (an RMSNorm over the whole
    hidden-wide q and k, before the split into heads) and rotary
    positions; no bias."""
    config: OlmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, C = x.shape
        H = cfg.num_attention_heads

        def proj(name, y):
            w = self.param(name, normal(cfg), (C, C), cfg.param_dtype)
            return jnp.dot(y, w.astype(cfg.dtype))

        with jax.named_scope("ds_attn_qkv"):
            q = RMSNorm(cfg, name="q_norm")(proj("q_proj", x))
            k = RMSNorm(cfg, name="k_norm")(proj("k_proj", x))
            v = proj("v_proj", x)
            q, k, v = (a.reshape(B, T, H, C // H) for a in (q, k, v))
            q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
        with jax.named_scope("ds_attn_train"):
            if cfg.use_flash_attention:
                from deepspeed_tpu.ops.pallas import flash_attention
                y = flash_attention(q, k, v, causal=True)
            else:
                from deepspeed_tpu.ops.pallas.flash_attention import (
                    dense_attention)
                y = dense_attention(q, k, v, causal=True)
        with jax.named_scope("ds_attn_out"):
            return proj("o_proj", y.reshape(B, T, C))


class SparseExperts(nn.Module):
    """``num_experts`` gated SiLU experts, each token through its
    ``num_experts_per_tok`` most probable, none dropped. Returns
    ``(y, stats)`` (`moe/dropless.py:dropless_moe`)."""
    config: OlmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, C = x.shape
        E, I = cfg.num_experts, cfg.intermediate_size
        init = normal(cfg)
        router = self.param("router", init, (C, E), cfg.param_dtype)
        w_gate = self.param("w_gate", init, (E, C, I), cfg.param_dtype)
        w_up = self.param("w_up", init, (E, C, I), cfg.param_dtype)
        w_down = self.param("w_down", init, (E, I, C), cfg.param_dtype)
        y, stats = dropless_moe(x.reshape(B * T, C), router, w_gate, w_up,
                                w_down, cfg.num_experts_per_tok)
        return y.reshape(B, T, C), stats


class DecoderLayer(nn.Module):
    """Pre-norm residual layer: attention, then the feed-forward part.
    Returns ``(x, stats of the feed-forward part)``."""
    config: OlmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        # each norm under the scope of what it feeds, each residual add
        # under that of what it follows (`telemetry/scopes.py`)
        with jax.named_scope("ds_attn_qkv"):
            n = RMSNorm(cfg, name="input_norm")(x)
        y = RotaryAttention(cfg, name="attn")(n)
        with jax.named_scope("ds_attn_out"):
            x = x + y
        with jax.named_scope("ds_experts"):
            y, stats = SparseExperts(cfg, name="experts")(
                RMSNorm(cfg, name="post_attn_norm")(x))
            return x + y, stats


class OlmoeLM(nn.Module):
    """Decoder-only LM with an untied head. Returns ``(logits, stats)``:
    ``stats`` the layers' router statistics stacked on a leading axis."""
    config: OlmoeConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        init = normal(cfg)
        embed = self.param("embed", init, (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with jax.named_scope("ds_embed"):
            x = embed.astype(cfg.dtype)[input_ids]
        stats = []
        for i in range(cfg.num_hidden_layers):
            x, s = DecoderLayer(cfg, name=f"layers_{i}")(x)
            stats.append(s)
        with jax.named_scope("ds_head"):
            x = RMSNorm(cfg, name="final_norm")(x)
            head = self.param("lm_head", init,
                              (cfg.hidden_size, cfg.vocab_size),
                              cfg.param_dtype)
            logits = jnp.dot(x, head.astype(cfg.dtype))
        return logits, jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                              *stats)


def router_losses(stats, cfg):
    """(load-balancing loss, z-loss) from the layers' stacked router
    statistics, as the published code computes them over all layers'
    tokens laid end to end: ``num_experts * sum_e f_e P_e`` with ``f_e``
    the pairs sent to expert e over the number of tokens and ``P_e`` its
    mean probability; the mean of ``logsumexp(router logits)^2``."""
    counts = stats["tokens_per_expert"].astype(jnp.float32)    # [L, E]
    tokens = counts.sum() / cfg.num_experts_per_tok             # L * N
    f = counts.sum(0) / tokens
    p = stats["prob_sum"].sum(0) / tokens
    return cfg.num_experts * jnp.sum(f * p), stats["z_sum"].sum() / tokens


def make_olmoe_loss_fn(model: OlmoeLM):
    """``loss_fn(params, batch, rng) -> (loss, scalars)`` for the
    engine: next-token cross entropy plus the two router losses at the
    configuration's coefficients; ``scalars`` are the step's counters.

    ``batch`` is a dict with ``input_ids`` [B, T] (labels default to the
    next-token shift) or explicit ``labels``."""
    cfg = model.config

    def loss_fn(params, batch, rng=None):
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = next_token_labels(input_ids)
        logits, stats = model.apply({"params": params}, input_ids)
        with jax.named_scope("ds_loss"):
            ce = cross_entropy_loss(logits, labels)
            lb, z = router_losses(stats, cfg)
            loss = ce + cfg.router_aux_loss_coef * lb + \
                cfg.router_z_loss_coef * z
            counts = stats["tokens_per_expert"]
            scalars = {
                "moe_ce_loss": ce,
                "moe_lb_loss": lb,
                "moe_z_loss": z,
                "moe_tokens_per_expert_max": counts.max(),
                "moe_tokens_per_expert_min": counts.min(),
                "moe_dropped_tokens": stats["dropped"].sum(),
            }
            return loss, jax.lax.stop_gradient(scalars)

    return loss_fn


def init_olmoe_params(model: OlmoeLM, rng, seq_len=8):
    return model.init({"params": rng},
                      jnp.zeros((1, seq_len), jnp.int32))["params"]


def olmoe_partition_specs(params):
    """Data parallelism only: every parameter replicated, each chip
    routing its own batch rows (`moe/dropless.py` wraps itself in a
    ``shard_map`` over ``data``; compiled for four chips in
    `tests/unit/test_tpu_compile.py`).
    (Expert parallelism shards the three banks' leading axis over
    ``expert``; nothing runs it yet.)"""
    return jax.tree_util.tree_map(lambda _: P(), params)

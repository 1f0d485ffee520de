"""Laguna: a decoder of window-attention layers beside full-attention
layers (three to one) over the same key heads, a kind of layer with
query heads of its own, a sigmoid gate a query head, two rotary
schemes, the first layer's feed-forward dense and every later one a
layer of softmax-routed experts beside a shared expert; served as one
chip's share of an expert-parallel stage.

What no other model here has: **query heads by layer**
(``num_attention_heads_per_layer``: 48 in a full layer, 72 in a window
layer, both over 8 key heads of 128, so 6 and 9 queries a key head
through the one decode kernel); **a gate a head** (``gating:
per-head``): ``sigmoid(x W_g)`` ``[heads]`` of the layer's normed input
times a head's attention output, before the output projection; **two
rotary schemes** (``rope_parameters``): a full layer turns the first
half of a head under YaRN (`models/mla_moe.py:yarn_inv_freq`'s blend;
``cos`` and ``sin`` times ``attention_factor``, given outright), a
window layer all of it at base 1e4, unscaled
(`models/qwen3_next.py:partial_rotary`); **a window with no sink**
(`inference/cache.py:PageGroup`: the two groups are alike in heads and
widths and differ by window alone; a ring of ``sliding_window //
page_size + 1`` pages a row).

Layer equations, from the published keys (``config.json`` of
``poolside/Laguna-S-2.1``, ``model_type: laguna``). ``norm(x) = x
rsqrt(mean(x^2) + rms_norm_eps) w``, no bias in any product, no
QK-norm, untied head. Layer ``i``: ``h = h + attn_i(norm(h))``; ``h = h
+ ffn_i(norm(h))``.

- ``attn_i``, kind ``layer_types[i]``, ``Hq =
  num_attention_heads_per_layer[i]``: ``q = x W_q`` ``[Hq, head_dim]``,
  ``k``, ``v`` ``[num_key_value_heads, head_dim]``; query head ``h``
  reads key head ``h // (Hq / num_key_value_heads)``; scores times
  ``head_dim^-0.5``; causal, and in a ``sliding_attention`` layer ``j``
  is seen iff ``0 <= t - j < sliding_window``; rotary (rotate-half) on
  the first ``int(partial_rotary_factor x head_dim)`` entries by the
  kind's ``rope_parameters``; ``g = sigmoid(x W_g)``, head ``h``'s
  output times ``g_h``; ``W_o`` from ``Hq x head_dim``.
- ``mlp_layer_types[i]`` ``dense``: SwiGLU at ``intermediate_size``.
  ``sparse``: ``p = softmax(x W_r)`` over ``num_experts`` float32, the
  ``num_experts_per_tok`` largest, ``p`` over their sum
  (``norm_topk_prob``) times ``moe_routed_scaling_factor``
  (`moe/dropless.py:softmax_top_k_scaled`); expert ``e`` is ``W_d[e]
  (silu(W_g[e] x) * W_u[e] x)`` at ``moe_intermediate_size``; plus one
  shared SwiGLU at ``shared_expert_intermediate_size``, ungated.

**The share** (as `models/mimo_v2.py`): ``experts_held = (first,
count)`` of the router's ``num_experts`` are held, routing runs over all
of them and pairs of experts held elsewhere add nothing here; the first
``vocab_size`` rows of embedding and head are held. Attention, gates,
router, shared expert and norms are whole. Nothing stands in for the
other chips.

Precision, part of the configuration: weights, activations and both
pools in ``dtype`` (bfloat16 as published); products accumulate in
float32; norm statistics, rotary angles, scores, softmax and its sums,
the gate's product and sigmoid float32; the router's product (at the
highest precision), softmax, choice and weights float32.
`benchmarks/suite/reference/laguna_ref.py` is the plain float32
statement of the same mathematics. Serving only.
"""

import collections
import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.blocks import (GatedMLP, RMSNorm, ServedLM,
                                         expert_counters, head_logits,
                                         init_served_params,
                                         last_token, normal, param,
                                         partial_rotary, rotate,
                                         summed_counters, token_mask,
                                         yarn_inv_freq)
from deepspeed_tpu.moe.dropless import dropless_moe, softmax_top_k_scaled

FULL, WINDOW = "full", "window"
# the published names of the two kinds (``layer_types``)
KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}
# what a decode step's span carries of the expert layers
# (`inference/engine.py` reads the names): sums over the layers, but
# the most pairs one held expert of one layer took
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_rows_visited", "moe_experts_held", "moe_pairs_max")
# the published patterns: a full layer of 48 heads, then three window
# layers of 72
TYPES_48 = ("full_attention",) + ("sliding_attention",) * 3
ROPE_S_2_1 = (
    ("full_attention", (
        ("rope_theta", 500000.0), ("rope_type", "yarn"), ("factor", 128.0),
        ("original_max_position_embeddings", 8192), ("beta_slow", 1.0),
        ("beta_fast", 32.0), ("attention_factor", 1.4852030263919618),
        ("partial_rotary_factor", 0.5))),
    ("sliding_attention", (
        ("rope_type", "default"), ("rope_theta", 10000.0),
        ("partial_rotary_factor", 1.0))))

# one kind of attention layer: its heads, widths, window and rotary
# scheme (``rope``: the kind's ``rope_parameters`` as pairs)
Kind = collections.namedtuple(
    "Kind", "heads kv_heads head_dim window rotary_dim rope_theta rope")


class LagunaUnsupported(ValueError):
    """A published key asks for what `models/laguna.py` does not
    build."""


def _pairs(tree):
    """``rope_parameters`` as nested pairs: a frozen configuration's
    fields are hashable."""
    if isinstance(tree, dict):
        tree = tree.items()
    return tuple((k, _pairs(v) if isinstance(v, (dict, tuple, list)) else v)
                 for k, v in tree)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The published ``config.json`` keys under their published names,
    the share that is held, and how it is run."""
    vocab_size: int = 100352            # rows held of embedding and head
    hidden_size: int = 3072
    intermediate_size: int = 12288      # the dense layers' MLP
    num_hidden_layers: int = 48
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 1048576
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024   # one expert
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    moe_apply_router_weight_on_input: bool = False
    moe_router_logit_softcapping: float = 0
    gating: str = "per-head"
    sliding_window: int = 512
    rope_parameters: Tuple = ROPE_S_2_1
    layer_types: Tuple[str, ...] = TYPES_48 * 12
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 47
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * 12
    initializer_range: float = 0.02
    experts_held: Tuple[int, int] = (0, 256)    # (first, count)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "rope_parameters",
                           _pairs(self.rope_parameters))
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and
                first + count <= self.num_experts):
            raise ValueError(
                f"experts_held {self.experts_held} must lie within the "
                f"{self.num_experts} routed experts")
        if self.gating != "per-head":
            raise LagunaUnsupported(
                f"a sigmoid gate a query head only (gating {self.gating!r})")
        if self.attention_bias or self.moe_router_logit_softcapping or \
                self.moe_apply_router_weight_on_input:
            raise LagunaUnsupported(
                "no bias in the attention's products, no cap on the "
                "router's logits, the router's weight on an expert's "
                "output")
        n = self.num_hidden_layers
        if min(len(self.layer_types), len(self.mlp_layer_types),
               len(self.num_attention_heads_per_layer)) < n:
            raise ValueError(
                f"layer_types, mlp_layer_types and "
                f"num_attention_heads_per_layer say what each of the {n} "
                f"layers is")
        if set(self.layer_types[:n]) - set(KINDS) or \
                set(self.mlp_layer_types[:n]) - {"dense", "sparse"}:
            raise LagunaUnsupported(
                f"layers of {sorted(KINDS)} with a dense or a sparse "
                f"feed-forward (got {self.layer_types[:n]}, "
                f"{self.mlp_layer_types[:n]})")
        for which in (FULL, WINDOW):
            heads = {h for h, t in zip(self.num_attention_heads_per_layer,
                                       self.layer_types[:n])
                     if KINDS[t] == which}
            if len(heads) > 1:
                raise LagunaUnsupported(
                    f"the {which} layers are alike in query heads: they "
                    f"share a group of page layers and a kernel (got "
                    f"{sorted(heads)})")
            kind = self.kind(which)
            if kind.heads % kind.kv_heads or kind.rotary_dim % 2 or \
                    kind.rope["rope_type"] not in ("yarn", "default"):
                raise ValueError(
                    f"key heads divide query heads, the rotary part of a "
                    f"head is even, rotary is plain or YaRN: {kind}")

    def kind(self, which):
        """The numbers of a full or a window layer."""
        n = self.num_hidden_layers
        heads = [h for h, t in zip(self.num_attention_heads_per_layer,
                                   self.layer_types[:n]) if KINDS[t] == which]
        rope = {KINDS[k]: dict(v) for k, v in self.rope_parameters}[which]
        return Kind(heads[0] if heads else self.num_attention_heads,
                    self.num_key_value_heads, self.head_dim,
                    self.sliding_window if which == WINDOW else 0,
                    int(rope["partial_rotary_factor"] * self.head_dim),
                    float(rope["rope_theta"]), rope)

    @property
    def layer_kinds(self):
        return tuple(KINDS[t] for t in
                     self.layer_types[:self.num_hidden_layers])

    def is_dense(self, i):
        return self.mlp_layer_types[i] == "dense"

    def names(self, which):
        return tuple(f"layers_{i}" for i, k in enumerate(self.layer_kinds)
                     if k == which)

    def cache_spec(self, max_batch, max_seq, kv_cache_dtype=None,
                   page_size=0, n_pages=0):
        """Two groups of page layers alike in heads and widths: the full
        layers (every page of a row) and the window layers (a ring a
        row)."""
        from deepspeed_tpu.inference.cache import page_pool_spec
        H, D = self.num_key_value_heads, self.head_dim
        groups = [(which, self.names(which), H, D, D, self.kind(which).window)
                  for which in (FULL, WINDOW) if self.names(which)]
        return page_pool_spec(
            max_batch, max_seq, n_layer=self.num_hidden_layers, n_head=H,
            head_dim=D, compute_dtype=self.dtype,
            n_positions=self.max_position_embeddings,
            kv_cache_dtype=kv_cache_dtype, page_size=page_size,
            n_pages=n_pages, groups=groups)


def laguna_s_2_1_share(n_layer=8, experts_held=(0, 32), vocab_size=12544,
                       **kw):
    """poolside/Laguna-S-2.1 at its published widths, as one chip of the
    8 that share each layer of a pipeline stage holds it: the first
    ``n_layer`` of the 48 layers (eight are the dense layer and two
    whole periods less it, ``F | W W W F W W W``), 32 of the 256
    experts, an eighth of the vocabulary's rows."""
    return LagunaConfig(num_hidden_layers=n_layer,
                        experts_held=tuple(experts_held),
                        vocab_size=vocab_size, **kw)


def laguna_tiny(**kw):
    """Test-size model: ``F | W W W F W``, a dense layer and five expert
    layers, 4 of 16 experts held, top 3; two key heads of 16, 12 query
    heads in a full layer and 18 in a window layer (6 and 9 a key head,
    as published), a window of 16, YaRN by 8 over 16 positions on half a
    head."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 96)
    kw.setdefault("moe_intermediate_size", 32)
    kw.setdefault("shared_expert_intermediate_size", 32)
    kw.setdefault("num_hidden_layers", 6)
    kw.setdefault("layer_types", (TYPES_48 * 2)[:6])
    kw.setdefault("mlp_layer_types", ("dense",) + ("sparse",) * 5)
    kw.setdefault("num_attention_heads", 12)
    kw.setdefault("num_attention_heads_per_layer", (12, 18, 18, 18, 12, 18))
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("sliding_window", 16)
    kw.setdefault("rope_parameters", (
        ("full_attention", (
            ("rope_theta", 100.0), ("rope_type", "yarn"), ("factor", 8.0),
            ("original_max_position_embeddings", 16), ("beta_slow", 0.25),
            ("beta_fast", 2.0), ("attention_factor", 1.2079441541679836),
            ("partial_rotary_factor", 0.5))),
        ("sliding_attention", (
            ("rope_type", "default"), ("rope_theta", 10000.0),
            ("partial_rotary_factor", 1.0)))))
    kw.setdefault("num_experts", 16)
    kw.setdefault("num_experts_per_tok", 3)
    kw.setdefault("experts_held", (4, 4))
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("initializer_range", 0.1)
    return LagunaConfig(**kw)


def rotary(x, positions, kind):
    """A kind's rotary embedding (rotate-half) of the first
    ``rotary_dim`` entries of each head of ``x`` ``[B, T, H, D]`` at
    ``positions`` ``[B, T]``; the rest pass. ``default``: the plain
    frequencies at the kind's base. ``yarn``: each a blend of itself and
    itself over ``factor`` (`models/blocks.py:yarn_inv_freq`), ``cos``
    and ``sin`` times ``attention_factor``. Angles in float32."""
    if kind.rope["rope_type"] == "default":
        return partial_rotary(x, positions, kind)
    r = kind.rotary_dim
    inv = jnp.asarray(yarn_inv_freq(r, kind.rope_theta, kind.rope),
                      jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv     # [B, T, r/2]
    m = kind.rope["attention_factor"]
    cos, sin = (jnp.cos(ang) * m)[:, :, None], (jnp.sin(ang) * m)[:, :, None]
    return jnp.concatenate([rotate(x[..., :r], cos, sin), x[..., r:]], -1)


class LagunaAttention(nn.Module):
    """Causal grouped-query attention of one kind through its group's
    pages (a full layer over every page of the row, a window layer over
    its ring), a sigmoid gate a query head on its output."""
    config: LagunaConfig
    which: str

    @nn.compact
    def __call__(self, x, layer_cache, positions, page_table, n_valid,
                 attn):
        from deepspeed_tpu.inference.cache import cached_attention
        cfg = self.config
        kind = cfg.kind(self.which)
        B, T, C = x.shape
        Hq, H, D = kind.heads, kind.kv_heads, kind.head_dim
        with jax.named_scope("ds_attn_qkv"):
            q = jnp.dot(x, param(self, "q_proj", cfg, (C, Hq * D)))
            k = jnp.dot(x, param(self, "k_proj", cfg, (C, H * D)))
            v = jnp.dot(x, param(self, "v_proj", cfg, (C, H * D)))
            q = rotary(q.reshape(B, T, Hq, D), positions, kind)
            k = rotary(k.reshape(B, T, H, D), positions, kind)
        y, layer_cache = cached_attention(
            q, k, v.reshape(B, T, H, D), layer_cache, positions, cfg.dtype,
            page_table, scale=D ** -0.5, window=kind.window, n_valid=n_valid,
            walk=True, **attn)
        with jax.named_scope("ds_attn_gate"):
            gate = jax.nn.sigmoid(jnp.dot(
                x, param(self, "g_proj", cfg, (C, Hq)),
                preferred_element_type=jnp.float32))        # [B, T, Hq]
            y = (y.astype(jnp.float32) * gate[..., None]).astype(cfg.dtype)
        with jax.named_scope("ds_attn_out"):
            y = jnp.dot(y.reshape(B, T, Hq * D),
                        param(self, "o_proj", cfg, (Hq * D, C)))
        return y, layer_cache


# jitted, so that the expert layers share one trace of the routing and
# of the three grouped matmuls (as `models/mla_moe.py`'s)
@functools.partial(jax.jit, static_argnames=(
    "top_k", "scaling", "renormalise", "first_expert"))
def _held_experts(x, mask, router, w_gate, w_up, w_down, *, top_k, scaling,
                  renormalise, first_expert):
    y, stats = dropless_moe(
        x, router, w_gate, w_up, w_down, top_k,
        route=softmax_top_k_scaled(scaling, renormalise),
        first_expert=first_expert, token_mask=mask)
    return y, expert_counters(mask, top_k, stats)


class SparseExperts(nn.Module):
    """The routed experts this chip holds and the shared expert,
    ungated. Returns ``(y, the layer's `blocks.ExpertCounters`)``;
    ``mask`` ``[B, T]`` says which tokens are real."""
    config: LagunaConfig

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
            w_down, top_k=cfg.num_experts_per_tok,
            scaling=float(cfg.moe_routed_scaling_factor),
            renormalise=cfg.norm_topk_prob, first_expert=first)
        with jax.named_scope("ds_moe_shared"):
            hidden = jax.nn.silu(
                jnp.dot(x, param(self, "shared_gate", cfg, (C, S)))) * \
                jnp.dot(x, param(self, "shared_up", cfg, (C, S)))
            shared = jnp.dot(hidden, param(self, "shared_down", cfg,
                                            (S, C)))
        return y.reshape(B, T, C) + shared, counters


class LagunaLayer(nn.Module):
    """Pre-norm residual layer: attention of its kind, then the dense
    MLP or the experts."""
    config: LagunaConfig
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
        y, layer_cache = LagunaAttention(cfg, self.which, name="attn")(
            n, layer_cache, positions, page_table, n_valid, attn)
        with jax.named_scope("ds_attn_out"):
            h = h + y
        with jax.named_scope("ds_mlp" if self.dense else "ds_experts"):
            n = RMSNorm(cfg, name="post_attn_norm")(h)
            if self.dense:
                y = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(n)
                counters = None
            else:
                y, counters = SparseExperts(cfg, name="experts")(n, mask)
            return h + y, layer_cache, counters


class LagunaLM(ServedLM, nn.Module):
    """The decoder with its untied head, through the serving cache.
    Returns ``(logits [B, vocab_size] float32 at each row's last real
    token, the cache, the counters of `COUNTERS`)``."""
    config: LagunaConfig
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
            h, new_cache[name], c = LagunaLayer(
                cfg, which, cfg.is_dense(i), name=name)(
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
        with jax.named_scope("ds_sample"):
            counters = summed_counters(
                COUNTERS, counted, moe_experts_held=jnp.int32(
                    cfg.experts_held[1] * len(counted)))
        return logits, new_cache, counters

    @nn.nowrap
    def serve_args(self, cache, tokens, positions, page_table, slots,
                   n_valid, attn_impl, attn_block_k, attn_mesh):
        del slots       # pages are the cache: a row's slot owns nothing
        if attn_mesh is not None:
            raise LagunaUnsupported("a 'model' mesh axis is not built")
        return (tokens, cache, positions, page_table, n_valid,
                {"impl": attn_impl, "block_k": attn_block_k})


# the matrices that write to the stream: out of an attention, a dense
# MLP and the shared expert, and the experts' third banks
_WRITERS = {"o_proj": 0, "w_out": 0, "shared_down": 0, "w_down": 1}


def init_laguna_params(model, rng):
    """The model's weights from ``rng``, the writers centred
    (`blocks.init_served_params`), over a page that holds a window."""
    return init_served_params(model, rng, _WRITERS,
                              page=max(model.config.sliding_window, 8))

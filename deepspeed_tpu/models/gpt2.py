"""GPT-2 model family, TPU-first.

The reference ships no in-tree GPT-2 (its perf harness drives Megatron-GPT2
externally, `tests/model/Megatron_GPT2/run_perf_baseline.py:18-60`); this
module provides the equivalent flagship decoder for the framework's
benchmarks: sizes matching the reference perf configs (125M … 1.5B),
bf16 compute over fp32 masters, optional rematerialization, and
Megatron-style tensor-parallel PartitionSpecs over the ``model`` mesh axis.
"""

import dataclasses
import functools
import re
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from flax.traverse_util import flatten_dict, unflatten_dict
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.fp8 import fp8_dot_general


def _fp8_dot(site):
    """Per-site ``dot_general`` hook: plain ``lax.dot_general`` unless an
    ``fp8_scope`` is active at trace time (the head matmul and attention
    einsums stay full precision — the standard fp8 recipe)."""
    return functools.partial(fp8_dot_general, site=site)


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16        # compute dtype (MXU-native)
    param_dtype: Any = jnp.float32   # master param dtype
    remat: bool = False              # activation checkpointing per block
    remat_policy: str = "full"       # "full" | "dots" | "nothing":
    #                                  full = save only block inputs;
    #                                  dots = save matmul outputs
    #                                  (jax.checkpoint_policies.
    #                                  checkpoint_dots) — recompute just
    #                                  the elementwise/softmax tails, the
    #                                  usual best trade on TPU where bwd
    #                                  is HBM-bound; nothing = save all
    #                                  (policy-form of remat=False)
    use_flash_attention: bool = False  # Pallas flash-attention kernel
    loss_chunk: int = 0              # >0: chunked cross-entropy over the
    #                                  vocab head (never materializes the
    #                                  [B, T, vocab] logits in HBM)
    scan_layers: bool = False        # stack the Blocks into one lax.scan
    #                                  over layer-stacked params: the HLO
    #                                  carries ONE block body instead of
    #                                  n_layer copies, collapsing trace +
    #                                  compile wall and HLO size (the
    #                                  autotuner's inner loop is a
    #                                  compile, so this pays per
    #                                  candidate). Params live under "h"
    #                                  with a leading layer axis; see
    #                                  stack_gpt2_layer_params /
    #                                  unstack_gpt2_layer_params for
    #                                  checkpoint conversion.

    def cache_spec(self, max_batch, max_seq, kv_cache_dtype=None,
                   page_size=0, n_pages=0):
        """The serving cache this model needs (the engine's protocol,
        `inference/engine.py`): one page pool a layer, every head."""
        from deepspeed_tpu.inference.cache import page_pool_spec
        return page_pool_spec(
            max_batch, max_seq, n_layer=self.n_layer, n_head=self.n_head,
            head_dim=self.n_embd // self.n_head, compute_dtype=self.dtype,
            n_positions=self.n_positions, stacked=self.scan_layers,
            kv_cache_dtype=kv_cache_dtype, page_size=page_size,
            n_pages=n_pages)


# Sizes follow the reference perf-harness configs
# (`tests/model/Megatron_GPT2/run_perf_baseline.py:18-60`).
def gpt2_125m(**kw):
    return GPT2Config(n_embd=768, n_layer=12, n_head=12, **kw)


def gpt2_350m(**kw):
    return GPT2Config(n_embd=1024, n_layer=24, n_head=16, **kw)


def gpt2_760m(**kw):
    return GPT2Config(n_embd=1536, n_layer=24, n_head=16, **kw)


def gpt2_1_5b(**kw):
    return GPT2Config(n_embd=1600, n_layer=48, n_head=25, **kw)


# Capacity-ladder sizes past the reference perf configs (GPT-3 paper
# shapes): used by BENCH_MODEL=capacity to answer "max trainable on one
# 16 GB v5e via ZeRO-Offload" — the proportional analog of the
# reference's 13B-on-one-32GB-V100 claim
# (`docs/_tutorials/zero-offload.md:9`).
def gpt2_2_7b(**kw):
    return GPT2Config(n_embd=2560, n_layer=32, n_head=32, **kw)


def gpt2_4b(**kw):
    return GPT2Config(n_embd=3072, n_layer=36, n_head=24, **kw)


def gpt2_tiny(**kw):
    """Test-size model (the `SimpleModel` analog for LM tests)."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("n_positions", 64)
    kw.setdefault("n_embd", 64)
    kw.setdefault("n_layer", 2)
    kw.setdefault("n_head", 4)
    return GPT2Config(**kw)


class CausalSelfAttention(nn.Module):
    """Causal attention; also the incremental-decode write/attend site.

    ``kv_cache`` (a layer's ``{"k", "v"(, scales)}`` pool leaves from
    `inference/cache.py`, addressed through ``kv_page_table``) switches
    to the cached path: this call's k/v are written at explicit
    ``positions`` and attention runs over the whole cache row under a
    position mask — the call then returns ``(y, updated_cache)``. With
    ``kv_cache=None`` the training path is untouched (same modules, same
    trace), so train and serve share every parameter."""
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True, positions=None,
                 kv_cache=None, attn_impl="dense", attn_block_k=128,
                 attn_mesh=None, attn_mask=None, kv_page_table=None):
        cfg = self.config
        B, T, C = x.shape
        H = cfg.n_head
        with jax.named_scope("ds_attn_qkv"):
            qkv = nn.Dense(3 * C, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype,
                           dot_general=_fp8_dot("c_attn"), name="c_attn")(x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, T, H, C // H)
            k = k.reshape(B, T, H, C // H)
            v = v.reshape(B, T, H, C // H)

        new_cache = None
        if kv_cache is not None:
            from deepspeed_tpu.inference.cache import cached_attention
            y, new_cache = cached_attention(q, k, v, kv_cache, positions,
                                            compute_dtype=cfg.dtype,
                                            impl=attn_impl,
                                            block_k=attn_block_k,
                                            mesh=attn_mesh,
                                            mask=attn_mask,
                                            page_table=kv_page_table)
        elif cfg.use_flash_attention:
            from deepspeed_tpu.ops.pallas import flash_attention
            # Attention-prob dropout runs inside the kernels (counter-based
            # mask regenerated in the backward), so the flash path stays on
            # in training configs — the round-3 gate that forced dense
            # attention whenever dropout was active is gone.
            rate, seed = 0.0, None
            with jax.named_scope("ds_attn_train"):
                if not deterministic and cfg.dropout > 0.0:
                    from deepspeed_tpu.ops.pallas.flash_attention import (
                        dropout_seed_from_rng)
                    rate = cfg.dropout
                    seed = dropout_seed_from_rng(self.make_rng("dropout"))
                y = flash_attention(q, k, v, causal=True,
                                    dropout_rate=rate, dropout_seed=seed)
        else:
            with jax.named_scope("ds_attn_train"):
                scale = 1.0 / jnp.sqrt(jnp.asarray(C // H, cfg.dtype))
                att = jnp.einsum("bthd,bshd->bhts", q, k) * scale
                mask = jnp.tril(jnp.ones((T, T), bool))
                att = jnp.where(mask[None, None], att,
                                jnp.finfo(att.dtype).min)
                att = jax.nn.softmax(att.astype(jnp.float32),
                                     axis=-1).astype(cfg.dtype)
                att = nn.Dropout(cfg.dropout)(att,
                                              deterministic=deterministic)
                y = jnp.einsum("bhts,bshd->bthd", att, v)
        with jax.named_scope("ds_attn_out"):
            y = y.reshape(B, T, C)
            y = nn.Dense(C, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         dot_general=_fp8_dot("c_proj"), name="c_proj")(y)
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        if kv_cache is not None:
            return y, new_cache
        return y


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        C = x.shape[-1]
        h = nn.Dense(4 * C, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     dot_general=_fp8_dot("c_fc"), name="c_fc")(x)
        h = nn.gelu(h, approximate=True)
        h = nn.Dense(C, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     dot_general=_fp8_dot("c_proj"), name="c_proj")(h)
        h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    """Transformer block, optionally with progressive layer drop.

    PLD (reference `runtime/progressive_layer_drop.py:5` + the engine's
    per-forward theta kwarg injection, reference engine.py:791-792): when
    ``pld_theta`` is given and training, each sublayer executes with
    probability ``1 - (l/L)(1 - theta)`` (deeper layers dropped more, the
    paper's depth schedule). The skip is a ``lax.cond``, so a dropped
    sublayer costs nothing at runtime on TPU — the paper's compute saving,
    not just its regularization."""
    config: GPT2Config
    layer_idx: int = 0
    n_layers: int = 1

    @nn.compact
    def __call__(self, x, deterministic=True, pld_theta=None,
                 layer_idx=None, positions=None, kv_cache=None,
                 attn_impl="dense", attn_block_k=128, attn_mesh=None,
                 attn_mask=None, kv_page_table=None):
        cfg = self.config
        attn = CausalSelfAttention(cfg, name="attn")
        mlp = MLP(cfg, name="mlp")
        ln1 = nn.LayerNorm(dtype=cfg.dtype, name="ln_1")
        ln2 = nn.LayerNorm(dtype=cfg.dtype, name="ln_2")

        # the two halves under their scopes (`telemetry/scopes.py`): the
        # norm with the projections it feeds, the residual add with the
        # projection it follows; ``gate`` is PLD's multiplier
        def attend(h, gate=None, **cached):
            with jax.named_scope("ds_attn_qkv"):
                n = ln1(h)
            out = attn(n, deterministic, **cached)
            a, new_cache = out if cached else (out, None)
            with jax.named_scope("ds_attn_out"):
                h = h + (a if gate is None else gate * a)
            return (h, new_cache) if cached else h

        def feed(h, gate=None):
            with jax.named_scope("ds_mlp"):
                m = mlp(ln2(h), deterministic)
                return h + (m if gate is None else gate * m)

        if kv_cache is not None:
            # incremental decode: PLD never applies (serving is
            # deterministic), and the attention call also returns the
            # layer's updated cache.
            x, new_cache = attend(
                x, positions=positions, kv_cache=kv_cache,
                attn_impl=attn_impl, attn_block_k=attn_block_k,
                attn_mesh=attn_mesh, attn_mask=attn_mask,
                kv_page_table=kv_page_table)
            return feed(x), new_cache

        if pld_theta is None or deterministic:
            return feed(attend(x))

        # ``layer_idx`` as a call arg overrides the attribute so the
        # scan_layers path can feed the (traced) loop counter into the
        # PLD depth schedule.
        idx = self.layer_idx if layer_idx is None else layer_idx
        keep_p = 1.0 - (idx + 1) / self.n_layers * \
            (1.0 - pld_theta)
        coin_a = jax.random.bernoulli(self.make_rng("pld"), keep_p)
        coin_m = jax.random.bernoulli(self.make_rng("pld"), keep_p)
        if cfg.scan_layers:
            # flax can't build submodules inside lax.cond branches under
            # the lifted scan trace, so the skip degrades to a
            # multiplicative gate: same dropped-layer values, but the
            # sublayer compute always runs (PLD's FLOP saving is the one
            # thing scan_layers gives up).
            x = attend(x, jnp.where(coin_a, 1, 0).astype(x.dtype))
            return feed(x, jnp.where(coin_m, 1, 0).astype(x.dtype))
        x = jax.lax.cond(coin_a, attend, lambda h: h, x)
        return jax.lax.cond(coin_m, feed, lambda h: h, x)


class GPT2LMHead(nn.Module):
    """Decoder-only LM with tied embedding / output head."""
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, deterministic=True, pld_theta=None,
                 return_hidden=False, positions=None, kv_cache=None,
                 attn_impl="dense", attn_block_k=128, attn_mesh=None,
                 kv_page_table=None, truncate_layers=None):
        cfg = self.config
        B, T = input_ids.shape
        # Early-exit truncation (speculative draft): run only the first
        # ``truncate_layers`` blocks, then the usual ln_f + tied head.
        # Decode-only — the caller must slice the stacked params/cache
        # leaves to [:truncate_layers] under scan_layers (nn.scan splits
        # params along axis 0, so the leading axis must equal the scan
        # length); unrolled trees pass whole and only h_0..h_{L-1} run.
        n_run = cfg.n_layer if truncate_layers is None \
            else int(truncate_layers)
        if not 0 < n_run <= cfg.n_layer:
            raise ValueError(
                f"truncate_layers {truncate_layers} outside "
                f"1..{cfg.n_layer}")
        if truncate_layers is not None and kv_cache is None:
            raise ValueError("truncate_layers is a decode-path knob "
                             "(requires kv_cache)")
        if kv_cache is not None and kv_page_table is None:
            raise ValueError("kv_cache is a page pool: it is addressed "
                             "through kv_page_table")
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.n_embd), cfg.param_dtype)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.n_positions, cfg.n_embd), cfg.param_dtype)
        with jax.named_scope("ds_embed"):
            if positions is None:
                # training/full-context: positions ARE the sequence index.
                pos_emb = wpe[None, :T]
            else:
                # incremental decode: a [B, T] chunk sits at explicit
                # absolute positions (past the prefill), so the position
                # embedding is a gather, not a prefix slice.
                pos_emb = wpe[positions]
            x = wte[input_ids].astype(cfg.dtype) + pos_emb.astype(cfg.dtype)
            x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)

        block_cls = Block
        if cfg.remat:
            policies = {
                "full": None,
                "dots": jax.checkpoint_policies.checkpoint_dots,
                "nothing": jax.checkpoint_policies.everything_saveable,
            }
            if cfg.remat_policy not in policies:
                raise ValueError(
                    f"remat_policy {cfg.remat_policy!r} not in "
                    f"{sorted(policies)}")
            policy = policies[cfg.remat_policy]
            block_cls = nn.remat(Block, prevent_cse=False, policy=policy)
        new_kv = None
        attn_mask = None
        if kv_cache is not None and (attn_impl == "dense" or T > 1):
            # Hoist the dense cached-attention position mask: computed
            # once here and broadcast to every layer, instead of each
            # layer rebuilding the same [B, T, max_seq] iota-compare
            # inside the compiled decode program (the flash decode step
            # masks in-kernel from the positions scalar and needs none,
            # and where a chunk goes through its kernel nothing reads
            # this one; S comes off the page table, not off the pool's
            # shape).
            from deepspeed_tpu.inference.cache import (attention_mask,
                                                       plain_scope)
            layer0 = kv_cache["h" if cfg.scan_layers else "h_0"]
            with jax.named_scope(plain_scope(T)):
                attn_mask = attention_mask(layer0, positions,
                                           kv_page_table)
        if cfg.scan_layers and kv_cache is not None:
            # decode over the scanned stack: the per-layer cache slices
            # ride the same lax.scan as the stacked params (in_axes=0
            # over the (iota, cache) pair), and the updated slices come
            # back as the scan's stacked ys. The page table (one per
            # ROW, not per layer) broadcasts like the positions.
            def body(block, h, xs, det, pos, mask, page_table):
                idx, layer_cache = xs
                h, new_c = block(h, det, None, layer_idx=idx,
                                 positions=pos, kv_cache=layer_cache,
                                 attn_impl=attn_impl,
                                 attn_block_k=attn_block_k,
                                 attn_mesh=attn_mesh, attn_mask=mask,
                                 kv_page_table=page_table)
                return h, new_c

            scan = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True, "pld": True},
                in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast,
                         nn.broadcast),
                length=n_run)
            x, new_h = scan(block_cls(cfg, n_layers=cfg.n_layer, name="h"),
                            x, (jnp.arange(n_run), kv_cache["h"]),
                            deterministic, positions, attn_mask,
                            kv_page_table)
            new_kv = {"h": new_h}
        elif cfg.scan_layers:
            # One lax.scan over layer-stacked params instead of n_layer
            # unrolled Block copies: the lowered HLO carries a single
            # block body (trip-count-weighted by the audit), so trace and
            # compile wall stop scaling with depth. Params live under
            # "h" with a leading layer axis (variable_axes={"params": 0});
            # per-layer rngs come from split_rngs, and the PLD depth
            # schedule rides the scanned iota as the layer index.
            def body(block, h, idx, det, theta):
                return block(h, det, theta, layer_idx=idx), None

            scan = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True, "pld": True},
                in_axes=(0, nn.broadcast, nn.broadcast),
                length=cfg.n_layer)
            x, _ = scan(block_cls(cfg, n_layers=cfg.n_layer, name="h"),
                        x, jnp.arange(cfg.n_layer), deterministic,
                        pld_theta)
        elif kv_cache is not None:
            new_kv = {}
            for i in range(n_run):
                x, new_kv[f"h_{i}"] = block_cls(
                    cfg, layer_idx=i, n_layers=cfg.n_layer,
                    name=f"h_{i}")(x, deterministic, None,
                                   positions=positions,
                                   kv_cache=kv_cache[f"h_{i}"],
                                   attn_impl=attn_impl,
                                   attn_block_k=attn_block_k,
                                   attn_mesh=attn_mesh,
                                   attn_mask=attn_mask,
                                   kv_page_table=kv_page_table)
        else:
            for i in range(cfg.n_layer):
                x = block_cls(cfg, layer_idx=i, n_layers=cfg.n_layer,
                              name=f"h_{i}")(x, deterministic, pld_theta)
        with jax.named_scope("ds_head"):
            x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
            if return_hidden:
                return x    # chunked-loss path applies the head itself
            logits = x @ wte.T.astype(cfg.dtype)
        if kv_cache is not None:
            return logits, new_kv
        return logits

    # -- the serving engine's protocol (`inference/engine.py`) -------------

    @nn.nowrap
    def cache_spec(self, *args, **kwargs):
        return self.config.cache_spec(*args, **kwargs)

    @nn.nowrap
    def partition_specs(self, params):
        return gpt2_partition_specs(params)

    @nn.nowrap
    def serve_apply(self, params, cache, tokens, positions, page_table,
                    slots, n_valid, **attn):
        """``[B, T]`` tokens at explicit positions through the cache;
        returns ``(logits [B, vocab] at each row's last real token, the
        cache)``. ``n_valid`` is how many of a row's ``T`` tokens are
        real; ``slots`` (the rows' slots) matters to a model that keeps
        a state: keys and values are addressed by position and page, and
        padding is masked by position."""
        del slots
        logits, cache = self.apply(
            {"params": params}, tokens, deterministic=True,
            positions=positions, kv_cache=cache,
            kv_page_table=page_table, **attn)
        with jax.named_scope("ds_head"):
            last = jnp.maximum(n_valid - 1, 0)[:, None, None]
            return jnp.take_along_axis(logits, last, axis=1)[:, 0], cache


def cross_entropy_sum_and_count(logits, labels, ignore_index=-100):
    """(summed token cross-entropy in fp32, valid-token count) — the
    weighted-loss form exact under sharded/microbatched averaging."""
    logits = logits.astype(jnp.float32)
    mask = (labels != ignore_index)
    safe_labels = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    token_loss = -jnp.take_along_axis(logp, safe_labels[..., None],
                                      axis=-1).squeeze(-1)
    token_loss = jnp.where(mask, token_loss, 0.0)
    return token_loss.sum(), mask.sum()


def cross_entropy_loss(logits, labels, ignore_index=-100):
    """Mean token cross-entropy in fp32, masking ``ignore_index`` labels."""
    total, count = cross_entropy_sum_and_count(logits, labels, ignore_index)
    return total / jnp.maximum(count, 1)


@jax.custom_vjp
def _head_matmul(xc, head):
    """[B, c, M] x [M, V] head matmul with an fp32-accumulated head
    cotangent.

    ``head`` arrives fp32 (widened outside the scan); the forward computes
    at ``xc``'s dtype so the MXU runs the usual bf16 pass. The point is
    the backward: the per-chunk head cotangent is produced DIRECTLY in
    fp32 (``preferred_element_type`` — the MXU's native fp32 accumulator,
    no bf16 rounding of the partial), and because the head PRIMAL is fp32,
    ``lax.scan``'s constant-transpose then sums the per-chunk partials in
    fp32 too. One downcast happens at the end, in the caller's
    ``astype`` VJP — the same round-once-from-fp32 the dense head gets
    from a single big matmul (VERDICT r4 weak #5 / next-round #6)."""
    return jnp.dot(xc, head.astype(xc.dtype))


def _head_matmul_fwd(xc, head):
    return _head_matmul(xc, head), (xc, head)


def _head_matmul_bwd(res, g):
    xc, head = res
    dx = jnp.dot(g, head.astype(g.dtype).T)
    dhead = jax.lax.dot_general(
        xc, g, dimension_numbers=(((0, 1), (0, 1)), ((), ())),
        preferred_element_type=jnp.float32)
    return dx, dhead


_head_matmul.defvjp(_head_matmul_fwd, _head_matmul_bwd)


def chunked_cross_entropy_with_head(x, head, bias, labels, chunk,
                                    ignore_index=-100):
    """CE against a vocab head without materializing [B, T, V] logits.

    At GPT-2 scale the fp32 logits are the single largest activation
    (bs8 x 1024 x 50257 x 4 B ≈ 1.6 GB — the reason 760M OOMs with fp32
    masters, BENCHNOTES r2). ``lax.scan`` over sequence chunks computes
    each [B, chunk, V] logit tile, reduces it to (loss sum, count), and
    drops it; ``jax.checkpoint`` on the body recomputes the tile in the
    backward, so peak HBM is O(B * chunk * V) in both directions. The
    head matmuls stay full-width [B*chunk, M] x [M, V] — MXU-shaped.

    The head (and bias) stay fp32 across the scan so their cotangents
    accumulate in fp32 — under bf16 compute this makes chunked grads
    match the dense head's single fp32-accumulated matmul to fp32
    summation-order noise instead of the bf16 noise floor (see
    :func:`_head_matmul`).

    x: [B, T, M] final hidden states; head: [M, V]; bias: [V] or None;
    labels: [B, T].
    """
    B, T, M = x.shape
    chunk = min(chunk, T)
    n = -(-T // chunk)
    pad = n * chunk - T
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)),
                         constant_values=ignore_index)
    xc = jnp.moveaxis(x.reshape(B, n, chunk, M), 1, 0)       # [n,B,c,M]
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)     # [n,B,c]
    head = head.astype(jnp.float32)
    if bias is not None:
        bias = bias.astype(jnp.float32)

    @jax.checkpoint
    def body(carry, inp):
        s, cnt = carry
        xcb, lcb = inp
        logits = _head_matmul(xcb, head)
        if bias is not None:
            # astype inside the body: the add's transpose reduces at the
            # logit dtype per chunk (same as dense), while the cast's VJP
            # widens so the CROSS-chunk bias accumulation stays fp32.
            logits = logits + bias.astype(logits.dtype)
        ls, c = cross_entropy_sum_and_count(logits, lcb, ignore_index)
        return (s + ls, cnt + c), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0)), (xc, lc))
    return total, count


def chunked_cross_entropy_sum_and_count(x, wte, labels, chunk,
                                        ignore_index=-100):
    """Tied-head form: CE against ``wte.T`` (see
    :func:`chunked_cross_entropy_with_head`)."""
    return chunked_cross_entropy_with_head(x, wte.T, None, labels, chunk,
                                           ignore_index)


def next_token_labels(input_ids, ignore_index=-100):
    """``input_ids`` [B, T] shifted left by one; the last position has
    nothing to predict and is ignored."""
    return jnp.concatenate(
        [input_ids[:, 1:],
         jnp.full((input_ids.shape[0], 1), ignore_index, input_ids.dtype)],
        axis=1)


def make_gpt2_loss_fn(model: GPT2LMHead):
    """loss_fn(params, batch, rng) for the engine.

    ``batch`` is a dict with ``input_ids`` [B, T] (labels default to the
    next-token shift) or explicit ``labels``.
    """

    def loss_fn(params, batch, rng=None, pld_theta=None):
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = next_token_labels(input_ids)
        rngs = {}
        if rng is not None:
            d_rng, p_rng = jax.random.split(rng)
            rngs = {"dropout": d_rng, "pld": p_rng}
        chunk = model.config.loss_chunk
        if chunk:
            hidden = model.apply(
                {"params": params}, input_ids,
                deterministic=rng is None, rngs=rngs,
                pld_theta=pld_theta if rng is not None else None,
                return_hidden=True)
            with jax.named_scope("ds_loss"):
                total, count = chunked_cross_entropy_sum_and_count(
                    hidden, params["wte"], labels, chunk)
                return total / jnp.maximum(count, 1)
        logits = model.apply({"params": params}, input_ids,
                             deterministic=rng is None, rngs=rngs,
                             pld_theta=pld_theta if rng is not None else None)
        with jax.named_scope("ds_loss"):
            return cross_entropy_loss(logits, labels)

    return loss_fn


def init_gpt2_params(model: GPT2LMHead, rng, batch_size=2, seq_len=None):
    cfg = model.config
    T = seq_len or min(cfg.n_positions, 64)
    dummy = jnp.zeros((batch_size, T), jnp.int32)
    return model.init({"params": rng}, dummy)["params"]


def gpt2_partition_specs(params, model_axis="model"):
    """Megatron-style tensor-parallel PartitionSpecs over the ``model`` axis.

    The reference delegates TP to an external Megatron mpu (SURVEY §2.1); here
    TP is first-class: column-parallel QKV/FC kernels shard their output dim,
    row-parallel projections shard their input dim, embeddings shard the
    vocab dim, and GSPMD inserts the psums that Megatron hand-codes.

    ``scan_layers`` trees (stacked ``h`` subtree) get the same per-weight
    specs with a replicated leading layer axis prepended.
    """
    flat = flatten_dict(params)
    specs = {}
    for path, leaf in flat.items():
        name = "/".join(str(p) for p in path)
        ndim = getattr(leaf, "ndim", 0)
        stacked = bool(path) and str(path[0]) == "h"
        if stacked:
            ndim -= 1           # leading layer axis from scan_layers
        if ndim <= 1:
            spec = P()
        elif name.endswith("wte"):
            spec = P(model_axis, None)
        elif name.endswith("wpe"):
            spec = P()
        elif "attn/c_attn" in name and name.endswith("kernel"):
            spec = P(None, model_axis)            # column parallel
        elif "attn/c_proj" in name and name.endswith("kernel"):
            spec = P(model_axis, None)            # row parallel
        elif "mlp/c_fc" in name and name.endswith("kernel"):
            spec = P(None, model_axis)
        elif "mlp/c_proj" in name and name.endswith("kernel"):
            spec = P(model_axis, None)
        else:
            spec = P()
        if stacked:
            spec = P(None, *spec)   # layer axis is never model-sharded
        specs[path] = spec
    return unflatten_dict(specs)


# ---------------------------------------------------------------------------
# scan_layers checkpoint interop: stacked <-> per-layer param layouts
# ---------------------------------------------------------------------------

_LAYER_KEY_RE = re.compile(r"^h_(\d+)$")


def stack_gpt2_layer_params(params):
    """Unrolled tree (``h_0`` … ``h_{L-1}``) -> ``scan_layers`` layout.

    The per-layer subtrees collapse into one ``h`` subtree whose leaves
    gain a leading layer axis; everything else (wte/wpe/ln_f) passes
    through untouched. Inverse of :func:`unstack_gpt2_layer_params`;
    the round trip is bit-exact, so existing checkpoints load into
    ``scan_layers=True`` models (and back) without loss.
    """
    idxs = sorted(int(m.group(1)) for k in params
                  if (m := _LAYER_KEY_RE.match(str(k))))
    if not idxs:
        raise ValueError("no per-layer 'h_<i>' entries to stack")
    if idxs != list(range(len(idxs))):
        raise ValueError(f"non-contiguous layer indices: {idxs}")
    out = {k: v for k, v in params.items()
           if not _LAYER_KEY_RE.match(str(k))}
    out["h"] = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0),
        *[params[f"h_{i}"] for i in idxs])
    return out


def unstack_gpt2_layer_params(params):
    """``scan_layers`` layout -> unrolled ``h_0`` … ``h_{L-1}`` tree (the
    inverse of :func:`stack_gpt2_layer_params`)."""
    if "h" not in params:
        raise ValueError("no stacked 'h' entry to unstack")
    out = {k: v for k, v in params.items() if str(k) != "h"}
    stacked = params["h"]
    n_layer = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    for i in range(n_layer):
        out[f"h_{i}"] = jax.tree_util.tree_map(
            lambda leaf: leaf[i], stacked)
    return out

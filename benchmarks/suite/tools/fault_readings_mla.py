#!/usr/bin/env python3
"""What the three checks of one layer on its own input
(``drivers/serve_mla.py``: ``check_latents``, ``check_attention``,
``check_experts``) read when something is wrong, at the published
widths on the chip: the readings the limits in the cell's
``correctness`` block stand against. One JSON line a reading.

A fault is put where it is cheapest to put and reads the same from
either side: most are given to the REFERENCE (another scale, another
rotary, a weight moved, a term left out), so that the sound program's
distance from a faulty reference is the faulty program's distance from
the sound one; "weights at 3 bits of mantissa" (the next precision
below the configuration's bfloat16: float8_e4m3) is given to the
program. The latents' faults: rotary angles rounded to bfloat16, the
norm left off ``c_kv`` (reference side), and a page that still holds
its last tenant's latents (the pool's reading has one page as another
prompt left it). Last, the cell's check of generated tokens' logits on
the whole share, sound and with two faults patched into the program
while its engine is built (the score scale, the rotary frequencies).

    python3 benchmarks/suite/tools/fault_readings_mla.py --seed 1
"""

import argparse
import copy
import gc
import json
import os
import sys

SUITE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(SUITE))
sys.path.insert(0, ROOT)

CELL = "serve-kimi-k2.7-code-repo"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--prompt", type=int, default=9000)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.suite import run
    from benchmarks.suite.drivers import serve_mla as drv
    from benchmarks.suite.reference import mla_moe_ref as ref
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.mla_moe import MlaMoeLM, init_mla_moe_params

    code, ctx, _ = run.prepare(CELL, args.seed, 51, 0)
    if code:
        return code
    cfg = copy.deepcopy(ctx.config)
    n_layer = cfg["n_layer"]
    cfg["n_layer"] = 2          # the dense layer and one expert layer
    ctx.config = cfg
    model_cfg = drv.model_config(cfg)
    model = MlaMoeLM(model_cfg)
    params = init_mla_moe_params(model, jax.random.PRNGKey(args.seed))
    inf = ctx.workload["inference"]
    chunk, page = inf["prefill_chunk"], inf["page_size"]
    tol = ctx.workload["correctness"]
    out = open(os.path.join(ROOT, "chiprun_out", "fault_readings_mla.jsonl"),
               "w") if os.path.isdir(os.path.join(ROOT, "chiprun_out")) \
        else None

    def say(check, fault, reading):
        line = {"check": check, "fault": fault, **{
            k: v for k, v in reading.items()
            if isinstance(v, (int, float, bool))}}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    def with_leaf(layer, group, name, fn):
        p = jax.tree_util.tree_map(lambda a: a, params)
        p[layer] = dict(p[layer], **{group: dict(
            p[layer][group], **{name: fn(p[layer][group][name])})})
        return p

    def at_3_bits(tree):
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim >= 2 else a, tree)

    # the same where it stands: the whole share is there once
    in_place = jax.jit(lambda a: a.astype(jnp.float8_e4m3fn).astype(
        a.dtype), donate_argnums=0)

    # --- an attention layer ---------------------------------------------
    H, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rkv = cfg["kv_lora_rank"]

    def attention(fault, **kw):
        say("attention", fault, drv.check_attention(
            kw.pop("model_cfg", model_cfg), kw.pop("ref_cfg", cfg),
            kw.pop("params", params), args.seed, chunk, page, "flash",
            tol["attention_rtol"], tol["attention_decode_rtol"], **kw))

    def wrong_rope_entries(w):      # a head's first 64 entries rotated
        w = w.reshape(w.shape[0], H, dn + dr)
        return jnp.concatenate([w[..., dn:], w[..., dr:dn], w[..., :dr]],
                               -1).reshape(w.shape[0], -1)

    def values_off(w):      # W_uv reads the latent an eighth of it on
        w = w.reshape(rkv, H, dn + dv)
        return jnp.concatenate([w[..., :dn],
                                jnp.roll(w[..., dn:], rkv // 8, 0)],
                               -1).reshape(rkv, -1)

    attention("none")
    attention("scale without YaRN's factor 2.0047",
              ref_scale=(dn + dr) ** -0.5)
    attention("plain rotary for YaRN",
              ref_cfg=dict(cfg, rope_scaling=dict(cfg["rope_scaling"],
                                                  factor=1)),
              ref_scale=ref.softmax_scale(cfg))
    attention("the queries' rotary on another 64 entries of a head",
              ref_params=with_leaf(drv.LAYER, "attn", "q_b_proj",
                                   wrong_rope_entries))
    attention("the rotary key left out of the scores",
              ref_params=with_leaf(
                  drv.LAYER, "attn", "kv_a_proj",
                  lambda w: w.at[:, rkv:].set(0)))
    attention("values cut from a latent 64 entries off",
              ref_params=with_leaf(drv.LAYER, "attn", "kv_b_proj",
                                   values_off))
    attention("weights at 3 bits of mantissa (float8_e4m3)",
              params=at_3_bits(params), ref_params=params)

    # --- an expert layer --------------------------------------------------
    first = model_cfg.experts_held[0]
    name = next(n for n in model_cfg.layer_names() if "experts" in params[n])

    def experts(fault, reference=None, **kw):
        say("experts", fault, drv.check_experts(
            model_cfg, cfg, kw.pop("params", params), args.seed, chunk,
            inf["max_batch"], tol["expert_rtol"], reference=reference))

    def softmax_router(p, x):
        logits = ref._mm(x, p["router"])
        s = jax.nn.softmax(logits, -1)
        _, chosen = jax.lax.top_k(s + p["e_score_correction_bias"],
                                  cfg["num_experts_per_tok"])
        w = jnp.take_along_axis(s, chosen, -1)
        w = w / w.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]
        full = jnp.zeros_like(s).at[jnp.arange(len(x))[:, None],
                                    chosen].set(w)
        y = ref.mlp(x, p["shared"])
        for e in range(p["w_gate"].shape[0]):
            h = jax.nn.silu(ref._mm(x, p["w_gate"][e])) * \
                ref._mm(x, p["w_up"][e])
            y = y + full[:, first + e, None] * ref._mm(h, p["w_down"][e])
        return y

    def variant(**kw):
        c = dict(cfg, **kw)
        return lambda p, x: ref.experts(x, p, c, first)

    experts("none")
    experts("softmax for sigmoid", softmax_router)
    experts("chosen by s without the bias", lambda p, x: ref.experts(
        x, dict(p, e_score_correction_bias=jnp.zeros_like(
            p["e_score_correction_bias"])), cfg, first))
    experts("weights not renormalised", variant(norm_topk_prob=False))
    experts("routed_scaling_factor 2.827 left out",
            variant(routed_scaling_factor=1.0))
    experts("shared expert left out",
            lambda p, x: ref.routed(x, p, cfg, first))
    experts("shared expert doubled", lambda p, x: ref.routed(
        x, p, cfg, first) + 2 * ref.mlp(x, p["shared"]))
    experts("a pair of an expert held elsewhere let in (the banks one "
            "expert off)", lambda p, x: ref.experts(x, p, cfg, first + 1))
    experts("weights at 3 bits of mantissa (float8_e4m3)",
            params=dict(params, **{name: at_3_bits(params[name])}),
            reference=lambda p, x: ref.experts(
                x, params[name]["experts"], cfg, first))

    # --- the first layer's latents in an engine's own pool ---------------
    small = dict(
        max_batch=2, seq_buckets=tuple(inf["seq_buckets"]), n_pages=400,
        prefill_chunk=chunk, page_size=page, attention_impl="flash")
    engine = InferenceEngine(model, params, config=small)
    rng = np.random.default_rng(args.seed)
    vocab = cfg["vocab_size"]
    prompt = rng.integers(0, vocab, args.prompt).tolist()
    # the pages the check will use, under another tenant first
    table = np.arange(engine.pages_per_row, 0, -1, dtype=np.int32)
    engine.prefill(1, rng.integers(0, vocab, min(
        args.prompt + 900, engine.max_seq)).tolist(), table)
    tenant = drv.pool_latents(engine, table, args.prompt)

    def latents(fault, **kw):
        say("latents", fault, drv.check_latents(
            ctx, engine, prompt, [7, 8, 9], **kw))

    def one_page_stale(engine, table, n):
        """The row's fourth page as its last tenant left it."""
        got = drv.pool_latents(engine, table, n)
        lo, hi = 3 * page, min(4 * page, n, len(tenant))
        got[lo:hi] = tenant[lo:hi]
        return got

    def no_norm(params, seq, cfg):
        lat = np.array(ref.first_layer_latents(params, seq, cfg))
        p = params[drv.LAYER]
        n = ref._rms_norm(params["embed"][jnp.asarray(seq)],
                          p["input_norm"]["weight"], cfg["rms_norm_eps"])
        lat[:, :rkv] = np.asarray(ref._blocks(
            lambda x: ref._mm(x, p["attn"]["kv_a_proj"]), n))[:, :rkv]
        return lat

    def bf16_angles(params, seq, cfg):
        lat = np.array(ref.first_layer_latents(params, seq, cfg))
        p = params[drv.LAYER]
        n = ref._rms_norm(params["embed"][jnp.asarray(seq)],
                          p["input_norm"]["weight"], cfg["rms_norm_eps"])
        k = ref._blocks(lambda x: ref._mm(x, p["attn"]["kv_a_proj"]),
                        n)[:, rkv:]
        ang = (jnp.arange(len(seq)).astype(jnp.bfloat16)[:, None] *
               jnp.asarray(ref.yarn_inv_freq(cfg), jnp.bfloat16)
               ).astype(jnp.float32)
        d = k.shape[-1] // 2
        lat[:, rkv:] = np.asarray(jnp.concatenate(
            [k[:, :d] * jnp.cos(ang) - k[:, d:] * jnp.sin(ang),
             k[:, d:] * jnp.cos(ang) + k[:, :d] * jnp.sin(ang)], -1))
        return lat

    latents("none")
    latents("rotary angles in bfloat16", reference=bf16_angles)
    latents("the norm left off c_kv", reference=no_norm)
    latents("a page still holds its last tenant's latents",
            read=one_page_stale)
    del engine
    gc.collect()        # an engine goes with its cycles, not its last name
    engine = InferenceEngine(model, at_3_bits(params), config=small)
    latents("weights at 3 bits of mantissa (float8_e4m3)",
            reference=lambda _, seq, cfg: ref.first_layer_latents(
                params, seq, cfg))
    del engine, params

    # --- the generated tokens' logits, the whole share --------------------
    from deepspeed_tpu.models import mla_moe as mm
    ctx.config = whole = copy.deepcopy(ctx.config)
    whole["n_layer"] = n_layer

    def logits(fault, low=False):
        """A prompt and 128 greedy tokens through a fresh engine of the
        whole share, built while ``fault`` (if any) is patched in, then
        the cell's own check of them against the reference (on the
        sound weights, made again from the seed, where the engine's
        were at 3 bits)."""
        gc.collect()    # the last engine's 10 GB, before this one's
        model = MlaMoeLM(drv.model_config(whole))
        key = jax.random.PRNGKey(args.seed)
        params = init_mla_moe_params(model, key)
        if low:
            params = jax.tree_util.tree_map(
                lambda a: in_place(a) if a.ndim >= 2 else a, params)
        bucket = min(4096, whole["n_positions"])
        eng = InferenceEngine(model, params, config=dict(
            max_batch=2, seq_buckets=(bucket,), n_pages=100,
            prefill_chunk=chunk, page_size=page, attention_impl="flash"))
        table = np.arange(1, eng.pages_per_row + 1, dtype=np.int32)
        text = rng.integers(0, vocab, min(3000, bucket - 140)).tolist()
        toks = [int(eng.prefill(0, text, table).argmax())]
        tokens, positions = np.zeros(2, np.int32), np.zeros(2, np.int32)
        tables = np.zeros((2, eng.pages_per_row), np.int32)
        tables[0] = table
        for j in range(127):
            tokens[0], positions[0] = toks[-1], len(text) + j
            toks.append(int(eng.decode(tokens, positions, tables)[0][0]))
        tracker = type("T", (), {"prompts": {"r": text},
                                 "tokens": {"r": toks}})
        if low:
            del eng, params
            gc.collect()
            eng = type("E", (), {
                "params": init_mla_moe_params(model, key),
                "max_seq": bucket})
        say("logits", fault, drv.check_logits(ctx, eng, tracker, ["r"])[0])

    logits("none")
    sound = mm.MlaMoeConfig.softmax_scale
    mm.MlaMoeConfig.softmax_scale = property(
        lambda self: (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5)
    logits("scale without YaRN's factor 2.0047")
    mm.MlaMoeConfig.softmax_scale = sound
    plain, mm.yarn_inv_freq = mm.yarn_inv_freq, lambda dim, theta, rs: \
        theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    logits("plain rotary for YaRN")
    mm.yarn_inv_freq = plain
    logits("weights at 3 bits of mantissa (float8_e4m3)", low=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

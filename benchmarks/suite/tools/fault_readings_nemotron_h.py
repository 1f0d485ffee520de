#!/usr/bin/env python3
"""What the four checks of one layer on its own input
(``drivers/serve_nemotron_h.py``: ``check_state``, ``check_mixer``,
``check_attention``, ``check_experts``) read when something is wrong,
at the published widths on the chip: the readings the limits in the
cell's ``correctness`` block stand against. One JSON line a reading, on
stdout and in ``chiprun_out/fault_readings_nemotron_h.jsonl``.

A fault is put where it is cheapest to put and reads the same from
either side: most are given to the REFERENCE (a weight moved, a term
left out, another function), so that the sound program's distance from
a faulty reference is the faulty program's distance from the sound one;
a bfloat16 state and "weights at 3 bits of mantissa" (the next
precision below the configuration's bfloat16: float8_e4m3) are given to
the program. Last, the cell's check of generated tokens' logits on the
whole share: sound, with two faults the program is built with (the
attention's scale, the experts' scaling factor) and at 3 bits.

    python3 benchmarks/suite/tools/fault_readings_nemotron_h.py --seed 1
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

CELL = "serve-nemotron-3-super-reason"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--prompt", type=int, default=2900)
    ap.add_argument("--only-logits", action="store_true",
                    help="the last section alone: the whole share's "
                    "generated tokens, sound and with three faults")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.suite import run
    from benchmarks.suite.drivers import serve_nemotron_h as drv
    from benchmarks.suite.reference import nemotron_h_ref as ref
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.nemotron_h import (NemotronHLM,
                                                 init_nemotron_h_params)
    from deepspeed_tpu.ops import ssm

    code, ctx, _ = run.prepare(CELL, args.seed, 51, 0)
    if code:
        return code
    whole = ctx.config
    cfg = copy.deepcopy(whole)
    # a block of each kind: a mixer, an expert layer, the attention
    cfg["hybrid_override_pattern"] = "ME*" + cfg[
        "hybrid_override_pattern"][3:]
    cfg["n_layer"] = 3
    ctx.config = cfg
    model_cfg = drv.model_config(cfg)
    model = NemotronHLM(model_cfg)
    params = init_nemotron_h_params(model, jax.random.PRNGKey(args.seed))
    inf = ctx.workload["inference"]
    chunk, page = inf["prefill_chunk"], inf["page_size"]
    tol = ctx.workload["correctness"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out",
                            "fault_readings_nemotron_h.jsonl"), "w")

    def say(check, fault, reading):
        line = {"check": check, "fault": fault, **{
            k: v for k, v in reading.items()
            if isinstance(v, (int, float, bool))}}
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()

    def at_3_bits(tree):
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim >= 2 else a, tree)

    rng = np.random.default_rng(args.seed)
    vocab = cfg["vocab_size"]

    def layer_checks():
        H, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
            cfg["ssm_state_size"]
        G, d_in = cfg["n_groups"], H * P

        # --- a mixer ------------------------------------------------------
        mix = params["layers_0"]["mixer"]

        def mixer(fault, reference=None, program=None):
            say("mixer", fault, drv.check_mixer(
                model_cfg, cfg, program or params, args.seed, chunk,
                tol["mixer_rtol"], reference=reference))

        def groups_rolled(p):
            """Head ``h`` reads group ``h // 16 + 1``: the B and C columns
            of in_proj and of the convolution moved one group on."""
            def roll(a):
                lo = d_in if a.shape[-1] == d_in + 2 * G * N else 2 * d_in
                bc = a[..., lo:lo + 2 * G * N].reshape(
                    a.shape[:-1] + (2, G, N))
                bc = jnp.roll(bc, 1, axis=-2).reshape(a.shape[:-1] + (-1,))
                return jnp.concatenate([a[..., :lo], bc,
                                        a[..., lo + 2 * G * N:]], -1)
            return dict(p, in_proj=roll(p["in_proj"]),
                        conv_weight=roll(p["conv_weight"]),
                        conv_bias=roll(p["conv_bias"]))

        mixer("none")
        mixer("heads read the next B/C group",
              lambda p, x: ref.mamba(x, groups_rolled(p), cfg)[0])
        mixer("the gated norm over one group (the whole inner width)",
              lambda p, x: ref._mm(ref.group_norm(
                  ref.gated_scan(x, p, cfg)[0], p["norm_weight"], 1,
                  cfg["layer_norm_epsilon"]), p["out_proj"]))
        mixer("the convolution's bias dropped",
              lambda p, x: ref.mamba(x, dict(p, conv_bias=jnp.zeros_like(
                  p["conv_bias"])), cfg)[0])
        mixer("weights at 3 bits of mantissa (float8_e4m3)",
              lambda p, x: ref.mamba(x, mix, cfg)[0],
              program=dict(params, layers_0=at_3_bits(params["layers_0"])))

        # --- the attention layer ---------------------------------------------
        att_name = model_cfg.names("*")[0]
        att = params[att_name]["attn"]

        def attention(fault, reference=None, program=None):
            say("attention", fault, drv.check_attention(
                model_cfg, cfg, program or params, args.seed, chunk, page,
                "flash", tol["attention_rtol"], tol["attention_decode_rtol"],
                reference=reference))

        def key_heads_swapped(p):
            out = dict(p)
            for name in ("k_proj", "v_proj"):
                w = p[name].reshape(-1, cfg["num_key_value_heads"],
                                    cfg["head_dim"])
                out[name] = w[:, ::-1].reshape(p[name].shape)
            return out

        attention("none")
        attention("1/128 for 128^-0.5", lambda p, x: ref.attention(
            x, p, cfg, scale=1.0 / cfg["head_dim"]))
        attention("key heads swapped", lambda p, x: ref.attention(
            x, key_heads_swapped(p), cfg))
        attention("weights at 3 bits of mantissa (float8_e4m3)",
                  lambda p, x: ref.attention(x, att, cfg),
                  program=dict(params, **{att_name: at_3_bits(
                      params[att_name])}))

        # --- an expert layer ----------------------------------------------
        first = model_cfg.experts_held[0]
        exp_name = model_cfg.names("E")[0]
        exp = params[exp_name]["experts"]

        def experts(fault, reference=None, program=None):
            say("experts", fault, drv.check_experts(
                model_cfg, cfg, program or params, args.seed, chunk,
                inf["max_batch"], tol["expert_rtol"], reference=reference))

        def routed_with(p, x, act=ref.relu2, latent=None, weights=None):
            """`ref.routed` with one piece exchanged."""
            w, chosen = weights(p, x) if weights else ref.route(x, p, cfg)
            lat = ref._mm(x, p["latent_down"]) if latent is None else latent(x)

            def expert(y, e_bank):
                e, up, down = e_bank
                mine = jnp.sum(jnp.where(chosen == e + first, w, 0.0), -1,
                               keepdims=True)
                return y + mine * ref._mm(act(ref._mm(lat, up)), down), None

            return jax.lax.scan(
                expert, jnp.zeros_like(lat),
                (jnp.arange(p["w_up"].shape[0]), p["w_up"], p["w_down"]))[0]

        def whole_layer(p, x, **kw):
            return ref._mm(routed_with(p, x, **kw), p["latent_up"]) + \
                ref.shared(x, p)

        def softmax_weights(p, x):
            s = jax.nn.softmax(ref._mm(x, p["router"]), -1)
            _, chosen = jax.lax.top_k(s + p["e_score_correction_bias"],
                                      cfg["num_experts_per_tok"])
            w = jnp.take_along_axis(s, chosen, -1)
            return w / w.sum(-1, keepdims=True) * \
                cfg["routed_scaling_factor"], chosen

        def variant(**kw):
            c = dict(cfg, **kw)
            return lambda p, x: ref.experts(x, p, c, first)

        L = cfg["moe_latent_size"]
        experts("none")
        experts("the loop of this tool for the reference's (sound)",
                whole_layer)
        experts("SiLU for relu^2", lambda p, x: whole_layer(
            p, x, act=jax.nn.silu))
        experts("the square left out (relu)", lambda p, x: whole_layer(
            p, x, act=jax.nn.relu))
        experts("experts fed n's first 1024 entries, not the latent",
                lambda p, x: whole_layer(p, x, latent=lambda x: x[:, :L]))
        experts("softmax for sigmoid", lambda p, x: whole_layer(
            p, x, weights=softmax_weights))
        experts("chosen by s without the bias", lambda p, x: ref.experts(
            x, dict(p, e_score_correction_bias=jnp.zeros_like(
                p["e_score_correction_bias"])), cfg, first))
        experts("weights not renormalised", variant(norm_topk_prob=False))
        experts("routed_scaling_factor 5.0 left out",
                variant(routed_scaling_factor=1.0))
        experts("shared expert left out", lambda p, x: ref.experts(
            x, p, cfg, first) - ref.shared(x, p))
        experts("shared expert doubled", lambda p, x: ref.experts(
            x, p, cfg, first) + ref.shared(x, p))
        experts("the banks one expert off (a pair of an expert held "
                "elsewhere let in)", lambda p, x: ref.experts(
                    x, p, cfg, first + 1))
        experts("weights at 3 bits of mantissa (float8_e4m3)",
                lambda p, x: ref.experts(x, exp, cfg, first),
                program=dict(params,
                             **{exp_name: at_3_bits(params[exp_name])}))

        # --- the first mixer's state in an engine's own leaves ------------
        small = dict(max_batch=4, seq_buckets=tuple(inf["seq_buckets"]),
                     n_pages=200, prefill_chunk=chunk, page_size=page,
                     attention_impl="flash")
        prompt = rng.integers(0, vocab, args.prompt).tolist()
        sound_scan, sound_step = ssm.ssd_chunked_scan, ssm.ssm_decode_step

        def rounded(fn):
            def bf16_state(*a, **k):
                y, s = fn(*a, **k)
                return y, jax.lax.reduce_precision(s, 8, 7)
            return bf16_state

        def state(fault, program=None, patch=None, reference=None):
            gc.collect()
            if fault == "a bfloat16 state":
                ssm.ssd_chunked_scan = rounded(sound_scan)
                ssm.ssm_decode_step = rounded(sound_step)
            try:
                engine = InferenceEngine(model, program or params,
                                         config=small)
                # the slot's earlier tenant
                engine.prefill(0, prompt[::-1][:700], np.arange(
                    1, engine.pages_per_row + 1))
                compiled = engine._prefill
                if patch == "unmasked_tail":
                    engine._prefill = lambda p, c, t, pos, pt, sl, nv: \
                        compiled(p, c, t, pos, pt, sl,
                                 jnp.full((1,), chunk, jnp.int32))
                if patch == "stale_state":
                    engine._prefill = lambda p, c, t, pos, *rest: compiled(
                        p, c, t, pos + 1, *rest)
                say("state", fault, drv.check_state(
                    ctx, engine, prompt, [7, 8, 9], reference=reference))
            finally:
                ssm.ssd_chunked_scan, ssm.ssm_decode_step = \
                    sound_scan, sound_step
            del engine

        def ref_with(mixer_params):
            faulty = dict(params, layers_0=dict(params["layers_0"],
                                                mixer=mixer_params))
            return lambda _, seq, c, **kw: ref.forward(faulty, seq, c, **kw)

        state("none")
        state("a bfloat16 state")
        state("a padded tail let into the state", patch="unmasked_tail")
        state("the slot's last tenant's state kept", patch="stale_state")
        state("heads read the next B/C group",
              reference=ref_with(groups_rolled(mix)))
        state("weights at 3 bits of mantissa (float8_e4m3)",
              program=at_3_bits(params),
              reference=lambda _, seq, c, **kw: ref.forward(params, seq, c,
                                                            **kw))

    if not args.only_logits:
        layer_checks()
    del params

    # --- the generated tokens' logits, the whole share --------------------
    ctx.config = whole
    in_place = jax.jit(lambda a: a.astype(jnp.float8_e4m3fn).astype(
        a.dtype), donate_argnums=0)

    def logits(fault, low=False, **built_with):
        """A prompt and 128 greedy tokens through a fresh engine of the
        whole share (its configuration ``built_with`` a fault, if any),
        then the cell's own check of them against the reference (on the
        sound weights, made again from the seed, where the engine's
        were at 3 bits)."""
        gc.collect()
        model = NemotronHLM(drv.model_config(whole, **built_with))
        key = jax.random.PRNGKey(args.seed)
        params = init_nemotron_h_params(model, key)
        if low:
            params = jax.tree_util.tree_map(
                lambda a: in_place(a) if a.ndim >= 2 else a, params)
        bucket = inf["seq_buckets"][0]
        eng = InferenceEngine(model, params, config=dict(
            max_batch=4, seq_buckets=(bucket,), n_pages=100,
            prefill_chunk=chunk, page_size=page, attention_impl="flash"))
        table = np.arange(1, eng.pages_per_row + 1, dtype=np.int32)
        text = rng.integers(0, vocab, min(3000, bucket - 140)).tolist()
        toks = [int(eng.prefill(0, text, table).argmax())]
        tokens, positions = np.zeros(4, np.int32), np.zeros(4, np.int32)
        tables = np.zeros((4, eng.pages_per_row), np.int32)
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
                "params": init_nemotron_h_params(model, key),
                "max_seq": bucket})
        say("logits", fault, drv.check_logits(ctx, eng, tracker, ["r"])[0])

    from deepspeed_tpu.models.nemotron_h import NemotronHConfig
    logits("none")
    logits("routed_scaling_factor 5.0 left out", routed_scaling_factor=1.0)
    sound = NemotronHConfig.attention_multiplier
    NemotronHConfig.attention_multiplier = property(
        lambda self: 1.0 / self.head_dim)
    logits("1/128 for 128^-0.5")
    NemotronHConfig.attention_multiplier = sound
    logits("weights at 3 bits of mantissa (float8_e4m3)", low=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The two readings behind each limit of the hybrid cell's own-input
checks, on the chip: a sound program's, and the same program with one
deliberate fault, at the published widths. The checks look at the first
mixer and the first attention layer only, so the model here is those two
layers (a mixer, then an attention layer) of the configuration, through
an ``InferenceEngine`` of the cell's shapes with four rows: the readings
are the cell's, at a twentieth of its set-up. The faults are patched in
from outside; the program has no switch for them. One JSON line a seed on
stdout and in ``chiprun_out/fault_readings_<cell>.jsonl``.

    python3 benchmarks/suite/tools/fault_readings_hybrid.py \
        --workload serve-granite-4.0-h-micro-rag --seeds 11,12
"""

import argparse
import dataclasses
import json
import os
import sys

SUITE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(SUITE))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--prompt", type=int, default=2166)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.suite import run
    from benchmarks.suite.drivers import serve_hybrid as drv
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models import granite_hybrid as gh
    from deepspeed_tpu.ops import ssm

    code, ctx, _ = run.prepare(args.workload, 0, 1.0, 0)
    if code:
        return code
    kinds = (gh.MAMBA, gh.ATTENTION)
    ref_cfg = dict(ctx.config, layer_types=list(kinds))
    ctx = dataclasses.replace(ctx, config=ref_cfg)
    cfg = drv.model_config(ctx.config, num_hidden_layers=2,
                           layer_types=kinds)
    inf = dict(ctx.workload["inference"], max_batch=4)
    inf["seq_buckets"] = tuple(inf["seq_buckets"])
    chunk, page = inf["prefill_chunk"], inf["page_size"]
    tol = ctx.workload["correctness"]
    model = gh.GraniteHybridLM(cfg)
    sound_scan, sound_step = ssm.ssd_chunked_scan, ssm.ssm_decode_step

    def rounded(fn):
        def bf16_state(*a, **k):
            y, s = fn(*a, **k)
            return y, jax.lax.reduce_precision(s, 8, 7)
        return bf16_state

    def state(params, prompt, fed, fault=None):
        if fault == "bf16_state":
            ssm.ssd_chunked_scan = rounded(sound_scan)
            ssm.ssm_decode_step = rounded(sound_step)
        try:
            engine = InferenceEngine(model, params, config=inf)
            # the slot's earlier tenant
            engine.prefill(0, prompt[::-1][:700], np.arange(
                1, engine.pages_per_row + 1))
            compiled = engine._prefill
            if fault == "unmasked_tail":
                engine._prefill = lambda p, c, t, pos, pt, sl, nv: \
                    compiled(p, c, t, pos, pt, sl,
                             jnp.full((1,), chunk, jnp.int32))
            if fault == "stale_state":
                engine._prefill = lambda p, c, t, pos, *rest: compiled(
                    p, c, t, pos + 1, *rest)
            got = drv.check_state(ctx, engine, prompt, fed)
        finally:
            ssm.ssd_chunked_scan, ssm.ssm_decode_step = \
                sound_scan, sound_step
        return {k: got[k] for k in ("after_prefill", "after_short_prefill",
                                    "after_decode", "ok")}

    def attention(params, seed, fault=None):
        program_cfg, program = cfg, params
        if fault == "wrong_scale":
            program_cfg = dataclasses.replace(
                cfg, attention_multiplier=cfg.head_dim ** -0.5)
        if fault == "wrong_key_head":
            attn = dict(params["layers_1"]["attn"])
            for name in ("k_proj", "v_proj"):
                w = attn[name].reshape(-1, cfg.num_key_value_heads,
                                       cfg.head_dim)
                attn[name] = w[:, ::-1].reshape(attn[name].shape)
            program = dict(params, layers_1=dict(params["layers_1"],
                                                 attn=attn))
        got = drv.check_attention(
            program_cfg, ref_cfg, program, seed, chunk, page,
            inf["attention_impl"], tol["attention_rtol"],
            ref_params=params)
        return {k: got[k] for k in ("prefill", "decode", "ok")}

    def mixer(params, seed, fault=None):
        program = params
        if fault:
            leaf = dict(params["layers_0"]["mixer"])
            if fault == "decay_halved":
                leaf["A_log"] = leaf["A_log"] + jnp.log(0.5).astype(
                    leaf["A_log"].dtype)
            else:
                leaf["conv_bias"] = jnp.zeros_like(leaf["conv_bias"])
            program = dict(params, layers_0=dict(params["layers_0"],
                                                 mixer=leaf))
        got = drv.check_mixer(cfg, ref_cfg, program, seed, chunk,
                              tol["mixer_rtol"], ref_params=params)
        return {k: got[k] for k in ("prefill", "decode", "ok")}

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"fault_readings_{args.workload}.jsonl"),
              "w") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            params = gh.init_granite_hybrid_params(
                model, jax.random.PRNGKey(seed))
            rng = np.random.default_rng(seed)
            prompt = rng.integers(0, cfg.vocab_size, args.prompt).tolist()
            fed = rng.integers(0, cfg.vocab_size, 8).tolist()
            line = {"seed": seed, "state": {
                k or "sound": state(params, prompt, fed, k) for k in (
                    None, "bf16_state", "unmasked_tail", "stale_state")},
                "attention": {k or "sound": attention(params, seed, k)
                              for k in (None, "wrong_scale",
                                        "wrong_key_head")},
                "mixer": {k or "sound": mixer(params, seed, k)
                          for k in (None, "decay_halved",
                                    "conv_bias_dropped")}}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

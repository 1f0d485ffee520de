#!/usr/bin/env python3
"""What the checks of ``drivers/serve_ling.py`` (``check_mixer``,
``check_attention``, ``check_experts``, ``check_slot``,
``check_logits``) read when something is wrong, at the published widths
on the chip: the readings the limits in the cell's ``correctness`` block
stand against. One JSON line a reading, on stdout and in
``chiprun_out/fault_readings_ling.jsonl``.

A fault is put where it is cheapest to put and reads the same from
either side: most are given to the REFERENCE, through a key of its
configuration (the bound -4, ``topk_group`` 3, the group stage or the
factor left out), through its weights (the next head's gate:
``gate_proj``'s columns one on) or by putting another function in the
place of one of the reference's small ones for the length of a trace
(the gate's mean over a head's channels, softplus for the bounded gate,
the output gate left out or SiLU, the L2 norms left out, the taps
reversed, the state or ``g`` rounded to bfloat16, a group's score its
top 1, the bias in the weights) or of its attention (rotary on the nope
part), so that the sound program's distance from a faulty reference is
the faulty program's distance from the sound one; "weights at 3 bits of
mantissa" (the next precision below the configuration's bfloat16:
float8_e4m3), a slot that keeps its last tenant's state and a state
rounded to bfloat16 at rest are given to the program. Last
(``--whole``), on the engine: the cell's check of generated tokens'
logits and of what a slot holds, sound and at 3 bits.

    python3 benchmarks/suite/tools/fault_readings_ling.py --seed 1 \
        [--whole [--skip-layers] [--layers 6] [--prompt 2900] [--tokens 64]]

(``--tokens``: how many tokens the engine generates for the logits'
check; the largest shortfall over 1,000 tokens is larger than over 64,
under sound weights and under faulty ones.)

`tests/benchmark_suite/test_ling_rehearsal.py` runs the same faults at
toy size on the CPU (`mixer_faults`, `attention_faults`,
`expert_faults`).
"""

import argparse
import gc
import json
import os
import sys
from unittest import mock

SUITE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(SUITE))
sys.path.insert(0, ROOT)

CELL = "serve-ling-3.0-flash-reason-docs"
LOW = "weights at 3 bits of mantissa (float8_e4m3)"


def low(tree):
    """Weights through float8_e4m3 and back, a leaf at a time, each cast
    a program of its own (inside one jitted program XLA drops a cast
    there and back: `tools/fault_readings_mimo_v2.py`'s note); the old
    leaf goes as its copy comes."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    for i, a in enumerate(leaves):
        if a.ndim >= 2:
            leaves[i] = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            leaves[i].block_until_ready()
            del a
    return treedef.unflatten(leaves)


def with_ref(fn, **attrs):
    """``fn`` traced with the reference's named functions replaced."""
    from benchmarks.suite.reference import ling_hybrid_ref as ref

    def run(p, x):
        with mock.patch.multiple(ref, **attrs):
            return fn(p, x)
    return run


def mixer_faults(cfg):
    """``{fault: (p, x) -> y}``: the reference's KDA mixer with one named
    fault each."""
    import jax
    import jax.numpy as jnp
    from benchmarks.suite.reference import ling_hybrid_ref as ref

    sound_gate, sound_step = ref.kda_gate, ref.delta_step
    H = cfg["num_attention_heads"]

    def mixer(c=cfg):
        return lambda p, x: ref.kda(x, p, c)[0]

    def scalar_gate(a, p, c):
        g = sound_gate(a, p, c)
        return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)

    def softplus_gate(a, p, c):
        x = (a + ref._f32(p["dt_bias"])).reshape(len(a), H, -1)
        return -jnp.exp(ref._f32(p["A_log"]))[:, None] * jax.nn.softplus(x)

    def bf16(x):
        # not a cast there and back, which XLA drops on the chip
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def ungated(o, x, p, c, act=None):
        y = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                              + c["rms_norm_eps"]) * ref._f32(p["norm_weight"])
        if act is None:
            return y
        return y * act(ref._mm(x, p["g_proj"])).reshape(o.shape)

    def step_bf16(S, *tok):
        S, o = sound_step(S, *tok)
        return bf16(S), o

    return {
        "a scalar decay a head (the gate's mean over its channels)":
            with_ref(mixer(), kda_gate=scalar_gate),
        "softplus for the bounded gate": with_ref(
            mixer(), kda_gate=softplus_gate),
        "the bound at -4": mixer(dict(cfg, kda_lower_bound=-4)),
        "the output gate left out": with_ref(mixer(), output_gate=ungated),
        "SiLU for the output gate's sigmoid": with_ref(
            mixer(), output_gate=lambda o, x, p, c: ungated(
                o, x, p, c, jax.nn.silu)),
        "the L2 norms of q and k left out": with_ref(
            mixer(), unit=lambda x: x),
        "the convolutions' taps reversed": with_ref(
            mixer(), convolve=lambda padded, w, T: jax.nn.silu(sum(
                w[len(w) - 1 - j] * padded[j:j + T]
                for j in range(len(w))))),
        "the state rounded to bfloat16 a token": with_ref(
            mixer(), delta_step=step_bf16),
        "g rounded to bfloat16": with_ref(
            mixer(), kda_gate=lambda a, p, c: bf16(sound_gate(a, p, c))),
        "beta left out": with_ref(
            mixer(), delta_step=lambda S, q, k, v, g, b: sound_step(
                S, q, k, v, g, jnp.ones_like(b))),
    }


def attention_faults(cfg):
    """``{fault: (p, x) -> y}``: the reference's latent attention with
    one named fault each."""
    import jax.numpy as jnp
    from benchmarks.suite.reference import ling_hybrid_ref as ref

    dn = cfg["qk_nope_head_dim"]
    sound_rotary = ref.rotary

    def attention(**kw):
        return lambda p, x: ref.attention(x, p, cfg, **kw)

    def next_head(p, x):
        return ref.attention(
            x, dict(p, gate_proj=jnp.roll(p["gate_proj"], 1, axis=1)), cfg)

    def rotary_on_nope(x, positions, theta):
        # the reference hands rotary a query's rope part [T, rope] and
        # the shared key [T, rope]: the query's is given back as it came
        # (its nope part is turned in `nope_turned` below), the key's too
        return x

    def nope_turned(p, x):
        # rotary moved from the rope part onto the first rope-many
        # entries of the nope part, in queries and keys alike: W_q's and
        # W_ukv's nope columns come out rotated, the rope parts plain
        r = cfg["qk_rope_head_dim"]

        def blocks(fn, rows, block=ref.TOKEN_BLOCK):
            out = sound_blocks(fn, rows, block)
            if out.ndim == 2 and out.shape[1] in (dn + r,
                                                  dn + cfg["v_head_dim"]):
                turned = sound_rotary(out[:, :r], jnp.arange(len(out)),
                                      cfg["rope_theta"])
                out = jnp.concatenate([turned, out[:, r:]], axis=1)
            return out
        sound_blocks = ref._blocks
        with mock.patch.multiple(ref, rotary=rotary_on_nope,
                                 _blocks=blocks):
            return ref.attention(x, p, cfg)

    return {
        "the gate left out": attention(gated=False),
        "the gate of the next head": next_head,
        "rotary on the nope part": nope_turned,
        "the scores' scale 128^-0.5": attention(scale=dn ** -0.5),
        "rotary at theta / 600 (1e4 for the published 6e6)":
            lambda p, x: ref.attention(
                x, p, dict(cfg, rope_theta=cfg["rope_theta"] / 600)),
    }


def expert_faults(cfg, first):
    """``{fault: check_experts' keywords}``: the reference's expert layer
    (``reference``, ``(p, x) -> y``) with one named fault each, and where
    the fault is in the routing weights the reference's ``route`` too."""
    import jax
    import jax.numpy as jnp
    from benchmarks.suite.reference import ling_hybrid_ref as ref

    sound_route = ref.route

    def experts(c=cfg):
        return lambda p, x: ref.experts(x, p, c, first)

    def top1_scores(c, conf):
        per = c.shape[1] // conf["n_group"]
        return c.reshape(len(c), conf["n_group"], per).max(-1)

    def bias_in_weights(n, p, conf):
        _, chosen, kept = sound_route(n, p, conf)
        c = jax.nn.sigmoid(ref._mm(ref._f32(n), p["router"])) + \
            ref._f32(p["expert_bias"])
        w = jnp.take_along_axis(c, chosen, axis=1)
        return w / w.sum(-1, keepdims=True) * \
            conf["routed_scaling_factor"], chosen, kept

    faults = {
        "the group stage left out": experts(
            dict(cfg, n_group=1, topk_group=1)),
        "a group's score its top 1": with_ref(
            experts(), group_scores=top1_scores),
        "topk_group 3": experts(dict(cfg, topk_group=3)),
        "routed_scaling_factor left out": experts(
            dict(cfg, routed_scaling_factor=1.0)),
        "the shared expert left out": lambda p, x: ref.routed(
            x, p, cfg, first),
        "the banks one expert off": lambda p, x: ref.experts(
            x, p, cfg, first + 1),
    }
    out = {name: {"reference": fn} for name, fn in faults.items()}
    out["the bias in the weights"] = {
        "reference": with_ref(experts(), route=bias_in_weights),
        "route": bias_in_weights}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--whole", action="store_true",
                    help="also the engine's own checks, sound and at 3 bits")
    ap.add_argument("--prompt", type=int, default=2900)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--tokens", type=int, default=64,
                    help="tokens generated for the logits' check")
    ap.add_argument("--skip-layers", action="store_true",
                    help="the engine's own checks alone")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from benchmarks.suite import run
    from benchmarks.suite.drivers import serve_ling as drv
    from deepspeed_tpu.models.ling_hybrid import (LingHybridLM,
                                                  init_ling_hybrid_params)

    code, ctx, _ = run.prepare(CELL, args.seed, 51, 0)
    if code:
        return code
    cfg, corr = ctx.config, ctx.workload["correctness"]
    inf = ctx.workload["inference"]
    chunk, page, impl = inf["prefill_chunk"], inf["page_size"], \
        inf["attention_impl"]
    mc = drv.model_config(cfg)
    model = LingHybridLM(mc)
    params = None if args.skip_layers else init_ling_hybrid_params(
        model, jax.random.PRNGKey(args.seed % (2 ** 31)))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    f = open(os.path.join(out_dir, "fault_readings_ling.jsonl"), "w")

    def say(check, fault, reading):
        line = {"check": check, "fault": fault, "seed": args.seed, **reading}
        print(json.dumps(line), flush=True)
        f.write(json.dumps(line) + "\n")
        f.flush()

    def mixer(p=None, **kw):
        return drv.check_mixer(mc, cfg, p or params, args.seed, chunk,
                               corr["mixer_rtol"], **kw)

    def attention(p=None, **kw):
        return drv.check_attention(
            mc, cfg, p or params, args.seed, chunk, page, impl,
            corr["attention_rtol"], corr["attention_decode_rtol"], **kw)

    def experts(p=None, **kw):
        return drv.check_experts(mc, cfg, p or params, args.seed, chunk,
                                 inf["max_batch"], corr["expert_rtol"], **kw)

    first = mc.experts_held[0]
    groups = () if args.skip_layers else (
        ("mixer", mixer, mixer_faults(cfg)),
        ("attention", attention, attention_faults(cfg)),
        ("experts", experts, expert_faults(cfg, first)))
    for check, run_check, faults in groups:
        say(check, "none", run_check())
        for fault, given in faults.items():
            if not isinstance(given, dict):
                given = {"reference": given}
            say(check, fault, run_check(**given))
            gc.collect()
    if groups:
        # the program's weights at 3 bits against the sound reference's:
        # the three layers the checks read
        swapped = dict(params, **{
            name: low(params[name])
            for name in ("layers_0", mc.names("mla")[0], "layers_2")})
        for check, run_check, _ in groups:
            say(check, LOW, run_check(p=swapped, sound=params))
        del swapped
    params = None
    gc.collect()
    if not args.whole:
        return 0

    # --- the engine's own two programs, on the first ``--layers`` layers
    # (two copies of the whole share's weights do not fit a chip) ----------
    from deepspeed_tpu.inference.engine import InferenceEngine

    ctx.config = dict(cfg, n_layer=args.layers)
    model = LingHybridLM(drv.model_config(ctx.config))
    sound = init_ling_hybrid_params(
        model, jax.random.PRNGKey(args.seed % (2 ** 31)))
    config = dict(inf, seq_buckets=tuple(inf["seq_buckets"]),
                  sampling_seed=args.seed)
    engine = InferenceEngine(model, sound, config=config)
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(0, cfg["vocab_size"], args.prompt).tolist()

    class Tracker:
        prompts, tokens = {}, {}

    def whole(fault):
        engine.reset()
        table = np.arange(engine.pages_per_row, 0, -1, dtype=np.int32)
        logits = engine.prefill(0, prompt, table)
        toks, tokens = [], np.zeros(engine.max_batch, np.int32)
        positions = np.zeros(engine.max_batch, np.int32)
        tables = np.zeros((engine.max_batch, engine.pages_per_row), np.int32)
        tables[0] = table
        tok = int(np.argmax(logits))
        for j in range(args.tokens):
            toks.append(tok)
            tokens[0], positions[0] = tok, len(prompt) + j
            tok = int(engine.decode(tokens, positions, tables)[0][0])
        Tracker.prompts, Tracker.tokens = {"p": prompt}, {"p": toks}
        stages = drv.slot_readings(engine, prompt, toks)
        engine.cache = None
        program_params, engine.params = engine.params, sound
        try:        # the reference reads the sound weights
            say("slot", fault, dict(
                drv.check_slot(ctx, engine, prompt, toks, stages=stages),
                layers=args.layers))
            say("logits", fault, dict(drv.check_logits(
                ctx, sound, chunk, Tracker, ["p"])[0], layers=args.layers))
        finally:
            engine.params = program_params

    whole("none")
    # a state kept in bfloat16: the program's two ops hand back a rounded
    # state (a new engine: a new trace)
    from deepspeed_tpu.models import ling_hybrid as lh

    def rounded(op):
        def run(*a):
            o, state = op(*a)
            return o, jax.lax.reduce_precision(state, 8, 7)
        return run
    first_engine, engine.cache = engine, None
    with mock.patch.multiple(lh.kda, kda_chunked=rounded(lh.kda.kda_chunked),
                             kda_step=rounded(lh.kda.kda_step)):
        engine = InferenceEngine(model, sound, config=config)
        whole("a state kept in bfloat16")
        engine.cache = None
    engine = first_engine
    engine.params = low(sound)      # two copies: the reference keeps sound
    whole(LOW)
    return 0


if __name__ == "__main__":
    sys.exit(main())

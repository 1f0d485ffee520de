#!/usr/bin/env python3
"""What the checks of ``drivers/serve_lfm2.py`` (``check_mixer``,
``check_attention``, ``check_experts``, ``check_slot``,
``check_logits``) read when something is wrong, at the published widths
on the chip: the readings the limits in the cell's ``correctness`` block
stand against. One JSON line a reading, on stdout and in
``chiprun_out/fault_readings_lfm2.jsonl``.

A fault is put where it is cheapest to put and reads the same from
either side: most are given to the REFERENCE, through a key of its
configuration (theta 1e4), through its weights (one weight for the two
head norms) or by putting another function in the place of one of the
reference's small ones for the length of a trace (either gate left out,
the gates exchanged, both gates after the taps, SiLU after the taps, the
taps reversed, the head norms left out, the bias in the weights, the
renormalisation left out, softmax for sigmoid, an untied head, one dense
layer for two), so that the sound program's distance from a faulty
reference is the faulty program's distance from the sound one. Two are
given to the PROGRAM, through `ops/ssm.py:causal_conv_prefill` as
`models/lfm2_moe.py` calls it (the slot's last tenant's window carried
into a prompt; the padded tail entering the window), and so are "weights
at 3 bits of mantissa" (the next precision below the configuration's
bfloat16: float8_e4m3). Last (``--whole``), on the engine: the cell's
check of generated tokens' logits and of what a slot holds, sound, under
the two whole-model faults and at 3 bits.

    python3 benchmarks/suite/tools/fault_readings_lfm2.py --seed 1 \
        [--whole [--skip-layers] [--pages 512] [--prompt 2900] [--tokens 64]]

(``--pages``: the engine's pool for ``--whole``: two copies of the
share's weights, 10.1 GB, stand beside it. ``--tokens``: how many tokens
the engine generates for the logits' check.)

`tests/benchmark_suite/test_lfm2_faults.py` runs the same faults at toy
size on the CPU (`mixer_faults`, `program_faults`, `attention_faults`,
`expert_faults`, `whole_faults`).
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

CELL = "serve-lfm2-8b-a1b-agent"
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
    from benchmarks.suite.reference import lfm2_moe_ref as ref

    def run(*args, **kw):
        with mock.patch.multiple(ref, **attrs):
            return fn(*args, **kw)
    return run


def mixer_faults(cfg):
    """``{fault: (p, x) -> y}``: the reference's short convolution with
    one named fault each."""
    import jax
    from benchmarks.suite.reference import lfm2_moe_ref as ref

    sound_taps = ref.taps

    def mixer(p, x):
        return ref.short_conv(x, p, cfg)[0]

    def split(n, p):
        bcx = ref._blocks(lambda x: ref._mm(x, p["in_proj"]), ref._f32(n))
        C = bcx.shape[1] // 3
        return bcx[:, :C], bcx[:, C:2 * C], bcx[:, 2 * C:]

    def ungated_input(n, p):
        b, c, x = split(n, p)
        return x, c

    def exchanged(n, p):
        b, c, x = split(n, p)
        return c * x, b

    def both_after(n, p):
        b, c, x = split(n, p)
        return x, b * c

    return {
        "the input gate left out (the window holds x)": with_ref(
            mixer, gated_input=ungated_input),
        "the output gate left out": with_ref(
            mixer, gate_out=lambda c, z: z),
        "b and c exchanged": with_ref(mixer, gated_input=exchanged),
        "both gates after the taps": with_ref(mixer, gated_input=both_after),
        "SiLU after the taps": with_ref(
            mixer, taps=lambda padded, w, T: jax.nn.silu(
                sound_taps(padded, w, T))),
        "the taps reversed": with_ref(
            mixer, taps=lambda padded, w, T: sound_taps(padded, w[::-1], T)),
    }


def program_faults(model_cfg, seed, chunk, rows=4):
    """``{fault: a context}`` in which `check_mixer` traces the PROGRAM's
    mixer with one named fault each, put into the convolution as the
    mixer calls it."""
    from benchmarks.suite.drivers.serve_lfm2 import mixer_inputs
    from deepspeed_tpu.ops import ssm

    sound = ssm.causal_conv_prefill
    stale = mixer_inputs(model_cfg, seed, chunk, rows)[2][:, 1]

    def patched(fn):
        return lambda: mock.patch.object(ssm, "causal_conv_prefill", fn)

    return {
        "the last tenant's window carried into a prompt": patched(
            lambda seq, window, w, b, n: sound(seq, stale, w, b, n)),
        "the padded tail entering the window": patched(
            lambda seq, window, w, b, n: sound(seq, window, w, b,
                                               seq.shape[0])),
    }


def attention_faults(cfg):
    """``{fault: (p, x) -> y}``: the reference's attention with one
    named fault each."""
    from benchmarks.suite.reference import lfm2_moe_ref as ref

    def attention(c=cfg, **kw):
        return lambda p, x: ref.attention(x, p, c, **kw)

    return {
        "the head norms left out": with_ref(
            attention(), norm=lambda x, w, eps: ref._f32(x)),
        "one weight for q and k": lambda p, x: ref.attention(
            x, dict(p, k_layernorm=p["q_layernorm"]), cfg),
        "rotary at theta 1e4": attention(dict(cfg, rope_theta=1e4)),
        "the scores' scale 1 / 64 for 1 / 8": attention(
            scale=1.0 / ref.head_dim(cfg)),
    }


def expert_faults(cfg, first):
    """``{fault: check_experts' keywords}``: the reference's expert layer
    (``reference``, ``(p, x) -> y``) and its ``route`` with one named
    fault of the routing each."""
    import jax
    import jax.numpy as jnp
    from benchmarks.suite.reference import lfm2_moe_ref as ref

    sound_route = ref.route
    k, scaling = cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]

    def scores(n, p):
        return ref._mm(ref._f32(n), p["router"])

    def bias_in_weights(n, p, conf):
        _, chosen = sound_route(n, p, conf)
        c = jax.nn.sigmoid(scores(n, p)) + ref._f32(p["expert_bias"])
        w = jnp.take_along_axis(c, chosen, axis=1)
        return w / (w.sum(-1, keepdims=True) + ref.ROUTE_EPS) * scaling, \
            chosen

    def unnormalised(n, p, conf):
        _, chosen = sound_route(n, p, conf)
        return jnp.take_along_axis(jax.nn.sigmoid(scores(n, p)), chosen,
                                   axis=1) * scaling, chosen

    def softmax(n, p, conf):
        s = jax.nn.softmax(scores(n, p), axis=-1)
        chosen = jnp.argsort(-(s + ref._f32(p["expert_bias"])), axis=1,
                             stable=True)[:, :k]
        w = jnp.take_along_axis(s, chosen, axis=1)
        return w / (w.sum(-1, keepdims=True) + ref.ROUTE_EPS) * scaling, \
            chosen

    def faulty(route):
        return {"reference": with_ref(
            lambda p, x: ref.experts(x, p, cfg, first), route=route),
            "route": route}

    return {"the bias in the weights": faulty(bias_in_weights),
            "the renormalisation left out": faulty(unnormalised),
            "softmax for sigmoid": faulty(softmax),
            "the banks one expert off": {
                "reference": lambda p, x: ref.experts(x, p, cfg, first + 1)}}


def whole_faults(model_cfg, seed):
    """``{fault: forward}``: the reference's whole forward with one
    named fault each, for `check_slot` and `check_logits`."""
    import jax
    from benchmarks.suite.reference import lfm2_moe_ref as ref
    from deepspeed_tpu.models.lfm2_moe import HeldExperts

    sound_head = ref._head
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31) + 7))

    def untied(h, final_norm, embed, rows, eps):
        other = model_cfg.initializer_range * jax.random.normal(
            keys[0], embed.shape, embed.dtype)
        return sound_head(h, final_norm, other, rows, eps)

    def one_dense(params, tokens, cfg, **kw):
        # layer 1 an expert layer (fresh experts) where the model has
        # its second dense MLP
        import jax.numpy as jnp
        x = jnp.zeros((1, 1, model_cfg.hidden_size), model_cfg.dtype)
        fresh = jax.jit(HeldExperts(model_cfg).init)(
            keys[1], x, jnp.ones((1, 1), bool))["params"]
        faulty = dict(params, layers_1=dict(params["layers_1"],
                                            experts=fresh))
        return ref.forward(faulty, tokens, dict(cfg, num_dense_layers=1),
                           **kw)

    return {"an untied head": with_ref(ref.forward, _head=untied),
            "one dense layer for two": one_dense}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--whole", action="store_true",
                    help="also the engine's own checks, sound and faulty")
    ap.add_argument("--prompt", type=int, default=2900)
    ap.add_argument("--pages", type=int, default=512)
    ap.add_argument("--tokens", type=int, default=64,
                    help="tokens generated for the logits' check")
    ap.add_argument("--skip-layers", action="store_true",
                    help="the engine's own checks alone")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from benchmarks.suite import run
    from benchmarks.suite.drivers import serve_lfm2 as drv
    from deepspeed_tpu.models.lfm2_moe import (ATTENTION, Lfm2MoeLM,
                                               init_lfm2_moe_params)

    code, ctx, _ = run.prepare(CELL, args.seed, 51, 0)
    if code:
        return code
    cfg, corr = ctx.config, ctx.workload["correctness"]
    inf = ctx.workload["inference"]
    chunk, page, impl = inf["prefill_chunk"], inf["page_size"], \
        inf["attention_impl"]
    mc = drv.model_config(cfg)
    model = Lfm2MoeLM(mc)
    sound = init_lfm2_moe_params(
        model, jax.random.PRNGKey(args.seed % (2 ** 31)))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    f = open(os.path.join(out_dir, "fault_readings_lfm2.jsonl"), "w")

    def say(check, fault, reading):
        line = {"check": check, "fault": fault, "seed": args.seed, **reading}
        print(json.dumps(line), flush=True)
        f.write(json.dumps(line) + "\n")
        f.flush()

    def mixer(p=None, **kw):
        return drv.check_mixer(mc, cfg, p or sound, args.seed, chunk,
                               corr["mixer_rtol"], **kw)

    def attention(p=None, **kw):
        return drv.check_attention(
            mc, cfg, p or sound, args.seed, chunk, page, impl,
            corr["attention_rtol"], corr["attention_decode_rtol"], **kw)

    def experts(p=None, **kw):
        return drv.check_experts(mc, cfg, p or sound, args.seed, chunk,
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
        for fault, patched in program_faults(mc, args.seed, chunk).items():
            with patched():
                say("mixer", fault, mixer())
        # the program's weights at 3 bits against the sound reference's:
        # the three layers the checks read
        swapped = dict(sound, **{
            name: low(sound[name])
            for name in ("layers_0", mc.names(ATTENTION)[0])})
        for check, run_check, _ in groups:
            say(check, LOW, run_check(p=swapped, sound=sound))
        del swapped
    gc.collect()
    if not args.whole:
        return 0

    # --- the engine's own two programs at the whole depth, over a small
    # pool (two copies of the share's weights stand beside it) -----------
    from deepspeed_tpu.inference.engine import InferenceEngine

    config = dict(inf, seq_buckets=tuple(inf["seq_buckets"]),
                  n_pages=args.pages, sampling_seed=args.seed)
    engine = InferenceEngine(model, sound, config=config)
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(0, cfg["vocab_size"], args.prompt).tolist()

    class Tracker:
        prompts, tokens = {}, {}

    def whole(fault, forwards=(("", None),)):
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
            for name, forward in forwards:
                say("slot", name or fault, drv.check_slot(
                    ctx, engine, prompt, stages, forward=forward))
                say("logits", name or fault, drv.check_logits(
                    ctx, sound, chunk, Tracker, ["p"], forward=forward)[0])
        finally:
            engine.params = program_params

    whole("none", (("none", None), *whole_faults(mc, args.seed).items()))
    engine.params = low(sound)      # two copies: the reference keeps sound
    whole(LOW)
    return 0


if __name__ == "__main__":
    sys.exit(main())

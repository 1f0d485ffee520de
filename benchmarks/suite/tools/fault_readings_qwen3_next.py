#!/usr/bin/env python3
"""What the four checks of one layer on its own input
(``drivers/serve_qwen3_next.py``: ``check_state``, ``check_mixer``,
``check_attention``, ``check_experts``) read when something is wrong,
at the published widths on the chip: the readings the limits in the
cell's ``correctness`` block stand against. One JSON line a reading, on
stdout and in ``chiprun_out/fault_readings_qwen3_next.jsonl``.

A fault is put where it is cheapest to put and reads the same from
either side: most are given to the REFERENCE (a term left out, another
function), so that the sound program's distance from a faulty
reference is the faulty program's distance from the sound one; a
bfloat16 state, a padded tail let in, a stale slot and "weights at 3
bits of mantissa" (the next precision below the configuration's
bfloat16: float8_e4m3) are given to the program. Last, the cell's check
of generated tokens' logits on the whole share: sound and at 3 bits.

    python3 benchmarks/suite/tools/fault_readings_qwen3_next.py --seed 1
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

CELL = "serve-qwen3-next-80b-a3b-longchat"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--prompt", type=int, default=2900)
    ap.add_argument("--only", default="mixer,attention,experts,state,logits",
                    help="the checks to read, by name")
    ap.add_argument("--state-faults", default="",
                    help="of the state's faults, those whose name holds "
                    "one of these words (comma-separated; default: all)")
    ap.add_argument("--decode-steps", default="",
                    help="read the state after each of these numbers of "
                    "decoded tokens (default: the check's own 256)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.suite import run
    from benchmarks.suite.drivers import serve_qwen3_next as drv
    from benchmarks.suite.reference import qwen3_next_ref as ref
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.qwen3_next import (Qwen3NextLM,
                                                 init_qwen3_next_params)
    from deepspeed_tpu.ops import gated_delta

    code, ctx, _ = run.prepare(CELL, args.seed, 51, 0)
    if code:
        return code
    whole = ctx.config
    cfg = copy.deepcopy(whole)
    cfg["n_layer"] = 4              # one period: three mixers, an attention
    ctx.config = cfg
    model_cfg = drv.model_config(cfg)
    model = Qwen3NextLM(model_cfg)
    params = init_qwen3_next_params(model, jax.random.PRNGKey(args.seed))
    inf = ctx.workload["inference"]
    chunk, page = inf["prefill_chunk"], inf["page_size"]
    tol = ctx.workload["correctness"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out",
                            "fault_readings_qwen3_next.jsonl"), "w")

    only = set(args.only.split(","))

    def say(check, fault, reading):
        line = {"check": check, "fault": fault, **{
            k: v for k, v in reading.items()
            if isinstance(v, (int, float, bool))}}
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()

    made = []

    def at_3_bits():
        """The weights through float8_e4m3 and back, made once, and only
        for a check that is read."""
        if not made:
            made.append(jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
                if a.ndim >= 2 else a, params))
        return made[0]

    rng = np.random.default_rng(args.seed)
    vocab = cfg["vocab_size"]

    def rule_with(beta_one=False, no_decay=False, swap_heads=False):
        def rule(q, k, v, g, beta, state_at=None):
            if beta_one:
                beta = jnp.ones_like(beta)
            if no_decay:
                g = jnp.zeros_like(g)
            if swap_heads:          # value head h on key head h // 2 + 1
                q, k = jnp.roll(q, 2, axis=1), jnp.roll(k, 2, axis=1)
            return ref.delta_rule(q, k, v, g, beta, state_at)
        return rule

    # --- a mixer --------------------------------------------------------
    def mixer(fault, reference=None, program=None):
        if "mixer" not in only:
            return
        say("mixer", fault, drv.check_mixer(
            model_cfg, cfg, program() if program else params, args.seed,
            chunk, tol["mixer_rtol"], reference=reference))

    def net(**kw):
        return lambda p, x: ref.delta_net(x, p, cfg, rule=rule_with(**kw))[0]

    def plain_gated_norm(p, x):
        """The gated norm with a zero-centred weight (1 + w)."""
        return ref.delta_net(x, dict(p, norm_weight=1.0 + jnp.asarray(
            p["norm_weight"], jnp.float32)), cfg)[0]

    mixer("none")
    mixer("beta left out (1 for sigmoid(b))", net(beta_one=True))
    mixer("exp(g) left out (no decay)", net(no_decay=True))
    mixer("value heads on the next key head", net(swap_heads=True))
    mixer("the gated norm's weight zero-centred (1 + w)", plain_gated_norm)
    mixer("weights at 3 bits of mantissa (float8_e4m3)",
          lambda _, x: ref.delta_net(x, params["layers_0"]["mixer"], cfg)[0],
          program=at_3_bits)

    # --- the attention layer ---------------------------------------------
    def attention(fault, reference=None, program=None):
        if "attention" not in only:
            return
        say("attention", fault, drv.check_attention(
            model_cfg, cfg, program() if program else params, args.seed,
            chunk, page, "flash", tol["attention_rtol"], tol["attention_decode_rtol"],
            reference=reference))

    def with_cfg(**kw):
        c = dict(cfg, **kw)
        return lambda p, x: ref.attention(x, p, c)

    def ungated(p, x):
        zero = jnp.asarray(p["q_proj"], jnp.float32).reshape(
            -1, cfg["num_attention_heads"], 2, cfg["head_dim"])
        zero = zero.at[:, :, 1].set(0.0).reshape(p["q_proj"].shape)
        return 2.0 * ref.attention(x, dict(p, q_proj=zero), cfg)

    def swapped(p, x):
        def swap(w):
            w = jnp.asarray(w, jnp.float32)
            return w.reshape(w.shape[0], 2, -1)[:, ::-1].reshape(w.shape)
        return ref.attention(x, dict(p, k_proj=swap(p["k_proj"]),
                                     v_proj=swap(p["v_proj"])), cfg)

    def plain_norms(p, x):
        return ref.attention(x, dict(
            p, q_norm=jnp.asarray(p["q_norm"], jnp.float32) - 1.0,
            k_norm=jnp.asarray(p["k_norm"], jnp.float32) - 1.0), cfg)

    attention("none")
    attention("1/256 for 256^-0.5",
              lambda p, x: ref.attention(x, p, cfg, scale=1.0 / 256))
    attention("rotary on all 256 entries", with_cfg(partial_rotary_factor=1.0))
    attention("rotary at theta 1e4", with_cfg(rope_theta=1e4))
    attention("the output gate left out (sigmoid(0) x 2)", ungated)
    attention("key heads swapped", swapped)
    attention("head norms by w, not 1 + w", plain_norms)
    attention("weights at 3 bits of mantissa (float8_e4m3)",
              lambda _, x: ref.attention(x, params["layers_3"]["attn"], cfg),
              program=at_3_bits)

    # --- an expert layer ---------------------------------------------------
    def experts(fault, reference=None, program=None):
        if "experts" not in only:
            return
        say("experts", fault, drv.check_experts(
            model_cfg, cfg, program() if program else params, args.seed,
            chunk, inf["max_batch"], tol["expert_rtol"], reference=reference))

    first = model_cfg.experts_held[0]
    sound_route = ref.route

    def routed_by(route):
        def fn(p, x):
            ref.route = route
            try:
                return ref.experts(x, p, cfg, first)
            finally:
                ref.route = sound_route
        return fn

    def not_renormalised(n, p, c):
        probs = jax.nn.softmax(ref._mm(ref._f32(n), p["router"]), axis=-1)
        return jax.lax.top_k(probs, c["num_experts_per_tok"])

    def renormalised_over_held(n, p, c):
        w, chosen = sound_route(n, p, c)
        held = (chosen >= first) & (chosen < first + p["w_up"].shape[0])
        return w / jnp.maximum((w * held).sum(-1, keepdims=True), 1e-9), \
            chosen

    experts("none")
    experts("weights not renormalised", routed_by(not_renormalised))
    experts("renormalised over the held experts alone",
            routed_by(renormalised_over_held))
    experts("the shared expert's gate left out (1 for sigmoid)",
            lambda p, x: ref.routed(x, p, cfg, first) + ref.shared(
                x, dict(p, shared_expert_gate=0.0 * jnp.asarray(
                    p["shared_expert_gate"], jnp.float32))) * 2.0)
    experts("the shared expert left out",
            lambda p, x: ref.routed(x, p, cfg, first))
    experts("the banks one expert off",
            lambda p, x: ref.experts(x, p, cfg, first + 1))
    experts("weights at 3 bits of mantissa (float8_e4m3)",
            lambda _, x: ref.experts(x, params["layers_0"]["experts"], cfg,
                                     first),
            program=at_3_bits)

    # --- the state in the engine's own leaves --------------------------------
    small = dict(max_batch=4, seq_buckets=(inf["seq_buckets"][0],),
                 n_pages=100, prefill_chunk=chunk, page_size=page,
                 attention_impl="flash")
    prompt = rng.integers(0, vocab, args.prompt).tolist()
    sound_chunked, sound_step = (gated_delta.gated_delta_chunked,
                                 gated_delta.gated_delta_step)

    def rounded(fn):
        # not a cast there and back: on the chip XLA drops such a pair
        # (my chip run, PR 43: the readings came out bit for bit sound)
        def wrapped(*a, **kw):
            o, s = fn(*a, **kw)
            return o, jax.lax.reduce_precision(s, 8, 7)
        return wrapped

    words = [w for w in args.state_faults.split(",") if w]
    lengths = [int(n) for n in args.decode_steps.split(",") if n] or [256]

    def state(fault, patch=None, reference=None, program=None):
        if "state" not in only or (
                words and not any(w in fault for w in words)):
            return
        gc.collect()
        if fault == "a bfloat16 state":
            gated_delta.gated_delta_chunked = rounded(sound_chunked)
            gated_delta.gated_delta_step = rounded(sound_step)
        try:
            engine = InferenceEngine(model, program() if program else params,
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
            for n in lengths:
                say("state", fault, drv.check_state(
                    ctx, engine, prompt, [7, 8, 9], reference=reference,
                    decode_steps=n))
        finally:
            gated_delta.gated_delta_chunked, gated_delta.gated_delta_step = \
                sound_chunked, sound_step
        del engine

    def ref_rule(**kw):
        """The reference's first layer under a faulty rule."""
        rule = rule_with(**kw)

        def forward(p, seq, c, state_at=None, **_):
            n = ref.norm(ref._f32(p["embed"][jnp.asarray(seq)]),
                         p["layers_0"]["input_norm"]["weight"],
                         c["rms_norm_eps"])
            _, kept = jax.jit(lambda n, q, at: ref.delta_net(
                n, q, c, at, rule=rule))(n, p["layers_0"]["mixer"],
                                         jnp.asarray(state_at, jnp.int32))
            return None, {"layers_0": kept}, {}
        return forward

    state("none")
    state("a bfloat16 state")
    state("a padded tail let into the state", patch="unmasked_tail")
    state("the slot's last tenant's state kept", patch="stale_state")
    state("beta left out (1 for sigmoid(b))",
          reference=ref_rule(beta_one=True))
    state("exp(g) left out (no decay)", reference=ref_rule(no_decay=True))
    state("weights at 3 bits of mantissa (float8_e4m3)",
          program=at_3_bits,
          reference=lambda _, seq, c, **kw: ref.forward(params, seq, c,
                                                        **kw))
    del params, made[:]
    if "logits" not in only:
        return 0

    # --- the generated tokens' logits, the whole share --------------------
    ctx.config = whole

    def logits(fault, low=False):
        """A prompt and 128 greedy tokens through a fresh engine of the
        whole share, then the cell's own check of them against the
        reference (on the sound weights, made again from the seed,
        where the engine's were at 3 bits), and `check_state`'s
        readings of every layer of that engine."""
        gc.collect()
        model = Qwen3NextLM(drv.model_config(whole))
        key = jax.random.PRNGKey(args.seed)
        params = init_qwen3_next_params(model, key)
        if low:
            # a leaf at a time, so that the share is held once, and each
            # cast a program of its own: inside one jitted program XLA
            # drops a cast there and back (PR 43's first such run read
            # the sound weights' numbers to the digit)
            leaves, tree = jax.tree_util.tree_flatten(params)
            del params
            for i, a in enumerate(leaves):
                if a.ndim >= 2:
                    leaves[i] = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            del a
            params = tree.unflatten(leaves)
            del leaves
        bucket = inf["seq_buckets"][0]
        eng = InferenceEngine(model, params, config=dict(
            small, seq_buckets=(bucket,)))
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
        stages = drv.slot_readings(eng, text, toks[:3],
                                   decode_steps=lengths[0])
        if low:
            del eng, params
            gc.collect()
            eng = type("E", (), {
                "params": init_qwen3_next_params(model, key),
                "max_seq": bucket, "prefill_chunk": chunk})
        say("logits", fault, drv.check_logits(ctx, eng, tracker, ["r"])[0])
        say("state, the whole share", fault, drv.check_state(
            ctx, eng, text, toks[:3], stages=stages))

    logits("none")
    logits("weights at 3 bits of mantissa (float8_e4m3)", low=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

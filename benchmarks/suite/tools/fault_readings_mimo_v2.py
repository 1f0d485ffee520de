#!/usr/bin/env python3
"""What the checks of ``drivers/serve_mimo_v2.py`` (``check_attention``
of a window layer and of a full layer, ``check_experts``, ``check_slot``,
``check_logits``) read when something is wrong, at the published widths
on the chip: the readings the limits in the cell's ``correctness`` block
stand against. One JSON line a reading, on stdout and in
``chiprun_out/fault_readings_mimo_v2.jsonl``.

A fault is put where it is cheapest to put and reads the same from
either side: most are given to the REFERENCE (another window, the sink
left out or given a value, the other base, another scale), so that the
sound program's distance from a faulty reference is the faulty
program's distance from the sound one; a mask by ring entry and not by
position, a prefill that leaves the ring to its last tenant and "weights
at 3 bits of mantissa" (the next precision below the configuration's
bfloat16: float8_e4m3) are given to the program. Last, on the whole
share: the cell's check of generated tokens' logits and of what a slot
holds, sound and at 3 bits.

    python3 benchmarks/suite/tools/fault_readings_mimo_v2.py --seed 1
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

CELL = "serve-mimo-v2.5-shortlong"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--prompt", type=int, default=9000)
    ap.add_argument("--only", default="window,full,experts,share",
                    help="the checks to read, by name")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.suite import run
    from benchmarks.suite.drivers import serve_mimo_v2 as drv
    from benchmarks.suite.reference import mimo_v2_ref as ref
    from deepspeed_tpu.inference import cache as cache_mod
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.mimo_v2 import MimoV2LM, init_mimo_v2_params

    code, ctx, _ = run.prepare(CELL, args.seed, 51, 0)
    if code:
        return code
    whole = ctx.config
    cfg = copy.deepcopy(whole)
    cfg["n_layer"] = 2              # a full layer (dense), a window layer
    model_cfg = drv.model_config(cfg)
    model = MimoV2LM(model_cfg)
    params = init_mimo_v2_params(model, jax.random.PRNGKey(args.seed))
    inf = ctx.workload["inference"]
    chunk, page = inf["prefill_chunk"], inf["page_size"]
    tol = ctx.workload["correctness"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out",
                            "fault_readings_mimo_v2.jsonl"), "w")
    only = set(args.only.split(","))

    def say(check, fault, reading):
        line = {"check": check, "fault": fault, **{
            k: v for k, v in reading.items()
            if isinstance(v, (int, float, bool))}}
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()

    def low(tree):
        """Weights through float8_e4m3 and back, a leaf at a time, each
        cast a program of its own (inside one jitted program XLA drops a
        cast there and back: configs' PR 43 note)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        for i, a in enumerate(leaves):
            if a.ndim >= 2:
                leaves[i] = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        return treedef.unflatten(leaves)

    def with_cfg(**kw):
        return dict(cfg, **kw)

    def faulty_attention(which, mask=None, sink_value=False, scale=None,
                         roll_heads=0):
        """The reference's attention written out once more with a hook
        for each fault that no key of the configuration reaches: a head
        at a time, ``[T, T]`` scores (the checks' inputs are two calls
        long)."""
        def attention(p, x):
            T = x.shape[0]
            Hq, Hkv, D, Dv, theta, sink, window = ref.kind_of(cfg, which)
            r = int(cfg["partial_rotary_factor"] * D)
            k, v = ref.keys_values(x, p, cfg, which)
            k = jnp.roll(k, roll_heads, axis=1)
            q = ref.rotary(ref._mm(x, p["q_proj"]).reshape(T, Hq, D),
                           jnp.arange(T), r, theta)
            t, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
            seen = (j <= t) & ((t - j < window) if window else True)
            if mask is not None:
                seen = mask(t, j)
            ys = []
            for h in range(Hq):
                kh, vh = k[:, h // (Hq // Hkv)], v[:, h // (Hq // Hkv)]
                s = jnp.matmul(q[:, h], kh.T, precision=ref.HIGHEST) * \
                    (scale or D ** -0.5)
                s = jnp.where(seen, s, -1e30)
                if sink:
                    s = jnp.concatenate(
                        [s, jnp.full((T, 1), p["sink"][h], jnp.float32)], 1)
                w = jax.nn.softmax(s, axis=-1)
                y = jnp.matmul(w[:, :T], vh, precision=ref.HIGHEST)
                if sink and sink_value:     # the sink as a key whose
                    y = y + w[:, T:] * vh[:1]       # value is token 0's
                ys.append(y)
            return ref._mm(jnp.stack(ys, 1).reshape(T, -1), p["o_proj"])
        return attention

    def attention(which, fault, reference=None, program=None):
        """``program``: the program's weights, the reference on the
        sound ones."""
        say(which, fault, drv.check_attention(
            model_cfg, cfg, (program or (lambda: params))(), which,
            args.seed, chunk, page, inf["attention_impl"],
            tol[f"{which}_rtol"], tol[f"{which}_decode_rtol"],
            reference=reference, sound=params))

    def by_cfg(which, **kw):
        c = with_cfg(**kw)
        return lambda p, x: ref.attention(x, p, c, which)

    made = []

    def at_3_bits():
        if not made:
            made.append(low(params))
        return made[0]

    for which in ("window", "full"):
        if which not in only:
            continue
        attention(which, "none")
        attention(which, "another score scale (128^-0.5)",
                  faulty_attention(which, scale=128 ** -0.5))
        attention(which, "rotary on all 192 entries",
                  by_cfg(which, partial_rotary_factor=1.0))
        attention(which, "the value scale left out",
                  by_cfg(which, attention_value_scale=1.0))
        attention(which, "query groups one key head on",
                  faulty_attention(which, roll_heads=1))
        attention(which, "weights at 3 bits of mantissa (float8_e4m3)",
                  program=at_3_bits)
        if which == "full":
            attention(which, "the window layers' base (theta 1e4)",
                      by_cfg(which, rope_theta=1e4))
            continue
        attention(which, "window 127", by_cfg(which, sliding_window=127))
        attention(which, "window 129", by_cfg(which, sliding_window=129))
        attention(which, "the sink left out",
                  by_cfg(which, add_swa_attention_sink_bias=False))
        attention(which, "the sink given a value",
                  faulty_attention(which, sink_value=True))
        attention(which, "the full layers' base (theta 1e7)",
                  by_cfg(which, swa_rope_theta=1e7))
        # what a kernel that masked by where a key lies would see: every
        # position the ring's two pages hold, whatever its distance
        attention(which, "mask by ring entry, not by position",
                  faulty_attention(which, mask=lambda t, j: (j <= t) & (
                      j >= (t // page - 1) * page)))

    if "experts" in only:
        first = model_cfg.experts_held[0]

        def experts(fault, reference=None, program=None):
            say("experts", fault, drv.check_experts(
                model_cfg, cfg, (program or (lambda: params))(), args.seed,
                chunk, inf["max_batch"], tol["expert_rtol"],
                reference=reference, sound=params))

        def not_renormalised(p, x):
            return ref.experts(x, p, with_cfg(norm_topk_prob=False), first)

        def biased_weights(p, x):
            moved = dict(p, router=p["router"] * 1.5)
            return ref.experts(x, moved, cfg, first)

        def one_expert_off(p, x):
            return ref.experts(x, p, cfg, first + 1)

        experts("none")
        experts("weights not renormalised", not_renormalised)
        experts("the router's product half again as large", biased_weights)
        experts("the banks one expert off", one_expert_off)
        experts("weights at 3 bits of mantissa (float8_e4m3)",
                program=at_3_bits)
    del params, made[:]
    if "share" not in only:
        return 0

    # --- the whole share: a slot, and generated tokens' logits -----------
    ctx.config = whole
    rng = np.random.default_rng(args.seed)
    vocab = whole["vocab_size"]
    small = dict(inf, max_batch=4, n_pages=600,
                 seq_buckets=tuple(inf["seq_buckets"]))
    text = rng.integers(0, vocab, args.prompt).tolist()

    def share(fault, low_weights=False, patch=None):
        """A prompt and 128 greedy tokens through a fresh engine of the
        whole share, then the cell's own checks: what the slot holds
        (`check_slot`) and the generated tokens' logits against the
        reference on the sound weights (made again from the seed where
        the engine's were at 3 bits)."""
        gc.collect()
        model = MimoV2LM(drv.model_config(whole))
        key = jax.random.PRNGKey(args.seed)
        params = init_mimo_v2_params(model, key)
        if low_weights:
            params = low(params)
        real = cache_mod.paged_write_kv
        if patch:
            cache_mod.paged_write_kv = patch(real)
        try:
            eng = InferenceEngine(model, params, config=small)
            table = np.concatenate([
                np.arange(eng.pages_per_row, 0, -1, dtype=np.int32),
                np.asarray([2, 1], np.int32)])
            toks = [int(eng.prefill(0, text, table).argmax())]
            tokens, positions = np.zeros(4, np.int32), np.zeros(4, np.int32)
            tables = np.zeros((4, eng.table_width), np.int32)
            tables[0] = table
            for j in range(127):
                tokens[0], positions[0] = toks[-1], len(text) + j
                toks.append(int(eng.decode(tokens, positions,
                                           tables)[0][0]))
            stages = drv.slot_readings(eng, text, toks[:3])
        finally:
            cache_mod.paged_write_kv = real
        tracker = type("T", (), {"prompts": {"r": text},
                                 "tokens": {"r": toks}})
        holder = type("E", (), {
            "params": init_mimo_v2_params(model, key) if low_weights
            else params, "model": model, "prefill_chunk": chunk})
        del eng, params
        gc.collect()
        say("slot, the whole share", fault, drv.check_slot(
            ctx, holder, text, toks[:3], stages=stages))
        say("logits", fault, drv.check_logits(
            ctx, holder.params, chunk, tracker, ["r"])[0])

    def ring_left_to_its_tenant(real):
        """A prefill whose chunks never reach the ring: every ring write
        of a chunk lands on the trash page."""
        def write(layer_cache, k_new, v_new, positions, page_table,
                  ring=False, n_valid=None):
            if ring and positions.shape[1] > 1:
                page_table = jnp.zeros_like(page_table)
            return real(layer_cache, k_new, v_new, positions, page_table,
                        ring=ring, n_valid=n_valid)
        return write

    share("none")
    share("a prefill that leaves the ring to its last tenant",
          patch=ring_left_to_its_tenant)
    share("weights at 3 bits of mantissa (float8_e4m3)", low_weights=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

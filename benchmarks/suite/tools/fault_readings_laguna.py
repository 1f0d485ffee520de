#!/usr/bin/env python3
"""What the checks of ``drivers/serve_laguna.py`` (``check_attention``
of a window layer and of a full layer, ``check_experts``, ``check_slot``,
``check_logits``) read when something is wrong, at the published widths
on the chip: the readings the limits in the cell's ``correctness`` block
stand against. One JSON line a reading, on stdout and in
``chiprun_out/fault_readings_laguna.jsonl``.

A fault is put where it is cheapest to put and reads the same from
either side: most are given to the REFERENCE, through a key of its
configuration (another window, plain rotary for YaRN, the other kind's
base or rotary width, the factor or the renormalisation left out),
through its weights (the next head's gate: ``g_proj``'s columns one on;
query groups one key head on: ``k_proj``'s and ``v_proj``'s heads one
on; the banks one expert off) or through a hook of the attention
written out once more below (the gate left out or an element, a mask by
ring entry), so that the sound program's distance from a faulty
reference is the faulty program's distance from the sound one; "weights
at 3 bits of mantissa" (the next precision below the configuration's
bfloat16: float8_e4m3) and a prefill that leaves the ring to its last
tenant are given to the program. Last, on the whole share: the cell's
check of generated tokens' logits and of what a slot holds, sound, with
the stale ring and at 3 bits.

    python3 benchmarks/suite/tools/fault_readings_laguna.py --seed 1

`tests/benchmark_suite/test_laguna_rehearsal.py` runs the same faults
at toy size on the CPU (`attention_faults`, `expert_faults`).
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

CELL = "serve-laguna-s-2.1-codegen"
FULL, WINDOW = "full", "window"
LOW = "weights at 3 bits of mantissa (float8_e4m3)"


def low(tree):
    """Weights through float8_e4m3 and back, a leaf at a time, each cast
    a program of its own (inside one jitted program XLA drops a cast
    there and back: `tools/fault_readings_mimo_v2.py`'s note)."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    for i, a in enumerate(leaves):
        if a.ndim >= 2:
            leaves[i] = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
    return treedef.unflatten(leaves)


def with_rope(cfg, which, **kw):
    """``cfg`` with keys of one kind's ``rope_parameters`` replaced."""
    from benchmarks.suite.reference import laguna_ref as ref

    out = copy.deepcopy(cfg)
    out["rope_parameters"][ref.PUBLISHED[which]].update(kw)
    return out


def faulty_attention(cfg, which, mask=None, gate="head"):
    """The reference's attention written out once more with a hook for
    each fault that neither a key of the configuration nor the weights
    reach: ``mask(t, j)`` in the causal window's place; ``gate`` ``none``
    (left out) or ``element`` (entry ``d`` of head ``h`` takes the gate
    of column ``(h head_dim + d) mod heads``: a gate an element under
    weights that hold a column a head). A head at a time, its scores
    ``[T, T]``."""
    import jax
    import jax.numpy as jnp
    from benchmarks.suite.reference import laguna_ref as ref

    def attention(p, x):
        T = x.shape[0]
        Hq, Hkv, D, window, rope = ref.kind_of(cfg, which)
        k, v = ref.keys_values(x, p, cfg, which)
        t, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        seen = (j <= t) & ((t - j < window) if window else True)
        if mask is not None:
            seen = mask(t, j)

        def head(h):
            kh, vh = k[:, h // (Hq // Hkv)], v[:, h // (Hq // Hkv)]
            w_q = ref._f32(jax.lax.dynamic_slice_in_dim(
                p["q_proj"], h * D, D, 1))
            q = ref.rotary(jnp.matmul(x, w_q, precision=ref.HIGHEST)[:, None],
                           jnp.arange(T), rope, D)[:, 0]
            s = jnp.matmul(q, kh.T, precision=ref.HIGHEST) * D ** -0.5
            w = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            return jnp.matmul(w, vh, precision=ref.HIGHEST)

        y = jnp.moveaxis(jax.lax.map(head, jnp.arange(Hq)), 0, 1)
        g = jax.nn.sigmoid(ref._mm(x, p["g_proj"]))            # [T, Hq]
        if gate == "head":
            y = y * g[:, :, None]
        elif gate == "element":
            y = y * g[:, (jnp.arange(Hq * D) % Hq).reshape(Hq, D)]
        return ref._mm(y.reshape(T, Hq * D), p["o_proj"])
    return attention


def attention_faults(cfg, which, page):
    """``{fault: (p, x) -> y}``: the reference's attention of kind
    ``which`` with one named fault each."""
    import jax.numpy as jnp
    from benchmarks.suite.reference import laguna_ref as ref

    D = cfg["head_dim"]
    other = WINDOW if which == FULL else FULL
    ring = cfg["sliding_window"] // page + 1
    queries = ref.kind_of(cfg, which)[0] // cfg["num_key_value_heads"]

    def by_cfg(c):
        return lambda p, x: ref.attention(x, p, c, which)

    def rolled(**shift):
        """The reference on weights whose columns are ``shift`` on."""
        def attention(p, x):
            moved = dict(p, **{name: jnp.roll(p[name], -n, axis=1)
                               for name, n in shift.items()})
            return ref.attention(x, moved, cfg, which)
        return attention

    theirs = ref.kind_of(cfg, other)[4]["rope_theta"]
    faults = {
        "the gate left out": faulty_attention(cfg, which, gate="none"),
        "the gate of the next head": rolled(g_proj=1),
        "a gate an element": faulty_attention(cfg, which, gate="element"),
        f"query groups one key head on (at {queries})":
            rolled(k_proj=D, v_proj=D),
        f"the {other} layers' base (theta {theirs:g})":
            by_cfg(with_rope(cfg, which, rope_theta=theirs)),
    }
    if which == FULL:
        faults.update({
            "plain rotary for YaRN":
                by_cfg(with_rope(cfg, which, rope_type="default")),
            "attention_factor left out":
                by_cfg(with_rope(cfg, which, attention_factor=1.0)),
            f"rotary on all {D} entries":
                by_cfg(with_rope(cfg, which, partial_rotary_factor=1.0)),
        })
    else:
        w = cfg["sliding_window"]
        faults.update({
            f"window {w - 1}": by_cfg(dict(cfg, sliding_window=w - 1)),
            f"window {w + 1}": by_cfg(dict(cfg, sliding_window=w + 1)),
            # what a kernel that masked by where a key lies would see:
            # every position the ring's pages hold, whatever its distance
            "mask by ring entry, not by position": faulty_attention(
                cfg, which, mask=lambda t, j: (j <= t) & (
                    j >= (t // page - (ring - 1)) * page)),
            f"rotary on {D // 2} entries":
                by_cfg(with_rope(cfg, which, partial_rotary_factor=0.5)),
        })
    return faults


def expert_faults(cfg, first):
    """``{fault: (p, x) -> y}``: the reference's expert layer with one
    named fault each."""
    import jax
    import jax.numpy as jnp
    from benchmarks.suite.reference import laguna_ref as ref

    def by_cfg(**kw):
        c = dict(cfg, **kw)
        return lambda p, x: ref.experts(x, p, c, first)

    def sigmoid_scores(p, x):
        """The held experts under sigmoid scores in the softmax's place
        (chosen, renormalised and scaled as before)."""
        s = jax.nn.sigmoid(ref._mm(x, p["router"]))
        w, chosen = jax.lax.top_k(s, cfg["num_experts_per_tok"])
        w = w / w.sum(-1, keepdims=True) * cfg["moe_routed_scaling_factor"]
        y = ref.shared_expert(x, p)
        for e in range(p["w_gate"].shape[0]):
            mine = (w * (chosen == first + e)).sum(-1)
            h = jax.nn.silu(ref._mm(x, p["w_gate"][e])) * \
                ref._mm(x, p["w_up"][e])
            y = y + mine[:, None] * ref._mm(h, p["w_down"][e])
        return y

    def shared_gated(p, x):
        """The family's gate on the shared expert, which this model has
        no weights for: a sigmoid of the input times the router's first
        column."""
        opened = jax.nn.sigmoid(ref._mm(x, p["router"][:, :1]))
        return ref.experts(x, p, cfg, first, shared=False) + \
            opened * ref.shared_expert(x, p)

    return {
        f"the factor {cfg['moe_routed_scaling_factor']:g} left out":
            by_cfg(moe_routed_scaling_factor=1.0),
        "the renormalisation left out": by_cfg(norm_topk_prob=False),
        "sigmoid for softmax": sigmoid_scores,
        "the shared expert left out":
            lambda p, x: ref.experts(x, p, cfg, first, shared=False),
        "the shared expert gated": shared_gated,
        "the banks one expert off":
            lambda p, x: ref.experts(x, p, cfg, first + 1),
    }


def ring_left_to_its_tenant(real):
    """A prefill whose chunks never reach the ring: every ring write of
    a chunk lands on the trash page."""
    import jax.numpy as jnp

    def write(layer_cache, k_new, v_new, positions, page_table,
              ring=False, n_valid=None):
        if ring and positions.shape[1] > 1:
            page_table = jnp.zeros_like(page_table)
        return real(layer_cache, k_new, v_new, positions, page_table,
                    ring=ring, n_valid=n_valid)
    return write


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--prompt", type=int, default=9000)
    ap.add_argument("--only", default="window,full,experts,share",
                    help="the checks to read, by name")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmarks.suite import run
    from benchmarks.suite.drivers import serve_laguna as drv
    from deepspeed_tpu.inference import cache as cache_mod
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.laguna import LagunaLM, init_laguna_params

    code, ctx, _ = run.prepare(CELL, args.seed, 51, 0)
    if code:
        return code
    whole = ctx.config
    cfg = copy.deepcopy(whole)
    cfg["n_layer"] = 2      # a full layer (dense), a window layer (experts)
    model_cfg = drv.model_config(cfg)
    model = LagunaLM(model_cfg)
    params = init_laguna_params(model, jax.random.PRNGKey(args.seed))
    inf = ctx.workload["inference"]
    chunk, page = inf["prefill_chunk"], inf["page_size"]
    tol = ctx.workload["correctness"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out",
                            "fault_readings_laguna.jsonl"), "w")
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
        if not made:
            made.append(low(params))
        return made[0]

    for which in (WINDOW, FULL):
        if which not in only:
            continue

        def attention(fault, reference=None, program=None):
            say(which, fault, drv.check_attention(
                model_cfg, cfg, (program or (lambda: params))(), which,
                args.seed, chunk, page, inf["attention_impl"],
                tol[f"{which}_rtol"], tol[f"{which}_decode_rtol"],
                reference=reference, sound=params))

        attention("none")
        for fault, reference in attention_faults(cfg, which, page).items():
            attention(fault, reference)
        attention(LOW, program=at_3_bits)

    if "experts" in only:
        def experts(fault, reference=None, program=None):
            say("experts", fault, drv.check_experts(
                model_cfg, cfg, (program or (lambda: params))(), args.seed,
                chunk, inf["max_batch"], tol["expert_rtol"],
                reference=reference, sound=params))

        experts("none")
        for fault, reference in expert_faults(
                cfg, model_cfg.experts_held[0]).items():
            experts(fault, reference)
        experts(LOW, program=at_3_bits)
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
        model = LagunaLM(drv.model_config(whole))
        key = jax.random.PRNGKey(args.seed)
        params = init_laguna_params(model, key)
        if low_weights:
            params = low(params)
        real = cache_mod.paged_write_kv
        if patch:
            cache_mod.paged_write_kv = patch(real)
        try:
            eng = InferenceEngine(model, params, config=small)
            ring = eng.spec.ring_pages
            table = np.concatenate([
                np.arange(eng.pages_per_row, 0, -1, dtype=np.int32),
                np.arange(ring, 0, -1, dtype=np.int32)])
            toks = [int(eng.prefill(0, text, table).argmax())]
            tokens, positions = np.zeros(4, np.int32), np.zeros(4, np.int32)
            tables = np.zeros((4, eng.table_width), np.int32)
            tables[0] = table
            for j in range(127):
                tokens[0], positions[0] = toks[-1], len(text) + j
                toks.append(int(eng.decode(tokens, positions,
                                           tables)[0][0]))
            stages = drv.parts.slot_readings(eng, text, toks[:3])
        finally:
            cache_mod.paged_write_kv = real
        tracker = type("T", (), {"prompts": {"r": text},
                                 "tokens": {"r": toks}})
        holder = type("E", (), {
            "params": init_laguna_params(model, key) if low_weights
            else params, "model": model, "prefill_chunk": chunk})
        del eng, params
        gc.collect()
        say("slot, the whole share", fault, drv.check_slot(
            ctx, holder, text, toks[:3], stages=stages))
        say("logits", fault, drv.check_logits(
            ctx, holder.params, chunk, tracker, ["r"])[0])

    share("none")
    share("a prefill that leaves the ring to its last tenant",
          patch=ring_left_to_its_tenant)
    share(LOW, low_weights=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

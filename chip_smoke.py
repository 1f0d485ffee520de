#!/usr/bin/env python3
"""chip_smoke.py — does the program still start on the chip?

A SMOKE, not a benchmark: it drives the two main paths once, at the
full width of GPT-2 350M (24 layers x 1024 x 16 heads, vocab 50257,
seeded random weights), through the entry points a user calls, and
checks that what comes out is right by the repo's own means. Every
number it prints is a fact about one run (compile seconds, peak bytes,
compile-cache hits) — none is a rate, and none is performance.

  python chip_smoke.py            one chip: train, checkpoint, serve
  python chip_smoke.py --chips 4  four chips: ZeRO-2 dp=4 vs one device

One chip (what the driver runs):

1. training — `deepspeed_tpu.initialize` -> `engine.train_batch` x 4 on
   a repeated batch (bf16, flash attention, bs8 x seq1024), then
   `engine.save_checkpoint`. Checks: the step compiled once, the loss
   is finite and falls, the compiled HLO holds the flash kernels
   (`tpu_custom_call`), `device.memory_stats()` reports, the checkpoint
   restores bit-exact and the next step's loss repeats.
2. serving — the checkpoint step 1 wrote, through `ds_tpu_serve
   --checkpoint <dir> --n-head 16` (`inference/serve.py:main`): max_batch
   8, max_seq 1024, seeded requests, twice: the defaults (dense
   attention, pages of two prefill chunks) and flash attention over
   pages of `page_size`. Checks: every request
   completes, each run compiles exactly 2 programs, the flash decode
   HLO holds the compiled kernel, and the two runs agree — greedy
   tokens equal, or (where float near-ties split them) logits within a
   stated tolerance of the dense run's; the line says which.

The LAST stdout line is `{"ok": true, "device": {...}}` only when every
phase passed at full width on a TPU. Without a TPU the script exits 2
before doing anything. `--reduced` is the CPU rehearsal (tiny widths,
Pallas interpret mode): it runs the same phases and can never print
`"ok": true` — it exits 3.

One process touches JAX; nothing is spawned. The compile cache lives
where `JAX_COMPILATION_CACHE_DIR` says, else in `<checkout>/.jax_cache`.
"""

import argparse
import contextlib
import gc
import io
import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

EXIT_NO_TPU = 2
EXIT_REHEARSAL = 3

# logits of two attention implementations may differ by float rounding;
# a greedy token may then flip where the top two logits nearly tie.
# Accepted: |dlogit| <= LOGIT_RTOL * max|dense logit| everywhere the two
# runs saw the same inputs, and at a flip the dense run's own logits
# rank the other token within twice that of its pick.
LOGIT_RTOL = 2.0 ** -7


TRAIN_STEPS = 4         # before the checkpoint, one chip
ZERO2_STEPS = 3         # each of the two four-chip runs

# The audit runs its whole rule catalog and every finding is printed.
# An error fails the smoke, except `peak_memory`'s: its default budget
# is a formula in parameter bytes that leaves 3x for activations, which
# a no-remat bs8 x seq1024 step exceeds by design — the device's own
# `memory_stats()` is what says whether the step fits.
NON_FATAL_RULES = ("peak_memory",)


def audit_step(engine, batch, hold=()):
    """The repo's own audit of the compiled train step: lowers it with
    train_batch's exact avals and runs the rule catalog over the HLO.
    ``hold``: rules that may raise nothing at all, not even a warning."""
    from deepspeed_tpu.analysis import audit_engine

    report = audit_engine(engine, batch)
    fatal = [f"{f.rule}: {f.message}" for f in report.findings
             if f.rule in hold or (f.severity == "error" and
                                   f.rule not in NON_FATAL_RULES)]
    check(not fatal, f"compiled-step audit: {fatal}")
    findings = [{"rule": f.rule, "severity": f.severity,
                 "message": f.message[:300]} for f in report.findings]
    return report, findings


class SmokeFailure(Exception):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def emit(phase, **facts):
    """One JSON line per phase fact sheet (never the last line)."""
    print(json.dumps({"smoke": phase, **facts}, sort_keys=True),
          flush=True)


def sizes(reduced):
    if reduced:
        return dict(name="gpt2_tiny (REDUCED rehearsal)", n_layer=2,
                    n_embd=64, n_head=4, vocab_size=256, seq=128,
                    batch=8, prefill_chunk=16, page_size=32,
                    prompt_lo=20, prompt_hi=60, max_new=8, n_requests=6)
    return dict(name="gpt2_350m", n_layer=24, n_embd=1024, n_head=16,
                vocab_size=50257, seq=1024, batch=8, prefill_chunk=64,
                page_size=128, prompt_lo=200, prompt_hi=400, max_new=32,
                n_requests=6)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def build_train_engine(sz, seed, mesh=None, zero_stage=0):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (
        GPT2Config, GPT2LMHead, init_gpt2_params, make_gpt2_loss_fn)

    cfg = GPT2Config(vocab_size=sz["vocab_size"], n_positions=sz["seq"],
                     n_embd=sz["n_embd"], n_layer=sz["n_layer"],
                     n_head=sz["n_head"], use_flash_attention=True)
    model = GPT2LMHead(cfg)
    params = init_gpt2_params(model, jax.random.PRNGKey(seed),
                              seq_len=sz["seq"])
    config = {
        "train_batch_size": sz["batch"],
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": zero_stage},
        "optimizer": {"type": "Adam", "params": {"lr": 3e-4}},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=config, loss_fn=make_gpt2_loss_fn(model), params=params,
        mesh=mesh)
    return engine


def seeded_batch(sz, seed):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(
        0, sz["vocab_size"], (sz["batch"], sz["seq"])).astype(np.int32)}


def run_steps(engine, batch, n):
    """``n`` train_batch calls; returns (losses, seconds per call)."""
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(batch)))   # blocks
        secs.append(round(time.perf_counter() - t0, 3))
    return losses, secs


def check_losses(losses):
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")


def memory_facts(devices, on_tpu):
    out = []
    for d in devices:
        ms = d.memory_stats()
        check(ms is not None or not on_tpu,
              f"device {d} reports no memory_stats()")
        if ms is not None:
            out.append({"device": d.id,
                        "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                        "bytes_in_use": ms.get("bytes_in_use"),
                        "bytes_limit": ms.get("bytes_limit")})
    return out


def host_copy(tree):
    import jax
    return jax.tree_util.tree_map(lambda a: np.array(a), tree)


def trees_equal(a, b):
    import jax
    la, lb = (jax.tree_util.tree_leaves(t) for t in (a, b))
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def train_phase(sz, seed, ckpt_dir, on_tpu):
    import jax
    from deepspeed_tpu.analysis import compiled_cache_size
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.telemetry import compile_cache

    # one device whatever the host holds: this is the one-chip path
    engine = build_train_engine(
        sz, seed, mesh=build_mesh(devices=jax.devices()[:1]))
    batch = seeded_batch(sz, seed)
    losses, secs = run_steps(engine, batch, TRAIN_STEPS)
    check_losses(losses)
    check(compiled_cache_size(engine) == 1,
          f"train step has {compiled_cache_size(engine)} jit cache "
          f"entries after {TRAIN_STEPS} same-shape steps (expected 1)")

    report, findings = audit_step(engine, batch)
    kernels = report.hlo_text.count("tpu_custom_call")
    check(kernels > 0 or not on_tpu,
          "no tpu_custom_call in the compiled train step: the flash "
          "attention kernels are not in the program")
    check(compiled_cache_size(engine) == 1,
          "the audit's lowering added a jit cache entry")

    # checkpoint round trip: save, step on, restore, repeat the step
    check(engine.save_checkpoint(ckpt_dir), "save_checkpoint failed")
    saved_params = host_copy(engine.params)
    saved_step = engine.global_steps
    (after_save,), _ = run_steps(engine, batch, 1)
    path, _ = engine.load_checkpoint(ckpt_dir)
    check(path is not None, f"no checkpoint restored from {ckpt_dir}")
    check(engine.global_steps == saved_step,
          f"restored step {engine.global_steps} != saved {saved_step}")
    check(trees_equal(engine.params, saved_params),
          "restored params differ from the params that were saved")
    del saved_params
    (replayed,), _ = run_steps(engine, batch, 1)
    check(replayed == after_save,
          f"step after restore gave loss {replayed}, the same step "
          f"before it gave {after_save}")
    check(compiled_cache_size(engine) == 1,
          "restoring the checkpoint recompiled the train step")

    emit("train", model=sz["name"], n_layer=sz["n_layer"],
         n_embd=sz["n_embd"], n_head=sz["n_head"],
         vocab_size=sz["vocab_size"], batch=sz["batch"], seq=sz["seq"],
         dtype="bfloat16", flash_attention=True, steps=TRAIN_STEPS,
         losses=losses, first_call_seconds_incl_compile=secs[0],
         later_call_seconds=secs[1:], jit_cache_entries=1,
         hlo_tpu_custom_calls=kernels,
         audit_findings=findings,
         checkpoint={"dir_is_temporary": True, "step": saved_step,
                     "params_bit_exact": True,
                     "loss_after_restore": replayed,
                     "loss_same_step_before": after_save},
         compile_cache=compile_cache.counts(),
         memory=memory_facts(jax.devices()[:1], on_tpu),
         note="smoke facts of one run, not performance")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

class EngineTap:
    """Records what the serve's InferenceEngine computed — prefill and
    decode logits, step by step — without changing what it does."""

    def __init__(self):
        self.engine = None
        self.prefills = {}      # slot -> last-prompt-token logits
        self.decodes = []       # (tokens_in, positions, next, logits)

    @contextlib.contextmanager
    def installed(self):
        from deepspeed_tpu.inference.engine import InferenceEngine
        orig_prefill, orig_decode = (InferenceEngine.prefill,
                                     InferenceEngine.decode)
        tap = self

        def prefill(eng, slot, prompt, *a, **kw):
            tap.engine = eng
            out = orig_prefill(eng, slot, prompt, *a, **kw)
            tap.prefills[int(slot)] = np.array(out)
            return out

        def decode(eng, tokens, positions, *a, **kw):
            nxt, logits = orig_decode(eng, tokens, positions, *a, **kw)
            tap.decodes.append((np.array(tokens), np.array(positions),
                                np.array(nxt), np.array(logits)))
            return nxt, logits

        InferenceEngine.prefill, InferenceEngine.decode = prefill, decode
        try:
            yield self
        finally:
            InferenceEngine.prefill = orig_prefill
            InferenceEngine.decode = orig_decode


def write_requests(path, sz, seed):
    rng = np.random.default_rng(seed + 1)
    with open(path, "w") as f:
        for i in range(sz["n_requests"]):
            n = int(rng.integers(sz["prompt_lo"], sz["prompt_hi"]))
            f.write(json.dumps({
                "rid": f"q{i}", "max_new_tokens": sz["max_new"],
                "prompt": rng.integers(0, sz["vocab_size"], n).tolist(),
            }) + "\n")


def serve_once(sz, seed, ckpt_dir, req_path, impl, on_tpu):
    """One `ds_tpu_serve --checkpoint` run, in this process."""
    import jax
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.inference import serve
    from deepspeed_tpu.inference.cache import payload_shape

    argv = ["--checkpoint", ckpt_dir, "--n-head", str(sz["n_head"]),
            "--max-batch", str(sz["batch"]),
            "--seq-buckets", str(sz["seq"]),
            "--prefill-chunk", str(sz["prefill_chunk"]),
            "--requests", req_path, "--seed", str(seed),
            "--expect-compiles", "2", "--json"]
    if impl is not None:        # None = the defaults
        argv += ["--attention", impl, "--page-size", str(sz["page_size"])]
    tap, out = EngineTap(), io.StringIO()
    t0 = time.perf_counter()
    with tap.installed(), contextlib.redirect_stdout(out):
        rc = serve.main(argv)
    wall = round(time.perf_counter() - t0, 3)
    text = out.getvalue()
    result = json.loads(text[text.index("{"):])
    label = impl or "default(dense)"
    check(rc == 0 and result["ok"],
          f"ds_tpu_serve {label} failed (rc {rc}): compile_counts "
          f"{result.get('compile_counts')}, "
          f"{len(result['completions'])}/{result['requests']} done")
    check(result["compile_counts"] == {"prefill": 1, "decode": 1},
          f"{label}: compile counts {result['compile_counts']}")
    for c in result["completions"]:
        check(len(c["tokens"]) == sz["max_new"] and
              c["finish_reason"] == "max_new_tokens",
              f"{label}: request {c['rid']} ended {c['finish_reason']} "
              f"after {len(c['tokens'])} tokens")
    eng = tap.engine
    check(eng.attention_impl == (impl or "dense") and
          eng.page_size == (sz["page_size"] if impl
                            else 2 * sz["prefill_chunk"]),
          f"{label}: engine ran {eng.attention_impl} over pages of "
          f"{eng.page_size}")
    decode_hlo = eng.decode_hlo()
    kernels = decode_hlo.count("tpu_custom_call")
    # each one a relayout or a duplicate of a whole K or V buffer
    copies = len(payload_shaped_copies(decode_hlo,
                                       payload_shape(eng.spec)))
    if on_tpu:
        check((kernels > 0) == (eng.attention_impl == "flash"),
              f"{label}: decode HLO holds {kernels} tpu_custom_call")
        if impl == "flash":
            check(copies == 0, f"{label}: the decode step copies the "
                               f"whole pool {copies} times")
    served = {
        "params": sorted({str(l.dtype) for l in
                          jax.tree_util.tree_leaves(eng.params)}),
        "compute": str(np.dtype(eng.model.config.dtype)),
        "kv_cache": result["cache"]["dtype_census"],
    }
    tap.engine = None           # let the engine's device memory go
    facts = dict(
        variant=label, requests=result["requests"],
        tokens_served=sum(len(c["tokens"])
                          for c in result["completions"]),
        decode_steps=result["decode_steps"],
        compile_counts=result["compile_counts"],
        decode_hlo_tpu_custom_calls=kernels,
        decode_hlo_cache_shaped_copies=copies,
        served_dtype=served,
        checkpoint=result["checkpoint"], wall_seconds_incl_compile=wall)
    return result, tap, facts


def compare_runs(ref, other):
    """Hold ``other`` (a flash run) to ``ref`` (the dense run).

    Returns the comparison that decided: greedy tokens equal, or — when
    near-ties flipped a token — logits within tolerance on every step
    the two runs entered with the same inputs."""
    ref_res, ref_tap = ref
    oth_res, oth_tap = other
    ref_toks = {c["rid"]: c["tokens"] for c in ref_res["completions"]}
    oth_toks = {c["rid"]: c["tokens"] for c in oth_res["completions"]}
    check(ref_toks.keys() == oth_toks.keys(), "different request ids")
    slots = {c["rid"]: c["slot"] for c in ref_res["completions"]}
    check(slots == {c["rid"]: c["slot"]
                    for c in oth_res["completions"]},
          "requests landed in different cache rows")

    rows = sorted(slots.values())
    scale = max([float(np.abs(l).max())
                 for l in ref_tap.prefills.values()] +
                [float(np.abs(d[3][rows]).max())
                 for d in ref_tap.decodes])
    tol = LOGIT_RTOL * scale
    max_diff, flips, compared = 0.0, 0, 0

    def hold(ref_logits, oth_logits, where):
        nonlocal max_diff, flips, compared
        d = float(np.abs(ref_logits - oth_logits).max())
        max_diff, compared = max(max_diff, d), compared + 1
        check(d <= tol, f"{where}: |dlogit| {d:.4g} > tolerance "
                        f"{tol:.4g} ({LOGIT_RTOL} x max|logit| {scale:.4g})")
        a, b = int(ref_logits.argmax()), int(oth_logits.argmax())
        if a != b:
            gap = float(ref_logits[a] - ref_logits[b])
            check(gap <= 2 * tol,
                  f"{where}: tokens {a} vs {b} differ and the dense "
                  f"logits separate them by {gap:.4g} > {2 * tol:.4g}")
            flips += 1
            return False
        return True

    in_sync = {}
    for rid, slot in slots.items():
        in_sync[slot] = hold(ref_tap.prefills[slot],
                             oth_tap.prefills[slot], f"{rid} prefill")
    for t, (r, o) in enumerate(zip(ref_tap.decodes, oth_tap.decodes)):
        for slot in in_sync:
            same_inputs = (r[0][slot] == o[0][slot] and
                           r[1][slot] == o[1][slot])
            if not (in_sync[slot] and same_inputs):
                in_sync[slot] = False
                continue
            in_sync[slot] = hold(r[3][slot], o[3][slot],
                                 f"row {slot} decode step {t}")
    equal = ref_toks == oth_toks
    check(equal or flips > 0,
          "greedy tokens differ but no near-tie flip was found on the "
          "steps compared")
    return {"comparison": "greedy tokens equal" if equal else
            "logits within tolerance (greedy tokens split at near-ties)",
            "greedy_tokens_equal": equal, "near_tie_flips": flips,
            "row_steps_compared": compared,
            "max_abs_dlogit": max_diff, "tolerance": tol,
            "tolerance_rule": f"{LOGIT_RTOL} x max|dense logit|"}


def serve_phase(sz, seed, ckpt_dir, workdir, on_tpu):
    from deepspeed_tpu.telemetry import compile_cache

    req_path = os.path.join(workdir, "requests.jsonl")
    write_requests(req_path, sz, seed)
    dense = None            # the first run: the parity oracle
    for impl in (None, "flash"):
        result, tap, facts = serve_once(sz, seed, ckpt_dir, req_path,
                                        impl, on_tpu)
        if dense is None:
            dense = (result, tap)
        else:
            facts["vs_dense"] = compare_runs(dense, (result, tap))
        emit("serve", **facts, compile_cache=compile_cache.counts(),
             note="smoke facts of one run, not performance")
        gc.collect()


# ---------------------------------------------------------------------------
# four chips: ZeRO-2 over data=4 against one device
# ---------------------------------------------------------------------------

def per_device_bytes(tree):
    """{device id: bytes of this tree's shards resident there}."""
    import jax
    out = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def tree_bytes(tree):
    import jax
    return sum(l.nbytes for l in jax.tree_util.tree_leaves(tree))


def zero2_run(sz, seed, mesh, on_tpu, label):
    import jax

    engine = build_train_engine(sz, seed, mesh=mesh, zero_stage=2)
    batch = seeded_batch(sz, seed)
    losses, secs = run_steps(engine, batch, ZERO2_STEPS)
    check_losses(losses)
    facts = {"run": label, "losses": losses,
             "first_call_seconds_incl_compile": secs[0],
             "mesh": {k: int(v) for k, v in engine.mesh.shape.items()}}
    dp = int(engine.mesh.shape["data"])
    if dp > 1:
        # the collectives the audit expects of a stage-2 step are its
        # `zero_budget` rule: one gradient exchange, however XLA spells
        # it, and one parameter-sized refresh gather — held to silence
        report, findings = audit_step(engine, batch, hold=("zero_budget",))
        coll = report.stats["collective_bytes"]
        check(coll.get("all-gather", 0) > 0,
              f"no all-gather (parameter refresh) in the ZeRO-2 step: "
              f"{coll}")
        kernels = report.hlo_text.count("tpu_custom_call")
        check(kernels > 0 or not on_tpu,
              "no tpu_custom_call in the four-chip train step")
        spread = {}
        for name, tree in (("adam_m", engine.opt_state.m),
                           ("adam_v", engine.opt_state.v)):
            total = tree_bytes(tree)
            shares = {d: round(b / total, 4)
                      for d, b in sorted(per_device_bytes(tree).items())}
            check(len(shares) == dp,
                  f"{name} lives on {len(shares)} devices, not {dp}")
            # small leaves that do not divide stay replicated, so a
            # share is a little over 1/dp — never the whole state
            check(all(s <= 1.0 / dp + 0.05 for s in shares.values()),
                  f"{name} is not spread over the data axis: {shares}")
            spread[name] = {"total_bytes": total,
                            "share_by_device": shares}
        facts.update(collective_bytes={k: int(v) for k, v in coll.items()},
                     hlo_tpu_custom_calls=kernels,
                     audit_findings=findings, state_spread=spread)
    facts["memory"] = memory_facts(list(engine.mesh.devices.flat), on_tpu)
    del engine
    gc.collect()
    jax.clear_caches()
    return losses, facts


def chips4_phase(sz, seed, on_tpu):
    import jax
    from deepspeed_tpu.parallel.mesh import build_mesh

    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, JAX sees "
                          f"{len(devs)}")
    one, facts1 = zero2_run(sz, seed, build_mesh(devices=devs[:1]),
                            on_tpu, "one device")
    emit("zero2", **facts1, note="smoke facts, not performance")
    four, facts4 = zero2_run(sz, seed, build_mesh({"data": 4}), on_tpu,
                             "data=4")
    # same seed, same global batch: the two differ only in the order
    # bf16 partial sums are reduced
    rel = [abs(a - b) / abs(a) for a, b in zip(one, four)]
    check(max(rel) <= 5e-3,
          f"dp=4 losses {four} vs one-device {one}: rel diff {rel}")
    emit("zero2", **facts4, loss_rel_diff_vs_one_device=rel,
         loss_tolerance=5e-3, note="smoke facts, not performance")


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the ZeRO-2 dp=4 vs one-device comparison "
                         "and no other phase")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal at tiny widths; can never "
                         "print the ok line (exit 3)")
    args = ap.parse_args(argv)

    import jax
    import deepspeed_tpu
    check(os.path.dirname(os.path.dirname(
        os.path.abspath(deepspeed_tpu.__file__))) == HERE,
        f"deepspeed_tpu imported from {deepspeed_tpu.__file__}, not "
        f"from this checkout")
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.reduced:
        print(f"chip_smoke: no TPU (JAX reports {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return EXIT_NO_TPU

    from deepspeed_tpu.telemetry import compile_cache
    cache_dir = compile_cache.configure(os.path.join(HERE, ".jax_cache"))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    emit("start", device=device, chips=args.chips, seed=args.seed,
         reduced=args.reduced, compile_cache_dir=cache_dir,
         jax=jax.__version__)

    sz = sizes(args.reduced)
    t0 = time.perf_counter()
    if args.chips == 4:
        chips4_phase(sz, args.seed, on_tpu)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
            ckpt_dir = os.path.join(work, "ckpt")
            train_phase(sz, args.seed, ckpt_dir, on_tpu)
            gc.collect()
            serve_phase(sz, args.seed, ckpt_dir, work, on_tpu)
    emit("done", seconds=round(time.perf_counter() - t0, 1),
         compile_cache=compile_cache.counts())

    if args.reduced:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "device": device}))
        return EXIT_REHEARSAL
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

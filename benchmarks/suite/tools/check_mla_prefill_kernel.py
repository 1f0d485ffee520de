#!/usr/bin/env python3
"""The prefill kernel's own reading on the chip: ``drivers/serve_mla.py:
check_attention``'s program (one attention layer, the first, on its own
input, into a small pool of its own, against the reference's expanded
attention over the output's largest entry) with the chunks under
``"flash"``, which that check runs under ``"dense"`` whatever the
engine serves with (`PERF.md` section 7: a ``benchmark`` issue's edit).
At the cell's sizes (chunks of ``prefill_chunk``, pages of
``page_size``, the published widths in bfloat16), ``--chunks`` chunks
one after the other, so the last walks a prefix of ``chunks - 1``
blocks before its own.

Per ``impl`` one JSON line a reading: sound weights, and the control,
weights at 3 bits of mantissa (float8_e4m3, the precision below the
file's bfloat16) in the program against the sound reference. Exit 0 iff
the kernel's sound reading is inside the cell's ``attention_rtol`` and
its control outside it. Also a chunk's device time by prefix, both
paths (the layer alone: projections, the page write and the walk).

    python3 benchmarks/suite/tools/check_mla_prefill_kernel.py --seed 1
"""

import argparse
import dataclasses
import json
import os
import sys
import time

SUITE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(SUITE))
sys.path.insert(0, ROOT)

CELL = "serve-kimi-k2.7-code-repo"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--chunks", type=int, default=4)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.suite import run
    from benchmarks.suite.drivers import serve_mla as drv
    from benchmarks.suite.reference import mla_moe_ref as ref
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.models.mla_moe import LatentAttention, yarn_cos_sin

    code, ctx, _ = run.prepare(CELL, args.seed, 51, 0)
    if code:
        return code
    cfg = ctx.config
    model_cfg = drv.model_config(cfg)
    inf = ctx.workload["inference"]
    chunk, page = inf["prefill_chunk"], inf["page_size"]
    tol = ctx.workload["correctness"]["attention_rtol"]
    n = args.chunks * chunk
    # whole blocks of the walk (eight pages)
    bucket = -(-n // (8 * page)) * 8 * page
    spec = dataclasses.replace(model_cfg, num_hidden_layers=1).cache_spec(
        1, bucket, page_size=page)
    layer = LatentAttention(model_cfg)
    table = jnp.arange(1, spec.pages_per_row + 1, dtype=jnp.int32)[None]
    first = jnp.arange(chunk, dtype=jnp.int32)[None]
    key = jax.random.PRNGKey(args.seed % (2 ** 31))
    p = jax.jit(lambda k: layer.init(
        {"params": k}, jnp.zeros((1, chunk, model_cfg.hidden_size),
                                 model_cfg.dtype),
        init_kv_cache(spec)[drv.LAYER], first, table,
        yarn_cos_sin(model_cfg, first),
        {"impl": "dense", "block_k": page})["params"])(key)
    x = jax.random.normal(jax.random.PRNGKey((args.seed + 1) % (2 ** 31)),
                          (n, model_cfg.hidden_size),
                          jnp.float32).astype(model_cfg.dtype)

    def one(impl):
        @jax.jit
        def call(p, xc, pool, lo):
            pos = lo + first
            return layer.apply({"params": p}, xc[None], pool, pos, table,
                               yarn_cos_sin(model_cfg, pos),
                               {"impl": impl, "block_k": page})
        return call

    def program(impl, p, timed=False):
        """The chunks one after the other; ``(outputs [n, hidden], ms a
        chunk)``."""
        call, pool, ys, ms = one(impl), init_kv_cache(spec)[drv.LAYER], [], []
        for lo in range(0, n, chunk):
            xc, at = x[lo:lo + chunk], jnp.asarray(lo, jnp.int32)
            if timed:
                jax.block_until_ready(call(p, xc, pool, at))   # compiled
                t = time.perf_counter()
                for _ in range(5):
                    out = call(p, xc, pool, at)
                jax.block_until_ready(out)
                ms.append(1e3 * (time.perf_counter() - t) / 5)
            y, pool = call(p, xc, pool, at)
            ys.append(y[0])
        return np.asarray(jnp.concatenate(ys), np.float32), ms

    want = np.asarray(jax.jit(lambda p, x: ref.attention(
        x.astype(jnp.float32), p, cfg))(p, x))
    scale = np.abs(want).max()
    at_3_bits = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, p)
    out_dir = os.path.join(ROOT, "chiprun_out")
    out = open(os.path.join(out_dir, "check_mla_prefill_kernel.jsonl"),
               "w") if os.path.isdir(out_dir) else None
    readings = {}
    for weights, params in (("sound", p), ("3 bits of mantissa", at_3_bits)):
        for impl in ("flash", "dense"):
            got, ms = program(impl, params, timed=weights == "sound")
            by_chunk = [float(np.abs(got[lo:lo + chunk] -
                                     want[lo:lo + chunk]).max() / scale)
                        for lo in range(0, n, chunk)]
            readings[weights, impl] = max(by_chunk)
            line = {"check": "attention, prefill chunks", "impl": impl,
                    "weights": weights, "seed": args.seed, "tokens": n,
                    "chunk": chunk, "reading": max(by_chunk),
                    "by_chunk": by_chunk, "tolerance": tol,
                    "ms_by_prefix": ms,
                    "device": jax.devices()[0].device_kind}
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    ok = readings["sound", "flash"] <= tol < readings[
        "3 bits of mantissa", "flash"]
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

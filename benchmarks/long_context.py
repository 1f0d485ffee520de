"""Long-context attention benchmark (VERDICT r3 item 3).

Reference claims being tested head-to-head (`/root/reference/README.md:38`
and `docs/_tutorials/sparse-attention.md`): block-sparse attention "up to
6.3x faster" than dense and "10x longer sequences". On TPU both paths are
Pallas kernels (`ops/pallas/flash_attention.py`,
`ops/sparse_attention/block_sparse_attention.py`), so this measures the
same trade the reference measured with Triton-vs-dense on V100.

Runs three studies on the live chip and prints one JSON line per row
(collect into BENCHNOTES.md):
  1. dense-flash vs block-sparse fwd+bwd wall-clock at seq 4k/8k/16k
  2. Pallas block-size sweep (16/32/64/128) at seq 4096
  3. max trainable sequence: grow seq until OOM, dense vs sparse

Usage (on TPU): python benchmarks/long_context.py [--study all|speed|block|maxseq]
"""

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import hb  # noqa: E402 - the one heartbeat format


def _materialize(out):
    """Force a device->host copy of one output: transferring any output
    of the XLA program guarantees the whole program ran before the
    clock stops."""
    import jax
    first = jax.tree_util.tree_leaves(out)[0]
    np.asarray(first)
    return out


def _timeit(fn, *args, iters=10):
    _materialize(fn(*args))              # warmup/compile
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    _materialize(out)
    return (time.perf_counter() - t0) / iters * 1e3   # ms


def make_inputs(jax, B, T, H, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def fwd_bwd(attn_fn):
    import jax

    def f(q, k, v):
        def loss(q, k, v):
            return attn_fn(q, k, v).astype(np.float32).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    return jax.jit(f)


def sparse_attn_fn(jax, T, H, block, num_local=4, num_global=1):
    from deepspeed_tpu.ops.sparse_attention import (
        FixedSparsityConfig, block_sparse_attention)

    cfg = FixedSparsityConfig(num_heads=H, block=block,
                              num_local_blocks=num_local,
                              num_global_blocks=num_global,
                              attention="unidirectional")
    layout = np.asarray(cfg.make_layout(T))

    def attn(q, k, v):
        return block_sparse_attention(q, k, v, layout, block, causal=True)

    return attn, layout


def study_speed(jax, emit):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    B, H, D = 1, 16, 64
    for T in (4096, 8192, 16384):
        hb(f"speed study: seq {T} dense")
        q, k, v = make_inputs(jax, B, T, H, D, jax.numpy.bfloat16)
        dense = fwd_bwd(functools.partial(
            flash_attention, causal=True, implementation="pallas"))
        d_ms = _timeit(dense, q, k, v)
        hb(f"speed study: seq {T} sparse")
        attn, layout = sparse_attn_fn(jax, T, H, block=128)
        density = float(layout.sum()) / layout.size
        s_ms = _timeit(fwd_bwd(attn), q, k, v)
        emit({"study": "speed", "seq": T, "dense_ms": round(d_ms, 2),
              "sparse_ms": round(s_ms, 2), "layout_density": round(density, 4),
              "speedup": round(d_ms / s_ms, 2)})


def study_block(jax, emit):
    B, H, D, T = 1, 16, 64, 4096
    q, k, v = make_inputs(jax, B, T, H, D, jax.numpy.bfloat16)
    for block in (16, 32, 64, 128):
        hb(f"block sweep: block {block}")
        attn, _ = sparse_attn_fn(jax, T, H, block=block,
                                 num_local=512 // block,
                                 num_global=128 // block)
        ms = _timeit(fwd_bwd(attn), q, k, v)
        emit({"study": "block_sweep", "seq": T, "block": block,
              "ms": round(ms, 2)})


def study_maxseq(jax, emit):
    """Largest causal-attention fwd+bwd that fits on one chip, dense vs
    block-sparse (fixed local+global pattern — constant memory per row)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    B, H, D = 1, 16, 64

    def fits(make_fn, T):
        try:
            hb(f"maxseq study: trying seq {T}")
            q, k, v = make_inputs(jax, B, T, H, D, jax.numpy.bfloat16)
            _materialize(fwd_bwd(make_fn(T))(q, k, v))
            return True
        except MemoryError:
            return False                 # host-side (layout/LUT) OOM
        except Exception as e:
            if "RESOURCE_EXHAUSTED" in str(e) or "exhausted" in str(e):
                return False
            raise

    def max_fit(make_fn, start=4096, cap=2 ** 18):
        # cap at 256k: the FixedSparsityConfig layout is a dense
        # [H, T/b, T/b] int64 host array (~0.5 GB at the cap) — past that
        # the *layout*, not the chip, is the limit.
        T = start
        best = 0
        while T <= cap and fits(make_fn, T):
            best = T
            T *= 2
        return best

    from deepspeed_tpu.ops.pallas.flash_attention import dense_attention
    # The reference's "10x longer sequences" claim compares sparse against
    # the standard O(T^2)-materializing attention (its BERT baseline); the
    # flash kernel is our own dense *compute* baseline and is itself O(T)
    # in memory, so both are reported.
    naive_fn = lambda T: functools.partial(dense_attention, causal=True)
    flash_fn = lambda T: functools.partial(flash_attention, causal=True,
                                           implementation="pallas")
    sparse_fn = lambda T: sparse_attn_fn(jax, T, H, block=128)[0]
    naive_max = max_fit(naive_fn, start=1024)
    flash_max = max_fit(flash_fn)
    sparse_max = max_fit(sparse_fn, start=4096)
    emit({"study": "maxseq", "naive_dense_max_seq": naive_max,
          "flash_max_seq": flash_max, "sparse_max_seq": sparse_max,
          "ratio_vs_naive": round(sparse_max / max(naive_max, 1), 1)})


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--study", default="all",
                        choices=["all", "speed", "block", "maxseq"])
    args = parser.parse_args()

    def emit(row):
        print(json.dumps(row), flush=True)

    # One process per chip: touch the backend here, not in a probing
    # child that would hold the chip this process then needs.
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        emit({"study": args.study, "error":
              f"long-context bench needs the real chip; backend is "
              f"{platform!r}"})
        return 1

    if args.study in ("all", "speed"):
        study_speed(jax, emit)
    if args.study in ("all", "block"):
        study_block(jax, emit)
    if args.study in ("all", "maxseq"):
        study_maxseq(jax, emit)


if __name__ == "__main__":
    sys.exit(main())

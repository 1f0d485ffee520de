"""Measured step-time attribution for the flagship train step.

`ANALYSIS_MFU.md`'s budget table models where the 350M step time goes;
this tool replaces the model with a measurement: it traces a few steps
with ``jax.profiler.trace`` and aggregates device-plane op durations from
the xplane proto (parsed via tensorflow.tsl's ``xplane_pb2`` — the same
artifact xprof/tensorboard reads). The reference ships CUDA-event timers
around its kernels (`csrc/includes/Timer.h`); under XLA the equivalent
visibility comes from the profiler's per-op device timeline.

Prints ONE JSON line: {"metric": "GPT-2 350M step-time attribution",
"ms_per_step": ..., "categories": {...}, "top_ops": [...]}.

Usage: python benchmarks/profile_step.py [--steps 3] [--keep-trace DIR]
"""

import argparse
import glob
import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def classify(name):
    """Coarse HLO-op category from the (fusion) op name."""
    n = name.lower()
    if "flash" in n or "custom-call" in n or "custom_call" in n:
        return "custom-call (pallas)"
    if any(k in n for k in ("all-reduce", "all-gather", "reduce-scatter",
                            "all-to-all", "collective")):
        return "collective"
    if "dot" in n or "conv" in n or "matmul" in n:
        return "matmul"
    if any(k in n for k in ("copy", "transpose", "bitcast", "reshape")):
        return "layout/copy"
    if any(k in n for k in ("dynamic-update-slice", "dynamic-slice",
                            "scatter", "gather")):
        return "slice/gather"
    if "infeed" in n or "outfeed" in n or "send" in n or "recv" in n:
        return "host-transfer"
    return "elementwise/other"


def aggregate_xplanes(trace_dir):
    """Mean per-device op durations by name across all xplane files.

    Returns ``(per_name_ps, device_total_ps, n_device_planes)`` — sums
    are divided by the number of device planes so multi-chip traces
    (one plane per chip, each recording the full per-shard step) report
    one device's step time, comparable to ANALYSIS_MFU's budget. Only
    device planes count — host threads are bookkeeping.
    """
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    per_name = {}
    total = 0
    n_planes = 0
    for path in paths:
        space = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        for plane in space.planes:
            pname = plane.name
            if not ("TPU" in pname or "GPU" in pname
                    or "/device:" in pname):
                continue
            n_planes += 1
            meta = {m.id: m.name for m in plane.event_metadata.values()}
            for line in plane.lines:
                # XLA-op lines carry the per-op events; "Steps"/"XLA
                # Modules" lines would double-count the same wall time.
                if "xla op" not in line.name.lower():
                    continue
                for ev in line.events:
                    name = meta.get(ev.metadata_id, str(ev.metadata_id))
                    dur = ev.duration_ps
                    per_name[name] = per_name.get(name, 0) + dur
                    total += dur
    if n_planes > 1:
        per_name = {k: v / n_planes for k, v in per_name.items()}
        total /= n_planes
    return per_name, total, n_planes


def emit(payload):
    print(json.dumps(payload), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--keep-trace", default=None,
                    help="persist the raw trace under this dir (a fresh "
                         "run-specific subdir — re-running never "
                         "aggregates a previous run's xplanes)")
    args = ap.parse_args()

    import bench  # repo-root bench.py: the heartbeat format

    # One process per chip: this process touches the backend itself
    # (a probing child and its parent would both want the one chip).
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # a CPU trace has no device plane: there is nothing to attribute
        emit({"metric": "step-time attribution (device op time)",
              "error": f"needs a TPU; backend is {platform!r}"})
        return 1

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (
        GPT2LMHead, gpt2_350m, init_gpt2_params,
        make_gpt2_loss_fn)

    chunk = int(os.environ.get("BENCH_LOSS_CHUNK", "0"))
    chunk_tag = f", chunked-CE{chunk}" if chunk else ""
    cfg_fn, bs, seq = gpt2_350m, 8, 1024
    label = f"GPT-2 350M (bf16, seq1024, bs8{chunk_tag})"

    cfg = cfg_fn(n_positions=seq, use_flash_attention=True,
                 loss_chunk=chunk)
    model = GPT2LMHead(cfg)
    bench.hb(f"profile: init params ({label})")
    params = init_gpt2_params(model, jax.random.PRNGKey(0), seq_len=seq)
    bench.hb("profile: params ready; building engine")
    engine, _, _, _ = deepspeed_tpu.initialize(
        config={"train_batch_size": bs, "bf16": {"enabled": True},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                "steps_per_print": 10 ** 9},
        loss_fn=make_gpt2_loss_fn(model), params=params)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (bs, seq)).astype(np.int32)}

    for i in range(2):  # compile + warm
        float(engine.train_batch(batch))
        bench.hb(f"profile: warmup {i + 1}/2 done")

    if args.keep_trace:
        os.makedirs(args.keep_trace, exist_ok=True)
        trace_dir = tempfile.mkdtemp(prefix="run_", dir=args.keep_trace)
    else:
        trace_dir = tempfile.mkdtemp(prefix="ds_tpu_prof_")
    with jax.profiler.trace(trace_dir):
        for _ in range(args.steps):
            loss = engine.train_batch(batch)
        float(loss)
    bench.hb("profile: trace captured; aggregating xplanes")

    per_name, total_ps, n_planes = aggregate_xplanes(trace_dir)
    cats = {}
    for name, ps in per_name.items():
        cats[classify(name)] = cats.get(classify(name), 0) + ps
    ms = 1e-9  # ps -> ms
    top = sorted(per_name.items(), key=lambda kv: -kv[1])[:15]
    out = {
        "metric": f"{label} step-time attribution (device op time)",
        "steps": args.steps,
        "device_planes": n_planes,
        "device_ms_per_step": round(total_ps * ms / args.steps, 3),
        "categories_ms_per_step": {
            k: round(v * ms / args.steps, 3)
            for k, v in sorted(cats.items(), key=lambda kv: -kv[1])},
        "top_ops_ms_per_step": [
            [n[:80], round(ps * ms / args.steps, 3)] for n, ps in top],
    }
    emit(out)
    if not args.keep_trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

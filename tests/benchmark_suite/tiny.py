"""The cells at toy size, for the CPU rehearsals: the committed workload
files with every size cut down, the widths of ``gpt2_tiny``."""

import copy
import json
import os
import sys
import time

SUITE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "suite")

CONFIG = {
    "vocab_size": 256, "n_positions": 128, "n_embd": 64, "n_layer": 2,
    "n_head": 4, "layer_norm_epsilon": 1e-6, "resid_pdrop": 0.0,
    "train": {"compute_dtype": "bfloat16", "param_dtype": "float32",
              "use_flash_attention": True},
    "serve": {"compute_dtype": "float32", "param_dtype": "float32"},
}


def workload(name):
    with open(os.path.join(SUITE, "workloads", name + ".json")) as f:
        return json.load(f)


def train_workload(name, mesh=None, stage=None):
    wl = copy.deepcopy(workload(name))
    wl["traffic"].update(rows=8, seq=128)
    wl["trace"] = {"reserve_s": 0.3, "blocking_steps": 2,
                   "profiled_steps": 2}
    if mesh is not None:
        wl["engine"]["mesh"] = mesh
    if stage is not None:
        wl["engine"]["ds_config"]["zero_optimization"] = {"stage": stage}
    return wl


def serve_workload(name):
    wl = copy.deepcopy(workload(name))
    wl["traffic"].update(
        rate_per_s=8.0, max_total=127, ramp_s=0.5, drain_s=1.0,
        prompt={"median": 30, "sigma": 0.8, "min": 4, "max": 80},
        output={"median": 10, "sigma": 0.6, "min": 2, "max": 24})
    wl["inference"].update(max_batch=4, seq_buckets=[128],
                           prefill_chunk=16, page_size=32)
    wl["warmup"] = [[80, 2], [4, 2]]
    wl["trace"] = {"profile_s": 0.3}
    return wl


def context(wl, devices, seconds, trace, seed=2 ** 31 + 77):
    from benchmarks.suite import harness
    return harness.Context(
        cell={"name": "tiny", "chips": len(devices)}, workload=wl,
        config=CONFIG, seed=seed, seconds=seconds, trace=trace,
        t_process=time.perf_counter(), devices=devices,
        peaks={"bf16_flops_per_s": 197e12},
        log=lambda msg: print(msg, file=sys.stderr),
        compiles=harness.CompileCounter())

"""The hybrid serving cell at toy size, for the CPU rehearsals: the
committed workload file with every size cut down, and the configuration
file's keys at the widths of ``granite_hybrid_tiny`` (``tiny.py`` is
GPT-2's)."""

import copy
import sys
import time

from . import tiny

CELL = "serve-granite-4.0-h-micro-rag"

CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "shared_intermediate_size": 96,
    "num_hidden_layers": 6,
    "layer_types": ["mamba", "mamba", "attention"] * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 8, "max_position_embeddings": 128,
    "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
    "logits_scaling": 8,
    "n_embd": 64, "n_layer": 6, "n_head": 4, "n_positions": 128,
    "assumed": {"initializer_range": 0.1},
    "serve": {"compute_dtype": "bfloat16", "param_dtype": "bfloat16"},
}


def workload():
    wl = copy.deepcopy(tiny.workload(CELL))
    wl["traffic"].update(
        rate_per_s=8.0, max_total=127, ramp_s=0.5, drain_s=1.0,
        prompt={"median": 30, "sigma": 0.8, "min": 4, "max": 80},
        output={"median": 10, "sigma": 0.6, "min": 2, "max": 24})
    wl["inference"].update(max_batch=4, seq_buckets=[128],
                           prefill_chunk=16, page_size=8)
    wl["warmup"] = [[80, 2], [4, 2]]
    wl["trace"]["profile_s"] = 0.3
    return wl


def context(devices, seconds, trace, seed=2 ** 31 + 77, config=None):
    from benchmarks.suite import harness
    return harness.Context(
        cell={"name": "tiny", "chips": len(devices)}, workload=workload(),
        config=config or CONFIG, seed=seed, seconds=seconds, trace=trace,
        t_process=time.perf_counter(), devices=devices,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        log=lambda msg: print(msg, file=sys.stderr),
        compiles=harness.CompileCounter())

"""The LFM2 serving cell at toy size, for the CPU rehearsals: the
committed workload file with every size cut down, and the configuration
file's keys at the widths of ``lfm2_moe_tiny`` (``tiny_ling.py`` is
Ling's)."""

import copy
import sys
import time

from . import tiny

CELL = "serve-lfm2-8b-a1b-agent"

CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 24,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention", "conv"] + ["conv"] * 18,
    "num_dense_layers": 1, "num_attention_heads": 4,
    "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1,
    "norm_eps": 1e-5, "rope_theta": 1000000,
    "max_position_embeddings": 512,
    "n_embd": 64, "n_layer": 6, "n_head": 4, "n_positions": 512,
    "assumed": {"initializer_range": 0.1, "norm_weight_range": 0.1,
                "router_bias_range": 0.1, "experts_held": [2, 4]},
    # float32 at toy size: in bfloat16 at 64 channels, 8 experts and top
    # 2 a near-tie flips and a whole model's logits say little
    "serve": {"compute_dtype": "float32", "param_dtype": "float32"},
}


def workload():
    wl = copy.deepcopy(tiny.workload(CELL))
    wl["traffic"].update(
        rate_per_s=6.0, max_total=127, ramp_s=0.5, drain_s=1.0,
        prompt={"median": 30, "sigma": 0.6, "min": 8, "max": 100},
        output={"median": 10, "sigma": 0.6, "min": 2, "max": 24})
    wl["inference"].update(max_batch=4, seq_buckets=[128], n_pages=0,
                           prefill_chunk=32, page_size=4)
    wl["warmup"] = [[100, 2], [8, 2]]
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

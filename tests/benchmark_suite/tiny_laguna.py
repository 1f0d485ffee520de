"""The Laguna serving cell at toy size, for the CPU rehearsals: the
committed workload file with every size cut down, and the configuration
file's keys at the widths of ``laguna_tiny`` (``tiny_mimo_v2.py`` is
MiMo-V2's)."""

import copy
import sys
import time

from . import tiny

CELL = "serve-laguna-s-2.1-codegen"

CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_hidden_layers": 48, "num_attention_heads": 12,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 5,
    "num_attention_heads_per_layer": [12, 18, 18, 18, 12, 18],
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 100.0, "rope_type": "yarn", "factor": 8.0,
            "original_max_position_embeddings": 16, "beta_slow": 0.25,
            "beta_fast": 2.0, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000.0,
            "partial_rotary_factor": 1.0}},
    "num_experts": 16, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5, "gating": "per-head",
    "rms_norm_eps": 1e-6, "max_position_embeddings": 512,
    "n_embd": 64, "n_layer": 6, "n_head": 12, "n_positions": 512,
    "assumed": {"initializer_range": 0.1, "experts_held": [4, 4]},
    # float32 at toy size: in bfloat16 at 64 channels, 16 experts and top
    # 3 a near-tie flips and a whole model's logits say little
    "serve": {"compute_dtype": "float32", "param_dtype": "float32"},
}


def workload():
    wl = copy.deepcopy(tiny.workload(CELL))
    wl["traffic"].update(
        rate_per_s=6.0, max_total=127, ramp_s=0.5, drain_s=1.0,
        prompt={"median": 40, "sigma": 0.6, "min": 16, "max": 100},
        output={"median": 10, "sigma": 0.6, "min": 2, "max": 24})
    wl["inference"].update(max_batch=4, seq_buckets=[128], n_pages=0,
                           prefill_chunk=32, page_size=4)
    wl["warmup"] = [[100, 2], [16, 2]]
    wl["correctness"]["slot_prompt_min"] = 50
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

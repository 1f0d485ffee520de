"""The MiMo-V2 serving cell at toy size, for the CPU rehearsals: the
committed workload file with every size cut down, and the configuration
file's keys at the widths of ``mimo_v2_tiny`` (``tiny_qwen3_next.py`` is
Qwen3-Next's)."""

import copy
import sys
import time

from . import tiny

CELL = "serve-mimo-v2.5-shortlong"

CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 48,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1], "moe_layer_freq": [0, 1, 1, 1, 1],
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 24,
    "v_head_dim": 16, "swa_num_attention_heads": 8,
    "swa_num_key_value_heads": 4, "swa_head_dim": 24, "swa_v_head_dim": 16,
    "partial_rotary_factor": 0.334, "rope_theta": 10000000,
    "swa_rope_theta": 10000, "sliding_window": 8,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "attention_value_scale": 0.707,
    "n_routed_experts": 16, "n_shared_experts": None,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": None, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "layernorm_epsilon": 1e-5, "max_position_embeddings": 256,
    "n_embd": 64, "n_layer": 5, "n_head": 8, "n_positions": 256,
    "assumed": {"initializer_range": 0.1, "router_bias_range": 0.1,
                "sink_bias_mean": 1.0, "sink_bias_range": 1.0,
                "experts_held": [4, 4]},
    # float32 at toy size: in bfloat16 at 64 channels, 16 experts and top
    # 2 a near-tie flips and a whole model's logits say little
    "serve": {"compute_dtype": "float32", "param_dtype": "float32"},
}


def workload():
    wl = copy.deepcopy(tiny.workload(CELL))
    wl["traffic"].update(
        rate_per_s=8.0, max_total=127, ramp_s=0.5, drain_s=1.0,
        classes=[{"share": 0.75, "prompt": {"median": 20, "sigma": 0.8,
                                            "min": 8, "max": 40}},
                 {"share": 0.25, "prompt": {"median": 70, "sigma": 0.3,
                                            "min": 50, "max": 100}}],
        output={"median": 10, "sigma": 0.6, "min": 2, "max": 24})
    wl["inference"].update(max_batch=4, seq_buckets=[128], n_pages=0,
                           prefill_chunk=16, page_size=8)
    wl["warmup"] = [[100, 2], [8, 2]]
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

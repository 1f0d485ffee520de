"""The Ling-3.0 serving cell at toy size, for the CPU rehearsals: the
committed workload file with every size cut down, and the configuration
file's keys at the widths of ``ling_hybrid_tiny`` (``tiny_laguna.py`` is
Laguna's)."""

import copy
import sys
import time

from . import tiny

CELL = "serve-ling-3.0-flash-reason-docs"

CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
    "num_hidden_layers": 42, "first_k_dense_replace": 1,
    "layer_group_size": 3, "num_attention_heads": 4, "head_dim": 16,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 10000.0, "rope_scaling": None, "num_experts": 16,
    "num_shared_experts": 1, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "expert_swiglu_limit_list": [0, 0, 0, 0, 4, 4],
    "share_expert_swiglu_limit_list": [0, 0, 0, 0, 5, 5],
    "rms_norm_eps": 1e-6, "max_position_embeddings": 512,
    "n_embd": 64, "n_layer": 4, "n_head": 4, "n_positions": 512,
    "assumed": {"initializer_range": 0.1, "router_bias_range": 0.05,
                "kda_chunk_size": 8, "experts_held": [4, 4]},
    # float32 at toy size: in bfloat16 at 64 channels, 16 experts and top
    # 3 a near-tie flips and a whole model's logits say little
    "serve": {"compute_dtype": "float32", "param_dtype": "float32"},
}


def workload():
    wl = copy.deepcopy(tiny.workload(CELL))
    wl["traffic"].update(
        rate_per_s=6.0, max_total=127, ramp_s=0.5, drain_s=1.0,
        classes=[
            {"share": 0.9,
             "prompt": {"median": 24, "sigma": 0.6, "min": 8, "max": 48}},
            {"share": 0.1,
             "prompt": {"median": 80, "sigma": 0.2, "min": 64, "max": 100}}],
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

"""The latent-attention serving cell at toy size, for the CPU
rehearsals: the committed workload file with every size cut down, and
the configuration file's keys at the widths of ``mla_moe_tiny``
(``tiny.py`` is GPT-2's, ``tiny_hybrid.py`` the hybrid's)."""

import copy
import sys
import time

from . import tiny

CELL = "serve-kimi-k2.7-code-repo"

CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 61,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.827, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 50000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"},
    "n_embd": 64, "n_layer": 3, "n_head": 4, "n_positions": 256,
    "assumed": {"initializer_range": 0.1, "router_bias_range": 0.1,
                "experts_held": [4, 4]},
    "serve": {"compute_dtype": "bfloat16", "param_dtype": "bfloat16"},
}


def workload():
    wl = copy.deepcopy(tiny.workload(CELL))
    wl["traffic"].update(
        rate_per_s=8.0, max_total=127, ramp_s=0.5, drain_s=1.0,
        prompt={"median": 30, "sigma": 0.8, "min": 4, "max": 80},
        output={"median": 10, "sigma": 0.6, "min": 2, "max": 24})
    wl["inference"].update(max_batch=4, seq_buckets=[128], n_pages=0,
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

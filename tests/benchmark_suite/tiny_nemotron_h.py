"""The Nemotron-H serving cell at toy size, for the CPU rehearsals: the
committed workload file with every size cut down, and the configuration
file's keys at the widths of ``nemotron_h_tiny`` (``tiny.py`` is
GPT-2's, ``tiny_hybrid.py`` the hybrid's, ``tiny_mla.py`` Kimi's)."""

import copy
import sys
import time

from . import tiny

CELL = "serve-nemotron-3-super-reason"
PATTERN = "ME*E" * 2
KINDS = {"M": "mamba", "*": "attention", "E": "experts"}

CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 88,
    "hybrid_override_pattern": PATTERN + "M" * 80,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "routed_scaling_factor": 5, "n_group": 1,
    "topk_group": 1, "mlp_hidden_act": "relu2", "use_conv_bias": True,
    "layer_norm_epsilon": 1e-5, "max_position_embeddings": 256,
    "n_embd": 64, "n_layer": 8, "n_head": 4, "n_positions": 256,
    # the aliases `flops_ssm.py` reads
    "layer_types": [KINDS[k] for k in PATTERN],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "assumed": {"initializer_range": 0.1, "router_bias_range": 0.1,
                "experts_held": [2, 4]},
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

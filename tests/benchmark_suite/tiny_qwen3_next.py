"""The Qwen3-Next serving cell at toy size, for the CPU rehearsals: the
committed workload file with every size cut down, and the configuration
file's keys at the widths of ``qwen3_next_tiny`` (``tiny.py`` is
GPT-2's, ``tiny_hybrid.py`` the hybrid's, ``tiny_mla.py`` Kimi's,
``tiny_nemotron_h.py`` Nemotron-H's)."""

import copy
import sys
import time

from . import tiny

CELL = "serve-qwen3-next-80b-a3b-longchat"

CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 48,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 16, "partial_rotary_factor": 0.5,
    "rope_theta": 10000000, "linear_num_key_heads": 2,
    "linear_key_head_dim": 16, "linear_num_value_heads": 4,
    "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
    "moe_intermediate_size": 48, "shared_expert_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "rms_norm_eps": 1e-6, "max_position_embeddings": 256,
    "n_embd": 64, "n_layer": 8, "n_head": 4, "n_positions": 256,
    "n_routed_experts": 4,
    "assumed": {"initializer_range": 0.1, "norm_weight_range": 0.1,
                "delta_chunk_size": 8, "experts_held": [2, 4]},
    # float32 at toy size: in bfloat16 at 64 channels, 8 experts and top 3
    # a near-tie flips and a whole model's logits say little
    # (`tests/unit/test_qwen3_next.py` holds the layers in bfloat16)
    "serve": {"compute_dtype": "float32", "param_dtype": "float32"},
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

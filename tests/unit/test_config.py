"""Config tests: batch triple solver + sanity checks.

Models the reference's `tests/unit/test_config.py` coverage.
"""

import pytest

from deepspeed_tpu.runtime.config import DeepSpeedConfig


def make_config(d, world_size=1):
    return DeepSpeedConfig(d, world_size=world_size)


def test_batch_all_three_consistent():
    cfg = make_config({
        "train_batch_size": 32,
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 2,
    }, world_size=4)
    assert cfg.train_batch_size == 32
    assert cfg.train_micro_batch_size_per_gpu == 4
    assert cfg.gradient_accumulation_steps == 2


def test_batch_all_three_inconsistent_raises():
    with pytest.raises(AssertionError):
        make_config({
            "train_batch_size": 33,
            "train_micro_batch_size_per_gpu": 4,
            "gradient_accumulation_steps": 2,
        }, world_size=4)


def test_batch_infer_grad_accum():
    cfg = make_config({
        "train_batch_size": 32,
        "train_micro_batch_size_per_gpu": 4,
    }, world_size=4)
    assert cfg.gradient_accumulation_steps == 2


def test_batch_infer_micro_batch():
    cfg = make_config({
        "train_batch_size": 32,
        "gradient_accumulation_steps": 2,
    }, world_size=4)
    assert cfg.train_micro_batch_size_per_gpu == 4


def test_batch_infer_train_batch():
    cfg = make_config({
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 2,
    }, world_size=4)
    assert cfg.train_batch_size == 32


def test_batch_only_train_batch():
    cfg = make_config({"train_batch_size": 32}, world_size=4)
    assert cfg.train_micro_batch_size_per_gpu == 8
    assert cfg.gradient_accumulation_steps == 1


def test_batch_none_raises():
    with pytest.raises(ValueError):
        make_config({}, world_size=1)


def test_zero_requires_low_precision():
    with pytest.raises(AssertionError):
        make_config({
            "train_batch_size": 8,
            "zero_optimization": {"stage": 2},
        }, world_size=1)


def test_zero_with_fp16():
    cfg = make_config({
        "train_batch_size": 8,
        "fp16": {"enabled": True},
        "zero_optimization": {"stage": 2},
    }, world_size=1)
    assert cfg.zero_enabled
    assert cfg.zero_optimization_stage == 2
    assert cfg.fp16_enabled


def test_zero_with_bf16():
    cfg = make_config({
        "train_batch_size": 8,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
    }, world_size=1)
    assert cfg.zero_enabled
    assert cfg.bf16_enabled and not cfg.fp16_enabled


def test_zero_offload_chunk_mb_key():
    """offload_chunk_mb (round 5): parsed with its default, overridable —
    sizes the offload host-phase pipeline's D2H/Adam/upload chunks."""
    cfg = make_config({
        "train_batch_size": 8,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2, "cpu_offload": True},
    }, world_size=1)
    assert cfg.zero_config.offload_chunk_mb == 64
    cfg2 = make_config({
        "train_batch_size": 8,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2, "cpu_offload": True,
                              "offload_chunk_mb": 16},
    }, world_size=1)
    assert cfg2.zero_config.offload_chunk_mb == 16


def test_zero_legacy_bool_form():
    cfg = make_config({
        "train_batch_size": 8,
        "fp16": {"enabled": True},
        "zero_optimization": True,
    }, world_size=1)
    assert cfg.zero_optimization_stage == 1


def test_fp16_and_bf16_mutually_exclusive():
    with pytest.raises(ValueError):
        make_config({
            "train_batch_size": 8,
            "fp16": {"enabled": True},
            "bf16": {"enabled": True},
        }, world_size=1)


def test_dynamic_loss_scale_args():
    cfg = make_config({
        "train_batch_size": 8,
        "fp16": {
            "enabled": True,
            "loss_scale": 0,
            "initial_scale_power": 16,
            "loss_scale_window": 500,
            "hysteresis": 3,
            "min_loss_scale": 2,
        },
    }, world_size=1)
    args = cfg.dynamic_loss_scale_args
    assert args["init_scale"] == 2 ** 16
    assert args["scale_window"] == 500
    assert args["delayed_shift"] == 3
    assert args["min_scale"] == 2
    assert cfg.initial_dynamic_scale == 2 ** 16


def test_static_loss_scale():
    cfg = make_config({
        "train_batch_size": 8,
        "fp16": {"enabled": True, "loss_scale": 128},
    }, world_size=1)
    assert cfg.loss_scale == 128


def test_optimizer_scheduler_sections():
    cfg = make_config({
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": 10}},
    }, world_size=1)
    assert cfg.optimizer_name == "adam"
    assert cfg.optimizer_params == {"lr": 1e-3}
    assert cfg.scheduler_name == "WarmupLR"
    assert cfg.scheduler_params == {"warmup_num_steps": 10}


def test_duplicate_json_keys_rejected(tmp_path):
    p = tmp_path / "ds_config.json"
    p.write_text('{"train_batch_size": 8, "train_batch_size": 16}')
    with pytest.raises(ValueError):
        DeepSpeedConfig(str(p), world_size=1)


def test_json_file_load(tmp_path):
    p = tmp_path / "ds_config.json"
    p.write_text('{"train_batch_size": 16, "fp16": {"enabled": true}}')
    cfg = DeepSpeedConfig(str(p), world_size=2)
    assert cfg.train_batch_size == 16
    assert cfg.train_micro_batch_size_per_gpu == 8
    assert cfg.fp16_enabled


def test_sparse_attention_fixed_mode():
    cfg = make_config({
        "train_batch_size": 8,
        "sparse_attention": {
            "mode": "fixed",
            "block": 16,
            "num_local_blocks": 4,
            "num_global_blocks": 1,
        },
    }, world_size=1)
    sa = cfg.sparse_attention
    assert sa["mode"] == "fixed"
    assert sa["block"] == 16
    assert sa["num_local_blocks"] == 4


def test_mesh_config():
    cfg = make_config({
        "train_batch_size": 8,
        "mesh": {"data": 2, "model": 4},
    }, world_size=2)
    assert cfg.mesh_shape == {"data": 2, "model": 4}


@pytest.mark.parametrize("placed_from_outside", [False, True])
def test_compilation_cache_dir_config(tmp_path, monkeypatch,
                                      placed_from_outside):
    """`compilation_cache_dir` turns the persistent cache on — but where
    JAX_COMPILATION_CACHE_DIR places the cache from outside, no code
    path sets another directory (`telemetry/compile_cache.configure`)."""
    import deepspeed_tpu
    import jax
    from tests.unit.simple_model import (base_config, simple_init_params,
                                         simple_loss_fn)

    before = jax.config.jax_compilation_cache_dir
    outside = str(tmp_path / "from_env")
    if placed_from_outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache = str(tmp_path / "xla_cache")
    cfg = base_config(compilation_cache_dir=cache)
    params = simple_init_params(jax.random.PRNGKey(0))
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            config=cfg, loss_fn=simple_loss_fn, params=params)
        if placed_from_outside:
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert jax.config.jax_compilation_cache_dir == cache
    finally:
        # restore the default so other tests are unaffected
        jax.config.update("jax_compilation_cache_dir", before)


def test_hot_checkpoint_config():
    cfg = make_config({
        "train_batch_size": 16,
        "resilience": {
            "save_dir": "/tmp/ckpt",
            "hot_checkpoint": {"enabled": True, "interval_steps": 2,
                               "capacity": 3, "mirror_dir": "/tmp/hot",
                               "mirror_keep": 2}}})
    rz = cfg.resilience
    assert rz.hot_enabled and rz.hot_interval_steps == 2
    assert rz.hot_capacity == 3 and rz.hot_mirror_keep == 2
    assert rz.hot_mirror_dir == "/tmp/hot"
    # disabled by default, knobs unvalidated when off
    assert not make_config(
        {"train_batch_size": 16}).resilience.hot_enabled


def test_hot_checkpoint_config_validation():
    with pytest.raises(ValueError, match="interval_steps"):
        make_config({
            "train_batch_size": 16,
            "resilience": {"hot_checkpoint": {
                "enabled": True, "interval_steps": 0}}})
    with pytest.raises(ValueError, match="capacity"):
        make_config({
            "train_batch_size": 16,
            "resilience": {"hot_checkpoint": {
                "enabled": True, "capacity": 0}}})


def test_inference_config_defaults_and_block():
    cfg = make_config({"train_batch_size": 16})
    inf = cfg.inference
    assert inf.max_batch == 8
    assert inf.seq_buckets == (128, 512)
    assert inf.prefill_chunk == 32
    assert inf.kv_cache_dtype is None
    assert inf.max_new_tokens == 64
    assert inf.attention_impl == "dense"
    assert inf.attention_block_k == 128
    assert inf.temperature == 0.0
    assert inf.top_k == 0
    assert inf.top_p == 1.0
    assert inf.sampling_seed == 0

    cfg = make_config({
        "train_batch_size": 16,
        "inference": {"max_batch": 4, "seq_buckets": [64, 256],
                      "prefill_chunk": 16, "kv_cache_dtype": "int8",
                      "max_new_tokens": 32, "attention_impl": "flash",
                      "attention_block_k": 64, "temperature": 0.8,
                      "top_k": 40, "top_p": 0.95, "sampling_seed": 7}})
    inf = cfg.inference
    assert inf.max_batch == 4
    assert inf.seq_buckets == (64, 256)   # list coerced to tuple
    assert inf.kv_cache_dtype == "int8"
    assert inf.attention_impl == "flash"
    assert inf.attention_block_k == 64
    assert inf.temperature == 0.8
    assert (inf.top_k, inf.top_p, inf.sampling_seed) == (40, 0.95, 7)


def test_inference_config_validation():
    def bad(block, match):
        with pytest.raises(ValueError, match=match):
            make_config({"train_batch_size": 16, "inference": block})

    bad({"max_batch": 0}, "max_batch")
    bad({"max_batch": True}, "max_batch")         # bools are not counts
    bad({"prefill_chunk": 0}, "prefill_chunk")
    bad({"seq_buckets": []}, "non-empty")
    bad({"seq_buckets": [64, 64]}, "strictly increasing")
    bad({"seq_buckets": [48, 64], "prefill_chunk": 32}, "multiple of")
    bad({"kv_cache_dtype": "e5m2"}, "kv_cache_dtype")
    bad({"max_new_tokens": 0}, "max_new_tokens")
    bad({"attention_impl": "sparse"}, "attention_impl")
    bad({"attention_block_k": 0}, "attention_block_k")
    bad({"temperature": -0.5}, "temperature")
    bad({"top_k": -1}, "top_k")
    bad({"top_p": 0.0}, "top_p")
    bad({"top_p": 1.5}, "top_p")
    bad({"sampling_seed": "abc"}, "sampling_seed")
    bad({"max_batc": 4}, "unknown key")


@pytest.mark.parametrize("value", ["ring", "", None, 0, "PAGED"])
def test_inference_kv_layout_is_refused_unless_paged(value):
    """The key chose a layout until PR 28; it is still read, so that a
    config that asks for the layout that went fails, typed, and does not
    silently serve through the other. `None` is JSON's null: absent."""
    block = {"max_batch": 4, "kv_layout": value}
    if value is None:
        assert make_config({"train_batch_size": 16,
                            "inference": block}).inference.max_batch == 4
        return
    with pytest.raises(ValueError, match="only KV layout since PR 28"):
        make_config({"train_batch_size": 16, "inference": block})


def test_inference_kv_layout_paged_is_accepted_and_changes_nothing():
    plain = make_config({"train_batch_size": 16,
                         "inference": {"page_size": 64}}).inference
    given = make_config({"train_batch_size": 16, "inference": {
        "page_size": 64, "kv_layout": "paged"}}).inference
    assert repr(given) == repr(plain) and "kv_layout" not in repr(given)
    assert not hasattr(given, "kv_layout")
    # the page keys are checked whatever the key says
    with pytest.raises(ValueError, match="page_size"):
        make_config({"train_batch_size": 16,
                     "inference": {"page_size": 48}})


def test_inference_fleet_config_defaults_and_block():
    cfg = make_config({"train_batch_size": 16})
    inf = cfg.inference
    assert inf.replicas == 1
    assert inf.max_redispatch == 2
    assert inf.max_queue_depth == 8
    assert inf.deadline_s == 0.0        # 0 = disabled
    assert inf.queue_timeout_s == 0.0

    cfg = make_config({
        "train_batch_size": 16,
        "inference": {"replicas": 3, "max_redispatch": 1,
                      "max_queue_depth": 4, "deadline_s": 2.5,
                      "queue_timeout_s": 0.5}})
    inf = cfg.inference
    assert (inf.replicas, inf.max_redispatch, inf.max_queue_depth,
            inf.deadline_s, inf.queue_timeout_s) == (3, 1, 4, 2.5, 0.5)


def test_inference_fleet_config_validation():
    def bad(block, match):
        with pytest.raises(ValueError, match=match):
            make_config({"train_batch_size": 16, "inference": block})

    bad({"replicas": 0}, "replicas")
    bad({"replicas": True}, "replicas")           # bools are not counts
    bad({"max_redispatch": -1}, "max_redispatch")
    bad({"max_queue_depth": 0}, "max_queue_depth")
    bad({"deadline_s": -1.0}, "deadline_s")
    bad({"deadline_s": True}, "deadline_s")
    bad({"queue_timeout_s": -0.5}, "queue_timeout_s")


def test_speculative_config_defaults_and_block():
    cfg = make_config({"train_batch_size": 16})
    inf = cfg.inference
    assert inf.speculative_enabled is False
    assert inf.speculative_k == 4
    assert inf.speculative_draft_layers == 0      # 0 = auto: n_layer//2
    assert inf.speculative_min_accept_to_grow == 0.0
    assert inf.speculative is None                # disabled -> None

    cfg = make_config({
        "train_batch_size": 16,
        "inference": {"speculative": {
            "enabled": True, "k": 3, "draft_layers": 2,
            "min_accept_to_grow": 0.8}}})
    inf = cfg.inference
    assert inf.speculative == {
        "enabled": True, "k": 3, "draft_layers": 2,
        "min_accept_to_grow": 0.8}

    # an explicitly disabled block validates but resolves to None
    cfg = make_config({
        "train_batch_size": 16,
        "inference": {"speculative": {"enabled": False, "k": 7}}})
    assert cfg.inference.speculative is None


def test_speculative_config_validation():
    def bad(block, match):
        with pytest.raises(ValueError, match=match):
            make_config({"train_batch_size": 16, "inference": block})

    bad({"speculative": 3}, "dict block")
    bad({"speculative": {"kk": 3}}, "unknown key")
    bad({"speculative": {"enabled": 1}}, "enabled must be a bool")
    # the validated config is strict: k >= 1 (only the engine's raw
    # dict path treats k=0 as a degenerate disable)
    bad({"speculative": {"k": 0}}, "speculative.k")
    bad({"speculative": {"k": True}}, "speculative.k")
    bad({"speculative": {"draft_layers": -1}}, "draft_layers")
    bad({"speculative": {"min_accept_to_grow": -0.1}},
        "min_accept_to_grow")
    # k+1 verify slots must leave headroom in the largest bucket
    bad({"seq_buckets": [8], "prefill_chunk": 8,
         "speculative": {"enabled": True, "k": 7}}, "headroom")
    # fleet router doesn't know the 3-program contract yet
    bad({"replicas": 2, "speculative": {"enabled": True, "k": 3}},
        "mutually")

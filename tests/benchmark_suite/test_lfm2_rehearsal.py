"""The LFM2 serving cell's driver end to end at toy size on the CPU, its
manifest entries, its configuration file, its work functions against
hand arithmetic and its metric files on a hand-made trace
(`test_lfm2_faults.py` has every named fault of
`tools/fault_readings_lfm2.py` over its limit). No number from here is a
device metric. Membership is asserted, never position or count, so that
the next cell breaks nothing here."""

import importlib
import json

import jax
import numpy as np
import pytest

from benchmarks.suite import flops_lfm2, flops_qwen3_next, harness, xplane
from benchmarks.suite.drivers import serve_lfm2, serve_qwen3_next
from benchmarks.suite.readers import setup_split
from benchmarks.suite.traffic import open_loop

from . import test_manifest, tiny, tiny_lfm2

CELL = tiny_lfm2.CELL
CONFIG = "lfm2-8b-a1b"
NEW = {"sconv_prefill_ms.serve": "ttft_p90_ms",
       "sconv_decode_ms.serve": "itl_p95_ms",
       "sconv_prefill_roofline.serve": "ttft_p90_ms",
       "sconv_decode_roofline.serve": "itl_p95_ms",
       "sconv_taps_ms.serve": "itl_p95_ms"}
# accepted metrics whose reader (and work function) give this
# configuration's own number, so the cell is appended to their lists
TAKEN = {"decode_step_ms.serve", "prefill_ms.serve", "queue_wait_ms.serve",
         "batch_occupancy_pct.serve", "device_idle_pct.serve",
         "kv_copy_ms.serve", "pool_fill_pct.serve",
         "sched_queue_wait_ms.serve", "sched_occupancy_pct.serve",
         "first_token_ready_ms.serve", "first_token_hold_ms.serve",
         "engine_prefill_ms.serve", "engine_decode_ms.serve",
         "sched_host_ms.serve", "kv_live_pages_pct.serve",
         "idle_logits_d2h_ms.serve", "idle_sched_ms.serve",
         "decode_grid_live_pct.serve", "kv_write_rows_live_pct.serve",
         "moe_ms.serve", "moe_expert_matmul_ms.serve",
         "moe_pairs_held_pct.serve", "moe_permute_ms.serve",
         "moe_prefill_ms.serve", "moe_experts_touched_pct.serve",
         "moe_dispatch_rows_useful_pct.serve",
         "moe_pairs_max_over_mean_q3n.serve",
         "swiglu_expert_matmul_roofline.serve",
         "flash_decode_paged_ms.serve", "kv_write_ms.serve",
         "dense_mlp_ms.serve", "dense_mlp_prefill_ms.serve",
         "attn_plain_prefill_ms.serve", "state_live_pct.serve",
         "attn_proj_ms.serve", "attn_proj_prefill_ms.serve",
         "head_ms.serve", "window_compiles.serve", "gc_pause_ms.serve",
         "stall_max_ms.serve", "prefill_stall_p99_ms.serve",
         "setup_trace_s", "setup_lower_s", "setup_compile_s", "setup_gc_s",
         "setup_engine_s", "setup_warmup_s", "setup_rest_s"}
# no shared expert; Granite's roofline counts layers named "attention";
# Mamba-2's, the delta rules', latents, windows and rings
NOT_TAKEN = {"moe_shared_ms.serve", "flash_decode_paged_roofline.serve",
             "ssm_decode_ms.serve", "ssd_prefill_ms.serve",
             "ssm_rows_live_pct.serve", "gdn_decode_ms.serve",
             "gdn_rows_live_pct.serve", "kda_decode_ms.serve",
             "kda_rows_live_pct.serve", "mla_project_ms.serve",
             "mla_decode_roofline.serve", "gqa256_decode_roofline.serve",
             "attn_gate_ms.serve", "logits_d2h_ms.serve",
             "window_blocks_in_window_pct.serve",
             "kv_window_bytes_pct.serve", "attn_prefill_full_ms.serve",
             "moe_expert_matmul_roofline.serve",
             "moe_pairs_max_over_mean.serve"}


def config_file():
    return test_manifest.load(test_manifest.ROOT, "benchmarks", "suite",
                              "configs", CONFIG + ".json")


def metric(ctx, res, name):
    spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
    reader = importlib.import_module(
        "benchmarks.suite.readers." + spec["reader"])
    return reader.read(ctx, res, **spec["args"])


def test_cell_config_and_metrics_are_in_the_manifest():
    manifest = test_manifest.MANIFEST
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "agent", 1)
    conf = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["n_routed_experts"]
    assert conf["source"] == config_file()["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert tiny.workload(CELL)["driver"] == "serve_lfm2"
    assert set(test_manifest.listed("end_to_end", CELL)) == {
        "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    listed = set(test_manifest.listed("per_layer", CELL))
    assert set(NEW) <= listed and TAKEN <= listed
    assert not listed & NOT_TAKEN
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, moves in NEW.items():
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == moves, name
        assert by_name[name]["layer"] == "recurrent state"
        assert by_name[name]["source"] == "device_trace"
        if name.endswith("_roofline.serve"):
            assert by_name[name]["unit"] == "%"
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        assert m.get("workloads", []).count(CELL) <= 1
    # the manifest's limit: this PR's five took the last of 128 places
    assert len(manifest["per_layer"]) <= 128
    # eleven of the twelve cells and more on one chip: a second and a
    # third four-chip cell are admissible
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four == 1 and len(manifest["workloads"]) // 4 >= 3


def test_cell_is_what_the_issue_names():
    wl = tiny.workload(CELL)
    inf, t = wl["inference"], wl["traffic"]
    assert (inf["max_batch"], inf["seq_buckets"], inf["prefill_chunk"],
            inf["page_size"], inf["attention_impl"], inf["n_pages"]) == (
                192, [9216], 1024, 128, "flash", 3841)
    assert t["prompt"] == {"median": 1536, "sigma": 0.8, "min": 256,
                           "max": 8192}
    assert t["output"] == {"median": 320, "sigma": 0.6, "min": 48,
                           "max": 1024}
    assert t["max_total"] == 9215 == inf["seq_buckets"][0] - 1
    assert (t["generator"], t["order_seed"], t["ramp_s"], t["drain_s"]) == (
        "open_loop", 1, 30, 5)
    assert "kv_cache_dtype" not in inf and "sampling" not in inf  # greedy
    assert "prefix_cache" not in inf    # off: the state refuses it
    assert wl["trace"]["scope_marker"] == "ds_"
    cfg = config_file()
    pool = (inf["n_pages"] - 1) * inf["page_size"] * \
        flops_lfm2.kv_bytes_per_token(cfg)
    assert pool == 6_039_797_760
    assert inf["max_batch"] * flops_lfm2.state_bytes_per_row(cfg) == \
        28_311_552
    # the trace at the cell's rate: a fixed set of sizes within the limits
    a = open_loop.make(t, t["order_seed"], cfg["vocab_size"], 51)
    lens = np.asarray([len(x.prompt) for x in a])
    assert lens.min() >= 256 and 4096 < lens.max() <= 8192
    assert max(len(x.prompt) + x.max_new_tokens for x in a) <= 9215
    # the distribution's p90 prompt is five calls of 1,024 (1,536 x
    # e^(0.8 x 1.2816) = 4,281); this trace's own sample stands at 4,085
    assert 3.9 * 1024 < np.percentile(lens, 90) <= 5 * 1024
    assert t["rate_per_s"] * 51 >= 200
    corr = wl["correctness"]
    assert corr["requests"] == 2
    for key in ("logit_rtol", "window_rtol", "deep_rtol", "mixer_rtol",
                "attention_rtol", "attention_decode_rtol", "expert_rtol"):
        assert 0 < corr[key] <= 0.4, key
    for text in (wl["why"], t["rate_why"], inf["pool_why"], corr["why"]):
        assert "TBD" not in text and len(text) > 200


def test_configuration_file_is_the_published_model_and_its_share():
    cfg = config_file()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "LFM2-8B-A1B")
    assert cfg["source"] == published["source_url"]
    for key, value in published["config"].items():    # nothing is changed
        assert cfg[key] == value, key
    assert (cfg["n_layer"], cfg["num_hidden_layers"],
            cfg["n_routed_experts"], cfg["num_experts"],
            cfg["assumed"]["experts_held"], cfg["reduced"]) == (
                24, 24, 8, 32, [0, 8], ["n_routed_experts"])
    for key in ("tie_embedding", "head_dim", "qk_layernorm", "rotary",
                "conv_split", "conv_window", "router_bias_why", "route_eps",
                "weights", "precision"):
        assert key in cfg["assumed"], key
    for word in ("four chips", "16.7 GB", "ALL 24 layers", "experts 0-7",
                 "whole vocabulary"):
        assert word in cfg["reduced_why"]["deployment"], word
    # the program's config from the file: the published list, read
    mc = serve_lfm2.model_config(cfg)
    assert mc.layer_types == tuple(cfg["layer_types"])
    assert [i for i, t in enumerate(mc.layer_types)
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert (mc.vocab_size, mc.num_hidden_layers, mc.experts_held,
            mc.head_dim, mc.num_dense_layers, mc.conv_L_cache) == (
                65536, 24, (0, 8), 64, 2, 3)
    assert (mc.rope_theta, mc.norm_eps, mc.routed_scaling_factor) == (
        1e6, 1e-5, 1)
    # ISSUE 57's arithmetic, reckoned again
    f = flops_lfm2
    assert f.sconv_params(cfg) == 16_783_360
    assert f.attention_params(cfg) == 10_485_888
    assert f.dense_mlp_params(cfg) == 44_040_192
    assert f.expert_params(cfg) == 11_010_048
    assert f.param_count(cfg) == 2_526_625_216
    assert f.param_count(cfg, held=32) == pytest.approx(8339.9e6, rel=1e-4)
    assert f.param_count(cfg, active=True) == pytest.approx(1557.7e6,
                                                            rel=1e-4)
    assert f.state_bytes_per_row(cfg) == 147_456
    assert f.kv_bytes_per_token(cfg) == 12_288


def test_param_count_equals_the_tiny_models_own_leaves():
    from deepspeed_tpu.models.lfm2_moe import (Lfm2MoeLM,
                                               init_lfm2_moe_params)
    cfg = tiny_lfm2.CONFIG
    model = Lfm2MoeLM(serve_lfm2.model_config(cfg))
    params = jax.eval_shape(
        lambda k: init_lfm2_moe_params(model, k), jax.random.PRNGKey(0))
    leaves = sum(int(np.prod(a.shape))
                 for a in jax.tree_util.tree_leaves(params))
    assert flops_lfm2.param_count(cfg) == leaves


@pytest.fixture(scope="module")
def traced():
    ctx = tiny_lfm2.context(jax.devices()[:1], seconds=1.5, trace=True)
    lines = []
    ctx.log = lines.append
    return ctx, serve_lfm2.run(ctx), lines


def test_serve_lfm2_driver(traced):
    ctx, res, _ = traced
    checks = res.detail["checks"]
    assert res.correct, checks
    assert res.failed == 0 and res.attempted > 5
    assert checks["compile_counts"] == {"prefill": 1, "decode": 1}
    assert checks["compiles_in_run"] == 0 and len(checks["reference"]) == 2
    own = checks["own_input"]
    assert set(own) == {"slot", "mixer", "attention", "experts"}
    # the windows, the pages and the logit row as the engine's own two
    # programs left them: float32
    for reading in ("after_prefill", "after_short_prefill", "after_decode",
                    "deep_rows", "deep_logits"):
        assert 0 <= own["slot"][reading] < 1e-4, own["slot"]
    assert own["slot"]["decode_steps"] > 16
    assert own["mixer"]["dead_rows_window_moved"] == 0.0
    assert own["attention"]["calls"] == 5 and \
        own["attention"]["tokens"] > 4 * 32
    for kind in ("mixer", "attention", "experts"):
        assert own[kind]["prefill"] < 1e-4 and own[kind]["decode"] < 1e-4
    ex = own["experts"]
    assert ex["pairs_routed"] == 2 * (ex["tokens"] + ex["rows"])
    assert ex["weights_sum_off"] < 1e-5 and ex["route_weights_off"] < 1e-5
    assert 0 < ex["pairs_held"] < ex["pairs_routed"]
    assert res.trace is None            # a CPU trace has no device plane
    facts = res.facts
    assert 0 < facts["sconv_rows_live_profiled"] <= \
        facts["sconv_rows_touched_profiled"] == 4
    assert facts["prefill_chunks_profiled"] >= 1
    scopes = facts["program_scopes"]
    for program, kinds in (("prefill", ("ds_attn_prefill_plain",
                                        "ds_kv_write")),
                           ("decode", ("ds_attn_decode_plain",
                                       "ds_flash_decode_paged"))):
        where = " ".join(scopes[program].values())
        for scope in kinds + ("ds_sconv_mixer", "ds_sconv_in_proj",
                              "ds_sconv_taps", "ds_sconv_out_proj",
                              "ds_attn_qkv", "ds_attn_qk_norm",
                              "ds_attn_out", "ds_mlp", "ds_moe_route",
                              "ds_moe_experts"):
            assert scope in where, (program, scope)
        assert "ds_moe_shared" not in where
    # the parts are `drivers/serve_qwen3_next.py`'s, by import, and that
    # module's four names are its own again after the call
    assert serve_lfm2.parts is serve_qwen3_next
    for name in ("check_logits", "own_input_checks", "ring_facts"):
        assert getattr(serve_qwen3_next, name).__module__.endswith(
            "serve_qwen3_next")
    assert serve_qwen3_next.ref.__name__.endswith("qwen3_next_ref")


def test_every_new_metric_is_a_number_at_toy_size(traced):
    """The counters' metrics from the program's own spans; the device's
    from a hand-made trace laid over the run's facts and scopes (a CPU
    run has no device plane), so that every new metric's file, reader
    and work function gives a number on what the driver hands over."""
    ctx, res, _ = traced
    assert 0 < metric(ctx, res, "moe_pairs_held_pct.serve") < 100
    assert 0 < metric(ctx, res, "moe_experts_touched_pct.serve") <= 100
    assert 0 < metric(ctx, res, "moe_dispatch_rows_useful_pct.serve") <= 100
    assert metric(ctx, res, "moe_pairs_max_over_mean_q3n.serve") > 0
    assert 0 < metric(ctx, res, "state_live_pct.serve") <= 100
    assert 0 < metric(ctx, res, "kv_write_rows_live_pct.serve") <= 100
    assert 0 < metric(ctx, res, "kv_live_pages_pct.serve") <= 100
    split = [metric(ctx, res, f"setup_{p}_s") for p in setup_split.PARTS]
    assert all(isinstance(v, float) and v >= 0 for v in split)
    assert metric(ctx, res, "window_compiles.serve") == 0
    for name in NEW:                    # no device plane: nothing, quietly
        assert metric(ctx, res, name) is None, name
    # one op under each scope the new metrics sum, 1 ms each
    scopes = res.facts["program_scopes"]
    ops, t = {"prefill": [], "decode": []}, 0.0
    for program, kinds in (
            ("prefill", ("ds_sconv_in_proj", "ds_sconv_taps", "ds_mlp")),
            ("decode", ("ds_sconv_in_proj", "ds_sconv_taps",
                        "ds_sconv_out_proj", "ds_mlp"))):
        for kind in kinds:
            name = next(k for k, v in scopes[program].items() if kind in v)
            ops[program].append((name + " fusion", t, t + 1e-3))
            t += 1e-3
    both = harness.Result(
        correct=True, attempted=1, failed=0, setup_s=1.0, end_to_end={},
        facts=res.facts, detail={}, trace=xplane.Trace(
            devices={0: ops["prefill"] + ops["decode"]},
            spans=[("prefill", -1e-3, 3e-3), ("decode", 3e-3, 8e-3)]))
    for name in list(NEW) + ["dense_mlp_ms.serve",
                             "dense_mlp_prefill_ms.serve"]:
        value = metric(ctx, both, name)
        assert isinstance(value, float) and value > 0, name
    # the mixer's scope takes its three inner ones; the taps' its own
    assert metric(ctx, both, "sconv_prefill_ms.serve") == pytest.approx(2.0)
    assert metric(ctx, both, "sconv_decode_ms.serve") == pytest.approx(3.0)
    assert metric(ctx, both, "sconv_taps_ms.serve") == pytest.approx(1.0)


def test_work_functions_against_hand_arithmetic():
    cfg = config_file()
    ctx = tiny_lfm2.context(jax.devices()[:1], 1.0, False, config=cfg)
    facts = {"sconv_rows_live_profiled": 100.0,
             "prefill_chunks_profiled": 2.5, "prefill_chunk": 1024,
             "prefill_pad_tokens_profiled": 500.0,
             "kv_bytes_per_element": 2, "moe_experts_touched_profiled": 150.0,
             "moe_pairs_held_profiled": 2200.0}
    res = harness.Result(True, 1, 0, 1.0, {}, facts, {})
    per_layer = 16_783_360
    ops, moved = flops_lfm2.sconv_decode_step(ctx, res)
    assert ops == 18 * 2 * per_layer * 100
    assert moved == 18 * 2 * (per_layer + 2 * 100 * 2 * 2048
                              + 2 * 100 * 2048)
    # a step is bound by the weights' bytes, a call by its operations
    peaks = ctx.peaks
    assert moved / peaks["hbm_bytes_per_s"] > ops / peaks["bf16_flops_per_s"]
    ops, moved = flops_lfm2.sconv_prefill_call(ctx, res)
    tokens = 2.5 * 1024 - 500
    assert ops == 18 * 2 * per_layer * tokens
    assert moved == 18 * 2 * (2.5 * per_layer + 2 * 2.5 * 2 * 2048
                              + 2 * tokens * 2048)
    assert ops / peaks["bf16_flops_per_s"] > moved / peaks["hbm_bytes_per_s"]
    # the experts' grouped matmuls by Qwen3-Next's function, unchanged
    ops, moved = flops_qwen3_next.expert_matmuls_decode_step(ctx, res)
    assert ops == 2 * 2200 * 11_010_048
    assert moved == (150 * 11_010_048 + 2200 * (2 * 2048 + 3 * 1792)) * 2
    empty = harness.Result(True, 1, 0, 1.0, {}, {}, {})
    for fn in (flops_lfm2.sconv_decode_step, flops_lfm2.sconv_prefill_call):
        assert fn(ctx, empty) is None


def test_parent_without_the_model_exits_2(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_model(name, *a, **k):
        if name.endswith("models.lfm2_moe"):
            raise ImportError(name)
        return real(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_model)
    ctx = tiny_lfm2.context(jax.devices()[:1], seconds=1.0, trace=False)
    with pytest.raises(SystemExit) as e:
        serve_lfm2.run(ctx)
    assert e.value.code == 2

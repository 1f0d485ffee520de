"""The Ling-3.0 serving cell's driver end to end at toy size on the CPU,
its manifest entries, its configuration file, its work functions against
hand arithmetic and its metric files on a hand-made trace
(`test_ling_faults.py` has every named fault of
`tools/fault_readings_ling.py` over its limit). No number from here is a
device metric. Membership is asserted, never position or
count, so that the next cell breaks nothing here."""

import importlib
import json

import jax
import numpy as np
import pytest

from benchmarks.suite import flops_ling, flops_qwen3_next, harness, xplane
from benchmarks.suite.drivers import serve_ling, serve_qwen3_next
from benchmarks.suite.readers import setup_split
from benchmarks.suite.traffic import open_loop_mixed

from . import test_manifest, tiny, tiny_ling

CELL = tiny_ling.CELL
CONFIG = "ling-3.0-flash"
NEW = {"kda_prefill_ms.serve": "ttft_p90_ms",
       "kda_decode_ms.serve": "itl_p95_ms",
       "kda_prefill_roofline.serve": "ttft_p90_ms",
       "kda_decode_roofline.serve": "itl_p95_ms",
       "kda_gate_ms.serve": "itl_p95_ms",
       "kda_rows_live_pct.serve": "itl_p95_ms",
       "moe_tokens_held_group_pct.serve": "itl_p95_ms",
       "mla_decode_1of8_roofline.serve": "itl_p95_ms",
       "mla_prefill_attn_1of8_roofline.serve": "ttft_p90_ms"}
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
         "moe_ms.serve", "moe_expert_matmul_ms.serve", "moe_shared_ms.serve",
         "moe_pairs_held_pct.serve", "moe_permute_ms.serve",
         "moe_prefill_ms.serve", "moe_experts_touched_pct.serve",
         "moe_dispatch_rows_useful_pct.serve",
         "moe_pairs_max_over_mean_q3n.serve",
         "swiglu_expert_matmul_roofline.serve", "mla_project_ms.serve",
         "mla_prefill_attn_ms.serve", "mla_prefill_kernel_blocks_pct.serve",
         "dense_mlp_ms.serve", "dense_mlp_prefill_ms.serve",
         "attn_gate_ms.serve", "state_live_pct.serve",
         "window_compiles.serve", "gc_pause_ms.serve", "stall_max_ms.serve",
         "prefill_stall_p99_ms.serve", "setup_trace_s", "setup_lower_s",
         "setup_compile_s", "setup_gc_s", "setup_engine_s",
         "setup_warmup_s", "setup_rest_s"}
# Kimi's two rooflines (their work functions multiply by n_layer and read
# q_lora_rank), the scalar delta rule's, Mamba-2's, windows and rings
NOT_TAKEN = {"mla_decode_roofline.serve", "mla_prefill_attn_roofline.serve",
             "gdn_decode_ms.serve", "gdn_prefill_ms.serve",
             "gdn_decode_roofline.serve", "gdn_prefill_roofline.serve",
             "gdn_rows_live_pct.serve", "gqa256_decode_roofline.serve",
             "ssm_decode_ms.serve", "ssd_prefill_ms.serve",
             "ssm_rows_live_pct.serve", "logits_d2h_ms.serve",
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
        CONFIG, "reason-docs", 1)
    conf = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["n_layer", "n_routed_experts", "vocab_size",
                               "num_nextn_predict_layers"]
    assert conf["source"] == config_file()["source"] == \
        "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/" \
        "config.json"
    assert tiny.workload(CELL)["driver"] == "serve_ling"
    assert set(test_manifest.listed("end_to_end", CELL)) == {
        "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    listed = set(test_manifest.listed("per_layer", CELL))
    assert set(NEW) <= listed and TAKEN <= listed
    assert not listed & NOT_TAKEN
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    layers = {"kda": "recurrent state", "moe": "experts", "mla": "kernels"}
    for name, moves in NEW.items():
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == moves, name
        assert by_name[name]["layer"] == layers[name[:3]]
        if name.endswith("_roofline.serve"):
            assert by_name[name]["unit"] == "%"
            assert by_name[name]["source"] == "device_trace"
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        assert m.get("workloads", []).count(CELL) <= 1
    # ten of the eleven cells and more on one chip: a second four-chip
    # cell is still admissible
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four == 1 and len(manifest["workloads"]) // 4 >= 2


def test_cell_is_what_the_issue_names():
    wl = tiny.workload(CELL)
    inf, t = wl["inference"], wl["traffic"]
    assert (inf["max_batch"], inf["seq_buckets"], inf["prefill_chunk"],
            inf["page_size"], inf["attention_impl"], inf["n_pages"]) == (
                64, [34816], 1024, 128, "flash", 4097)
    assert t["classes"] == [
        {"share": 0.9, "prompt": {"median": 1024, "sigma": 0.8, "min": 128,
                                  "max": 4096}},
        {"share": 0.1, "prompt": {"median": 16384, "sigma": 0.5,
                                  "min": 8192, "max": 32768}}]
    assert t["output"] == {"median": 768, "sigma": 0.5, "min": 192,
                           "max": 1536}
    assert t["max_total"] == 34304 <= inf["seq_buckets"][0]
    assert (t["generator"], t["order_seed"], t["ramp_s"], t["drain_s"]) == (
        "open_loop_mixed", 1, 40, 5)
    assert "kv_cache_dtype" not in inf and "sampling" not in inf  # greedy
    assert "prefix_cache" not in inf    # off: it refuses the state
    assert wl["trace"]["scope_marker"] == "ds_"
    cfg = config_file()
    pool = (inf["n_pages"] - 1) * inf["page_size"] * \
        flops_ling.latent_bytes_per_token(cfg)
    assert pool == 603_979_776
    assert inf["max_batch"] * flops_ling.state_bytes_per_row(cfg) == \
        972_554_240
    # the trace at the cell's rate: a fixed set of sizes within the limits
    a = open_loop_mixed.make(t, t["order_seed"], cfg["vocab_size"], 51)
    lens = np.asarray([len(x.prompt) for x in a])
    assert lens.min() >= 128 and 8192 <= lens.max() <= 32768
    assert max(len(x.prompt) + x.max_new_tokens for x in a) <= 34304
    assert 0.03 < np.mean(lens >= 8192) < 0.2
    assert t["rate_per_s"] * 51 >= 40
    corr = wl["correctness"]
    assert corr["requests"] == 2
    for key in ("logit_rtol", "state_rtol", "window_rtol", "deep_rtol",
                "mixer_rtol", "attention_rtol", "attention_decode_rtol",
                "expert_rtol"):
        assert 0 < corr[key] <= 0.25, key
    for key in ("rate_why", "pool_why"):
        assert "PLACEHOLDER" not in (t.get(key) or inf.get(key)), key
    assert "PLACEHOLDER" not in corr["why"]


def test_configuration_file_is_the_published_model_and_its_share():
    cfg = config_file()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "Ling-3.0-flash")
    assert cfg["source"] == published["source_url"]
    for key, value in published["config"].items():
        if key not in ("vocab_size", "num_nextn_predict_layers"):
            assert cfg[key] == value, key
    assert (cfg["vocab_size"], cfg["vocab_size_published"], cfg["n_layer"],
            cfg["n_routed_experts"], cfg["num_nextn_predict_layers"],
            cfg["assumed"]["experts_held"]) == (
                39296, published["config"]["vocab_size"], 8, 128, 0,
                [0, 128])
    assert cfg["layer_kinds"] == ["kda"] * 5 + ["mla"] + ["kda"] * 2
    for key in ("kda_gate", "gate_projection", "linear_silu", "use_qk_norm",
                "num_kv_heads_for_linear_attn", "no_kda_lora", "routing",
                "rotary", "attention_gate", "swiglu_limits", "unread",
                "kda_chunk_why", "centred_why", "precision"):
        assert key in cfg["assumed"], key
    for word in ("4 chips", "pipeline stages", "first stage"):
        assert word in cfg["reduced_why"]["deployment"], word
    # the program's config from the file
    mc = serve_ling.model_config(cfg)
    assert mc.layer_types == tuple(cfg["layer_kinds"])
    assert (mc.vocab_size, mc.num_hidden_layers, mc.experts_held) == (
        39296, 8, (0, 128))
    assert (mc.n_group, mc.topk_group, mc.kda_lower_bound,
            mc.kda_chunk_size) == (8, 4, -5, 64)
    # the published lists are kept whole, and a layer past 33 would raise
    assert mc.expert_swiglu_limit_list[35] == 4
    with pytest.raises(ValueError, match="clamp"):
        serve_ling.model_config(dict(cfg, n_layer=36))
    # ISSUE 55's arithmetic, reckoned again
    f = flops_ling
    assert f.kda_params(cfg) == 63_049_888
    assert f.mla_params(cfg) == 31_965_696
    assert f.dense_mlp_params(cfg) == 47_185_920
    assert f.expert_params(cfg) == 5_898_240
    assert f.param_count(cfg) == pytest.approx(5342e6, rel=0.0005)
    assert f.param_count(cfg, held=512, n_layer=42, vocab_size=157184) == \
        pytest.approx(124.4e9, rel=0.001)
    assert f.param_count(cfg, n_layer=42, vocab_size=157184,
                         active=True) == pytest.approx(5.5e9, rel=0.01)
    assert f.state_bytes_per_row(cfg) == 15_196_160
    assert f.latent_bytes_per_token(cfg) == 1152


def test_param_count_equals_the_tiny_models_own_leaves():
    from deepspeed_tpu.models.ling_hybrid import (LingHybridLM,
                                                  init_ling_hybrid_params)
    cfg = tiny_ling.CONFIG
    model = LingHybridLM(serve_ling.model_config(cfg))
    params = jax.eval_shape(
        lambda k: init_ling_hybrid_params(model, k), jax.random.PRNGKey(0))
    leaves = sum(int(np.prod(a.shape))
                 for a in jax.tree_util.tree_leaves(params))
    assert flops_ling.param_count(cfg) == leaves


@pytest.fixture(scope="module")
def traced():
    ctx = tiny_ling.context(jax.devices()[:1], seconds=1.5, trace=True)
    lines = []
    ctx.log = lines.append
    return ctx, serve_ling.run(ctx), lines


def test_serve_ling_driver(traced):
    ctx, res, _ = traced
    checks = res.detail["checks"]
    assert res.correct, checks
    assert res.failed == 0 and res.attempted > 5
    assert checks["compile_counts"] == {"prefill": 1, "decode": 1}
    assert checks["compiles_in_run"] == 0 and len(checks["reference"]) == 2
    own = checks["own_input"]
    assert set(own) == {"slot", "mixer", "attention", "experts"}
    # the seven... here three states, the windows, the latent pages and
    # the logit row as the engine's own two programs left them: float32
    for reading in ("after_prefill", "after_short_prefill", "after_decode",
                    "window", "deep_state", "deep_rows", "deep_logits"):
        assert 0 <= own["slot"][reading] < 1e-4, own["slot"]
    assert own["slot"]["state_bfloat16_share"] < 0.01
    assert own["slot"]["decode_steps"] > 16
    assert own["mixer"]["dead_rows_state_max"] == 0.0
    assert own["attention"]["calls"] == 9 and \
        own["attention"]["tokens"] > 8 * 32
    for kind in ("mixer", "attention", "experts"):
        assert own[kind]["prefill"] < 1e-4 and own[kind]["decode"] < 1e-4
    ex = own["experts"]
    assert ex["pairs_routed"] == 3 * (ex["tokens"] + ex["rows"])
    assert ex["chosen_in_kept_groups"] and ex["weights_sum_off"] < 1e-5
    assert 0 < ex["tokens_held_group"] < ex["tokens_routed"]
    assert res.trace is None            # a CPU trace has no device plane
    facts = res.facts
    assert facts["kda_rows_live_profiled"] == \
        facts["kda_rows_touched_profiled"] > 0
    assert facts["prefill_chunks_profiled"] >= 1
    assert 0 <= facts["moe_tokens_held_group_profiled"] <= \
        facts["moe_tokens_routed_profiled"]
    scopes = facts["program_scopes"]
    for program, kinds in (("prefill", ("ds_kda_scan", "ds_kda_scan_chunks",
                                        "ds_mla_prefill_attn",
                                        "ds_flash_prefill_latent")),
                           ("decode", ("ds_kda_step", "ds_kda_step_rows",
                                       "ds_mla_decode_attn",
                                       "ds_flash_decode_paged"))):
        where = " ".join(scopes[program].values())
        for scope in kinds + ("ds_kda_mixer", "ds_kda_gate", "ds_attn_gate",
                              "ds_mla_project", "ds_mlp", "ds_moe_route",
                              "ds_moe_experts", "ds_moe_shared"):
            assert scope in where, (program, scope)
    # the parts are `drivers/serve_qwen3_next.py`'s, by import, and that
    # module's four names are its own again after the call
    assert serve_ling.parts is serve_qwen3_next
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
    assert metric(ctx, res, "kda_rows_live_pct.serve") == 100.0
    assert 0 <= metric(ctx, res, "moe_tokens_held_group_pct.serve") <= 100
    assert 0 < metric(ctx, res, "moe_pairs_held_pct.serve") < 80
    assert 0 < metric(ctx, res, "moe_experts_touched_pct.serve") <= 100
    assert 0 < metric(ctx, res, "moe_dispatch_rows_useful_pct.serve") <= 100
    assert metric(ctx, res, "moe_pairs_max_over_mean_q3n.serve") > 0
    assert metric(ctx, res, "mla_prefill_kernel_blocks_pct.serve") == 100.0
    assert 0 < metric(ctx, res, "state_live_pct.serve") <= 100
    assert 0 < metric(ctx, res, "kv_write_rows_live_pct.serve") <= 100
    assert 0 < metric(ctx, res, "kv_live_pages_pct.serve") <= 100
    split = [metric(ctx, res, f"setup_{p}_s") for p in setup_split.PARTS]
    assert all(isinstance(v, float) and v >= 0 for v in split)
    assert metric(ctx, res, "window_compiles.serve") == 0
    device = [n for n in NEW if not n.endswith("_pct.serve")]
    for name in device:                 # no device plane: nothing, quietly
        assert metric(ctx, res, name) is None, name
    # one op under each scope the new metrics sum, 1 ms each
    scopes = res.facts["program_scopes"]
    ops, t = {"prefill": [], "decode": []}, 0.0
    for program, kinds in (
            ("prefill", ("ds_kda_scan_chunks", "ds_mla_prefill_attn")),
            ("decode", ("ds_kda_step_rows", "ds_kda_gate",
                        "ds_mla_decode_attn", "ds_attn_gate",
                        "ds_mla_project"))):
        for kind in kinds:
            name = next(k for k, v in scopes[program].items() if kind in v)
            ops[program].append((name + " fusion", t, t + 1e-3))
            t += 1e-3
    both = harness.Result(
        correct=True, attempted=1, failed=0, setup_s=1.0, end_to_end={},
        facts=res.facts, detail={}, trace=xplane.Trace(
            devices={0: ops["prefill"] + ops["decode"]},
            spans=[("prefill", -1e-3, 2e-3), ("decode", 2e-3, 8e-3)]))
    for name in device + ["mla_prefill_attn_ms.serve", "mla_project_ms.serve",
                          "attn_gate_ms.serve"]:
        value = metric(ctx, both, name)
        assert isinstance(value, float) and value > 0, name
    assert metric(ctx, both, "kda_gate_ms.serve") == pytest.approx(1.0)
    assert metric(ctx, both, "kda_decode_ms.serve") == pytest.approx(1.0)


def test_work_functions_against_hand_arithmetic():
    cfg = config_file()
    ctx = tiny_ling.context(jax.devices()[:1], 1.0, False, config=cfg)
    facts = {"kda_rows_live_profiled": 10.0, "prefill_chunks_profiled": 2.0,
             "prefill_chunk": 1024, "kv_tokens_per_step_profiled": 50000.0,
             "kv_rows_written_profiled": 10.0, "attention_block_k": 128,
             "kv_bytes_per_element": 2, "moe_experts_touched_profiled": 300.0,
             "moe_pairs_held_profiled": 120.0}
    res = harness.Result(True, 1, 0, 1.0, {}, facts, {})
    elems = 10 * 32 * 128 * 128 * 7
    assert flops_ling.kda_decode_step(ctx, res) == (
        7 * elems, 8 * elems + 10 * 7 * 4 * 4096)
    ops, moved = flops_ling.kda_prefill_call(ctx, res)
    assert ops == 2 * 7 * 1024 * 32 * (64 * (4 * 128 + 2 * 128)
                                       + 6 * 128 * 128)
    assert moved == 2 * 7 * (1024 * (6 * 4096 + 4 * 4096 + 4 * 32
                                     + 4 * 4096) + 8 * 32 * 128 * 128)
    ops, moved = flops_ling.mla_decode_step(ctx, res)
    assert ops == 2 * 50000 * 32 * (576 + 512)      # one latent layer
    assert moved == (50000 + 10 * 128) * 576 * 2
    ops, moved = flops_ling.mla_prefill_attention_prompt(ctx, res)
    assert ops == 3 * 2 * 1024 * 512 * 32 * 256 + \
        (1 + 1) * 1024 * 1024 * 32 * 2 * 320
    # the experts' grouped matmuls by Qwen3-Next's function, unchanged
    ops, moved = flops_qwen3_next.expert_matmuls_decode_step(ctx, res)
    assert ops == 2 * 120 * 5_898_240
    assert moved == (300 * 5_898_240 + 120 * (2 * 2560 + 3 * 768)) * 2
    empty = harness.Result(True, 1, 0, 1.0, {}, {}, {})
    for fn in (flops_ling.kda_decode_step, flops_ling.kda_prefill_call,
               flops_ling.mla_decode_step,
               flops_ling.mla_prefill_attention_prompt):
        assert fn(ctx, empty) is None


def test_parent_without_the_model_exits_2(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_model(name, *a, **k):
        if name.endswith("models.ling_hybrid"):
            raise ImportError(name)
        return real(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_model)
    ctx = tiny_ling.context(jax.devices()[:1], seconds=1.0, trace=False)
    with pytest.raises(SystemExit) as e:
        serve_ling.run(ctx)
    assert e.value.code == 2

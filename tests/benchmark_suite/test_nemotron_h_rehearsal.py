"""The Nemotron-H serving cell's driver end to end at toy size on the
CPU, its manifest entries, its configuration file, its work functions
against hand arithmetic and its metric files on a hand-made trace. No
number from here is a device metric."""

import importlib

import jax
import pytest

from benchmarks.suite import flops_nemotron_h, flops_ssm, harness, xplane
from benchmarks.suite.drivers import serve_nemotron_h
from benchmarks.suite.readers import setup_split

from . import test_manifest, tiny, tiny_nemotron_h

CELL = tiny_nemotron_h.CELL
NEW = {"latent_expert_matmul_roofline.serve", "moe_latent_ms.serve",
       "moe_permute_ms.serve", "moe_prefill_ms.serve",
       "moe_experts_touched_pct.serve", "moe_pairs_max_over_mean.serve",
       "ssd_prefill_grouped_roofline.serve"}
# accepted metrics whose reader and work function give this
# configuration's own number, so the cell is appended to their lists
TAKEN = {"decode_step_ms.serve", "prefill_ms.serve", "queue_wait_ms.serve",
         "batch_occupancy_pct.serve", "device_idle_pct.serve",
         "kv_copy_ms.serve", "pool_fill_pct.serve",
         "sched_queue_wait_ms.serve", "sched_occupancy_pct.serve",
         "first_token_ready_ms.serve", "first_token_hold_ms.serve",
         "engine_prefill_ms.serve", "engine_decode_ms.serve",
         "sched_host_ms.serve", "kv_live_pages_pct.serve",
         "idle_logits_d2h_ms.serve", "idle_sched_ms.serve",
         "decode_grid_live_pct.serve", "ssm_decode_ms.serve",
         "ssm_decode_roofline.serve", "ssd_prefill_ms.serve",
         "ssm_rows_live_pct.serve", "state_live_pct.serve",
         "flash_decode_paged_ms.serve", "flash_decode_paged_roofline.serve",
         "kv_write_rows_live_pct.serve", "moe_ms.serve",
         "moe_expert_matmul_ms.serve", "moe_shared_ms.serve",
         "moe_pairs_held_pct.serve", "setup_trace_s", "setup_lower_s",
         "setup_compile_s", "setup_gc_s", "setup_engine_s",
         "setup_warmup_s", "setup_rest_s", "window_compiles.serve",
         "gc_pause_ms.serve", "stall_max_ms.serve",
         "prefill_stall_p99_ms.serve"}
# nothing to read since PR 37; GPT-2's head count; three hidden-wide
# matrices an expert; one B/C group; a pattern that takes the grouped
# matmuls' own loops for KV writes; another model's attention
NOT_TAKEN = {"logits_d2h_ms.serve", "flash_decode_ms.serve",
             "flash_decode_roofline.serve",
             "moe_expert_matmul_roofline.serve",
             "ssd_prefill_roofline.serve", "kv_write_ms.serve"}
KERNEL = "ds_flash_decode_paged.7 custom-call:tpu_custom_call"
GMM = "gmm.3 custom-call:tpu_custom_call"


def config_file():
    return test_manifest.load(test_manifest.ROOT, "benchmarks", "suite",
                              "configs", "nemotron-3-super-120b-a12b.json")


def metric(ctx, res, name):
    spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
    reader = importlib.import_module(
        "benchmarks.suite.readers." + spec["reader"])
    return reader.read(ctx, res, **spec["args"])


def test_cell_is_in_the_manifest_with_its_metrics():
    manifest = test_manifest.MANIFEST
    entry = manifest["workloads"][-1]       # appended
    assert (entry["name"], entry["config"], entry["traffic"],
            entry["chips"]) == (CELL, "nemotron-3-super-120b-a12b",
                                "reason", 1)
    assert manifest["configs"][-1]["name"] == entry["config"]
    assert tiny.workload(CELL)["driver"] == "serve_nemotron_h"
    assert set(test_manifest.listed("end_to_end", CELL)) == {
        "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    listed = set(test_manifest.listed("per_layer", CELL))
    assert listed == NEW | TAKEN
    assert not listed & NOT_TAKEN
    assert not {n for n in listed if n.startswith("mla_")}
    per_layer = manifest["per_layer"]
    # the new entries at the end, in this cell and in no other
    assert {m["name"] for m in per_layer[-len(NEW):]} == NEW
    assert all(m["workloads"] == [CELL] for m in per_layer[-len(NEW):])
    # an accepted entry has the cell's name appended and nothing else
    for m in per_layer[:-len(NEW)]:
        if m["name"] in TAKEN:
            assert m["workloads"][-1] == CELL
            assert CELL not in m["workloads"][:-1]
    for m in manifest["end_to_end"]:
        if "workloads" in m and m["name"] != \
                "train_tokens_per_s_per_chip":
            assert m["workloads"][-1] == CELL


def test_cell_is_what_the_issue_names():
    wl = tiny.workload(CELL)
    inf, t = wl["inference"], wl["traffic"]
    assert (inf["max_batch"], inf["seq_buckets"], inf["prefill_chunk"],
            inf["page_size"], inf["n_pages"], inf["attention_impl"]) == (
                96, [5120], 1024, 128, 3841, "flash")
    assert t["prompt"] == {"median": 1024, "sigma": 0.8, "min": 128,
                           "max": 4096}
    assert t["output"] == {"median": 512, "sigma": 0.5, "min": 128,
                           "max": 1024}
    assert t["max_total"] == 5119 < inf["seq_buckets"][0]
    assert (t["generator"], t["order_seed"], t["ramp_s"],
            t["drain_s"]) == ("open_loop", 1, 30, 5)
    assert "kv_cache_dtype" not in inf and "sampling" not in inf  # greedy
    assert wl["trace"]["scope_marker"] == "ds_"
    assert set(wl["correctness"]) == {
        "requests", "logit_rtol", "state_rtol", "mixer_rtol",
        "attention_rtol", "attention_decode_rtol", "expert_rtol", "why"}


def test_configuration_file_is_the_published_model_and_its_share():
    cfg = config_file()
    assert cfg["reduced"] == ["n_layer", "n_routed_experts", "vocab_size",
                              "num_nextn_predict_layers"]
    # the published keys as published: depth, pattern, router's width
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["num_experts_per_tok"], cfg["num_nextn_predict_layers"],
            len(cfg["hybrid_override_pattern"])) == (88, 512, 22, 1, 88)
    assert (cfg["hidden_size"], cfg["moe_latent_size"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"], cfg["n_groups"],
            cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["head_dim"],
            cfg["num_key_value_heads"]) == (
                4096, 1024, 2688, 5376, 8, 128, 64, 128, 128, 2)
    assert (cfg["n_layer"], cfg["vocab_size"], cfg["n_embd"], cfg["n_head"],
            cfg["n_positions"]) == (11, 32768, 4096, 32, 262144)
    assert cfg["assumed"]["experts_held"] == [0, 128]
    # the aliases `flops_ssm.py` reads say what the published keys say
    assert cfg["layer_types"] == [
        tiny_nemotron_h.KINDS[k]
        for k in flops_nemotron_h.pattern(cfg)]
    assert flops_nemotron_h.pattern(cfg) == "MEMEMEM*EME"
    for alias, key in (("mamba_n_heads", "mamba_num_heads"),
                       ("mamba_d_head", "mamba_head_dim"),
                       ("mamba_d_state", "ssm_state_size"),
                       ("mamba_n_groups", "n_groups"),
                       ("mamba_d_conv", "conv_kernel"),
                       ("mamba_chunk_size", "chunk_size")):
        assert cfg[alias] == cfg[key]
    model = serve_nemotron_h.model_config(cfg)
    from deepspeed_tpu.models.nemotron_h import nemotron_3_super_share
    assert model == nemotron_3_super_share()
    # hand arithmetic, ISSUE 41's table
    f = flops_nemotron_h
    assert f.mixer_params(cfg) == 4096 * 18560 + 8192 * 4096 + \
        5 * 10240 + 3 * 128 + 8192 + 4096
    assert abs(f.mixer_params(cfg) - 109.64e6) < 0.005e6
    assert abs(f.attention_params(cfg) - 35.66e6) < 0.005e6
    assert abs(f.expert_layer_params(cfg) - 54.53e6) < 0.005e6
    assert f.expert_params(cfg) == 2 * 1024 * 2688
    assert abs(f.param_count(cfg) - 4648.2e6) < 0.1e6
    whole = dict(cfg, vocab_size=131072)
    assert abs(f.param_count(
        whole, held=512, layers=cfg["hybrid_override_pattern"])
        - 120.67e9) < 0.005e9
    assert f.state_bytes_per_row(cfg) == 5 * 128 * 64 * 128 * 4
    assert flops_ssm.state_bytes_per_row(cfg) == f.state_bytes_per_row(cfg)
    spec = model.cache_spec(96, 5120, page_size=128, n_pages=3841)
    assert spec.state_bytes_per_slot == \
        f.state_bytes_per_row(cfg) + 5 * 3 * 10240 * 2
    pool = spec.n_pages * 128 * 2 * 128 * 2 * 2
    total = 2 * f.param_count(cfg) + 96 * spec.state_bytes_per_slot + pool
    assert 11.7e9 < total < 11.9e9          # of the chip's 16


@pytest.fixture(scope="module")
def traced():
    ctx = tiny_nemotron_h.context(jax.devices()[:1], seconds=2.0,
                                  trace=True)
    lines = []
    ctx.log = lines.append
    return ctx, serve_nemotron_h.run(ctx), lines


def test_serve_nemotron_h_driver_untraced():
    ctx = tiny_nemotron_h.context(jax.devices()[:1], seconds=2.0,
                                  trace=False)
    res = serve_nemotron_h.run(ctx)
    assert res.correct, res.detail["checks"]
    assert res.facts["program_scopes"] is None
    assert res.facts["moe_experts_touched_profiled"] is None
    assert res.end_to_end["ttft_p90_ms"] > 0


def test_serve_nemotron_h_driver(traced):
    ctx, res, _ = traced
    checks = res.detail["checks"]
    assert res.correct, checks
    assert res.failed == 0 and res.attempted > 5
    assert checks["compile_counts"] == {"prefill": 1, "decode": 1}
    assert checks["compiles_in_run"] == 0
    assert len(checks["reference"]) == 2
    own = checks["own_input"]
    assert set(own) == {"state", "mixer", "attention", "experts"}
    assert own["state"]["layer"] == "layers_0"
    assert own["experts"]["bias_moves_choice"] > 0.05
    assert 0 < own["experts"]["pairs_held"] < own["experts"]["pairs_routed"]
    assert res.trace is None            # a CPU trace has no device plane
    facts = res.facts
    assert facts["kv_bytes_per_element"] == 2       # a bfloat16 pool
    # 4 expert layers x 4 held experts at toy size
    assert 0 < facts["moe_experts_touched_profiled"] <= 16
    assert facts["moe_pairs_held_profiled"] <= \
        facts["moe_pairs_routed_profiled"]
    assert 0 < facts["ssm_rows_live_profiled"] <= 4
    assert facts["ssm_rows_touched_profiled"] == 4
    assert facts["prefill_chunks_profiled"] >= 1
    scopes = facts["program_scopes"]
    assert set(scopes) == {"prefill", "decode"}
    for program in scopes:
        where = " ".join(scopes[program].values())
        for scope in ("ds_ssm_in_proj", "ds_ssm_conv", "ds_ssm_scan",
                      "ds_ssm_gate_norm", "ds_ssm_out_proj",
                      "ds_moe_route", "ds_moe_dispatch", "ds_moe_experts",
                      "ds_moe_combine", "ds_moe_latent_down",
                      "ds_moe_latent_up", "ds_moe_shared",
                      "ds_ssd_prefill" if program == "prefill"
                      else "ds_ssm_decode"):
            assert scope in where, (program, scope)


def test_counter_metrics_and_set_up_are_numbers_at_toy_size(traced):
    """What `test_record_readers.py` asks of every cell whose driver it
    knows, and the three counter ratios: half the experts are held at
    toy size (4 of 8)."""
    ctx, res, lines = traced
    assert 20 < metric(ctx, res, "moe_pairs_held_pct.serve") < 80
    assert 0 < metric(ctx, res, "moe_experts_touched_pct.serve") <= 100
    assert 0 < metric(ctx, res, "ssm_rows_live_pct.serve") <= 100
    assert 0 < metric(ctx, res, "state_live_pct.serve") <= 100
    # the file's scale is the cell's 128 held x 5 layers: here 4 x 4
    spec = test_manifest.load(tiny.SUITE, "metrics",
                              "moe_pairs_max_over_mean.serve.json")
    assert spec["args"]["scale"] == 128 * 5
    from benchmarks.suite.readers import span_counter_ratio
    ratio = span_counter_ratio.read(ctx, res, **dict(spec["args"],
                                                     scale=16))
    assert ratio >= 1.0             # the fullest expert against the mean
    split = [metric(ctx, res, f"setup_{p}_s") for p in setup_split.PARTS]
    assert all(isinstance(v, float) and v >= 0 for v in split)
    assert sum(split) == pytest.approx(
        res.setup_s - ctx.workload["traffic"]["ramp_s"], abs=1e-6)
    assert metric(ctx, res, "window_compiles.serve") == 0
    for name in ("gc_pause_ms.serve", "stall_max_ms.serve",
                 "prefill_stall_p99_ms.serve"):
        assert metric(ctx, res, name) >= 0
    # a CPU run has no device plane: nothing reported, nothing raised
    for name in NEW - {"moe_experts_touched_pct.serve",
                       "moe_pairs_max_over_mean.serve"}:
        assert metric(ctx, res, name) is None, name
    assert sum("set-up by the program's records" in ln for ln in lines) == 1


def test_parent_without_the_model_exits_2(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_model(name, *a, **k):
        if name.endswith("nemotron_h") and "reference" not in name:
            raise ImportError(name)
        return real(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_model)
    ctx = tiny_nemotron_h.context(jax.devices()[:1], seconds=1.0,
                                  trace=False)
    with pytest.raises(SystemExit) as e:
        serve_nemotron_h.run(ctx)
    assert e.value.code == 2


def hand_made():
    """A prefill span of three ops, two decode spans: the kernel, the
    grouped matmuls and four fusions under scopes."""
    trace = xplane.Trace(
        devices={0: [(GMM, 0.0, 4e-3),
                     ("fusion.1 fusion", 4e-3, 6e-3),
                     ("fusion.2 fusion", 6e-3, 7e-3),
                     (KERNEL, 10e-3, 10.5e-3), (GMM, 10.5e-3, 12.5e-3),
                     ("fusion.1 fusion", 12.5e-3, 13e-3),
                     ("fusion.2 fusion", 13e-3, 13.2e-3),
                     ("fusion.3 fusion", 13.2e-3, 13.8e-3),
                     ("fusion.4 fusion", 13.8e-3, 14.8e-3),
                     (KERNEL, 20e-3, 20.5e-3), (GMM, 20.5e-3, 21.5e-3),
                     ("fusion.4 fusion", 21.5e-3, 22.5e-3)]},
        spans=[("prefill", -1e-3, 8e-3), ("decode", 9e-3, 15e-3),
               ("decode", 19e-3, 23e-3)])
    facts = {"program_scopes": {
        "prefill": {"gmm.3": "jit(p)/ds_moe_experts/gmm",
                    "fusion.1": "jit(p)/ds_ssm_scan/ds_ssd_prefill/dot",
                    "fusion.2": "jit(p)/ds_moe_latent_up/dot"},
        "decode": {"gmm.3": "jit(d)/ds_moe_experts/gmm",
                   "fusion.1": "jit(d)/ds_moe_route/sort",
                   "fusion.2": "jit(d)/ds_moe_latent_down/dot",
                   "fusion.3": "jit(d)/ds_moe_shared/dot",
                   "fusion.4": "jit(d)/ds_ssm_scan/ds_ssm_decode/mul"}},
        "moe_experts_touched_profiled": 600.0,
        "moe_pairs_held_profiled": 2600.0,
        "ssm_rows_live_profiled": 50.0, "prefill_chunks_profiled": 2.0,
        "prefill_chunk": 1024, "kv_tokens_per_step_profiled": 80000.0,
        "kv_bytes_per_element": 2}
    return harness.Result(correct=True, attempted=1, failed=0, setup_s=1.0,
                          end_to_end={}, facts=facts, detail={},
                          trace=trace)


def test_metric_files_and_work_functions_against_hand_arithmetic():
    cfg = config_file()
    ctx = tiny_nemotron_h.context(jax.devices()[:1], 1.0, True, config=cfg)
    res = hand_made()
    ms = pytest.approx
    # the decode program's grouped matmuls: 3 ms over 2 decode spans
    assert metric(ctx, res, "moe_expert_matmul_ms.serve") == ms(1.5)
    assert metric(ctx, res, "flash_decode_paged_ms.serve") == ms(0.5)
    # latent pair: fusion.2 in decode, 0.2 ms over two spans
    assert metric(ctx, res, "moe_latent_ms.serve") == ms(0.1)
    # route + dispatch + combine: fusion.1 in decode, 0.5 ms over two
    assert metric(ctx, res, "moe_permute_ms.serve") == ms(0.25)
    assert metric(ctx, res, "moe_shared_ms.serve") == ms(0.3)
    # the four phases: gmm 3 ms + fusion.1 0.5 ms over two spans
    assert metric(ctx, res, "moe_ms.serve") == ms(1.75)
    # the prefill program: gmm 4 ms and the latent's 1 ms, one span
    assert metric(ctx, res, "moe_prefill_ms.serve") == ms(5.0)
    assert metric(ctx, res, "ssd_prefill_ms.serve") == ms(2.0)
    assert metric(ctx, res, "ssm_decode_ms.serve") == ms(1.0)

    # 600 experts touched x 2 x 1024 x 2688 x 2 B = 6.6 GB, and 2,600
    # pairs' rows (1024 in, 2688 out and in, 1024 out): 8.07 ms at
    # 819 GB/s, over the 1.5 ms the hand-made step's calls took
    ops, moved = flops_nemotron_h.latent_expert_matmuls_decode_step(ctx, res)
    assert moved == (600 * 2 * 1024 * 2688 + 2600 * 2 * (1024 + 2688)) * 2
    assert ops == 2 * 2600 * 2 * 1024 * 2688
    assert moved / 819e9 > ops / 197e12         # bound by bytes
    assert metric(ctx, res, "latent_expert_matmul_roofline.serve") == \
        ms(100 * (moved / 819e9) / 1.5e-3)
    # 50 live rows x 5 mixers x 4.19 MB read and written = 2.10 GB
    ops, moved = flops_ssm.ssm_decode_step(ctx, res)
    assert moved == 50 * 5 * 128 * 64 * 128 * 4 * 2
    assert metric(ctx, res, "ssm_decode_roofline.serve") == \
        ms(100 * (moved / 819e9) / 1e-3)
    # 80,000 positions x 2 key heads x 128 x (k, v) x 2 B, one layer;
    # every element meets its 16 queries
    ops, moved = flops_ssm.gqa_decode_step(ctx, res)
    assert moved == 80000 * 2 * 128 * 2 * 2 and ops == 2 * 16 * moved / 2
    assert metric(ctx, res, "flash_decode_paged_roofline.serve") == \
        ms(100 * (moved / 819e9) / 0.5e-3)
    # two calls of 1024 tokens, five mixers, 8 groups: a token's x in
    # bf16 and y out in float32 (8192 each), B and C of 8 x 128 in
    # bf16, dt of 128 heads in float32, the state in and out
    ops, moved = flops_nemotron_h.ssd_prefill_call(ctx, res)
    per_call = 1024 * (2 * 8192 + 4 * 8192 + 2 * 2 * 8 * 128 + 4 * 128) \
        + 2 * 4 * 128 * 64 * 128
    assert moved == 2 * 5 * per_call
    assert ops == 2 * 5 * (1024 * 128 * (8 * 128 + 8192)
                           + 4 * 1024 * 8192 * 128)
    assert ops / 197e12 < moved / 819e9         # bound by bytes
    one_group = flops_ssm.ssd_prefill_call(ctx, res)
    assert one_group[1] < moved and one_group[0] < ops  # why the twin
    assert metric(ctx, res, "ssd_prefill_grouped_roofline.serve") == \
        ms(100 * (moved / 819e9) / 2e-3)
    # nothing to read: nothing reported, nothing raised
    for key in ("moe_experts_touched_profiled", "prefill_chunks_profiled"):
        res.facts[key] = None
    assert flops_nemotron_h.latent_expert_matmuls_decode_step(
        ctx, res) is None
    assert flops_nemotron_h.ssd_prefill_call(ctx, res) is None
    assert metric(ctx, res, "latent_expert_matmul_roofline.serve") is None
    assert metric(ctx, res, "ssd_prefill_grouped_roofline.serve") is None
    res.facts["program_scopes"] = None
    for name in ("moe_latent_ms.serve", "moe_permute_ms.serve",
                 "moe_prefill_ms.serve"):
        assert metric(ctx, res, name) is None

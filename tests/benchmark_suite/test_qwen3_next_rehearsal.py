"""The Qwen3-Next serving cell's driver end to end at toy size on the
CPU, its manifest entries, its configuration file, its work functions
against hand arithmetic and its metric files on a hand-made trace. No
number from here is a device metric. Membership is asserted, never
position or count, so that the next cell breaks nothing here."""

import importlib

import jax
import pytest

from benchmarks.suite import flops_qwen3_next, harness, xplane
from benchmarks.suite.drivers import serve_qwen3_next
from benchmarks.suite.readers import setup_split

from . import test_manifest, tiny, tiny_qwen3_next

CELL = tiny_qwen3_next.CELL
CONFIG = "qwen3-next-80b-a3b-instruct"
NEW = {"gdn_decode_ms.serve", "gdn_decode_roofline.serve",
       "gdn_prefill_ms.serve", "gdn_prefill_roofline.serve",
       "gdn_rows_live_pct.serve", "gqa256_decode_roofline.serve",
       "swiglu_expert_matmul_roofline.serve",
       "moe_pairs_max_over_mean_q3n.serve"}
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
         "decode_grid_live_pct.serve", "state_live_pct.serve",
         "flash_decode_paged_ms.serve", "kv_write_rows_live_pct.serve",
         "moe_ms.serve", "moe_expert_matmul_ms.serve", "moe_shared_ms.serve",
         "moe_pairs_held_pct.serve", "moe_permute_ms.serve",
         "moe_prefill_ms.serve", "moe_experts_touched_pct.serve",
         "setup_trace_s", "setup_lower_s", "setup_compile_s", "setup_gc_s",
         "setup_engine_s", "setup_warmup_s", "setup_rest_s",
         "window_compiles.serve", "gc_pause_ms.serve", "stall_max_ms.serve",
         "prefill_stall_p99_ms.serve"}
# Mamba-2's work functions and scopes; nothing to read since PR 37; a
# pattern that takes the grouped matmuls' own loops for KV writes
# (section 7 (ac)); work functions without the written-back block, of
# GPT-2's heads, of Kimi's or Nemotron's experts; another file's scale
NOT_TAKEN = {"ssm_decode_ms.serve", "ssm_decode_roofline.serve",
             "ssd_prefill_ms.serve", "ssd_prefill_roofline.serve",
             "ssd_prefill_grouped_roofline.serve",
             "ssm_rows_live_pct.serve", "logits_d2h_ms.serve",
             "kv_write_ms.serve", "flash_decode_ms.serve",
             "flash_decode_roofline.serve",
             "flash_decode_paged_roofline.serve",
             "moe_expert_matmul_roofline.serve",
             "latent_expert_matmul_roofline.serve", "moe_latent_ms.serve",
             "moe_pairs_max_over_mean.serve"}
KERNEL = "ds_flash_decode_paged.7 custom-call:tpu_custom_call"
GMM = "gmm.3 custom-call:tpu_custom_call"


def config_file():
    return test_manifest.load(test_manifest.ROOT, "benchmarks", "suite",
                              "configs", CONFIG + ".json")


def metric(ctx, res, name):
    spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
    reader = importlib.import_module(
        "benchmarks.suite.readers." + spec["reader"])
    return reader.read(ctx, res, **spec["args"])


def test_cell_is_in_the_manifest_with_its_metrics():
    manifest = test_manifest.MANIFEST
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "longchat", 1)
    conf = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["n_layer", "n_routed_experts", "vocab_size"]
    assert conf["source"] == config_file()["source"]
    assert tiny.workload(CELL)["driver"] == "serve_qwen3_next"
    assert set(test_manifest.listed("end_to_end", CELL)) == {
        "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    listed = set(test_manifest.listed("per_layer", CELL))
    assert listed == NEW | TAKEN
    assert not listed & NOT_TAKEN
    assert not {n for n in listed if n.startswith(("mla_", "ssm_", "ssd_"))}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    # the new entries list this cell; every entry that has it lists it once
    for name in NEW:
        assert CELL in by_name[name]["workloads"]
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        assert m.get("workloads", []).count(CELL) <= 1
    assert by_name["gdn_prefill_ms.serve"]["moves"] == \
        by_name["gdn_prefill_roofline.serve"]["moves"] == "ttft_p90_ms"
    for name in NEW - {"gdn_prefill_ms.serve", "gdn_prefill_roofline.serve"}:
        assert by_name[name]["moves"] == "itl_p95_ms"
    for name in NEW:
        if name.endswith("_roofline.serve"):
            assert (by_name[name]["unit"], by_name[name]["source"]) == (
                "%", "device_trace")


def test_cell_is_what_the_issue_names():
    wl = tiny.workload(CELL)
    inf, t = wl["inference"], wl["traffic"]
    assert (inf["max_batch"], inf["seq_buckets"], inf["prefill_chunk"],
            inf["page_size"], inf["attention_impl"]) == (
                128, [9216], 1024, 128, "flash")
    assert t["prompt"]["median"] == 2048 and t["output"]["median"] == 256
    assert (t["prompt"]["min"], t["output"]["min"], t["output"]["max"]) == (
        256, 32, 768)
    assert t["prompt"]["max"] == 8192       # ISSUE 43's range, whole
    assert t["max_total"] == t["prompt"]["max"] + t["output"]["max"] < \
        inf["seq_buckets"][0]
    assert (t["generator"], t["order_seed"]) == ("open_loop", 1)
    assert "kv_cache_dtype" not in inf and "sampling" not in inf  # greedy
    assert "prefix_cache" not in inf        # off: a recurrent state
    assert wl["trace"]["scope_marker"] == "ds_"
    assert set(wl["correctness"]) == {
        "requests", "logit_rtol", "state_rtol", "state_bfloat16_share_max",
        "deep_rtol", "window_rtol", "mixer_rtol", "attention_rtol",
        "attention_decode_rtol", "expert_rtol", "why"}
    # the pool: what the file says it is
    cfg = config_file()
    pool = (inf["n_pages"] - 1) * inf["page_size"] * \
        flops_qwen3_next.kv_bytes_per_token(cfg)
    assert 1.0e9 < pool < 3.0e9


def test_configuration_file_is_the_published_model_and_its_share():
    cfg = config_file()
    assert cfg["reduced"] == ["n_layer", "n_routed_experts", "vocab_size"]
    # the published keys as published: depth, router's width, vocabulary
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["full_attention_interval"],
            cfg["vocab_size_published"]) == (48, 512, 10, 4, 151936)
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["partial_rotary_factor"],
            cfg["rope_theta"], cfg["linear_num_key_heads"],
            cfg["linear_key_head_dim"], cfg["linear_num_value_heads"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["rms_norm_eps"],
            cfg["intermediate_size"]) == (
                2048, 256, 16, 2, 0.25, 10000000, 16, 128, 32, 128, 4, 512,
                512, 1e-6, 5120)
    assert (cfg["n_layer"], cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["n_embd"], cfg["n_head"], cfg["n_positions"]) == (
                8, 128, 37984, 2048, 16, 262144)
    assert cfg["vocab_size"] * 4 == cfg["vocab_size_published"]
    assert cfg["assumed"]["experts_held"] == [0, cfg["n_routed_experts"]]
    assert cfg["layer_types"] == flops_qwen3_next.layer_types(cfg) == \
        (["linear_attention"] * 3 + ["full_attention"]) * 2
    for key in ("deployment", "n_layer", "n_routed_experts", "vocab_size"):
        assert key in cfg["reduced_why"]
    for said in ("24 v5e chips", "7.33 GB", "12.88 MB", "4,096 B"):
        assert said in " ".join(cfg["reduced_why"].values()), said
    model = serve_qwen3_next.model_config(cfg)
    from deepspeed_tpu.models.qwen3_next import qwen3_next_80b_share
    assert model == qwen3_next_80b_share()
    # hand arithmetic, ISSUE 43's
    f = flops_qwen3_next
    assert f.delta_params(cfg) == 2048 * 12288 + 2048 * 64 + 4 * 8192 + \
        64 + 128 + 4096 * 2048
    assert abs(f.delta_params(cfg) - 33.72e6) < 0.005e6
    assert f.attention_params(cfg) == 2048 * 8192 + 2 * 2048 * 512 + \
        4096 * 2048 + 512
    assert abs(f.attention_params(cfg) - 27.26e6) < 0.005e6
    assert f.expert_params(cfg) == 3 * 2048 * 512
    assert f.expert_layer_params(cfg) == 2048 * 512 + 3 * 2048 * 512 + 2048
    assert abs(f.param_count(cfg) - 3667.3e6) < 0.1e6
    assert abs(2 * f.param_count(cfg) - 7.33e9) < 0.01e9
    assert abs(f.param_count(cfg, held=512, n_layer=48, vocab_size=151936)
               - 79.67e9) < 0.005e9              # "80B"
    # what a token touches: 10 of 512 experts a block: "A3B"
    active = f.param_count(cfg, held=10, n_layer=48, vocab_size=151936)
    assert 2.9e9 < active < 3.9e9
    assert f.state_bytes_per_row(cfg) == 6 * (32 * 128 * 128 * 4
                                              + 3 * 8192 * 2) == 12_877_824
    assert f.kv_bytes_per_token(cfg) == 4096
    inf = tiny.workload(CELL)["inference"]
    assert inf["n_pages"] == 4097
    spec = model.cache_spec(128, 9216, page_size=128, n_pages=4097)
    assert spec.state_bytes_per_slot == f.state_bytes_per_row(cfg)
    pool = spec.n_pages * 128 * f.kv_bytes_per_token(cfg)
    total = 2 * f.param_count(cfg) + 128 * spec.state_bytes_per_slot + pool
    assert 11.0e9 < total < 11.3e9          # of the chip's 16


@pytest.fixture(scope="module")
def traced():
    ctx = tiny_qwen3_next.context(jax.devices()[:1], seconds=2.0,
                                  trace=True)
    lines = []
    ctx.log = lines.append
    return ctx, serve_qwen3_next.run(ctx), lines


def test_serve_qwen3_next_driver_untraced():
    ctx = tiny_qwen3_next.context(jax.devices()[:1], seconds=2.0,
                                  trace=False)
    res = serve_qwen3_next.run(ctx)
    assert res.correct, res.detail["checks"]
    assert res.facts["program_scopes"] is None
    assert res.facts["moe_experts_touched_profiled"] is None
    assert res.end_to_end["ttft_p90_ms"] > 0


def test_serve_qwen3_next_driver(traced):
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
    # every later layer's leaves, the pooled keys and values and the
    # logit row, as the engine's own two programs left them: float32 here
    for reading in ("deep_state", "deep_rows", "deep_logits"):
        assert 0 <= own["state"][reading] < 1e-4, own["state"]
    assert own["state"]["state_bfloat16_share"] < 0.05
    assert own["attention"]["layer"] == "layers_3"
    assert own["experts"]["weights_sum_off"] < 1e-5
    assert own["experts"]["pairs_routed"] == 3 * (
        own["experts"]["tokens"] + own["experts"]["rows"])
    assert 0 < own["experts"]["pairs_held"] < own["experts"]["pairs_routed"]
    assert res.trace is None            # a CPU trace has no device plane
    facts = res.facts
    # 8 expert layers x 4 held experts at toy size
    assert 0 < facts["moe_experts_touched_profiled"] <= 32
    assert facts["moe_pairs_held_profiled"] <= \
        facts["moe_pairs_routed_profiled"]
    assert 0 < facts["gdn_rows_live_profiled"] <= 4
    assert facts["gdn_rows_touched_profiled"] == 4
    assert facts["kv_rows_written_profiled"] == \
        facts["gdn_rows_live_profiled"]
    assert facts["prefill_chunks_profiled"] >= 1
    assert facts["attention_block_k"] == 8
    scopes = facts["program_scopes"]
    assert set(scopes) == {"prefill", "decode"}
    for program in scopes:
        where = " ".join(scopes[program].values())
        for scope in ("ds_gdn_conv", "ds_attn_gate", "ds_moe_route",
                      "ds_moe_dispatch", "ds_moe_experts", "ds_moe_combine",
                      "ds_moe_shared",
                      "ds_gdn_scan" if program == "prefill"
                      else "ds_gdn_step"):
            assert scope in where, (program, scope)


@pytest.mark.parametrize("fault", ["none", "a bfloat16 state",
                                   "a later layer's keys off"])
def test_state_check_holds_every_layer_and_the_state_s_precision(
        fault, monkeypatch):
    """`check_state` on a fresh toy engine: sound it passes; a state
    rounded to bfloat16 after every call fails by the share of its
    entries that are bfloat16 numbers; the second attention layer's
    pooled keys a fifth off fail the later layers' limit."""
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.ops import gated_delta

    if fault == "a bfloat16 state":
        for name in ("gated_delta_chunked", "gated_delta_step"):
            sound = getattr(gated_delta, name)
            monkeypatch.setattr(
                gated_delta, name,
                lambda *a, _f=sound, **kw: (lambda o, s: (
                    o, jax.lax.reduce_precision(s, 8, 7)))(*_f(*a, **kw)))
    ctx = tiny_qwen3_next.context(jax.devices()[:1], seconds=1.0,
                                  trace=False)
    engine, _ = serve_qwen3_next.build(ctx)
    prompt = np.random.default_rng(3).integers(0, 256, 41).tolist()
    engine.prefill(0, prompt[::-1], np.arange(1, engine.pages_per_row + 1))
    stages = None
    if fault == "a later layer's keys off":
        stages = serve_qwen3_next.slot_readings(engine, prompt, [7, 8])
        k, v = stages[0][2]["layers_7"]
        stages[0][2]["layers_7"] = (1.2 * k, v)
    got = serve_qwen3_next.check_state(ctx, engine, prompt, [7, 8],
                                       stages=stages)
    assert got["ok"] == (fault == "none"), got
    assert (got["state_bfloat16_share"] > 0.99) == (
        fault == "a bfloat16 state")
    if fault != "a bfloat16 state":     # whose later layers may flip an
        # expert at toy size (top 3 of 8)
        assert (got["deep_rows"] > 0.05) == (fault != "none")
    assert got["after_decode"] < 3e-2 and got["decode_steps"] > 40


def test_counter_metrics_and_set_up_are_numbers_at_toy_size(traced):
    """What `test_record_readers.py` asks of every cell whose driver it
    knows, and the counter ratios: half the experts are held at toy
    size (4 of 8)."""
    ctx, res, lines = traced
    assert 20 < metric(ctx, res, "moe_pairs_held_pct.serve") < 80
    assert 0 < metric(ctx, res, "moe_experts_touched_pct.serve") <= 100
    assert 0 < metric(ctx, res, "gdn_rows_live_pct.serve") <= 100
    assert 0 < metric(ctx, res, "state_live_pct.serve") <= 100
    assert 0 < metric(ctx, res, "kv_write_rows_live_pct.serve") <= 100
    assert 0 < metric(ctx, res, "decode_grid_live_pct.serve") <= 100
    assert 0 < metric(ctx, res, "kv_live_pages_pct.serve") <= 100
    # the file's scale is the cell's 128 held x 8 layers: here 4 x 8
    spec = test_manifest.load(tiny.SUITE, "metrics",
                              "moe_pairs_max_over_mean_q3n.serve.json")
    assert spec["args"]["scale"] == 128 * 8
    from benchmarks.suite.readers import span_counter_ratio
    ratio = span_counter_ratio.read(ctx, res, **dict(spec["args"],
                                                     scale=32))
    assert ratio >= 1.0             # the fullest expert against the mean
    split = [metric(ctx, res, f"setup_{p}_s") for p in setup_split.PARTS]
    assert all(isinstance(v, float) and v >= 0 for v in split)
    assert sum(split) == pytest.approx(
        res.setup_s - ctx.workload["traffic"]["ramp_s"], abs=1e-6)
    assert metric(ctx, res, "window_compiles.serve") == 0
    for name in ("gc_pause_ms.serve", "stall_max_ms.serve",
                 "prefill_stall_p99_ms.serve", "sched_host_ms.serve",
                 "engine_decode_ms.serve", "engine_prefill_ms.serve",
                 "sched_occupancy_pct.serve", "first_token_ready_ms.serve"):
        assert metric(ctx, res, name) >= 0, name
    # a CPU run has no device plane: nothing reported, nothing raised
    for name in NEW - {"gdn_rows_live_pct.serve",
                       "moe_pairs_max_over_mean_q3n.serve"}:
        assert metric(ctx, res, name) is None, name
    assert sum("set-up by the program's records" in ln for ln in lines) == 1


def test_parent_without_the_model_exits_2(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_model(name, *a, **k):
        if name.endswith("models.qwen3_next"):
            raise ImportError(name)
        return real(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_model)
    ctx = tiny_qwen3_next.context(jax.devices()[:1], seconds=1.0,
                                  trace=False)
    with pytest.raises(SystemExit) as e:
        serve_qwen3_next.run(ctx)
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
                    "fusion.1": "jit(p)/ds_gdn_scan/dot",
                    "fusion.2": "jit(p)/ds_moe_route/sort"},
        "decode": {"gmm.3": "jit(d)/ds_moe_experts/gmm",
                   "fusion.1": "jit(d)/ds_moe_route/sort",
                   "fusion.2": "jit(d)/ds_gdn_conv/mul",
                   "fusion.3": "jit(d)/ds_moe_shared/dot",
                   "fusion.4": "jit(d)/ds_gdn_step/mul"}},
        "moe_experts_touched_profiled": 700.0,
        "moe_pairs_held_profiled": 1600.0,
        "gdn_rows_live_profiled": 60.0, "prefill_chunks_profiled": 3.0,
        "prefill_chunk": 1024, "kv_tokens_per_step_profiled": 150000.0,
        "kv_rows_written_profiled": 60.0, "attention_block_k": 128,
        "kv_bytes_per_element": 2}
    return harness.Result(correct=True, attempted=1, failed=0, setup_s=1.0,
                          end_to_end={}, facts=facts, detail={},
                          trace=trace)


def test_metric_files_and_work_functions_against_hand_arithmetic():
    cfg = config_file()
    ctx = tiny_qwen3_next.context(jax.devices()[:1], 1.0, True, config=cfg)
    res = hand_made()
    ms = pytest.approx
    # the appended metrics, on this cell's scopes
    assert metric(ctx, res, "moe_expert_matmul_ms.serve") == ms(1.5)
    assert metric(ctx, res, "flash_decode_paged_ms.serve") == ms(0.5)
    assert metric(ctx, res, "moe_permute_ms.serve") == ms(0.25)
    assert metric(ctx, res, "moe_shared_ms.serve") == ms(0.3)
    assert metric(ctx, res, "moe_ms.serve") == ms(1.75)
    # the prefill program: gmm 4 ms and the route's 1 ms, one span
    assert metric(ctx, res, "moe_prefill_ms.serve") == ms(5.0)
    # the new ones: the step's update 2 ms over two decode spans, the
    # chunked rule 2 ms over one prefill span
    assert metric(ctx, res, "gdn_decode_ms.serve") == ms(1.0)
    assert metric(ctx, res, "gdn_prefill_ms.serve") == ms(2.0)

    # 60 live rows x 6 layers x 32 x 128 x 128 float32 read and written
    ops, moved = flops_qwen3_next.gdn_decode_step(ctx, res)
    assert moved == 60 * 6 * 32 * 128 * 128 * 4 * 2 and ops == 7 * moved / 8
    assert moved / 819e9 > ops / 197e12         # bound by bytes
    assert metric(ctx, res, "gdn_decode_roofline.serve") == \
        ms(100 * (moved / 819e9) / 1e-3)
    # three calls of 1024 tokens, six layers, 32 value heads, chunks of
    # 64: a token's 64 x (3 x 128 + 2 x 128) + 6 x 128 x 128 operations;
    # q, k (2 x 2048 bf16), v (4096 bf16), o (4096 float32), g and beta
    # (32 float32 each), the state in and out
    ops, moved = flops_qwen3_next.gdn_prefill_call(ctx, res)
    assert ops == 3 * 6 * 1024 * 32 * (64 * 640 + 6 * 128 * 128)
    per_call = 1024 * (2 * 2 * 2048 + 2 * 4096 + 4 * 4096 + 2 * 4 * 32) \
        + 2 * 4 * 32 * 128 * 128
    assert moved == 3 * 6 * per_call
    assert ops / 197e12 < moved / 819e9         # bound by bytes
    assert metric(ctx, res, "gdn_prefill_roofline.serve") == \
        ms(100 * (moved / 819e9) / 2e-3)
    # 150,000 positions read and 60 rows' blocks of 128 written back, x
    # 2 key heads x 256 x (k, v) x 2 B x 2 layers; an element meets 8
    # queries
    ops, moved = flops_qwen3_next.gqa_decode_step(ctx, res)
    assert moved == (150000 + 60 * 128) * 2 * 256 * 2 * 2 * 2
    assert ops == 2 * 8 * 150000 * 2 * 256 * 2 * 2
    assert metric(ctx, res, "gqa256_decode_roofline.serve") == \
        ms(100 * (moved / 819e9) / 0.5e-3)
    # 700 experts touched x 3 x 2048 x 512 x 2 B = 4.4 GB and 1,600
    # pairs' rows (2048 in, 2 x 512 out, 512 in, 2048 out)
    ops, moved = flops_qwen3_next.expert_matmuls_decode_step(ctx, res)
    assert moved == (700 * 3 * 2048 * 512 + 1600 * (2 * 2048 + 3 * 512)) * 2
    assert ops == 2 * 1600 * 3 * 2048 * 512
    assert moved / 819e9 > ops / 197e12         # bound by bytes
    assert metric(ctx, res, "swiglu_expert_matmul_roofline.serve") == \
        ms(100 * (moved / 819e9) / 1.5e-3)
    # nothing to read: nothing reported, nothing raised
    for key in ("moe_experts_touched_profiled", "prefill_chunks_profiled",
                "gdn_rows_live_profiled", "kv_rows_written_profiled"):
        res.facts[key] = None
    f = flops_qwen3_next
    for work in (f.gdn_decode_step, f.gdn_prefill_call, f.gqa_decode_step,
                 f.expert_matmuls_decode_step):
        assert work(ctx, res) is None
    for name in NEW - {"gdn_decode_ms.serve", "gdn_prefill_ms.serve",
                       "gdn_rows_live_pct.serve",
                       "moe_pairs_max_over_mean_q3n.serve"}:
        assert metric(ctx, res, name) is None, name
    res.facts["program_scopes"] = None
    for name in ("gdn_decode_ms.serve", "gdn_prefill_ms.serve"):
        assert metric(ctx, res, name) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_resolves(name):
    spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
    reader = importlib.import_module(
        "benchmarks.suite.readers." + spec["reader"])
    assert callable(reader.read)
    if "work" in spec["args"]:
        assert spec["args"]["module"] == "flops_qwen3_next"
        assert callable(getattr(flops_qwen3_next, spec["args"]["work"]))


@pytest.mark.parametrize("name", sorted(TAKEN))
def test_appended_metric_lists_the_cell_once(name):
    m = next(x for x in test_manifest.MANIFEST["per_layer"]
             if x["name"] == name)
    assert m["workloads"].count(CELL) == 1

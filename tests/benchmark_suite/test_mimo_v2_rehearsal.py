"""The MiMo-V2 serving cell's driver end to end at toy size on the CPU,
its manifest entries, its configuration file, its traffic generator, its
work functions against hand arithmetic and its metric files on a
hand-made trace. No number from here is a device metric. Membership is
asserted, never position or count, so that the next cell breaks nothing
here."""

import importlib

import jax
import numpy as np
import pytest

from benchmarks.suite import flops_mimo_v2, harness, xplane
from benchmarks.suite.drivers import serve_mimo_v2
from benchmarks.suite.readers import setup_split
from benchmarks.suite.traffic import open_loop_mixed

from . import test_manifest, tiny, tiny_mimo_v2

CELL = tiny_mimo_v2.CELL
CONFIG = "mimo-v2.5"
NEW = {"attn_prefill_full_ms.serve", "attn_prefill_window_ms.serve",
       "attn_prefill_full_roofline.serve", "attn_decode_full_roofline.serve",
       "attn_decode_window_roofline.serve",
       "window_blocks_in_window_pct.serve", "kv_window_bytes_pct.serve"}
# accepted metrics whose reader (and work function) give this
# configuration's own number, so the cell is appended to their lists
TAKEN = {"decode_step_ms.serve", "prefill_ms.serve", "queue_wait_ms.serve",
         "batch_occupancy_pct.serve", "device_idle_pct.serve",
         "pool_fill_pct.serve", "sched_queue_wait_ms.serve",
         "sched_occupancy_pct.serve", "first_token_ready_ms.serve",
         "first_token_hold_ms.serve", "engine_prefill_ms.serve",
         "engine_decode_ms.serve", "sched_host_ms.serve",
         "kv_live_pages_pct.serve", "decode_grid_live_pct.serve",
         "flash_decode_paged_ms.serve", "kv_write_rows_live_pct.serve",
         "moe_ms.serve", "moe_expert_matmul_ms.serve",
         "moe_pairs_held_pct.serve", "moe_permute_ms.serve",
         "moe_prefill_ms.serve", "moe_experts_touched_pct.serve",
         "swiglu_expert_matmul_roofline.serve", "window_compiles.serve",
         "gc_pause_ms.serve", "stall_max_ms.serve",
         "prefill_stall_p99_ms.serve"}
# a recurrent state's, a shared expert's, another model's heads or scale
NOT_TAKEN = {"state_live_pct.serve", "moe_shared_ms.serve",
             "gdn_decode_ms.serve", "gdn_prefill_ms.serve",
             "gdn_rows_live_pct.serve", "gqa256_decode_roofline.serve",
             "moe_pairs_max_over_mean_q3n.serve", "ssm_decode_ms.serve",
             "mla_decode_roofline.serve", "flash_decode_roofline.serve",
             "flash_decode_paged_roofline.serve"}


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
        CONFIG, "shortlong", 1)
    conf = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["n_layer", "n_routed_experts", "vocab_size"]
    assert conf["source"] == config_file()["source"] == \
        "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
    assert tiny.workload(CELL)["driver"] == "serve_mimo_v2"
    assert set(test_manifest.listed("end_to_end", CELL)) == {
        "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    listed = set(test_manifest.listed("per_layer", CELL))
    assert NEW <= listed and TAKEN <= listed
    assert not listed & NOT_TAKEN
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        assert m.get("workloads", []).count(CELL) <= 1
    for name in NEW:
        want = "ttft_p90_ms" if "prefill" in name else "itl_p95_ms"
        assert by_name[name]["moves"] == want, name
        if name.endswith("_roofline.serve"):
            assert (by_name[name]["unit"], by_name[name]["source"]) == (
                "%", "device_trace")
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_cell_is_what_the_issue_names():
    wl = tiny.workload(CELL)
    inf, t = wl["inference"], wl["traffic"]
    assert (inf["max_batch"], inf["seq_buckets"], inf["prefill_chunk"],
            inf["page_size"], inf["attention_impl"], inf["n_pages"]) == (
                64, [33792], 1024, 128, "flash", 6145)
    short, long = t["classes"]
    assert (short["share"], long["share"]) == (0.75, 0.25)
    assert short["prompt"] == {"median": 768, "sigma": 0.8, "min": 128,
                               "max": 4096}
    assert long["prompt"] == {"median": 16384, "sigma": 0.5, "min": 8192,
                              "max": 32768}
    assert t["output"] == {"median": 256, "sigma": 0.6, "min": 32,
                           "max": 768}
    assert t["max_total"] == 33536 < inf["seq_buckets"][0]
    assert (t["generator"], t["order_seed"], t["ramp_s"], t["drain_s"]) == (
        "open_loop_mixed", 1, 30, 5)
    assert "kv_cache_dtype" not in inf and "sampling" not in inf  # greedy
    assert "prefix_cache" not in inf        # off: it refuses the ring
    assert wl["trace"]["scope_marker"] == "ds_"
    cfg = config_file()
    pool = (inf["n_pages"] - 1) * inf["page_size"] * \
        flops_mimo_v2.kv_bytes_per_token(cfg, "full")
    assert 4.0e9 < pool < 4.1e9
    rings = inf["max_batch"] * flops_mimo_v2.ring_bytes_per_row(
        cfg, inf["page_size"])
    assert 0.41e9 < rings < 0.43e9


def test_configuration_file_is_the_published_model_and_its_share():
    cfg = config_file()
    assert cfg["reduced"] == ["n_layer", "n_routed_experts", "vocab_size"]
    published = {
        "hidden_size": 4096, "num_hidden_layers": 48,
        "num_attention_heads": 64, "num_key_value_heads": 4,
        "swa_num_key_value_heads": 8, "head_dim": 192, "v_head_dim": 128,
        "swa_head_dim": 192, "swa_v_head_dim": 128,
        "intermediate_size": 16384, "moe_intermediate_size": 2048,
        "n_routed_experts": 256, "num_experts_per_tok": 8,
        "sliding_window": 128, "rope_theta": 10000000,
        "swa_rope_theta": 10000, "partial_rotary_factor": 0.334,
        "attention_value_scale": 0.707, "layernorm_epsilon": 1e-05,
        "max_position_embeddings": 1048576, "n_shared_experts": None,
        "routed_scaling_factor": None, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1,
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert sum(cfg["hybrid_layer_pattern"]) == 39 and \
        cfg["moe_layer_freq"] == [0] + [1] * 47
    assert (cfg["vocab_size"], cfg["vocab_size_published"], cfg["n_layer"],
            cfg["assumed"]["experts_held"]) == (19072, 152576, 7, [0, 16])
    for key in ("attention_chunk_size", "attention_projection_layout",
                "sink_bias_why", "centred_why", "partial_rotary"):
        assert key in cfg["assumed"], key
    # the program's config from the file
    mc = serve_mimo_v2.model_config(cfg)
    assert mc.layer_kinds == tuple(cfg["layer_kinds"])
    assert (mc.vocab_size, mc.num_hidden_layers, mc.experts_held) == (
        19072, 7, (0, 16))
    # ISSUE 47's arithmetic, reckoned again
    f = flops_mimo_v2
    assert f.attention_params(cfg, "full") == 89_128_960
    assert f.attention_params(cfg, "window") == 94_371_904
    assert f.expert_params(cfg) == 25_165_824
    assert f.param_count(cfg) == pytest.approx(3429.9e6, rel=0.005)
    assert f.param_count(cfg, held=256, n_layer=48, vocab_size=152576) == \
        pytest.approx(309e9, rel=0.01)
    assert f.param_count(cfg, held=256, n_layer=48, vocab_size=152576,
                         active=True) == pytest.approx(15e9, rel=0.02)
    assert (f.kv_bytes_per_token(cfg, "full"),
            f.kv_bytes_per_token(cfg, "window")) == (5120, 25600)
    assert f.ring_bytes_per_row(cfg, 128) == 6_553_600


def test_the_mixed_generator_draws_two_classes_from_the_trace_seed():
    t = tiny.workload(CELL)["traffic"]
    a = open_loop_mixed.make(t, 1, 19072, 51)
    b = open_loop_mixed.make(t, 9, 19072, 51)
    lens = np.asarray([len(x.prompt) for x in a])
    assert sorted(lens) == sorted(len(x.prompt) for x in b)  # one trace
    assert [x.due_s for x in a] == [x.due_s for x in b]
    long = lens >= 8192
    assert 0.15 < long.mean() < 0.35
    assert lens[long].max() <= 32768 and lens[~long].max() <= 4096
    assert lens[~long].min() >= 128
    assert max(len(x.prompt) + x.max_new_tokens for x in a) <= 33536
    assert all(0 <= tok < 19072 for x in a[:3] for tok in x.prompt)
    with pytest.raises(ValueError, match="shares add up"):
        open_loop_mixed.make(dict(t, classes=t["classes"][:1]), 1, 10, 5)


@pytest.fixture(scope="module")
def traced():
    ctx = tiny_mimo_v2.context(jax.devices()[:1], seconds=2.0, trace=True)
    lines = []
    ctx.log = lines.append
    return ctx, serve_mimo_v2.run(ctx), lines


def test_serve_mimo_v2_driver(traced):
    ctx, res, _ = traced
    checks = res.detail["checks"]
    assert res.correct, checks
    assert res.failed == 0 and res.attempted > 5
    assert checks["compile_counts"] == {"prefill": 1, "decode": 1}
    assert checks["compiles_in_run"] == 0 and len(checks["reference"]) == 2
    own = checks["own_input"]
    assert set(own) == {"slot", "window", "full", "experts"}
    # the slot's full pages and ring, and the logit row, as the engine's
    # own two programs left them: float32 here
    for reading in ("first_rows", "deep_rows", "deep_logits"):
        assert 0 <= own["slot"][reading] < 1e-4, own["slot"]
    assert own["slot"]["prompt_len"] >= 50 and \
        own["slot"]["decode_steps"] > 8         # the ring wrapped again
    assert (own["window"]["kind"], own["full"]["kind"]) == ("window", "full")
    for kind in ("window", "full", "experts"):
        assert own[kind]["prefill"] < 1e-4 and own[kind]["decode"] < 1e-4
    assert own["experts"]["pairs_routed"] == 2 * (
        own["experts"]["tokens"] + own["experts"]["rows"])
    assert res.trace is None            # a CPU trace has no device plane
    facts = res.facts
    assert facts["attn_blocks_in_window_profiled"] == \
        facts["attn_blocks_visited_window_profiled"] > 0
    assert facts["prefill_pairs_profiled"] > facts["prefill_tokens_profiled"]
    assert facts["sliding_window"] == 8 and facts["attention_block_k"] == 8
    groups = res.detail["page_groups_at_end"]
    assert set(groups) == {"full", "window"}
    assert groups["window"]["pages_total"] == 4 * 2
    assert res.detail["cache"]["table_width"] == 16 + 2
    scopes = facts["program_scopes"]
    for program, kinds in (("prefill", ("ds_attn_prefill_full",
                                        "ds_attn_prefill_window")),
                           ("decode", ("ds_attn_decode_full",
                                       "ds_attn_decode_window"))):
        where = " ".join(scopes[program].values())
        for scope in kinds + ("ds_moe_route", "ds_moe_dispatch",
                              "ds_moe_experts", "ds_moe_combine"):
            assert scope in where, (program, scope)


def test_every_new_metric_is_a_number_at_toy_size(traced):
    """The counters' metrics from the program's own spans; the device's
    from a hand-made trace laid over the run's facts and scopes (a CPU
    run has no device plane), so that every new metric's file, reader
    and work function gives a number on what the driver hands over."""
    ctx, res, lines = traced
    assert metric(ctx, res, "window_blocks_in_window_pct.serve") == 100.0
    assert 0 < metric(ctx, res, "kv_window_bytes_pct.serve") < 100
    assert 0 < metric(ctx, res, "moe_pairs_held_pct.serve") < 80
    assert 0 < metric(ctx, res, "moe_experts_touched_pct.serve") <= 100
    assert 0 < metric(ctx, res, "kv_write_rows_live_pct.serve") <= 100
    assert 0 < metric(ctx, res, "decode_grid_live_pct.serve") <= 100
    assert 0 < metric(ctx, res, "kv_live_pages_pct.serve") <= 100
    split = [metric(ctx, res, f"setup_{p}_s") for p in setup_split.PARTS]
    assert all(isinstance(v, float) and v >= 0 for v in split)
    assert metric(ctx, res, "window_compiles.serve") == 0
    for name in ("gc_pause_ms.serve", "stall_max_ms.serve",
                 "prefill_stall_p99_ms.serve", "sched_host_ms.serve",
                 "engine_decode_ms.serve", "engine_prefill_ms.serve",
                 "sched_occupancy_pct.serve", "first_token_ready_ms.serve"):
        assert metric(ctx, res, name) >= 0, name
    device = NEW - {"window_blocks_in_window_pct.serve",
                    "kv_window_bytes_pct.serve"}
    for name in device:                 # no device plane: nothing, quietly
        assert metric(ctx, res, name) is None, name
    # one op under each attention scope of each program, 1 ms each
    scopes = res.facts["program_scopes"]
    ops, t = {"prefill": [], "decode": []}, 0.0
    for program, kinds in (("prefill", ("prefill_full", "prefill_window")),
                           ("decode", ("decode_full", "decode_window"))):
        for kind in kinds:
            name = next(k for k, v in scopes[program].items()
                        if f"ds_attn_{kind}" in v)
            ops[program].append((name + " fusion", t, t + 1e-3))
            t += 1e-3
    both = harness.Result(
        correct=True, attempted=1, failed=0, setup_s=1.0, end_to_end={},
        facts=res.facts, detail={}, trace=xplane.Trace(
            devices={0: ops["prefill"] + ops["decode"]},
            spans=[("prefill", -1e-3, 2e-3), ("decode", 2e-3, 5e-3)]))
    for name in device:
        value = metric(ctx, both, name)
        assert isinstance(value, float) and value > 0, name


def test_parent_without_the_model_exits_2(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_model(name, *a, **k):
        if name.endswith("models.mimo_v2"):
            raise ImportError(name)
        return real(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_model)
    ctx = tiny_mimo_v2.context(jax.devices()[:1], seconds=1.0, trace=False)
    with pytest.raises(SystemExit) as e:
        serve_mimo_v2.run(ctx)
    assert e.value.code == 2


def hand_made():
    """A prefill span of two attention ops, one decode span of two."""
    trace = xplane.Trace(
        devices={0: [("fusion.1 fusion", 0.0, 40e-3),
                     ("fusion.2 fusion", 40e-3, 42e-3),
                     ("fusion.3 fusion", 50e-3, 50.2e-3),
                     ("fusion.4 fusion", 50.2e-3, 50.5e-3)]},
        spans=[("prefill", -1e-3, 45e-3), ("decode", 49e-3, 51e-3)])
    facts = {"program_scopes": {
        "prefill": {"fusion.1": "jit(p)/ds_attn_prefill_full/while/dot",
                    "fusion.2": "jit(p)/ds_attn_prefill_window/dot"},
        "decode": {"fusion.3": "jit(d)/ds_attn_decode_full/jit(_paged_call)"
                               "/ds_flash_decode_paged/x",
                   "fusion.4": "jit(d)/ds_attn_decode_window/jit(_paged_"
                               "call)/ds_flash_decode_paged/x"}},
        "kv_tokens_per_step_profiled": 100000.0,
        "kv_rows_written_profiled": 20.0, "attention_block_k": 128,
        "kv_bytes_per_element": 2, "sliding_window": 128,
        "prefill_tokens_profiled": 4000.0,
        "prefill_pairs_profiled": 4000 * 4001 / 2,
        "prefill_prefix_tokens_profiled": 10000.0}
    return harness.Result(correct=True, attempted=1, failed=0, setup_s=1.0,
                          end_to_end={}, facts=facts, detail={},
                          trace=trace)


def test_metric_files_and_work_functions_against_hand_arithmetic():
    cfg = config_file()
    ctx = tiny_mimo_v2.context(jax.devices()[:1], 1.0, True, config=cfg)
    res = hand_made()
    ms = pytest.approx
    assert metric(ctx, res, "attn_prefill_full_ms.serve") == ms(40.0)
    assert metric(ctx, res, "attn_prefill_window_ms.serve") == ms(2.0)
    f = flops_mimo_v2
    # 100,000 positions read and 20 rows' blocks of 128 written back, x 4
    # key heads x (192 + 128) x 2 B x 2 layers; an element meets 16 queries
    ops, moved = f.full_decode_step(ctx, res)
    assert moved == (100000 + 20 * 128) * 4 * 320 * 2 * 2
    assert ops == 2 * 16 * 100000 * 4 * 320 * 2
    assert moved / 819e9 > ops / 197e12         # bound by bytes
    assert metric(ctx, res, "attn_decode_full_roofline.serve") == \
        ms(100 * (moved / 819e9) / 0.2e-3)
    # 20 rows x 128 positions read and as many written back, x 8 key
    # heads x 320 x 2 B x 5 layers; an element meets 8 queries
    ops, moved = f.window_decode_step(ctx, res)
    assert moved == (20 * 128 + 20 * 128) * 8 * 320 * 2 * 5
    assert ops == 2 * 8 * 20 * 128 * 8 * 320 * 5
    assert metric(ctx, res, "attn_decode_window_roofline.serve") == \
        ms(100 * (moved / 819e9) / 0.3e-3)
    # 8,002,000 pairs x 64 heads x 2 x 320 x 2 layers; 4,000 tokens'
    # queries in and outputs out and 10,000 walked positions' keys and
    # values
    ops, moved = f.full_prefill_call(ctx, res)
    assert ops == 2 * 8_002_000 * 64 * 320 * 2
    assert moved == (4000 * 64 * 320 + 10000 * 4 * 320) * 2 * 2
    assert ops / 197e12 > moved / 819e9         # bound by operations
    assert metric(ctx, res, "attn_prefill_full_roofline.serve") == \
        ms(100 * (ops / 197e12) / 40e-3)
    # nothing to read: nothing reported, nothing raised
    for key in ("kv_rows_written_profiled", "prefill_pairs_profiled"):
        res.facts[key] = None
    for work in (f.full_decode_step, f.window_decode_step,
                 f.full_prefill_call):
        assert work(ctx, res) is None
    for name in NEW:
        if name.endswith("_roofline.serve"):
            assert metric(ctx, res, name) is None, name
    res.facts["program_scopes"] = None
    for name in ("attn_prefill_full_ms.serve",
                 "attn_prefill_window_ms.serve"):
        assert metric(ctx, res, name) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_resolves(name):
    spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
    reader = importlib.import_module(
        "benchmarks.suite.readers." + spec["reader"])
    assert callable(reader.read)
    if "work" in spec["args"]:
        assert spec["args"]["module"] == "flops_mimo_v2"
        assert callable(getattr(flops_mimo_v2, spec["args"]["work"]))


@pytest.mark.parametrize("name", sorted(TAKEN))
def test_appended_metric_lists_the_cell_once(name):
    m = next(x for x in test_manifest.MANIFEST["per_layer"]
             if x["name"] == name)
    assert m["workloads"].count(CELL) == 1

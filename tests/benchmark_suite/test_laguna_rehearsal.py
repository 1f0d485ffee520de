"""The Laguna serving cell's driver end to end at toy size on the CPU,
its manifest entries, its configuration file, its work functions against
hand arithmetic, its metric files on a hand-made trace and every named
fault of `tools/fault_readings_laguna.py` over its limit. No number from
here is a device metric. Membership is asserted, never position or
count, so that the next cell breaks nothing here."""

import importlib
import json

import jax
import numpy as np
import pytest

from benchmarks.suite import flops_laguna, flops_qwen3_next, harness, xplane
from benchmarks.suite.drivers import serve_laguna, serve_mimo_v2
from benchmarks.suite.readers import setup_split
from benchmarks.suite.tools import fault_readings_laguna as faults
from benchmarks.suite.traffic import open_loop

from . import test_manifest, tiny, tiny_laguna

CELL = tiny_laguna.CELL
CONFIG = "laguna-s-2.1"
NEW = {"attn_decode_full_g6_roofline.serve",
       "attn_decode_window_g9_roofline.serve",
       "attn_prefill_full_g6_roofline.serve", "attn_gate_ms.serve"}
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
         "decode_grid_live_pct.serve", "flash_decode_paged_ms.serve",
         "kv_write_rows_live_pct.serve", "moe_ms.serve",
         "moe_expert_matmul_ms.serve", "moe_shared_ms.serve",
         "moe_pairs_held_pct.serve", "moe_permute_ms.serve",
         "moe_prefill_ms.serve", "moe_experts_touched_pct.serve",
         "swiglu_expert_matmul_roofline.serve", "window_compiles.serve",
         "gc_pause_ms.serve", "stall_max_ms.serve",
         "prefill_stall_p99_ms.serve", "attn_prefill_full_ms.serve",
         "attn_prefill_window_ms.serve",
         "window_blocks_in_window_pct.serve", "kv_window_bytes_pct.serve",
         "setup_trace_s", "setup_lower_s", "setup_compile_s", "setup_gc_s",
         "setup_engine_s", "setup_warmup_s", "setup_rest_s"}
# a span that went with PR 37, a recurrent state's, a latent pool's, and
# MiMo's three rooflines (their work functions read MiMo's keys)
NOT_TAKEN = {"logits_d2h_ms.serve", "state_live_pct.serve",
             "gdn_decode_ms.serve", "gdn_prefill_ms.serve",
             "gdn_rows_live_pct.serve", "gqa256_decode_roofline.serve",
             "ssm_decode_ms.serve", "mla_decode_roofline.serve",
             "mla_prefill_attn_ms.serve", "flash_decode_roofline.serve",
             "attn_prefill_full_roofline.serve",
             "attn_decode_full_roofline.serve",
             "attn_decode_window_roofline.serve",
             "moe_pairs_max_over_mean_q3n.serve"}


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
        CONFIG, "codegen", 1)
    conf = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["n_layer", "n_routed_experts", "vocab_size"]
    assert conf["source"] == config_file()["source"] == \
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    assert tiny.workload(CELL)["driver"] == "serve_laguna"
    assert set(test_manifest.listed("end_to_end", CELL)) == {
        "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    listed = set(test_manifest.listed("per_layer", CELL))
    assert NEW <= listed and TAKEN <= listed
    assert not listed & NOT_TAKEN
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        want = "ttft_p90_ms" if "prefill" in name else "itl_p95_ms"
        assert by_name[name]["moves"] == want, name
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["layer"] == "kernels"
        if name.endswith("_roofline.serve"):
            assert by_name[name]["unit"] == "%"
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        assert m.get("workloads", []).count(CELL) <= 1
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_cell_is_what_the_issue_names():
    wl = tiny.workload(CELL)
    inf, t = wl["inference"], wl["traffic"]
    assert (inf["max_batch"], inf["seq_buckets"], inf["prefill_chunk"],
            inf["page_size"], inf["attention_impl"]) == (
                64, [34816], 1024, 128, "flash")
    # ISSUE 51's pool, and no smaller than it allows
    assert 4609 <= inf["n_pages"] <= 5633
    assert t["prompt"] in (
        {"median": 6144, "sigma": 0.9, "min": 512, "max": 32768},
        {"median": 4096, "sigma": 0.9, "min": 512, "max": 32768})
    assert t["output"] == {"median": 640, "sigma": 0.6, "min": 64,
                           "max": 2048}
    assert t["max_total"] == 34816 == inf["seq_buckets"][0]
    assert (t["generator"], t["order_seed"], t["ramp_s"], t["drain_s"]) == (
        "open_loop", 1, 30, 5)
    assert "kv_cache_dtype" not in inf and "sampling" not in inf  # greedy
    assert "prefix_cache" not in inf        # off: it refuses the ring
    assert wl["trace"]["scope_marker"] == "ds_"
    cfg = config_file()
    # every live row's ring is full: the shortest prompt is the window
    assert t["prompt"]["min"] == cfg["sliding_window"] == 512
    pool = (inf["n_pages"] - 1) * inf["page_size"] * \
        flops_laguna.kv_bytes_per_token(cfg, "full")
    assert 4.8e9 < pool < 5.95e9
    rings = inf["max_batch"] * flops_laguna.ring_bytes_per_row(
        cfg, inf["page_size"])
    assert 1.0e9 < rings < 1.02e9
    # the trace at the cell's rate: a fixed set of sizes within the limits
    a = open_loop.make(t, t["order_seed"], cfg["vocab_size"], 51)
    lens = np.asarray([len(x.prompt) for x in a])
    assert lens.min() >= 512 and lens.max() <= 32768
    assert max(len(x.prompt) + x.max_new_tokens for x in a) <= 34816
    # ISSUE 51's floor: 40 requests due in a window
    assert t["rate_per_s"] * 51 >= 40
    corr = wl["correctness"]
    assert corr["requests"] == 2 and corr["slot_prompt_min"] == 8192
    for key in ("logit_rtol", "rows_rtol", "deep_rows_rtol",
                "deep_logits_rtol", "window_rtol", "window_decode_rtol",
                "full_rtol", "full_decode_rtol", "expert_rtol"):
        assert 0 < corr[key] < 0.2, key


def test_configuration_file_is_the_published_model_and_its_share():
    cfg = config_file()
    assert cfg["reduced"] == ["n_layer", "n_routed_experts", "vocab_size"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "Laguna-S-2.1")
    assert cfg["source"] == published["source_url"]
    for key, value in published["config"].items():
        if key != "vocab_size":
            assert cfg[key] == value, key
    assert (cfg["vocab_size"], cfg["vocab_size_published"], cfg["n_layer"],
            cfg["n_routed_experts"], cfg["assumed"]["experts_held"]) == (
                12544, published["config"]["vocab_size"], 8, 32, [0, 32])
    assert cfg["layer_kinds"] == ["full"] + ["window"] * 3 + \
        ["full"] + ["window"] * 3
    for key in ("no_qk_norm", "rotary", "gating", "query_heads", "routing",
                "shared_expert", "centred_why", "initializer_range_why"):
        assert key in cfg["assumed"], key
    for word in ("8 chips", "four pipeline stages"):
        assert word in cfg["reduced_why"]["deployment"], word
    # the program's config from the file
    mc = serve_laguna.model_config(cfg)
    assert mc.layer_kinds == tuple(cfg["layer_kinds"])
    assert (mc.vocab_size, mc.num_hidden_layers, mc.experts_held) == (
        12544, 8, (0, 32))
    assert (mc.kind("full").heads, mc.kind("window").heads) == (48, 72)
    assert mc.kind("full").rope == \
        cfg["rope_parameters"]["full_attention"]
    # ISSUE 51's arithmetic, reckoned again
    f = flops_laguna
    assert f.attention_params(cfg, "full") == 44_187_648
    assert f.attention_params(cfg, "window") == 63_135_744
    assert f.expert_params(cfg) == f.shared_params(cfg) == 9_437_184
    assert f.router_params(cfg) == 786_432
    assert f.param_count(cfg) == pytest.approx(2843e6, rel=0.001)
    assert f.param_count(cfg, held=256, n_layer=48, vocab_size=100352) == \
        pytest.approx(117.6e9, rel=0.001)
    assert f.param_count(cfg, held=256, n_layer=48, vocab_size=100352,
                         active=True) == pytest.approx(8.1e9, rel=0.01)
    assert (f.kv_bytes_per_token(cfg, "full"),
            f.kv_bytes_per_token(cfg, "window")) == (8192, 24576)
    assert f.ring_bytes_per_row(cfg, 128) == 15_728_640


def test_param_count_equals_the_tiny_models_own_leaves():
    from deepspeed_tpu.models.laguna import LagunaLM, init_laguna_params
    cfg = tiny_laguna.CONFIG
    model = LagunaLM(serve_laguna.model_config(cfg))
    params = jax.eval_shape(
        lambda k: init_laguna_params(model, k), jax.random.PRNGKey(0))
    leaves = sum(int(np.prod(a.shape))
                 for a in jax.tree_util.tree_leaves(params))
    assert flops_laguna.param_count(cfg) == leaves


@pytest.fixture(scope="module")
def traced():
    ctx = tiny_laguna.context(jax.devices()[:1], seconds=2.0, trace=True)
    lines = []
    ctx.log = lines.append
    return ctx, serve_laguna.run(ctx), lines


def test_serve_laguna_driver(traced):
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
        own["slot"]["decode_steps"] > 16        # the ring wrapped again
    assert (own["window"]["kind"], own["full"]["kind"]) == ("window", "full")
    # a full layer's nine calls end past YaRN's original context
    assert (own["window"]["calls"], own["full"]["calls"]) == (2, 9)
    assert own["full"]["tokens"] > 8 * 32 > 16
    for kind in ("window", "full", "experts"):
        assert own[kind]["prefill"] < 1e-4 and own[kind]["decode"] < 1e-4
    assert own["experts"]["pairs_routed"] == 3 * (
        own["experts"]["tokens"] + own["experts"]["rows"])
    assert res.trace is None            # a CPU trace has no device plane
    facts = res.facts
    assert facts["attn_blocks_in_window_profiled"] == \
        facts["attn_blocks_visited_window_profiled"] > 0
    assert facts["prefill_pairs_profiled"] > facts["prefill_tokens_profiled"]
    assert facts["sliding_window"] == 16 and facts["attention_block_k"] == 4
    groups = res.detail["page_groups_at_end"]
    assert set(groups) == {"full", "window"}
    assert groups["window"]["pages_total"] == 4 * 5
    assert res.detail["cache"]["table_width"] == 32 + 5
    scopes = facts["program_scopes"]
    for program, kinds in (("prefill", ("ds_attn_prefill_full",
                                        "ds_attn_prefill_window")),
                           ("decode", ("ds_attn_decode_full",
                                       "ds_attn_decode_window"))):
        where = " ".join(scopes[program].values())
        for scope in kinds + ("ds_attn_gate", "ds_moe_route",
                              "ds_moe_dispatch", "ds_moe_experts",
                              "ds_moe_combine", "ds_moe_shared"):
            assert scope in where, (program, scope)
    # the parts are `drivers/serve_mimo_v2.py`'s, by import, and that
    # module's two names are its own again after the call
    assert serve_laguna.parts is serve_mimo_v2
    assert serve_mimo_v2.own_input_checks.__module__.endswith(
        "serve_mimo_v2")
    assert serve_mimo_v2.ref.__name__.endswith("mimo_v2_ref")


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
    for name in NEW:                    # no device plane: nothing, quietly
        assert metric(ctx, res, name) is None, name
    # one op under each attention scope of each program and under the
    # gate's and the shared expert's in the decode program, 1 ms each
    scopes = res.facts["program_scopes"]
    ops, t = {"prefill": [], "decode": []}, 0.0
    for program, kinds in (
            ("prefill", ("attn_prefill_full", "attn_prefill_window")),
            ("decode", ("attn_decode_full", "attn_decode_window",
                        "attn_gate", "moe_shared"))):
        for kind in kinds:
            name = next(k for k, v in scopes[program].items()
                        if f"ds_{kind}" in v)
            ops[program].append((name + " fusion", t, t + 1e-3))
            t += 1e-3
    both = harness.Result(
        correct=True, attempted=1, failed=0, setup_s=1.0, end_to_end={},
        facts=res.facts, detail={}, trace=xplane.Trace(
            devices={0: ops["prefill"] + ops["decode"]},
            spans=[("prefill", -1e-3, 2e-3), ("decode", 2e-3, 7e-3)]))
    for name in NEW | {"attn_prefill_full_ms.serve",
                       "attn_prefill_window_ms.serve", "moe_shared_ms.serve"}:
        value = metric(ctx, both, name)
        assert isinstance(value, float) and value > 0, name
    assert metric(ctx, both, "attn_gate_ms.serve") == pytest.approx(1.0)


def test_parent_without_the_model_exits_2(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_model(name, *a, **k):
        if name.endswith("models.laguna"):
            raise ImportError(name)
        return real(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_model)
    ctx = tiny_laguna.context(jax.devices()[:1], seconds=1.0, trace=False)
    with pytest.raises(SystemExit) as e:
        serve_laguna.run(ctx)
    assert e.value.code == 2


def hand_made():
    """A prefill span of two attention ops, one decode span of three."""
    trace = xplane.Trace(
        devices={0: [("fusion.1 fusion", 0.0, 40e-3),
                     ("fusion.2 fusion", 40e-3, 42e-3),
                     ("fusion.3 fusion", 50e-3, 50.2e-3),
                     ("fusion.4 fusion", 50.2e-3, 50.5e-3),
                     ("fusion.5 fusion", 50.5e-3, 50.6e-3)]},
        spans=[("prefill", -1e-3, 45e-3), ("decode", 49e-3, 51e-3)])
    facts = {"program_scopes": {
        "prefill": {"fusion.1": "jit(p)/ds_attn_prefill_full/while/dot",
                    "fusion.2": "jit(p)/ds_attn_prefill_window/dot"},
        "decode": {"fusion.3": "jit(d)/ds_attn_decode_full/jit(_paged_call)"
                               "/ds_flash_decode_paged/x",
                   "fusion.4": "jit(d)/ds_attn_decode_window/jit(_paged_"
                               "call)/ds_flash_decode_paged/x",
                   "fusion.5": "jit(d)/ds_attn_gate/mul"}},
        "kv_tokens_per_step_profiled": 100000.0,
        "kv_rows_written_profiled": 20.0, "attention_block_k": 128,
        "kv_bytes_per_element": 2, "sliding_window": 512,
        "prefill_tokens_profiled": 4000.0,
        "prefill_pairs_profiled": 4000 * 4001 / 2,
        "prefill_prefix_tokens_profiled": 10000.0,
        "moe_experts_touched_profiled": 200.0,
        "moe_pairs_held_profiled": 500.0}
    return harness.Result(correct=True, attempted=1, failed=0, setup_s=1.0,
                          end_to_end={}, facts=facts, detail={},
                          trace=trace)


def test_metric_files_and_work_functions_against_hand_arithmetic():
    cfg = config_file()
    ctx = tiny_laguna.context(jax.devices()[:1], 1.0, True, config=cfg)
    res = hand_made()
    ms = pytest.approx
    assert metric(ctx, res, "attn_prefill_full_ms.serve") == ms(40.0)
    assert metric(ctx, res, "attn_prefill_window_ms.serve") == ms(2.0)
    assert metric(ctx, res, "attn_gate_ms.serve") == ms(0.1)
    f = flops_laguna
    # 100,000 positions read and 20 rows' one new position written, x 8
    # key heads x 256 x 2 B x 2 layers, and 20 rows' 48 queries in and
    # outputs out; an element meets 6 queries. What the kernel writes
    # back beyond (a whole block a row) is not the mathematics'
    ops, moved = f.full_decode_step(ctx, res)
    assert moved == ((100000 + 20) * 8 * 256 + 20 * 2 * 48 * 128) * 2 * 2
    assert ops == 2 * 6 * 100000 * 8 * 256 * 2
    assert moved / 819e9 > ops / 197e12         # bound by bytes
    assert metric(ctx, res, "attn_decode_full_g6_roofline.serve") == \
        ms(100 * (moved / 819e9) / 0.2e-3)
    # 20 rows x 512 positions read, x 8 key heads x 256 x 2 B x 6 layers;
    # an element meets 9 queries
    ops, moved = f.window_decode_step(ctx, res)
    assert moved == ((20 * 512 + 20) * 8 * 256 + 20 * 2 * 72 * 128) * 6 * 2
    assert ops == 2 * 9 * 20 * 512 * 8 * 256 * 6
    assert metric(ctx, res, "attn_decode_window_g9_roofline.serve") == \
        ms(100 * (moved / 819e9) / 0.3e-3)
    # 8,002,000 pairs x 48 heads x 2 x 256 x 2 layers; 4,000 tokens'
    # queries in, outputs out, keys and values written and read once
    ops, moved = f.full_prefill_call(ctx, res)
    assert ops == 2 * 8_002_000 * 48 * 256 * 2
    assert moved == 4000 * (2 * 48 * 128 + 2 * 2 * 8 * 128) * 2 * 2
    assert ops / 197e12 > moved / 819e9         # bound by operations
    assert metric(ctx, res, "attn_prefill_full_g6_roofline.serve") == \
        ms(100 * (ops / 197e12) / 40e-3)
    # the gates of a step: W_g once a layer, 20 rows' inputs and heads
    ops, moved = f.gate_decode_step(ctx, res)
    assert ops == 20 * (2 * (2 * 3072 * 48 + 2 * 48 * 128) +
                        6 * (2 * 3072 * 72 + 2 * 72 * 128))
    assert moved == 2 * (2 * (3072 * 48 + 20 * (3072 + 2 * 48 * 128)) +
                         6 * (3072 * 72 + 20 * (3072 + 2 * 72 * 128)))
    # Qwen3-Next's work function fits three banks at 3072 x 1024
    ops, moved = flops_qwen3_next.expert_matmuls_decode_step(ctx, res)
    assert ops == 2 * 500 * 3 * 3072 * 1024
    assert moved == (200 * 9_437_184 + 500 * (2 * 3072 + 3 * 1024)) * 2
    # nothing to read: nothing reported, nothing raised
    for key in ("kv_rows_written_profiled", "prefill_pairs_profiled"):
        res.facts[key] = None
    for work in (f.full_decode_step, f.window_decode_step,
                 f.full_prefill_call, f.gate_decode_step):
        assert work(ctx, res) is None
    for name in NEW:
        if name.endswith("_roofline.serve"):
            assert metric(ctx, res, name) is None, name
    res.facts["program_scopes"] = None
    assert metric(ctx, res, "attn_gate_ms.serve") is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_resolves(name):
    spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
    reader = importlib.import_module(
        "benchmarks.suite.readers." + spec["reader"])
    assert callable(reader.read)
    if "work" in spec["args"]:
        assert spec["args"]["module"] == "flops_laguna"
        assert callable(getattr(flops_laguna, spec["args"]["work"]))


@pytest.mark.parametrize("name", sorted(TAKEN))
def test_appended_metric_lists_the_cell_once(name):
    m = next(x for x in test_manifest.MANIFEST["per_layer"]
             if x["name"] == name)
    assert m["workloads"].count(CELL) == 1


# --- every named fault over its limit, at toy size -------------------------

@pytest.fixture(scope="module")
def toy():
    """The toy share's program config, weights and the committed file's
    limits."""
    from deepspeed_tpu.models.laguna import LagunaLM, init_laguna_params
    cfg = tiny_laguna.CONFIG
    mc = serve_laguna.model_config(cfg)
    params = init_laguna_params(LagunaLM(mc), jax.random.PRNGKey(3))
    return cfg, mc, params, tiny.workload(CELL)["correctness"]


def _attention(toy, which, reference=None, params=None):
    cfg, mc, sound, tol = toy
    return serve_laguna.check_attention(
        mc, cfg, params or sound, which, 5, 32, 4, "flash",
        tol[f"{which}_rtol"], tol[f"{which}_decode_rtol"],
        reference=reference, sound=sound)


FAULTS = {which: sorted(faults.attention_faults(
    tiny_laguna.CONFIG, which, 4)) for which in ("full", "window")}
FAULTS["experts"] = sorted(faults.expert_faults(tiny_laguna.CONFIG, 4))


@pytest.mark.parametrize("which, fault", [
    (w, f) for w in ("full", "window") for f in FAULTS[w]])
def test_attention_fault_is_over_its_limit(toy, which, fault):
    reading = _attention(toy, which, faults.attention_faults(
        toy[0], which, 4)[fault])
    assert not reading["ok"], (fault, reading)
    assert reading["prefill"] > reading["tolerance"] or \
        reading["decode"] > reading["decode_tolerance"]


@pytest.mark.parametrize("fault", FAULTS["experts"])
def test_expert_fault_is_over_its_limit(toy, fault):
    cfg, mc, params, tol = toy
    reading = serve_laguna.check_experts(
        mc, cfg, params, 5, 32, 6, tol["expert_rtol"],
        reference=faults.expert_faults(cfg, 4)[fault], sound=params)
    assert not reading["ok"] and max(
        reading["prefill"], reading["decode"]) > tol["expert_rtol"], reading


def test_sound_readings_and_low_weights_at_toy_size(toy):
    """Sound, every own-input check is inside its limit; with the
    weights through float8_e4m3 (the precision below the file's) it is
    not."""
    cfg, mc, params, tol = toy
    assert {"the gate left out", "the gate of the next head",
            "a gate an element", "plain rotary for YaRN",
            "attention_factor left out"} <= set(FAULTS["full"])
    assert {"window 15", "window 17",
            "mask by ring entry, not by position"} <= set(FAULTS["window"])
    assert any(f.startswith("query groups one key head on (at 6)")
               for f in FAULTS["full"])
    assert any(f.startswith("query groups one key head on (at 9)")
               for f in FAULTS["window"])
    assert {"the factor 2.5 left out", "the renormalisation left out",
            "sigmoid for softmax", "the shared expert left out",
            "the shared expert gated", "the banks one expert off"} == \
        set(FAULTS["experts"])
    lowered = faults.low(params)
    for which in ("full", "window"):
        assert _attention(toy, which)["ok"]
        assert not _attention(toy, which, params=lowered)["ok"]
    sound = serve_laguna.check_experts(mc, cfg, params, 5, 32, 6,
                                       tol["expert_rtol"])
    assert sound["ok"] and sound["weights_sum_off"] < 1e-5
    assert not serve_laguna.check_experts(
        mc, cfg, lowered, 5, 32, 6, tol["expert_rtol"], sound=params)["ok"]

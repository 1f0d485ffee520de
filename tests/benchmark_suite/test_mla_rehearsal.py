"""The latent-attention serving cell's driver end to end at toy size on
the CPU, its manifest entries, its work functions and its readers on a
hand-made trace. No number from here is a device metric."""

import jax
import pytest

from benchmarks.suite import flops_mla, harness, xplane
from benchmarks.suite.drivers import serve_mla
from benchmarks.suite.readers import (program_op_roofline, program_op_time,
                                      program_scope_time)

from . import test_manifest, tiny, tiny_mla

CELL = tiny_mla.CELL
NEW = {"mla_decode_roofline.serve", "mla_prefill_attn_ms.serve",
       "mla_project_ms.serve", "moe_ms.serve",
       "moe_expert_matmul_ms.serve", "moe_expert_matmul_roofline.serve",
       "moe_shared_ms.serve", "moe_pairs_held_pct.serve"}
KERNEL = "ds_flash_decode_paged.7 custom-call:tpu_custom_call"


def config_file():
    return test_manifest.load(test_manifest.ROOT, "benchmarks", "suite",
                              "configs", "kimi-k2.7-code.json")


def metric(ctx, res, name):
    spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
    reader = __import__("benchmarks.suite.readers." + spec["reader"],
                        fromlist=["read"])
    return reader.read(ctx, res, **spec["args"])


def test_cell_is_in_the_manifest_with_its_metrics():
    assert CELL in test_manifest.CELLS
    entry = next(w for w in test_manifest.MANIFEST["workloads"]
                 if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "kimi-k2.7-code", "repo", 1)
    wl = tiny.workload(CELL)
    assert wl["driver"] == "serve_mla"
    assert set(test_manifest.listed("end_to_end", CELL)) == {
        "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    listed = set(test_manifest.listed("per_layer", CELL))
    assert NEW <= listed
    for name in NEW:        # in this cell and in no other
        m = next(x for x in test_manifest.MANIFEST["per_layer"]
                 if x["name"] == name)
        assert m["workloads"] == [CELL]
    assert {"decode_step_ms.serve", "prefill_ms.serve",
            "device_idle_pct.serve", "kv_copy_ms.serve",
            "decode_grid_live_pct.serve", "kv_write_rows_live_pct.serve",
            "engine_prefill_ms.serve",
            # the decode kernel keeps its name, so its accepted metric
            # reads it here too
            "flash_decode_paged_ms.serve"} <= listed
    # patterns that take any tpu_custom_call or any while, work functions
    # that count another model's heads, another model's state
    assert not {n for n in listed - {"flash_decode_paged_ms.serve"}
                if n.startswith(("ssm_", "ssd_", "state_", "flash_"))}
    assert "kv_write_ms.serve" not in listed
    inf, t = wl["inference"], wl["traffic"]
    assert inf["max_batch"] == 32 and inf["page_size"] == 128
    assert inf["n_pages"] == 2049 and inf["prefill_chunk"] == 1024
    assert inf["seq_buckets"] == [17408] and inf["attention_impl"] == "flash"
    assert t["prompt"] == {"median": 4096, "sigma": 0.8, "min": 1024,
                           "max": 16384}
    assert t["output"] == {"median": 192, "sigma": 0.6, "min": 32,
                           "max": 512}
    assert t["max_total"] == 16895 < inf["seq_buckets"][0]
    assert (t["generator"], t["order_seed"], t["ramp_s"],
            t["drain_s"]) == ("open_loop", 1, 25, 5)
    # six cells, one of them on four chips
    assert len(test_manifest.CELLS) == 6
    assert sum(w["chips"] == 4
               for w in test_manifest.MANIFEST["workloads"]) == 1


def test_configuration_file_is_the_published_model_and_its_share():
    cfg = config_file()
    assert cfg["reduced"] == ["n_layer", "n_routed_experts", "vocab_size"]
    # the published keys as published: depth and the router's width stay
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"]) == (61, 384)
    assert (cfg["n_layer"], cfg["vocab_size"], cfg["n_embd"], cfg["n_head"],
            cfg["n_positions"]) == (7, 20480, 7168, 64, 262144)
    assert cfg["assumed"]["experts_held"] == [0, 12]
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    model = serve_mla.model_config(cfg)
    from deepspeed_tpu.models.mla_moe import kimi_k2_share
    assert model == kimi_k2_share()
    assert model.softmax_scale == pytest.approx(0.1447, abs=5e-5)
    # 101.1 M of attention a layer, 44.04 M an expert; the dense layer
    # 497.5 M, an expert layer 676.4 M, embedding + head 293.6 M
    assert abs(flops_mla.attention_params(cfg) - 101.1e6) < 0.05e6
    assert abs(flops_mla.expert_params(cfg) - 44.04e6) < 0.005e6
    assert flops_mla.expert_layers(cfg) == 6
    assert abs(flops_mla.param_count(cfg) - 4849.5e6) < 0.1e6
    spec = model.cache_spec(32, 17408, page_size=128, n_pages=2049)
    pool = 7 * spec.n_pages * flops_mla.latent_dim(cfg) * 128 * 2
    total = 2 * flops_mla.param_count(cfg) + pool
    assert 11.7e9 < total < 11.9e9          # of the chip's 16


@pytest.mark.parametrize("trace", [False, True])
def test_serve_mla_driver(trace):
    ctx = tiny_mla.context(jax.devices()[:1], seconds=2.0, trace=trace)
    res = serve_mla.run(ctx)
    checks = res.detail["checks"]
    assert res.correct, checks
    assert res.failed == 0 and res.attempted > 5
    assert checks["compile_counts"] == {"prefill": 1, "decode": 1}
    assert checks["compiles_in_run"] == 0
    assert len(checks["reference"]) == 2
    own = checks["own_input"]
    assert set(own) == {"latents", "attention", "experts"}
    assert own["experts"]["bias_moves_choice"] > 0.05
    assert 0 < own["experts"]["pairs_held"] < own["experts"]["pairs_routed"]
    assert res.end_to_end["ttft_p90_ms"] > 0
    assert res.end_to_end["itl_p95_ms"] > 0
    assert res.trace is None            # a CPU trace has no device plane
    facts = res.facts
    assert facts["kv_bytes_per_element"] == 2       # a bfloat16 pool
    assert facts["attention_block_k"] == 8
    # the program's counters, whole window: a quarter of the experts are
    # held at toy size (4 of 16)
    held = metric(ctx, res, "moe_pairs_held_pct.serve")
    assert 5 < held < 60
    for name in NEW - {"moe_pairs_held_pct.serve"}:
        assert metric(ctx, res, name) is None, name
    if trace:
        assert facts["moe_experts_touched_profiled"] <= 8   # 2 layers x 4
        assert facts["moe_pairs_held_profiled"] <= \
            facts["moe_pairs_routed_profiled"]
        assert 0 < facts["kv_rows_written_profiled"] <= 4
        assert facts["kv_tokens_per_step_profiled"] > 0
        scopes = facts["program_scopes"]
        assert set(scopes) == {"prefill", "decode"}
        for program, attn in (("prefill", "ds_mla_prefill_attn"),
                              ("decode", "ds_flash_decode_paged")):
            where = " ".join(scopes[program].values())
            for scope in ("ds_mla_project", "ds_moe_shared", "ds_moe_route",
                          "ds_moe_experts", attn):
                assert scope in where, (program, scope)
    else:
        assert facts["program_scopes"] is None
        assert facts["moe_experts_touched_profiled"] is None


def test_parent_without_the_model_exits_2(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_model(name, *a, **k):
        if name.endswith("mla_moe"):
            raise ImportError(name)
        return real(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_model)
    ctx = tiny_mla.context(jax.devices()[:1], seconds=1.0, trace=False)
    with pytest.raises(SystemExit) as e:
        serve_mla.run(ctx)
    assert e.value.code == 2


def hand_made(ctx):
    """A prefill span that calls ``gmm`` too, and two decode spans."""
    gmm = "gmm.3 custom-call:tpu_custom_call"
    trace = xplane.Trace(
        devices={0: [(gmm, 0.0, 4e-3),
                     ("fusion.2 fusion", 4e-3, 5e-3),
                     (KERNEL, 10e-3, 11e-3), (gmm, 11e-3, 13e-3),
                     ("fusion.2 fusion", 13e-3, 13.5e-3),
                     (KERNEL, 20e-3, 20.5e-3), (gmm, 20.5e-3, 21.5e-3)]},
        spans=[("prefill", -1e-3, 6e-3), ("decode", 9e-3, 14e-3),
               ("decode", 19e-3, 22e-3)])
    facts = {"program_scopes": {
        "prefill": {"fusion.2": "jit(p)/ds_mla_prefill_attn/while/dot"},
        "decode": {"fusion.2": "jit(d)/ds_mla_project/dot"}},
        "kv_tokens_per_step_profiled": 100000.0,
        "kv_rows_written_profiled": 20.0, "attention_block_k": 128,
        "moe_experts_touched_profiled": 30.0,
        "moe_pairs_held_profiled": 36.0, "kv_bytes_per_element": 2}
    return harness.Result(correct=True, attempted=1, failed=0, setup_s=1.0,
                          end_to_end={}, facts=facts, detail={},
                          trace=trace)


def test_new_readers_and_work_functions_on_a_hand_made_trace():
    cfg = config_file()
    ctx = tiny_mla.context(jax.devices()[:1], 1.0, True, config=cfg)
    res = hand_made(ctx)
    pattern = r"^gmm[.\w]* custom-call:tpu_custom_call$"
    # the decode program's calls alone, a decode span
    assert program_op_time.read(ctx, res, program="decode", pattern=pattern,
                                per="span:decode") == pytest.approx(1.5)
    assert program_op_time.read(ctx, res, program="prefill",
                                pattern=pattern,
                                per="span:prefill") == pytest.approx(4.0)
    assert program_op_time.read(ctx, res, program="decode",
                                pattern="^nothing$") is None
    assert metric(ctx, res, "moe_expert_matmul_ms.serve") == \
        pytest.approx(1.5)
    assert metric(ctx, res, "flash_decode_paged_ms.serve") == \
        pytest.approx(0.75)
    assert metric(ctx, res, "mla_project_ms.serve") == pytest.approx(0.25)
    assert metric(ctx, res, "mla_prefill_attn_ms.serve") == \
        pytest.approx(1.0)
    assert metric(ctx, res, "moe_shared_ms.serve") is None
    # 30 experts touched x 44.04 M x 2 B = 2.64 GB: 3.2 ms at 819 GB/s,
    # over the 1.5 ms the hand-made step's calls took
    ops, moved = flops_mla.expert_matmuls_decode_step(ctx, res)
    assert moved == (30 * 3 * 7168 * 2048 + 36 * 3 * (7168 + 2048)) * 2
    assert ops == 2 * 36 * 3 * 7168 * 2048
    assert metric(ctx, res, "moe_expert_matmul_roofline.serve") == \
        pytest.approx(100 * (moved / 819e9) / 1.5e-3)
    # 100,000 positions x 1,152 B x 7 layers read, 20 rows' blocks
    # written back; 64 heads x (576 + 512) x 2 operations a position
    ops, moved = flops_mla.mla_decode_step(ctx, res)
    assert moved == (100000 + 20 * 128) * 576 * 2 * 7
    assert ops == 2 * 100000 * 64 * (576 + 512) * 7
    assert moved / 819e9 > ops / 197e12         # bound by bytes
    assert metric(ctx, res, "mla_decode_roofline.serve") == \
        pytest.approx(100 * (moved / 819e9) / 0.75e-3)
    assert program_op_roofline.read(
        ctx, res, program="decode", pattern="^nothing$", per="span:decode",
        work="mla_decode_step", module="flops_mla") is None
    # nothing to read: nothing reported, nothing raised
    for key in ("kv_tokens_per_step_profiled",
                "moe_experts_touched_profiled"):
        res.facts[key] = None
    assert flops_mla.mla_decode_step(ctx, res) is None
    assert flops_mla.expert_matmuls_decode_step(ctx, res) is None
    assert metric(ctx, res, "mla_decode_roofline.serve") is None
    res.facts["program_scopes"] = None
    assert program_scope_time.read(ctx, res, program="decode",
                                   scopes=["ds_mla_project"]) is None

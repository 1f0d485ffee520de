"""The hybrid serving cell's driver end to end at toy size on the CPU,
its manifest entries, its work functions and its readers on a hand-made
trace. No number from here is a device metric."""

import jax
import pytest

from benchmarks.suite import flops_ssm, harness, xplane
from benchmarks.suite.drivers import serve_hybrid
from benchmarks.suite.readers import (program_scope_roofline,
                                      program_scope_time)

from . import test_manifest, tiny, tiny_hybrid

CELL = tiny_hybrid.CELL
NEW = {"ssm_decode_ms.serve", "ssm_decode_roofline.serve",
       "ssd_prefill_ms.serve", "ssd_prefill_roofline.serve",
       "ssm_rows_live_pct.serve", "state_live_pct.serve",
       "flash_decode_paged_ms.serve", "flash_decode_paged_roofline.serve"}


def test_cell_is_in_the_manifest_with_its_metrics():
    assert CELL in test_manifest.CELLS
    wl = tiny.workload(CELL)
    assert wl["driver"] == "serve_hybrid"
    assert set(test_manifest.listed("end_to_end", CELL)) == {
        "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    listed = set(test_manifest.listed("per_layer", CELL))
    assert NEW <= listed
    assert {"decode_step_ms.serve", "prefill_ms.serve",
            "device_idle_pct.serve", "kv_copy_ms.serve",
            "decode_grid_live_pct.serve",
            "engine_prefill_ms.serve"} <= listed
    # their pattern takes any tpu_custom_call: the cell has the anchored
    # twins instead
    assert not {"flash_decode_ms.serve",
                "flash_decode_roofline.serve"} & listed
    inf, t = wl["inference"], wl["traffic"]
    assert inf["prefill_chunk"] % 256 == 0 and inf["page_size"] == 128
    assert inf["max_batch"] == 48 and inf["seq_buckets"] == [4608]
    assert t["max_total"] == 4607 and t["ramp_s"] <= 25
    assert t["generator"] == "open_loop" and t["drain_s"] == 5


def test_configuration_file_is_the_published_model():
    cfg = test_manifest.load(test_manifest.ROOT, "benchmarks", "suite",
                             "configs", "granite-4.0-h-micro.json")
    assert cfg["reduced"] == [] and cfg["n_layer"] == 40
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    model = serve_hybrid.model_config(cfg)
    from deepspeed_tpu.models.granite_hybrid import granite_4_0_h_micro
    assert model == granite_4_0_h_micro()
    # 36 x 76.18 M + 4 x 60.82 M + 205.5 M
    assert abs(flops_ssm.param_count(cfg) - 3191.3e6) < 0.1e6
    assert flops_ssm.state_bytes_per_row(cfg) == 36 * 64 * 64 * 128 * 4
    spec = model.cache_spec(48, 4608, page_size=128)
    pool = 4 * 2 * spec.n_pages * 8 * 64 * 128 * 2
    total = 2 * flops_ssm.param_count(cfg) + 48 * \
        spec.state_bytes_per_slot + pool
    assert 11.7e9 < total < 12.0e9          # of the chip's 16


@pytest.mark.parametrize("trace", [False, True])
def test_serve_hybrid_driver(trace):
    ctx = tiny_hybrid.context(jax.devices()[:1], seconds=2.0, trace=trace)
    res = serve_hybrid.run(ctx)
    checks = res.detail["checks"]
    assert res.correct, checks
    assert res.failed == 0 and res.attempted > 5
    assert checks["compile_counts"] == {"prefill": 1, "decode": 1}
    assert checks["compiles_in_run"] == 0
    assert len(checks["reference"]) == 4
    assert set(checks["own_input"]) == {"state", "mixer", "attention"}
    assert res.end_to_end["ttft_p90_ms"] > 0
    assert res.end_to_end["itl_p95_ms"] > 0
    assert res.trace is None            # a CPU trace has no device plane
    facts = res.facts
    assert facts["kv_bytes_per_element"] == 2       # a bfloat16 pool
    # the program's counters, whole window (tracing does not move them)
    def metric(name):
        spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
        reader = __import__("benchmarks.suite.readers." + spec["reader"],
                            fromlist=["read"])
        return reader.read(ctx, res, **spec["args"])

    live = metric("ssm_rows_live_pct.serve")
    held = metric("state_live_pct.serve")
    assert 0 < live <= 100 and 0 < held <= 100
    # no device trace on the CPU: the trace's readers have nothing
    for name in NEW - {"ssm_rows_live_pct.serve", "state_live_pct.serve"}:
        assert metric(name) is None, name
    if trace:
        assert 0 < facts["ssm_rows_live_profiled"] <= 4
        assert facts["prefill_chunks_profiled"] >= 1
        assert facts["kv_tokens_per_step_profiled"] > 0
        scopes = facts["program_scopes"]
        assert set(scopes) == {"prefill", "decode"}
        for program in scopes:
            where = " ".join(scopes[program].values())
            assert "ds_ssm_scan" in where and "ds_ssm_conv" in where
        assert "ds_ssm_decode" in " ".join(scopes["decode"].values())
        assert "ds_ssd_prefill" in " ".join(scopes["prefill"].values())
    else:
        assert facts["program_scopes"] is None
        assert facts["ssm_rows_live_profiled"] is None
        assert facts["kv_tokens_per_step_profiled"] is None


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 77])
def test_every_seed_has_the_same_work_in_the_same_order(seed):
    """``--seed`` draws tokens (and weights), never which sizes meet:
    the order is the workload file's ``order_seed``."""
    def sizes(arrivals):
        return [(a.rid, a.due_s, len(a.prompt), a.max_new_tokens)
                for a in arrivals]

    assert tiny.workload(CELL)["traffic"]["order_seed"] == 1
    dev = jax.devices()[:1]
    base = serve_hybrid.arrivals_of(
        tiny_hybrid.context(dev, seconds=1.0, trace=False, seed=1))
    got = serve_hybrid.arrivals_of(
        tiny_hybrid.context(dev, seconds=1.0, trace=False, seed=seed))
    again = serve_hybrid.arrivals_of(
        tiny_hybrid.context(dev, seconds=1.0, trace=False, seed=seed))
    assert sizes(got) == sizes(base)
    assert [a.prompt for a in got] == [a.prompt for a in again]
    assert [a.prompt for a in got] != [a.prompt for a in base]
    vocab = tiny_hybrid.CONFIG["vocab_size"]
    assert all(0 <= t < vocab for a in got for t in a.prompt)


def test_parent_without_the_model_exits_2(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_model(name, *a, **k):
        if name.endswith("granite_hybrid"):
            raise ImportError(name)
        return real(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_model)
    ctx = tiny_hybrid.context(jax.devices()[:1], seconds=1.0, trace=False)
    with pytest.raises(SystemExit) as e:
        serve_hybrid.run(ctx)
    assert e.value.code == 2


def hand_made(ctx):
    """Two programs whose instructions share names: a prefill span with
    two ops, two decode spans with three."""
    trace = xplane.Trace(
        devices={0: [("fusion.1 fusion", 0.0, 4e-3),
                     ("fusion.2 fusion", 4e-3, 5e-3),
                     ("fusion.1 fusion", 10e-3, 11e-3),
                     ("fusion.2 fusion", 11e-3, 13e-3),
                     ("fusion.1 fusion", 20e-3, 21e-3)]},
        spans=[("prefill", -1e-3, 6e-3), ("decode", 9e-3, 14e-3),
               ("decode", 19e-3, 22e-3)])
    facts = {"program_scopes": {
        "prefill": {"fusion.1": "jit(p)/ds_ssm_scan/ds_ssd_prefill/dot",
                    "fusion.2": "jit(p)/ds_ssm_conv/add"},
        "decode": {"fusion.1": "jit(d)/mlp/dot",
                   "fusion.2": "jit(d)/ds_ssm_scan/ds_ssm_decode/mul"}},
        "ssm_rows_live_profiled": 30.0, "prefill_chunks_profiled": 3.0,
        "prefill_chunk": 512, "kv_tokens_per_step": 90000.0,
        "kv_tokens_per_step_profiled": 40000.0,
        "kv_bytes_per_element": 2}
    return harness.Result(correct=True, attempted=1, failed=0, setup_s=1.0,
                          end_to_end={}, facts=facts, detail={},
                          trace=trace)


def test_program_scope_readers_on_a_hand_made_trace():
    cfg = test_manifest.load(test_manifest.ROOT, "benchmarks", "suite",
                             "configs", "granite-4.0-h-micro.json")
    ctx = tiny_hybrid.context(jax.devices()[:1], 1.0, True, config=cfg)
    res = hand_made(ctx)
    read = program_scope_time.read
    # the same instruction name is another phase in the other program
    assert read(ctx, res, program="prefill", scopes=["ds_ssm_scan"],
                per="span:prefill") == pytest.approx(4.0)
    assert read(ctx, res, program="decode", scopes=["ds_ssm_scan"],
                per="span:decode") == pytest.approx(1.0)
    assert read(ctx, res, program="decode", scopes=["ds_ssm_conv"],
                per="span:decode") is None
    # 30 live rows x 36 mixers x 2 MB read and written = 4.53 GB:
    # 5.5 ms at 819 GB/s, over the 1 ms the hand-made step took
    share = program_scope_roofline.read(
        ctx, res, program="decode", scopes=["ds_ssm_scan"],
        per="span:decode", work="ssm_decode_step", module="flops_ssm")
    ops, moved = flops_ssm.ssm_decode_step(ctx, res)
    assert moved == 30 * 36 * 64 * 64 * 128 * 4 * 2
    assert share == pytest.approx(100 * (moved / 819e9) / 1e-3)
    ops, moved = flops_ssm.ssd_prefill_call(ctx, res)
    assert ops / 197e12 < moved / 819e9         # bound by bytes
    assert 3 * 36 * 16e6 < moved < 3 * 36 * 20e6
    # the profiled segment's own tokens a step, not the window's mean
    ops, moved = flops_ssm.gqa_decode_step(ctx, res)
    assert moved == 40000 * 8 * 64 * 2 * 4 * 2 and ops == 4 * moved
    res.facts["kv_tokens_per_step_profiled"] = None
    assert flops_ssm.gqa_decode_step(ctx, res) is None
    res.facts["program_scopes"] = None
    assert read(ctx, res, program="decode", scopes=["ds_ssm_scan"]) is None

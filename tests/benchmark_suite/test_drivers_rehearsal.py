"""Both drivers end to end at toy size on the CPU, through the same
functions the chip runs: the first two rehearsals of the
``on-chip-measurement`` guide. No number from here is a device metric."""

import jax
import pytest

from benchmarks.suite import flops, run
from benchmarks.suite.drivers import serve, train
from benchmarks.suite.readers import device_idle, mfu, op_time, series_stat

from . import test_manifest, tiny

TRAIN = [c for c in test_manifest.CELLS if tiny.workload(c)["driver"] ==
         "train"]
SERVE = [c for c in test_manifest.CELLS if tiny.workload(c)["driver"] ==
         "serve"]


@pytest.mark.parametrize("cell", TRAIN)
@pytest.mark.parametrize("trace", [False, True])
def test_train_driver(cell, trace):
    wl = tiny.train_workload(cell)
    chips = 4 if wl["engine"].get("mesh") else 1
    ctx = tiny.context(wl, jax.devices()[:chips], seconds=1.0, trace=trace)
    res = train.run(ctx)
    assert res.correct and res.failed == 0 and res.attempted > 3
    assert res.detail["checks"]["compiles_in_window"] == 0
    assert res.end_to_end["train_tokens_per_s_per_chip"] > 0
    assert res.setup_s > 0
    assert mfu.read(ctx, res) > 0
    assert res.trace is None        # a CPU trace has no device plane
    assert device_idle.read(ctx, res) is None
    assert op_time.read(ctx, res, pattern="x", per="step") is None
    step = series_stat.read(ctx, res, series="train_step", stat="median",
                            scale=1000)
    assert (step > 0) if trace else (step is None)


def test_train_driver_zero2_over_four_devices():
    wl = tiny.train_workload(TRAIN[0], mesh={"data": 4}, stage=2)
    ctx = tiny.context(wl, jax.devices()[:4], seconds=0.5, trace=False)
    res = train.run(ctx)
    assert res.correct, res.detail["checks"]
    assert res.detail["checks"]["reference"]["abs_diff"] < 1e-3


@pytest.mark.parametrize("cell", SERVE)
@pytest.mark.parametrize("trace", [False, True])
def test_serve_driver(cell, trace):
    ctx = tiny.context(tiny.serve_workload(cell), jax.devices()[:1],
                       seconds=2.0, trace=trace)
    res = serve.run(ctx)
    assert res.correct, res.detail["checks"]
    assert res.attempted >= 10 and res.failed == 0
    assert set(res.end_to_end) == {"serve_tokens_per_s", "ttft_p90_ms",
                                   "itl_p95_ms"}
    assert all(v > 0 for v in res.end_to_end.values())
    assert res.detail["checks"]["compiles_in_run"] == 0
    assert len(res.detail["checks"]["reference"]) == 4
    occ = series_stat.read(ctx, res, series="occupancy", stat="mean",
                           scale=100)
    assert 0 < occ <= 100
    assert series_stat.read(ctx, res, series="queue_wait", stat="p90",
                            scale=1000) >= 0
    fill = series_stat.read(ctx, res, series="pool_fill", stat="mean",
                            scale=100)
    assert 0 < fill <= 100
    # the first two tokens of a request share a stamp: that gap is not
    # among the gaps between tokens, so none is 0
    assert res.detail["itl_ms"]["n"] > 0
    assert res.detail["itl_ms"]["min"] > 0
    decode = series_stat.read(ctx, res, series="decode", stat="median",
                              scale=1000)
    assert (decode > 0) if trace else (decode is None)
    ops, moved = flops.flash_decode_step(ctx, res)
    assert moved == 2 * ops > 0     # float32 cache: 4 B per element


def test_every_metric_file_reads_or_returns_nothing():
    """Each per-layer metric's reader takes its arguments as written."""
    ctx = tiny.context(tiny.train_workload(TRAIN[0]), jax.devices()[:1],
                       seconds=0.1, trace=True)
    from benchmarks.suite import harness
    res = harness.Result(correct=True, attempted=0, failed=0, setup_s=1.0,
                         end_to_end={}, facts={}, detail={})
    for name in test_manifest.PER_LAYER:
        spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
        reader = run.importlib.import_module(
            "benchmarks.suite.readers." + spec["reader"])
        assert reader.read(ctx, res, **spec["args"]) is None

"""What PR 52 added to the benchmark, at toy size on the CPU: one
per-layer metric, data files only. `attn_prefill_window_kernel_pct.serve`
is the share of the window layers' prefill calls that went through the
band's kernel (`ops/pallas/window_prefill.py`), from the program's own
counters on the ``prefill`` span: in the manifest for exactly the two
cells whose models have window layers; under ``attention_impl: "flash"``
100 where the window is longer than the kernel's smallest query block
(Laguna: 512 against 128 on the chip; here 16 against a block cut to 8)
and 0 where it is not (MiMo: 128 against 128; here 8 against 8: XLA's
band is the faster there, `cache.band_kernel_takes`), 0 under
``"dense"``, and nothing (no raise) from a program whose spans carry no
such counters, as the parent's do not. No number from here is a device
metric."""

import importlib

import jax
import pytest

from . import test_manifest, tiny, tiny_laguna, tiny_mimo_v2

NAME = "attn_prefill_window_kernel_pct.serve"
CELLS = {"laguna": (tiny_laguna, "serve_laguna"),
         "mimo_v2": (tiny_mimo_v2, "serve_mimo_v2")}
COUNTERS = ("attn_window_calls_kernel", "attn_window_calls")
# under "flash": Laguna's window is long against the query block, MiMo's
# is not
FLASH = {"laguna": 100.0, "mimo_v2": 0.0}


@pytest.fixture
def toy_query_block(monkeypatch):
    """The kernel's smallest query block at toy size: 8 for the chip's
    128, so that the toy windows (16 and 8) stand to it as the cells'
    (512 and 128) stand to 128."""
    from deepspeed_tpu.ops.pallas import window_prefill as wp
    monkeypatch.setattr(wp, "QUERY_BLOCK", 8)
    wp._band_call.clear_cache()
    yield
    wp._band_call.clear_cache()


def metric(ctx, res, name=NAME):
    spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
    reader = importlib.import_module(
        "benchmarks.suite.readers." + spec["reader"])
    return reader.read(ctx, res, **spec["args"])


def test_metric_is_in_the_manifest_for_the_two_cells():
    by_name = {m["name"]: m for m in test_manifest.MANIFEST["per_layer"]}
    assert by_name[NAME] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "ttft_p90_ms",
        "workloads": [tiny_mimo_v2.CELL, tiny_laguna.CELL]}
    for module, _ in CELLS.values():
        assert NAME in test_manifest.listed("per_layer", module.CELL)
        assert "ttft_p90_ms" in test_manifest.listed("end_to_end",
                                                     module.CELL)
    others = {w["name"] for w in test_manifest.MANIFEST["workloads"]} - {
        tiny_mimo_v2.CELL, tiny_laguna.CELL}
    for cell in others:
        assert NAME not in test_manifest.listed("per_layer", cell)


def test_metric_file_is_data_over_an_accepted_reader():
    spec = test_manifest.load(tiny.SUITE, "metrics", NAME + ".json")
    twin = test_manifest.load(tiny.SUITE, "metrics",
                              "mla_prefill_kernel_blocks_pct.serve.json")
    assert spec["reader"] == twin["reader"] == "span_counter_ratio"
    assert spec["args"] == dict(twin["args"], attr=list(COUNTERS))
    assert spec["args"]["path"] == "serve/step/admit/prefill"
    assert spec["args"]["scale"] == 100


@pytest.mark.parametrize("impl", ["flash", "dense"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_share_of_window_calls_through_the_kernel(toy_query_block, cell,
                                                  impl):
    """The cell's driver at toy size: every window layer's call of every
    prompt goes through the kernel under ``"flash"`` (the cells' own
    setting) where the window is long, none under ``"dense"``, and the
    cell is ``correct`` either way; a program whose spans carry no such
    counters reads nothing."""
    module, driver = CELLS[cell]
    share = FLASH[cell] if impl == "flash" else 0.0
    ctx = module.context(jax.devices()[:1], seconds=2.0, trace=False)
    assert ctx.workload["inference"]["attention_impl"] == "flash"
    ctx.workload["inference"]["attention_impl"] = impl
    res = importlib.import_module(
        "benchmarks.suite.drivers." + driver).run(ctx)
    assert res.correct, res.detail["checks"]
    assert res.failed == 0
    assert metric(ctx, res) == share
    if impl == "flash":
        from deepspeed_tpu.telemetry import spans
        for r in spans.recent(0.0):
            if r[3]:
                for key in COUNTERS:
                    r[3].pop(key, None)
        assert metric(ctx, res) is None

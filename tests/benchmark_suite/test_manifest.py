"""``BENCHMARK.json`` against the contract's limits, and every name in it
and under ``workloads/`` and ``metrics/`` against the files that must
exist: a cell, a configuration, a per-layer metric and a reader are each
added by new files plus manifest entries."""

import importlib
import json
import os
import re

import pytest

from . import tiny

ROOT = os.path.dirname(os.path.dirname(tiny.SUITE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = load(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
PER_LAYER = [m["name"] for m in MANIFEST["per_layer"]]


def listed(section, cell):
    return [m["name"] for m in MANIFEST[section]
            if "workloads" not in m or cell in m["workloads"]]


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_manifest_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert len(MANIFEST["command"]) <= 32
    assert all(one_line(w) for w in MANIFEST["command"])
    assert any(w.startswith(p + "/") for w in MANIFEST["command"]
               for p in MANIFEST["paths"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    names = [e["name"] for s in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in MANIFEST[s]]
    assert all(NAME.match(n) for n in names)
    for section in ("configs", "workloads"):
        ns = [e["name"] for e in MANIFEST[section]]
        assert len(ns) == len(set(ns))
    metrics = list(E2E) + PER_LAYER
    assert len(metrics) == len(set(metrics))
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)


def test_configs():
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert one_line(c["why"]) and one_line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        body = load(ROOT, c["file"])
        assert body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|n_embd|hidden|n_head)", key)
        assert "assumed" in body
        for width in ("n_embd", "n_layer", "n_head", "vocab_size",
                      "n_positions"):
            assert isinstance(body[width], int)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and one_line(entry["why"])
    assert NAME.match(entry["traffic"])
    assert entry["config"] in {c["name"] for c in MANIFEST["configs"]}
    wl = load(tiny.SUITE, "workloads", cell + ".json")
    assert (entry["chips"] == 4) == bool(wl.get("engine", {}).get("mesh"))
    driver = importlib.import_module(
        "benchmarks.suite.drivers." + wl["driver"])
    assert callable(driver.run)
    gen = importlib.import_module(
        "benchmarks.suite.traffic." + wl["traffic"]["generator"])
    assert callable(gen.make)
    # every run reports setup_s, another end-to-end metric and at least
    # one per-layer metric
    e2e = listed("end_to_end", cell)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert listed("per_layer", cell)


def test_every_workload_file_is_a_cell():
    files = {f[:-5] for f in os.listdir(os.path.join(tiny.SUITE,
                                                     "workloads"))}
    assert files == set(CELLS)


def test_end_to_end_metrics():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1
    for m in E2E.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_resolves(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES and one_line(m["layer"])
    assert m["moves"] in E2E
    cells = m.get("workloads", CELLS)
    assert cells and set(cells) <= set(CELLS)
    for cell in cells:      # the metric it moves is reported there too
        assert m["moves"] in listed("end_to_end", cell)
    spec = load(tiny.SUITE, "metrics", metric + ".json")
    assert set(spec) == {"reader", "args"}
    reader = importlib.import_module(
        "benchmarks.suite.readers." + spec["reader"])
    assert callable(reader.read)


def test_every_metric_file_is_listed():
    files = {f[:-5] for f in os.listdir(os.path.join(tiny.SUITE,
                                                     "metrics"))}
    assert files == set(PER_LAYER)


def test_file_names_under_paths():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MANIFEST["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel

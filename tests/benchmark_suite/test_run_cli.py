"""``run.py`` without a TPU: another exit code than 0 and no result."""

import json

import pytest

from benchmarks.suite import run

from . import test_manifest


@pytest.mark.parametrize("cell", test_manifest.CELLS)
def test_no_tpu_no_result(cell, capsys):
    code = run.main(["--workload", cell, "--seed", str(2 ** 31 + 5),
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code == run.EXIT_DEVICE
    assert out.out == ""
    assert "Nothing was run" in out.err


def test_unknown_cell_is_refused(capsys):
    code = run.main(["--workload", "no-such-cell", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert code == run.EXIT_NAMES and capsys.readouterr().out == ""


def test_metrics_of_follows_the_workloads_key():
    manifest = {"per_layer": [
        {"name": "a"}, {"name": "b", "workloads": ["x"]},
        {"name": "c", "workloads": ["y"]}]}
    assert [m["name"] for m in run.metrics_of(manifest, "per_layer",
                                              "x")] == ["a", "b"]
    assert json.dumps(run.metrics_of(manifest, "per_layer", "z")) == \
        json.dumps([{"name": "a"}])

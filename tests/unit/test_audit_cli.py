"""Smoke tests for ``bin/ds_tpu_audit`` (subprocess, CPU backend).

The CLI is the operator-facing face of `deepspeed_tpu/analysis/`: it
must run anywhere (no TPU), audit a user config end to end, and emit
machine-readable JSON. Mirrors the ``ds_tpu_reshard`` CLI test pattern.
"""

import json
import os
import subprocess
import sys

import pytest

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CLI = os.path.join(REPO, "bin", "ds_tpu_audit")


def run_cli(*args, check=True):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, CLI, *args],
                          capture_output=True, text=True, env=env)
    if check and proc.returncode != 0:
        raise AssertionError(
            f"ds_tpu_audit {' '.join(args)} exited "
            f"{proc.returncode}\nstdout:\n{proc.stdout}\n"
            f"stderr:\n{proc.stderr}")
    return proc


def _json_payload(stdout):
    """The report is the JSON object at the tail of stdout (engine build
    logs precede it)."""
    start = stdout.index("{")
    return json.loads(stdout[start:])


def test_list_rules():
    proc = run_cli("--list-rules")
    out = proc.stdout
    for rule_id in ("donation", "dtype_hygiene", "zero_budget",
                    "host_transfer", "trip_count", "overlap", "recompile"):
        assert rule_id in out, out


def test_unknown_rule_and_flavor_rejected():
    proc = run_cli("--rules", "no_such_rule", check=False)
    assert proc.returncode == 2 and "unknown rule id" in proc.stderr
    proc = run_cli("--flavors", "no_such_flavor", check=False)
    assert proc.returncode == 2 and "unknown flavor" in proc.stderr


def test_dense_flavor_json_clean():
    proc = run_cli("--flavors", "dense", "--json")
    payload = _json_payload(proc.stdout)
    assert payload["ok"] is True
    assert payload["findings_total"] == 0
    rep = payload["reports"]["dense"]
    assert rep["ok"] is True
    assert rep["stats"]["donated_expected"] > 0
    assert rep["stats"]["donated_aliased"] == \
        rep["stats"]["donated_expected"]


def test_gpt2_config_audit(tmp_path):
    """End-to-end on a user config: toy GPT-2, bf16 — the audit must
    come back clean and carry real accounting in its stats."""
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "bf16": {"enabled": True},
           "steps_per_print": 10 ** 9}
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))
    proc = run_cli("--config", str(cfg_path), "--json")
    payload = _json_payload(proc.stdout)
    assert payload["ok"] is True, proc.stdout
    rep = payload["reports"]["config"]
    stats = rep["stats"]
    assert stats["collective_bytes"]["all-reduce"] > 0
    assert stats["donated_expected"] > 0
    assert stats["unknown_trip_counts"] == 0
    assert stats["compile_cache_size"] == 1


@pytest.mark.slow
def test_all_flavors_cli_clean():
    """The full six-flavor sweep through the CLI (the in-process flavor
    pins run in tier-1; this exercises the CLI packaging of the same)."""
    proc = run_cli("--json")
    payload = _json_payload(proc.stdout)
    assert payload["ok"] is True
    assert sorted(payload["reports"]) == sorted(
        ["dense", "zero1", "zero2", "offload", "quantized", "pipeline"])


@pytest.mark.slow
def test_pipeline_tp_flavor_cli_clean():
    """The TP-overlap flavor through the CLI: the compiled 1F1B step with
    tensor_parallel.overlap chunks=4 passes every rule, including the
    overlap pin (chunked collective-permute rings, no in-loop
    all-reduce) and the recompile detector."""
    proc = run_cli("--flavors", "pipeline_tp", "--steps", "2", "--json")
    payload = _json_payload(proc.stdout)
    assert payload["ok"] is True, proc.stdout
    rep = payload["reports"]["pipeline_tp"]
    assert rep["findings"] == []
    assert rep["stats"]["collective_bytes"].get("collective-permute", 0) > 0


# ---------------------------------------------------------------------------
# --hlo mode + JSON exit-code contract
# ---------------------------------------------------------------------------

BAD_HLO = """\
HloModule bad_step, is_scheduled=true

ENTRY %main (p0: f32[1024,1024]) -> f32[1024,1024] {
  %p0 = f32[1024,1024] parameter(0)
  %tok = token[] after-all()
  %inf = (f32[1024,1024], token[]) infeed(%tok)
  %val = f32[1024,1024] get-tuple-element(%inf), index=0
  ROOT %add = f32[1024,1024] add(%p0, %val)
}
"""

CLEAN_HLO = """\
HloModule clean_step, is_scheduled=true

ENTRY %main (p0: f32[256,256]) -> f32[256,256] {
  %p0 = f32[256,256] parameter(0)
  ROOT %add = f32[256,256] add(%p0, %p0)
}
"""


def test_hlo_mode_json_failing_exit_code_and_schema(tmp_path):
    """--json mode must still gate the exit code on --fail-on, and the
    finding schema (rule id / severity / flavor) is pinned here so
    downstream CI parsers can rely on it."""
    hlo = tmp_path / "bad.txt"
    hlo.write_text(BAD_HLO)
    proc = run_cli("--hlo", str(hlo), "--json", check=False)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = _json_payload(proc.stdout)
    assert payload["ok"] is False
    assert payload["fail_on"] == "error"
    assert payload["failing_findings"] >= 1
    rep = payload["reports"]["hlo"]
    assert rep["flavor"] == "custom"
    finding = rep["findings"][0]
    assert set(finding) == {"rule", "severity", "message", "details"}
    assert finding["rule"] == "host_transfer"
    assert finding["severity"] == "error"
    # the static peak-memory stats ride every report
    assert rep["stats"]["peak_memory"]["peak_bytes"] > 0


def test_hlo_mode_clean_exit_zero(tmp_path):
    hlo = tmp_path / "clean.txt"
    hlo.write_text(CLEAN_HLO)
    proc = run_cli("--hlo", str(hlo), "--json", "--fail-on", "warning")
    payload = _json_payload(proc.stdout)
    assert proc.returncode == 0
    assert payload["ok"] is True
    assert payload["findings_total"] == 0
    # the audit JSON carries the telemetry schema tag so downstream
    # tooling can join it with run event logs by version
    from deepspeed_tpu.telemetry.events import SCHEMA_VERSION
    assert payload["schema"] == SCHEMA_VERSION


def test_memory_table_text_mode(tmp_path):
    hlo = tmp_path / "clean.txt"
    hlo.write_text(CLEAN_HLO)
    proc = run_cli("--hlo", str(hlo), "--memory")
    assert "static peak memory" in proc.stdout
    assert "peak" in proc.stdout and "donated" in proc.stdout


def test_hlo_and_config_mutually_exclusive(tmp_path):
    hlo = tmp_path / "clean.txt"
    hlo.write_text(CLEAN_HLO)
    proc = run_cli("--hlo", str(hlo), "--config", "x.json", check=False)
    assert proc.returncode == 2
    assert "mutually exclusive" in proc.stderr

"""Smoke tests for ``bin/ds_tpu_tune`` (subprocess, CPU backend).

Mirrors the ``ds_tpu_audit`` CLI test pattern: the tuner must run
anywhere (no TPU), emit both human text and machine JSON, write its
artifacts (tuned config + expected-run JSONL), and exit 2 on an invalid
base config before touching jax. The search here is restricted to the
cheap ``scan`` dimension (two candidate compiles per run) — the full
sweep is ``BENCH_MODEL=tune``'s job.
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
CLI = os.path.join(REPO, "bin", "ds_tpu_tune")

BASE_CONFIG = {
    "train_batch_size": 8,
    "train_micro_batch_size_per_gpu": 1,
    "gradient_accumulation_steps": 1,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    "steps_per_print": 10 ** 9,
    "bf16": {"enabled": True},
    "zero_optimization": {"stage": 3, "gather_chunks": 2},
}


def run_cli(*args, check=True):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, CLI, *args],
                          capture_output=True, text=True, env=env)
    if check and proc.returncode != 0:
        raise AssertionError(
            f"ds_tpu_tune {' '.join(args)} exited "
            f"{proc.returncode}\nstdout:\n{proc.stdout}\n"
            f"stderr:\n{proc.stderr}")
    return proc


def _json_payload(stdout):
    start = stdout.index("{")
    return json.loads(stdout[start:])


@pytest.fixture(scope="module")
def base_config_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("tune") / "base.json"
    path.write_text(json.dumps(BASE_CONFIG))
    return str(path)


def test_json_mode_with_artifacts(tmp_path, base_config_path):
    tuned_path = tmp_path / "tuned.json"
    log_path = tmp_path / "expected.jsonl"
    proc = run_cli("--config", base_config_path,
                   "--dimensions", "scan", "--json",
                   "--output", str(tuned_path),
                   "--expected-log", str(log_path),
                   "--metrics-steps", "3")
    payload = _json_payload(proc.stdout)
    assert payload["schema"] == "ds-tpu-telemetry/1"
    assert payload["candidates_total"] == 2
    assert payload["base"]["ok"] is True
    assert payload["base"]["score"] > 0
    # the winner is never a rejected candidate …
    assert payload["best"]["reject_reason"] is None
    # … and rejected ones carry a typed reason, never a silent drop.
    # (scan_layers on a ZeRO-3 base is legitimately rejected here: the
    # stacked "h" leaf defeats the per-leaf gather-on-use schedule and
    # the audit's zero_budget/dtype rules catch it.)
    for cand in payload["candidates"]:
        if cand["reject_reason"] is None:
            assert cand["cost"]["ok"] is True
        else:
            assert cand["reject_reason"] in (
                "audit_rule_findings", "candidate_build_error",
                "peak_memory_over_budget")
            assert cand["reject_detail"]
    # artifacts: tuned config JSON + metrics-compatible expected log
    tuned = json.loads(tuned_path.read_text())
    assert tuned["zero_optimization"]["stage"] == 3
    events = [json.loads(line)
              for line in log_path.read_text().splitlines()]
    assert [e["event"] for e in events] == \
        ["run_start", "compile", "step", "step", "step"]
    assert all(e["schema"] == "ds-tpu-telemetry/1" for e in events)
    assert events[1]["collective_bytes_by_dtype"]


@pytest.mark.slow
def test_text_mode_mentions_candidates(base_config_path):
    proc = run_cli("--config", base_config_path,
                   "--dimensions", "scan", "--max-candidates", "1")
    assert "candidate" in proc.stdout
    assert "base" in proc.stdout
    assert "winner:" in proc.stdout


def test_invalid_base_config_exits_2(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    proc = run_cli("--config", str(bad), check=False)
    assert proc.returncode == 2
    assert "cannot read --config" in proc.stderr
    missing = run_cli("--config", str(tmp_path / "nope.json"),
                      check=False)
    assert missing.returncode == 2
    scalar = tmp_path / "scalar.json"
    scalar.write_text("42")
    proc = run_cli("--config", str(scalar), check=False)
    assert proc.returncode == 2
    assert "JSON object" in proc.stderr


def test_unknown_dimension_and_platform_exit_2(tmp_path,
                                               base_config_path):
    proc = run_cli("--config", base_config_path,
                   "--dimensions", "warp_drive", check=False)
    assert proc.returncode == 2
    assert "unknown dimension" in proc.stderr
    proc = run_cli("--config", base_config_path,
                   "--platform", "tpu_v9000", check=False)
    assert proc.returncode == 2
    assert "unknown platform" in proc.stderr

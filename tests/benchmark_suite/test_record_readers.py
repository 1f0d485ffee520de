"""The readers of the program's kept records (PR 36;
``program_records.py``, ``readers/setup_split.py``, ``window_compiles``,
``gc_pause``, ``stall_max``, ``prefill_stall``): on hand-made rings, and
on the toy form of every cell, where each new metric that lists the
cell must be a number. No number from here is a device metric.

Every hand-made run lives in a ring of its own (``own_ring``), at clock
readings no other test uses (``test_program_readers.fake_run``'s
windows, a day apart): nothing planted here is in the process's ring
when another file's tests read it. The toy cells use the process's.

``test_mla_prefill_kernel.py::test_the_two_metrics_are_in_the_manifest``
asserts that PR 35's two metrics are the manifest's *last* two, where
new entries have to go, and its file is the benchmark's, not a later
PR's to edit: it fails from PR 36 on (PERF.md, section 7 (x)), and what
else it checks, the two entries key for key, is checked here.
"""

import importlib

import jax
import pytest

from benchmarks.suite import program_records, program_ring
from benchmarks.suite.readers import (gc_pause, prefill_stall, setup_split,
                                      stall_max, window_compiles)
from deepspeed_tpu.telemetry import spans

from . import test_manifest, tiny, tiny_hybrid, tiny_mla
from .test_program_readers import S, fake_run

NEW = ["setup_trace_s", "setup_lower_s", "setup_compile_s", "setup_gc_s",
       "setup_engine_s", "setup_warmup_s", "setup_rest_s",
       "window_compiles.serve", "window_compiles.train",
       "gc_pause_ms.serve", "gc_pause_ms.train", "stall_max_ms.serve",
       "stall_max_ms.train", "prefill_stall_p99_ms.serve"]
T = "train/step"


@pytest.fixture(autouse=True)
def own_ring(request, monkeypatch):
    if "toy_size" not in request.node.name:
        monkeypatch.setattr(spans, "ring", spans.SpanRing())


def keep(path, t0, t1, attrs=None):
    spans.ring.keep((path, t0, t1, attrs))


def metric(ctx, res, name):
    spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
    reader = importlib.import_module(
        "benchmarks.suite.readers." + spec["reader"])
    return reader.read(ctx, res, **spec["args"])


def logged(ctx):
    lines = []
    ctx.log = lines.append
    return lines


def test_the_new_metrics_are_in_the_manifest_and_the_old_stand():
    per_layer = test_manifest.MANIFEST["per_layer"]
    by_name = {m["name"]: m for m in per_layer}
    assert set(NEW) <= set(by_name)
    # added after what was there: the 56 entries this PR found stand as
    # they stood, in their places, PR 35's two the last of them
    at = [m["name"] for m in per_layer].index(NEW[0])
    before = per_layer[:at]
    assert len(before) == 56 and not set(NEW) & {m["name"] for m in before}
    for m, source in zip(before[-2:], ("device_trace", "program_counter")):
        assert m == {"name": m["name"], "unit": "%", "better": "higher",
                     "source": source, "layer": "kernels",
                     "moves": "ttft_p90_ms", "workloads": [tiny_mla.CELL]}
    assert [m["name"] for m in before[-2:]] == [
        "mla_prefill_attn_roofline.serve",
        "mla_prefill_kernel_blocks_pct.serve"]
    serve = [c for c in test_manifest.CELLS if c.startswith("serve-")]
    train = [c for c in test_manifest.CELLS if c.startswith("train-")]
    for name in NEW:
        m = by_name[name]
        assert m["source"] == "program_span" and m["better"] == "lower"
        if name.startswith("setup_"):
            assert (m["moves"], m["layer"], m["unit"]) == (
                "setup_s", "set-up", "s")
            assert m["workloads"] == test_manifest.CELLS
        elif name.endswith(".serve"):
            assert m["moves"] == "itl_p95_ms" and m["workloads"] == serve
        else:
            assert m["moves"] == "train_tokens_per_s_per_chip"
            assert m["workloads"] == train


# ---------------------------------------------------------------------------
# the set-up split
# ---------------------------------------------------------------------------

def planted_setup(ramp_s):
    """A process of 5 s of set-up whose last ``ramp_s`` are the ramp:
    0.5 s before anything, an engine of 1.5 s holding a trace of 0.6
    (with a trace of 0.2 inside it), a lowering of 0.3, a cache read of
    0.2 and a collection of 0.1; bare records of the weights' jit; two
    warm-up steps of 0.4 each, one with a compile of 0.25 inside."""
    ctx, res, w0 = fake_run(seconds=10.0)
    ctx.workload["traffic"]["ramp_s"] = ramp_s
    p = ctx.t_process
    keep("jax/trace", p + 0.10, p + 0.20, {"fun": "init"})
    keep("jax/backend_compile", p + 0.20, p + 0.45,
         {"fun": "jit(init)", "cache": "miss"})
    e = p + 0.5
    keep("setup/engine/params/jax/trace", e + 0.2, e + 0.4, {"fun": "inner"})
    keep("setup/engine/params/jax/trace", e + 0.1, e + 0.7, {"fun": "outer"})
    keep("setup/engine/params/jax/lower", e + 0.7, e + 1.0,
         {"fun": "jit(outer)"})
    keep("setup/engine/params/jax/backend_compile", e + 1.0, e + 1.2,
         {"fun": "jit(outer)", "cache": "hit"})
    keep("setup/engine/params", e + 0.05, e + 1.25)
    keep("setup/engine/gc", e + 1.3, e + 1.4,
         {"generation": 2, "collected": 7})
    keep("setup/engine", e, e + 1.5)
    s = p + 2.5
    for i, name in enumerate((S, T)):
        t = s + 0.5 * i
        if i == 0:
            keep(name + "/decode/jax/backend_compile", t + 0.1, t + 0.35,
                 {"fun": "jit(step)", "cache": "hit", "step": 0})
        spans.record(name + "/decode", t + 0.05, t + 0.38, None)
        spans.record(name, t, t + 0.4, {"step": i})
    return ctx, res, w0


@pytest.mark.parametrize("ramp_s", [0.0, 1.5])
def test_setup_split_sums_to_setup_less_the_ramp(ramp_s):
    ctx, res, w0 = planted_setup(ramp_s)
    lines = logged(ctx)
    got = {p: setup_split.read(ctx, res, part=p) for p in setup_split.PARTS}
    want = {"trace": 0.1 + 0.6,         # the nested trace counted once
            "lower": 0.3, "compile": 0.25 + 0.2 + 0.25, "gc": 0.1,
            "engine": 1.5 - 0.6 - 0.3 - 0.2 - 0.1,
            "warmup": 2 * 0.4 - 0.25}
    want["rest"] = (5.0 - ramp_s) - sum(want.values())
    assert got == pytest.approx(want, abs=1e-9)
    assert sum(got.values()) == pytest.approx(res.setup_s - ramp_s)
    # one line a run, with the first of the seven, and it names the
    # functions
    assert len(lines) == 1 and "outer 0.40" in lines[0]
    assert "jit(init) 0.25" in lines[0] and "ramp of " in lines[0]
    with pytest.raises(ValueError, match="unknown part"):
        setup_split.read(ctx, res, part="imports")


def test_setup_split_cuts_records_at_the_end_of_set_up():
    ctx, res, w0 = planted_setup(0.0)
    # a step that straddles the window's start, with a collection that
    # does too, and a compile wholly inside the window
    spans.record(S, w0 - 0.3, w0 + 0.5, {"step": 9})
    keep(S + "/gc", w0 - 0.1, w0 + 0.2, {"generation": 2, "collected": 0})
    keep(S + "/decode/jax/backend_compile", w0 + 1.0, w0 + 3.0,
         {"fun": "jit(late)", "cache": "miss", "step": 11})
    logged(ctx)
    got = {p: setup_split.read(ctx, res, part=p) for p in setup_split.PARTS}
    assert got["gc"] == pytest.approx(0.1 + 0.1)
    assert got["warmup"] == pytest.approx(2 * 0.4 - 0.25 + 0.2)
    assert got["compile"] == pytest.approx(0.7)
    assert sum(got.values()) == pytest.approx(res.setup_s)
    # with a ramp those 0.3 s are the ramp's, not set-up's
    ctx2, res2, w2 = planted_setup(1.0)
    spans.record(S, w2 - 0.3, w2 + 0.5, {"step": 9})
    logged(ctx2)
    assert setup_split.read(ctx2, res2, part="warmup") == pytest.approx(
        2 * 0.4 - 0.25)


# ---------------------------------------------------------------------------
# compiles in the window
# ---------------------------------------------------------------------------

def test_window_compiles_counts_and_names_what_closed_in_the_window():
    ctx, res, w0 = planted_setup(1.0)
    lines = logged(ctx)
    assert window_compiles.read(ctx, res, step=S) == 0
    assert window_compiles.read(ctx, res, step=T) == 0
    keep(S + "/admit/prefill/jax/backend_compile", w0 + 2.0, w0 + 2.5,
         {"fun": "jit(_prefill_fn)", "cache": "miss", "step": 321})
    keep(S + "/admit/prefill/jax/trace", w0 + 1.8, w0 + 1.9,
         {"fun": "_prefill_fn", "step": 321})      # a trace is no compile
    keep("jax/backend_compile", w0 + 10.5, w0 + 10.6,
         {"fun": "jit(reference)", "cache": "hit"})   # after the window
    keep("jax/backend_compile", w0 - 0.5, w0 - 0.4,
         {"fun": "jit(ramp)", "cache": "hit"})        # in the ramp
    assert window_compiles.read(ctx, res, step=S) == 1
    assert len(lines) == 1
    for word in ("jit(_prefill_fn)", S + "/admit/prefill/jax/backend_compile",
                 "step 321", "0.500 s", "2.50 s into the window"):
        assert word in lines[0], (word, lines[0])
    # a training run counts to the close of its last step: the blocking
    # and profiled steps that follow the window are the harness's too
    assert window_compiles.read(ctx, res, step=T) == 0
    spans.record(T, w0 + 10.2, w0 + 10.7, {"step": 77})
    assert window_compiles.read(ctx, res, step=T) == 2
    with pytest.raises(ValueError, match="unknown step"):
        window_compiles.read(ctx, res, step="eval/step")


# ---------------------------------------------------------------------------
# the collector's share of a step
# ---------------------------------------------------------------------------

def test_gc_pause_is_the_mean_over_the_steps_that_worked():
    ctx, res, w0 = planted_setup(0.0)
    assert gc_pause.read(ctx, res, step=S) is None      # none in window
    for i, (gc_s, batch) in enumerate([(0.001, 2), (0.003, 1), (0.5, 0)]):
        spans.record(S, w0 + 1 + i, w0 + 1.5 + i,
                     {"step": i, "batch": batch, "gc_s": gc_s})
        spans.record(T, w0 + 1 + i, w0 + 1.5 + i,
                     {"step": i, "gc_s": gc_s})
    spans.record(S, w0 + 5, w0 + 5.5, {"step": 9, "batch": 1})  # no gc_s
    # an idle tick's collection is nobody's wait
    assert gc_pause.read(ctx, res, step=S) == pytest.approx(2.0)
    assert gc_pause.read(ctx, res, step=T) == pytest.approx(168.0)


# ---------------------------------------------------------------------------
# stalls
# ---------------------------------------------------------------------------

def put_serve_step(t0, step, inputs_s=0.0004, prefill_s=None, cpu=None):
    """A ``serve/step`` as the program nests it, with ``inputs`` of a
    given length; returns its end."""
    t = t0 + 0.0001
    spans.record(S + "/expire", t, t + 0.0001, None)
    t += 0.0002
    if prefill_s is not None:
        spans.record(S + "/admit/pages", t, t + 0.0001, None)
        spans.record(S + "/admit/prefill", t + 0.0001,
                     t + 0.0001 + prefill_s,
                     {"rid": "x", "rows_waiting": step % 2})
        spans.record(S + "/admit", t, t + prefill_s + 0.0003,
                     {"rid": "x", "rows_waiting": step % 2})
        t += prefill_s + 0.0004
    spans.record(S + "/inputs", t, t + inputs_s, None)
    d0 = t + inputs_s
    spans.record(S + "/decode/dispatch", d0, d0 + 0.0003, None)
    spans.record(S + "/decode/wait_tokens", d0 + 0.0003, d0 + 0.003, None)
    spans.record(S + "/decode", d0, d0 + 0.0031, None)
    t1 = d0 + 0.0035
    attrs = {"step": step, "batch": 2, "gc_s": 0.0}
    if cpu is not None:     # a CPU mark: (cpu_s, cpu_wall_s)
        attrs["cpu_s"], attrs["cpu_wall_s"] = cpu
    spans.record(S, t0, t1, attrs)
    return t1


def test_stall_reader_picks_the_planted_span_and_lists_the_collection():
    ctx, res, w0 = planted_setup(0.0)
    assert stall_max.read(ctx, res, step=S) is None
    t = w0 + 1.0
    for i in range(40):
        if i == 17:         # a long prompt is no stall
            t = put_serve_step(t, i, prefill_s=2.0)
        elif i == 25:       # the stall: `inputs` stands still for 1 s
            keep(S + "/inputs/gc", t + 0.1, t + 0.9,
                 {"generation": 2, "collected": 12345, "step": i})
            t = put_serve_step(t, i, inputs_s=1.0004, cpu=(0.031, 1.04))
        else:
            t = put_serve_step(t, i, prefill_s=0.01 if i % 5 == 0 else None)
        t += 0.001
    lines = logged(ctx)
    got = stall_max.read(ctx, res, step=S)
    assert got == pytest.approx(1000.0, abs=1e-3)
    assert len(lines) == 1
    for word in (S + "/inputs 1000.40 ms", "step 25",
                 "(1 of 0.2 s or more in it)",
                 "the thread's CPU 31 ms of the 1040 ms up to the close "
                 "of step 25",
                 S + "/inputs/gc 800.0 ms", "'collected': 12345"):
        assert word in lines[0], (word, lines[0])
    # the rows that stood still for the long prompt are another
    # metric's: odd steps' prefills had a row waiting, even steps' none
    assert prefill_stall.read(ctx, res, stat="p100") == pytest.approx(
        2000.0)
    assert prefill_stall.read(ctx, res, stat="p50") == 0.0
    # the un-spanned rest of a step is a path like any other
    spans.record(S, t, t + 0.5, {"step": 40, "batch": 0, "gc_s": 0.0})
    assert stall_max.read(ctx, res, step=S) == pytest.approx(1000.0,
                                                             abs=1e-3)
    spans.record(S, t + 1, t + 3.0, {"step": 41, "batch": 0, "gc_s": 0.0})
    # (less the median of the steps' un-spanned rest, under a ms)
    assert stall_max.read(ctx, res, step=S) == pytest.approx(2000.0, abs=1.0)
    assert " serve/step 2000.00 ms of its own" in lines[-1]
    assert "no gc or jax record overlaps it" in lines[-1]
    assert "no CPU mark after it" in lines[-1]


def test_train_stall_is_laid_to_where_the_interval_grew():
    ctx, res, w0 = planted_setup(0.0)
    ctx.trace, ctx.workload["trace"] = True, {"reserve_s": 4.0}
    assert stall_max.read(ctx, res, step=T) is None
    t = w0 + 0.5
    for i in range(30):
        gap = 0.100
        dispatch = 0.008
        if i == 12:
            gap = 1.100             # the harness waits a second longer
        if i == 20:
            dispatch = 0.408        # the engine's dispatch 0.4 s longer
        t += gap
        spans.record(T + "/dispatch", t + 0.001, t + 0.001 + dispatch, None)
        spans.record(T, t, t + dispatch + 0.002,
                     {"step": i, "cpu_s": 0.009, "gc_s": 0.0,
                      "cpu_wall_s": gap + dispatch + 0.002})
        t += dispatch + 0.002
    # a slower step after the untraced window (6 s): not this metric's
    spans.record(T, w0 + 8.0, w0 + 9.9, {"step": 99, "gc_s": 0.0})
    lines = logged(ctx)
    assert stall_max.read(ctx, res, step=T) == pytest.approx(1000.0,
                                                             abs=1e-3)
    assert "step 12" in lines[0] and "laid to outside the engine" in lines[0]
    assert "(2 of 0.2 s or more over the median in it)" in lines[0]
    assert "CPU 9 ms of the 1110 ms up to the close of step 12" in lines[0]
    assert "1100.00 ms, median 100.00" in lines[0]
    # without the longer wait the longest interval is the slow dispatch
    recs = [r for r in spans.ring.records
            if r[0].startswith(T) and w0 <= r[2] < w0 + 6.0]
    late = [r for r in recs if (r[3] or {}).get("step", 0) >= 12
            or r[0] != T and r[2] > w0 + 1.8]
    for r in late:
        spans.ring.records.remove(r)
        spans.record(r[0], r[1] - 1.0, r[2] - 1.0, r[3])
    assert stall_max.read(ctx, res, step=T) == pytest.approx(400.0,
                                                             abs=1e-3)
    assert "step 20" in lines[-1]
    assert "laid to train/step/dispatch (408.00 ms" in lines[-1]


def test_a_wrapped_ring_with_too_little_of_the_window_reads_as_nothing(
        monkeypatch):
    """The per-step records wrap and the kept ones do not: where fewer
    than ``MIN_AFTER_WRAP`` per-step records of the window are left,
    the readers of per-step records give ``None`` and the readers of
    kept records still read."""
    monkeypatch.setattr(spans, "ring", spans.SpanRing(maxlen=400))
    ctx, res, w0 = planted_setup(0.0)
    t = w0 + 9.0
    for i in range(30):             # the window's last second
        t = put_serve_step(t, i) + 0.001
    assert not spans.ring.dropped
    assert stall_max.read(ctx, res, step=S) is not None
    t = w0 + 10.5                   # the drain pushes the window out
    for i in range(30, 85):
        t = put_serve_step(t, i) + 0.001
    held = [r for r in spans.ring.records if w0 <= r[2] < w0 + 10.0]
    assert spans.ring.dropped and 0 < len(held) < program_ring.MIN_AFTER_WRAP
    assert program_records.per_step_from() > w0
    for name in ("gc_pause_ms.serve", "stall_max_ms.serve",
                 "prefill_stall_p99_ms.serve"):
        assert metric(ctx, res, name) is None, name
    # what is kept does not wrap: set-up and the compiles still read
    logged(ctx)
    assert metric(ctx, res, "setup_engine_s") == pytest.approx(0.3)
    assert metric(ctx, res, "window_compiles.serve") == 0
    # (the accepted ``program_ring.view`` tests the ring's first record,
    # which is a kept one now: PERF.md 7 (aa) says why no cell's run
    # can reach this state, and asks a ``benchmark`` PR for the repair)
    for r in list(spans.ring.records):
        if w0 <= r[2] < w0 + 10.0:
            spans.ring.records.remove(r)
    assert program_ring.view(ctx, res) is None      # nothing of it left


# ---------------------------------------------------------------------------
# a program without the records
# ---------------------------------------------------------------------------

def test_a_ring_without_such_records_reads_as_nothing(monkeypatch):
    """The parent's ring: steps and their children, no ledger, no
    ``gc_s``, no ``rows_waiting``; and no ring at all."""
    ctx, res, w0 = fake_run(seconds=10.0)
    t = w0 + 1.0
    for i in range(10):
        spans.record(S + "/admit/prefill", t, t + 0.01, {"rid": "x"})
        spans.record(S + "/decode", t + 0.01, t + 0.02, None)
        spans.record(S, t, t + 0.021, {"step": i, "batch": 1})
        t += 0.03
    for name in NEW:
        assert metric(ctx, res, name) is None, name
    assert program_records.run_of(ctx, res) is None
    # with the ledger on but steps that carry nothing
    keep("jax/backend_compile", ctx.t_process + 0.1, ctx.t_process + 0.2,
         {"fun": "jit(f)", "cache": "off"})
    assert program_records.run_of(ctx, res) is not None
    for name in ("gc_pause_ms.serve", "gc_pause_ms.train",
                 "stall_max_ms.serve", "stall_max_ms.train",
                 "prefill_stall_p99_ms.serve"):
        assert metric(ctx, res, name) is None, name
    assert metric(ctx, res, "window_compiles.serve") == 0
    monkeypatch.delattr(spans, "ring")
    for name in NEW:
        assert metric(ctx, res, name) is None, name


def test_kind_and_self_times():
    assert program_records.kind("jax/trace") == "trace"
    assert program_records.kind("a/b/jax/backend_compile") == "compile"
    assert program_records.kind("serve/step/gc") == "gc"
    assert program_records.kind("gc") == "gc"
    assert program_records.kind("serve/step/logic") is None
    assert program_records.kind("serve/step/jax") is None
    recs = [("a", 0.0, 10.0, None), ("a/b", 1.0, 4.0, None),
            ("a/b/c", 2.0, 3.0, None), ("a/d", 5.0, 10.000001, None),
            ("serve/request", 0.0, 50.0, None), ("e", 12.0, 13.0, None)]
    own = {r[0]: s for r, s in program_records.self_times(recs, 0.5, 12.5)}
    assert own == pytest.approx({"a": 9.5 - 3.0 - 5.0, "a/b": 2.0,
                                 "a/b/c": 1.0, "a/d": 5.0, "e": 0.5})


# ---------------------------------------------------------------------------
# the six cells at toy size
# ---------------------------------------------------------------------------

def run_tiny(cell):
    wl = tiny.workload(cell)
    driver = importlib.import_module(
        "benchmarks.suite.drivers." + wl["driver"])
    if wl["driver"] == "train":
        twl = tiny.train_workload(cell)
        chips = 4 if twl["engine"].get("mesh") else 1
        ctx = tiny.context(twl, jax.devices()[:chips], seconds=1.0,
                           trace=True)
    elif wl["driver"] == "train_olmoe":
        from . import test_olmoe_rehearsal
        ctx = test_olmoe_rehearsal.context(seconds=1.0, trace=True)
    elif wl["driver"] == "serve":
        ctx = tiny.context(tiny.serve_workload(cell), jax.devices()[:1],
                           seconds=2.0, trace=True)
    else:
        mod = {"serve_hybrid": tiny_hybrid, "serve_mla": tiny_mla}[
            wl["driver"]]
        ctx = mod.context(jax.devices()[:1], seconds=2.0, trace=True)
    return ctx, driver.run(ctx)


@pytest.mark.parametrize("cell", test_manifest.CELLS)
def test_every_new_metric_of_a_cell_is_a_number_at_toy_size(cell):
    ctx, res = run_tiny(cell)
    checks = res.detail["checks"]
    assert res.correct, checks
    lines = logged(ctx)
    mine = [n for n in test_manifest.listed("per_layer", cell) if n in NEW]
    assert len(mine) == (11 if cell.startswith("serve-") else 10)
    got = {n: metric(ctx, res, n) for n in mine}
    assert all(isinstance(v, (int, float)) for v in got.values()), got
    split = [got[f"setup_{p}_s"] for p in setup_split.PARTS]
    traffic = ctx.workload["traffic"]
    assert sum(split) == pytest.approx(
        res.setup_s - traffic.get("ramp_s", 0.0), abs=1e-6)
    assert all(v >= 0 for v in split), got
    # this process compiled its programs, inside the engine's spans
    assert got["setup_compile_s"] > 0 and got["setup_trace_s"] > 0
    assert got["setup_lower_s"] > 0 and got["setup_warmup_s"] > 0
    kind = "serve" if cell.startswith("serve-") else "train"
    assert got[f"window_compiles.{kind}"] == 0 == checks.get(
        "compiles_in_window", checks.get("compiles_in_run"))
    assert got[f"gc_pause_ms.{kind}"] >= 0
    assert got[f"stall_max_ms.{kind}"] >= 0
    if kind == "serve":
        assert got["prefill_stall_p99_ms.serve"] >= 0
    assert sum("set-up by the program's records" in ln
               for ln in lines) == 1
    assert sum("longest " in ln for ln in lines) == 1

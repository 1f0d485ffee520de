"""The readers of the program's own spans and stamps
(``program_ring.py``, ``readers/request_stat.py``, ``span_stat.py``,
``idle_under_span.py``): on hand-made rings and a hand-made
``xplane.Trace``, and on the toy serve rehearsal, where the program's
numbers must equal what the driver sees from outside.

The ring is the process's, so every test puts its records at clock
readings no other test uses (a window of its own) and reads by window.
"""

import itertools
import re

import jax
import pytest

from benchmarks.suite import harness, program_ring, xplane
from benchmarks.suite.drivers import serve
from benchmarks.suite.readers import (idle_under_span, request_stat,
                                      series_stat, span_stat)
from deepspeed_tpu.telemetry import spans

from . import test_manifest, tiny

SERVE = [c for c in test_manifest.CELLS if tiny.workload(c)["driver"] ==
         "serve"]
S = "serve/step"
# hand-made windows live far in the clock's future, a day apart
_windows = itertools.count(1)


def fake_run(seconds=10.0, profile_s=None, trace=False):
    """A context and result whose window [w0, w0 + seconds) holds
    nothing yet: ``(ctx, result, w0)``."""
    wl = tiny.serve_workload(SERVE[0])
    if profile_s is not None:
        wl["trace"] = {"profile_s": profile_s}
    ctx = tiny.context(wl, jax.devices()[:1], seconds=seconds, trace=trace)
    ctx.t_process = spans.clock() + 86400.0 * next(_windows)
    res = harness.Result(correct=True, attempted=0, failed=0, setup_s=5.0,
                         end_to_end={}, facts={}, detail={})
    return ctx, res, ctx.t_process + 5.0


def put_step(t0, decode_s=0.010, prefill_s=None, attrs=None, d2h_s=0.002):
    """One ``serve/step`` with its children, as the program nests them;
    returns the step's end."""
    t = t0 + 0.0001
    if prefill_s is not None:
        spans.record(S + "/admit/prefill", t + 0.0001,
                     t + 0.0001 + prefill_s, {"rid": "x", "chunks": 1})
        spans.record(S + "/admit", t, t + prefill_s + 0.0003, {"rid": "x"})
        t += prefill_s + 0.0004
    d0 = t
    spans.record(S + "/decode/upload", d0, d0 + 0.0002, None)
    spans.record(S + "/decode/dispatch", d0 + 0.0002, d0 + 0.0005, None)
    spans.record(S + "/decode/wait_tokens", d0 + 0.0005,
                 d0 + decode_s - d2h_s, None)
    spans.record(S + "/decode/logits_d2h", d0 + decode_s - d2h_s,
                 d0 + decode_s, None)
    spans.record(S + "/decode", d0, d0 + decode_s, None)
    t1 = d0 + decode_s + 0.0005
    spans.record(S, t0, t1, attrs)
    return t1


# ---------------------------------------------------------------------------
# the view
# ---------------------------------------------------------------------------

def test_view_is_none_when_the_ring_holds_nothing_of_the_window():
    ctx, res, w0 = fake_run()
    assert program_ring.view(ctx, res) is None
    assert span_stat.read(ctx, res, path=S, stat="median") is None
    assert request_stat.read(ctx, res, field_from="submit_t",
                             field_to="admit_t", stat="p90",
                             over="due_in_window") is None
    assert idle_under_span.read(ctx, res, match="x") is None
    spans.record(S, w0 - 2.0, w0 - 1.0, None)       # before the window
    spans.record(S, w0 + 11.0, w0 + 12.0, None)     # after it
    assert program_ring.view(ctx, res) is None
    spans.record(S, w0 + 1.0, w0 + 2.0, None)
    v = program_ring.view(ctx, res)
    assert (v.w0, v.w1, v.seg0) == (w0, w0 + 10.0, w0 + 10.0)
    assert len(v.spans(S)) == 1


def test_view_refuses_a_ring_wrapped_past_the_window_start(monkeypatch):
    ctx, res, w0 = fake_run()
    for i in range(50):
        spans.record(S, w0 + 5.0 + 0.01 * i, w0 + 5.005 + 0.01 * i, None)
    small = spans.SpanRing(maxlen=50)
    for r in spans.recent()[-50:]:
        small.append(r)
    monkeypatch.setattr(spans, "ring", small)
    assert program_ring.view(ctx, res) is not None  # nothing dropped yet
    for i in range(30):     # the window's first records fall out
        small.append((S, w0 + 6.0 + 0.01 * i, w0 + 6.005 + 0.01 * i, None))
    assert small.dropped == 30 and small.recent()[0][2] > w0
    assert program_ring.view(ctx, res) is None      # 50 < 100 left


def test_a_program_without_a_ring_reads_as_nothing(monkeypatch):
    ctx, res, w0 = fake_run()
    spans.record(S, w0 + 1.0, w0 + 2.0, None)
    assert program_ring.view(ctx, res) is not None
    monkeypatch.delattr(spans, "ring")
    assert program_ring.ring_records() == (None, 0)
    assert program_ring.view(ctx, res) is None
    assert span_stat.read(ctx, res, path=S, stat="median") is None


# ---------------------------------------------------------------------------
# span_stat
# ---------------------------------------------------------------------------

def test_span_stat_durations_minus_children_and_ratios():
    ctx, res, w0 = fake_run(seconds=10.0, profile_s=2.0, trace=True)
    t = w0 + 1.0
    for i in range(5):      # decode 10..14 ms; the third step admits
        t = put_step(t, decode_s=0.010 + 0.001 * i,
                     prefill_s=0.020 if i == 2 else None,
                     attrs={"live_rows": i, "max_batch": 4,
                            "pages_live": 10 * i, "pages_total": 100})
        t += 0.001
    # a slow step in the profiled segment: counted by ratios only
    put_step(w0 + 9.0, decode_s=0.500,
             attrs={"live_rows": 4, "max_batch": 4, "pages_live": 100,
                    "pages_total": 100})
    read = lambda **kw: span_stat.read(ctx, res, **kw)
    assert read(path=S + "/decode", stat="median",
                scale=1000) == pytest.approx(12.0)
    assert read(path=S + "/decode", stat="mean",
                scale=1000) == pytest.approx(12.0)
    assert read(path=S + "/decode/logits_d2h", stat="median",
                scale=1000) == pytest.approx(2.0)
    assert read(path=S + "/admit/prefill", stat="median",
                scale=1000) == pytest.approx(20.0)
    # the step less prefill and decode: 0.6 ms of its own, 1.0 when it
    # admits (the admit span's own 0.3 ms + 0.1 ms before the decode)
    host = read(path=S, stat="median", scale=1000,
                minus=[S + "/admit/prefill", S + "/decode"])
    assert host == pytest.approx(0.6)
    assert read(path=S, stat="p100", scale=1000,
                minus=[S + "/admit/prefill", S + "/decode"]) == \
        pytest.approx(1.0)
    occ = read(path=S, stat="mean", attr=["live_rows", "max_batch"],
               scale=100)
    assert occ == pytest.approx(100 * (0 + 1 + 2 + 3 + 4 + 4) / 6 / 4)
    assert read(path=S, stat="mean", attr=["pages_live", "pages_total"],
                scale=100) == pytest.approx(100 * 200 / 6 / 100)
    assert read(path=S, stat="mean", attr=["nope", "max_batch"]) is None
    # the harness's own series start with the ramp: so can a twin
    put_step(w0 - 0.2, decode_s=0.002)      # in the 0.5 s ramp
    put_step(w0 - 0.7, decode_s=0.001)      # before it
    assert read(path=S + "/decode", stat="p0", scale=1000) == \
        pytest.approx(10.0)
    assert read(path=S + "/decode", stat="p0", scale=1000,
                over="run") == pytest.approx(2.0)
    with pytest.raises(ValueError, match="unknown over"):
        read(path=S, stat="median", over="ever")
    assert read(path="serve/none", stat="median") is None
    with pytest.raises(ValueError, match="unknown stat"):
        read(path=S, stat="mode")


# ---------------------------------------------------------------------------
# request_stat
# ---------------------------------------------------------------------------

def put_requests(ctx, w0, late_s=0.001):
    """One ``serve/request`` record per arrival of the cell's own
    generator, stamped by rule; returns ``{rid: due}``."""
    traffic = ctx.workload["traffic"]
    arrivals = serve.importlib.import_module(
        "benchmarks.suite.traffic." + traffic["generator"]).make(
            traffic, ctx.seed, seconds=ctx.seconds,
            vocab_size=ctx.config["vocab_size"])
    due = {}
    for n, a in enumerate(arrivals):
        d = due[a.rid] = w0 - traffic["ramp_s"] + a.due_s
        submit = d + late_s
        admit = submit + 0.002 * (n % 5)
        first = admit + 0.010
        ret = first + 0.050
        spans.record("serve/request", submit, ret + 0.3, {
            "rid": a.rid, "prompt_len": len(a.prompt),
            "finish_reason": "max_new_tokens", "arrival_t": submit,
            "submit_t": submit, "admit_t": admit, "first_token_t": first,
            "first_return_t": ret, "token_t": [first, ret + 0.1],
            "finish_t": ret + 0.3})
    return due


def test_request_stat_over_the_requests_due_in_the_window():
    ctx, res, w0 = fake_run(seconds=4.0)
    due = put_requests(ctx, w0)
    # another run's request of the same id, a day earlier: not this run's
    spans.record("serve/request", w0 - 86400.0, w0 - 86399.0, {
        "rid": "r0", "submit_t": 0.0, "admit_t": 99.0, "finish_t": 0.0})
    in_window = [r for r, d in due.items() if w0 <= d < w0 + 4.0]
    assert 10 < len(in_window) < len(due)
    read = lambda **kw: request_stat.read(ctx, res, scale=1000, **kw)
    waits = sorted(2.0 * (int(r[1:]) % 5) for r in in_window)
    got = read(field_from="submit_t", field_to="admit_t", stat="p90",
               over="due_in_window")
    assert got == pytest.approx(
        program_ring.statistic(waits, "p90"), abs=1e-6)
    assert read(field_from="first_token_t", field_to="first_return_t",
                stat="median", over="due_in_window") == pytest.approx(50.0)
    ready = read(field_from="due", field_to="first_token_t", stat="p90",
                 over="due_in_window")
    assert ready == pytest.approx(
        program_ring.statistic([w + 11.0 for w in waits], "p90"), abs=1e-6)
    # all of them finish 0.36 s after they were due, so fewer end in
    # the window than are due in it; the statistic is over those
    assert read(field_from="submit_t", field_to="finish_t", stat="mean",
                over="ending_in_window") > 360.0
    with pytest.raises(ValueError, match="unknown over"):
        read(field_from="submit_t", field_to="admit_t", stat="p90",
             over="always")


def test_request_stat_skips_requests_that_never_got_a_stamp():
    ctx, res, w0 = fake_run(seconds=4.0)
    put_requests(ctx, w0)
    for r in spans.recent(w0 - 1.0):
        if r[0] == "serve/request" and r[3]["rid"] in ("r5", "r6"):
            r[3]["admit_t"] = None       # timed out in the queue
    got = request_stat.read(ctx, res, field_from="submit_t",
                            field_to="admit_t", stat="p100",
                            over="due_in_window", scale=1000)
    assert got == pytest.approx(8.0)


# ---------------------------------------------------------------------------
# idle_under_span, and the two clocks
# ---------------------------------------------------------------------------

def traced_run(shift=None, drop_last=False):
    """Four steps, 5 ms apart, in a profiled segment of 1 s; the trace's
    clock is a constant away from the ring's. The device works all
    through each decode except for 1.5 ms under ``logits_d2h``, then
    0.4 ms round the end of the step, then 5.95 ms that begin after the
    step and end 1 ms into the next decode.
    ``shift`` moves one program decode span against the others."""
    ctx, res, w0 = fake_run(seconds=10.0, profile_s=1.0, trace=True)
    off = -(w0 + 9.0) + 0.25    # the profile started 0.25 s into it
    spans.record(S, w0 + 1.0, w0 + 1.1, None)       # the window's own
    t, ops, marks = w0 + 9.3, [], []
    for i in range(4):
        t1 = put_step(t, decode_s=0.100, d2h_s=0.002)
        d0 = t + 0.0001
        marks.append(("decode", d0 - 5e-6 + off, d0 + 0.100 + 5e-6 + off))
        ops += [("fusion.1 fusion", d0 + 0.001 + off, d0 + 0.098 + off),
                ("copy.2 copy", d0 + 0.0995 + off, d0 + 0.1002 + off),
                ("add.3 add", d0 + 0.1006 + off, d0 + 0.10065 + off)]
        t = t1 + 0.005
    if shift is not None:
        recs = spans.recent(w0 + 9.0)
        old = [r for r in recs if r[0] == S + "/decode"][2]
        spans.ring.records.remove(old)
        spans.record(old[0], old[1] + shift, old[2] + shift, old[3])
    if drop_last:
        marks.pop()
    res.trace = xplane.Trace(devices={0: ops}, spans=marks)
    return ctx, res, w0, off


SCHED = "^(no_span|serve/step(/(?!decode|admit/prefill).*)?)$"


def test_idle_is_laid_to_the_program_span_open_at_each_gap():
    ctx, res, w0, off = traced_run()
    v = program_ring.view(ctx, res)
    assert v.seg0 == pytest.approx(w0 + 9.0)
    fit_off, worst = program_ring.trace_offset(v, res.trace)
    assert fit_off == pytest.approx(off, abs=1e-6)
    assert worst == pytest.approx(-5e-6, abs=1e-6)  # 5 us inside
    acc, decodes = idle_under_span.split(v, res.trace, fit_off)
    assert decodes == 4
    per_step = {p: 1e3 * t / 4 for p, t in acc.items()}
    # each gap is cut at the spans' edges: the 5.95 ms between two
    # steps' ops are 0.1 ms of the old step's end, 4.85 ms of no span,
    # then the new step's 0.1 ms, its upload, dispatch and the first
    # 0.5 ms of its wait for the tokens
    assert per_step == pytest.approx({
        S + "/decode/logits_d2h": 1.5, S: (4 * 0.3 + 3 * 0.1) / 4,
        "no_span": (4 * 0.1 + 3 * 4.85) / 4,
        S + "/decode/upload": 3 * 0.2 / 4,
        S + "/decode/dispatch": 3 * 0.3 / 4,
        S + "/decode/wait_tokens": 3 * 0.5 / 4}, abs=1e-3)
    read = lambda m, **kw: idle_under_span.read(ctx, res, match=m, **kw)
    assert read("^serve/step/decode/logits_d2h$") == pytest.approx(
        1.5, abs=1e-3)
    assert read(SCHED) == pytest.approx(
        per_step[S] + per_step["no_span"], abs=1e-3)
    assert read("^serve/step/decode/upload$") == pytest.approx(
        0.15, abs=1e-3)
    assert read("^serve/step/admit") == 0.0
    assert read(".", per="window") == pytest.approx(
        4 * 1.9 + 3 * 5.95, abs=1e-3)
    # all of the first chip's idle time is laid somewhere
    w = res.trace.window()
    assert sum(acc.values()) == pytest.approx(
        (w[1] - w[0]) - res.trace.busy_seconds())


@pytest.mark.parametrize("path, lands_in_sched", [
    ("no_span", True), ("serve/step", True), ("serve/step/book", True),
    ("serve/step/admit", True), ("serve/step/admit/pages", True),
    ("serve/step/admit/sample", True), ("serve/step/inputs", True),
    ("serve/step/admit/prefill", False), ("serve/step/decode", False),
    ("serve/step/decode/upload", False),
    ("serve/step/decode/logits_d2h", False)])
def test_the_sched_metric_matches_what_is_outside_the_two_programs(
        path, lands_in_sched):
    spec = test_manifest.load(tiny.SUITE, "metrics",
                              "idle_sched_ms.serve.json")
    assert spec["args"]["match"] == SCHED
    assert bool(re.search(SCHED, path)) == lands_in_sched


@pytest.mark.parametrize("kind", ["one_span_5ms_off", "a_decode_missing",
                                  "periodic"])
def test_clocks_that_cannot_be_shown_to_agree_give_nothing(kind):
    if kind == "one_span_5ms_off":
        ctx, res, w0, off = traced_run(shift=0.005)
    elif kind == "a_decode_missing":
        # five harness spans, four program spans
        ctx, res, w0, off = traced_run()
        s, e = res.trace.spans[-1][1:]
        res.trace.spans.append(("decode", s + 0.2, e + 0.2))
    else:
        # three of four harness spans in the trace and all steps alike:
        # two runs of program spans fit, so none is trusted
        ctx, res, w0, off = traced_run(drop_last=True)
    v = program_ring.view(ctx, res)
    assert program_ring.trace_offset(v, res.trace) is None
    assert idle_under_span.read(ctx, res, match=".") is None


# ---------------------------------------------------------------------------
# the three flash kernels by name
# ---------------------------------------------------------------------------

def test_flash_kernels_are_found_by_name_and_sum_to_the_lump():
    from benchmarks.suite.readers import kernel_time, op_time
    ctx, res, _ = fake_run()
    res.facts["profiled_steps"] = 2
    call = " custom-call:tpu_custom_call"
    named = xplane.Trace(spans=[], devices={0: [
        ("ds_flash_fwd.3" + call, 0.000, 0.010),
        ("fusion.7 fusion", 0.010, 0.020),
        ("ds_flash_dkv.1.remat" + call, 0.020, 0.050),
        ("ds_flash_dq.2" + call, 0.050, 0.070),
        ("ds_flash_fwd.4" + call, 0.070, 0.080),
        ("fused_adam.9" + call, 0.080, 0.090)]})
    unnamed = xplane.Trace(spans=[], devices={0: [
        ("attn.135" + call, 0.0, 0.010), ("fusion.7 fusion", 0.010, 0.020)]})
    got = {}
    for short in ("fwd", "dq", "dkv"):
        spec = test_manifest.load(tiny.SUITE, "metrics",
                                  f"flash_{short}_ms.train.json")
        assert spec["reader"] == "kernel_time"
        res.trace = named
        got[short] = kernel_time.read(ctx, res, **spec["args"])
        res.trace = unnamed             # a program that names nothing
        assert kernel_time.read(ctx, res, **spec["args"]) is None
        res.trace = None
        assert kernel_time.read(ctx, res, **spec["args"]) is None
    assert got == pytest.approx({"fwd": 10.0, "dq": 10.0, "dkv": 15.0})
    # the lump finds any Pallas kernel, another one in the step too
    lump = test_manifest.load(tiny.SUITE, "metrics",
                              "flash_attn_ms.train.json")
    res.trace = named
    assert op_time.read(ctx, res, **lump["args"]) == pytest.approx(
        sum(got.values()) + 5.0)


# ---------------------------------------------------------------------------
# the toy rehearsal: the program's numbers against the driver's
# ---------------------------------------------------------------------------

class SpyTracker(serve.Tracker):
    seen = None

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        SpyTracker.seen = self


@pytest.mark.parametrize("cell", SERVE)
def test_program_stamps_equal_the_drivers_outside_view(cell, monkeypatch):
    monkeypatch.setattr(serve, "Tracker", SpyTracker)
    ctx = tiny.context(tiny.serve_workload(cell), jax.devices()[:1],
                       seconds=2.0, trace=True)
    res = serve.run(ctx)
    assert res.correct, res.detail["checks"]
    tracker = SpyTracker.seen
    v = program_ring.view(ctx, res)
    reqs = program_ring.requests(ctx, v)
    due = program_ring.due_times(ctx, v)
    finished = [r for r in tracker.finish if r in tracker.stamps]
    assert len(finished) >= 10 and set(finished) <= set(reqs)
    late = []
    for rid in finished:
        # the same due time, and the driver's stamp of the first token
        # is the program's first_return_t (the driver reads its clock
        # right after step() returns)
        assert due[rid] == pytest.approx(tracker.due[rid], abs=1e-9)
        outside = tracker.stamps[rid][0]
        late.append(outside - reqs[rid]["first_return_t"])
        assert reqs[rid]["first_token_t"] <= reqs[rid]["first_return_t"]
        assert len(reqs[rid]["token_t"]) == len(tracker.stamps[rid])
        # ready + hold is the driver's time to first token
        ready = reqs[rid]["first_token_t"] - due[rid]
        hold = reqs[rid]["first_return_t"] - reqs[rid]["first_token_t"]
        assert ready + hold == pytest.approx(
            outside - tracker.due[rid], abs=1e-3)
        # and the driver's queue wait is not the scheduler's: it ends
        # where the step that admitted the request begins
        assert reqs[rid]["admit_t"] >= tracker.admitted[rid]
    # within 1 ms (a busy test machine may stall one now and then)
    assert min(late) >= 0
    assert program_ring.statistic(late, "median") < 1e-3
    occ = span_stat.read(ctx, res, path=S, stat="mean",
                         attr=["live_rows", "max_batch"], scale=100)
    assert occ == pytest.approx(series_stat.read(
        ctx, res, series="occupancy", stat="mean", scale=100), abs=0.5)
    fill = span_stat.read(ctx, res, path=S, stat="mean",
                          attr=["pages_live", "pages_total"], scale=100)
    assert fill == pytest.approx(series_stat.read(
        ctx, res, series="pool_fill", stat="mean", scale=100), abs=0.5)
    # the engine's own spans lie inside the harness's, call for call:
    # the harness keeps its durations from the start of the ramp until
    # the profiler starts
    run_start = v.w0 - ctx.workload["traffic"]["ramp_s"]
    run_end = v.w1 + ctx.workload["traffic"]["drain_s"] + 60.0
    for name, path in (("decode", S + "/decode"),
                       ("prefill", S + "/admit/prefill")):
        outside = ctx.recorder.series[name]
        inside = [r[2] - r[1] for r in v.ring
                  if r[0] == path and run_start <= r[1] < run_end]
        assert len(inside) >= len(outside) > 10
        over = [o - i for i, o in zip(inside, outside)]
        # a busy test machine may stall the host between the two
        # clocks' readings now and then: all inside, most closely
        assert min(over) >= 0
        assert program_ring.statistic(over, "median") < 1e-3
    # every metric file of the cell's new readers gives a number here,
    # but for the two that need a device trace
    for name in test_manifest.PER_LAYER:
        spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
        if spec["reader"] in ("request_stat", "span_stat"):
            reader = serve.importlib.import_module(
                "benchmarks.suite.readers." + spec["reader"])
            assert reader.read(ctx, res, **spec["args"]) >= 0, name
        elif spec["reader"] == "idle_under_span":
            assert idle_under_span.read(ctx, res, **spec["args"]) is None

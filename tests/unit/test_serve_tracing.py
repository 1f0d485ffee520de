"""The serving path's own stamps and spans (`inference/scheduler.py`,
`inference/engine.py`, `inference/paging.py`) and the one ring they
land in (`telemetry/spans.py`).

The ring is process-wide and outlives a test, so every test here reads
it from a clock reading of its own (``recent(since)``), never "all of
it".
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.scheduler import (
    ContinuousBatchingScheduler,
    Request,
)
from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny
from deepspeed_tpu.telemetry import spans
from deepspeed_tpu.telemetry.flight import FlightRecorder
from deepspeed_tpu.telemetry.session import TelemetrySession
from deepspeed_tpu.telemetry.spans import Span, SpanRing, clock
from tests.unit.test_inference_engine import StubEngine

STAMPS = ("arrival_t", "submit_t", "admit_t", "first_token_t",
          "first_return_t", "finish_t")
DECODE_CHILDREN = {"upload", "dispatch", "wait_tokens"}


def build_engine(session=None, **overrides):
    cfg = gpt2_tiny(n_embd=32, dtype=jnp.float32)
    model = GPT2LMHead(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    inf = {"max_batch": 2, "seq_buckets": (16, 32), "prefill_chunk": 4}
    inf.update(overrides)
    return InferenceEngine(model, params, config=inf, session=session)


def stream(n=6, seed=1, vocab=256):
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}",
                    rng.integers(0, vocab,
                                 int(rng.integers(2, 20))).tolist(),
                    max_new_tokens=int(rng.integers(1, 7)))
            for i in range(n)]


def ring_since(since):
    """The ring's records between ``since`` and now: other tests of
    the process may have put hand-made records at later clock
    readings."""
    now = clock()
    return [r for r in spans.recent(since) if r[2] <= now]


def serve(engine, requests):
    """Run the stream; returns (completions, the ring's records of
    this run, the scheduler)."""
    since = clock()
    sched = ContinuousBatchingScheduler(engine)
    comps = sched.run(requests)
    return comps, ring_since(since), sched


@pytest.fixture(scope="module", params=["dense", "flash"])
def served(request):
    return serve(build_engine(attention_impl=request.param,
                              attention_block_k=8), stream())


# ---------------------------------------------------------------------------
# stamps
# ---------------------------------------------------------------------------

def test_stamps_are_ordered_and_one_per_token(served):
    comps, _, _ = served
    assert len(comps) == 6
    for c in comps:
        ts = [getattr(c, k) for k in STAMPS]
        assert all(t is not None for t in ts), c
        assert ts == sorted(ts), (c.rid, ts)
        assert len(c.token_t) == len(c.tokens) >= 1
        assert c.token_t == sorted(c.token_t)
        assert c.token_t[0] == c.first_token_t
        assert c.token_t[-1] <= c.finish_t


def test_arrival_t_is_the_callers_when_given():
    eng = StubEngine(max_batch=1)
    sched = ContinuousBatchingScheduler(eng)
    t_due = clock() - 0.25
    sched.submit(Request("a", [1, 2], max_new_tokens=3, arrival_t=t_due))
    sched.submit(Request("b", [1, 2], max_new_tokens=3))
    comps = {c.rid: c for c in sched.run()}
    assert comps["a"].arrival_t == t_due < comps["a"].submit_t
    assert comps["b"].arrival_t == comps["b"].submit_t
    # b waited for a's row: its queue wait spans a's whole service
    assert comps["b"].admit_t >= comps["a"].finish_t


def test_first_token_is_out_before_the_rounds_decode():
    """The ``step()`` that admits a request returns with its first
    token, and the round's decode is the next call: the token is not
    held for a decode step it has no part in."""

    class Slow(StubEngine):
        def decode(self, tokens, positions, page_tables):
            time.sleep(0.02)
            return super().decode(tokens, positions, page_tables)

    eng = Slow(max_batch=2)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(Request("a", [1, 2], max_new_tokens=4))
    before = clock()
    assert sched.step()
    after = clock()
    slot = sched.slots[0]
    assert before <= slot.token_t[0] < slot.first_return_t <= after
    assert slot.first_return_t - slot.token_t[0] < 0.02
    assert len(slot.token_t) == len(slot.generated) == 1
    assert eng.decodes == 0 and sched.step_count == 0
    sched.step()                        # the round's decode
    assert len(slot.token_t) == len(slot.generated) == 2
    assert slot.token_t[1] - slot.first_return_t >= 0.02
    assert eng.decodes == 1 == sched.step_count
    comp = sched.run()[0]
    assert comp.first_return_t == slot.first_return_t
    assert comp.first_return_t < comp.finish_t
    assert comp.hold_s < 0.02


def test_request_finished_in_its_first_step_is_stamped_at_return():
    since = clock()
    sched = ContinuousBatchingScheduler(StubEngine(max_batch=2))
    sched.submit(Request("one", [1, 2], max_new_tokens=1))
    sched.step()
    after = clock()
    (comp,) = sched.completions
    assert comp.first_token_t < comp.first_return_t == comp.finish_t
    assert comp.finish_t <= after
    (rec,) = [r for r in ring_since(since) if r[0] == "serve/request"]
    assert rec[2] == comp.finish_t
    assert rec[3]["first_return_t"] == comp.first_return_t


def test_queue_timeout_is_recorded_without_the_later_stamps():
    since = clock()
    sched = ContinuousBatchingScheduler(StubEngine(max_batch=1))
    sched.submit(Request("hog", [1, 2], max_new_tokens=4))
    sched.submit(Request("late", [3], max_new_tokens=4,
                         queue_timeout_s=0.0))
    late = {c.rid: c for c in sched.run()}["late"]
    assert late.finish_reason == "timeout" and late.token_t == []
    assert late.admit_t is None and late.first_return_t is None
    assert late.submit_t <= late.finish_t
    recs = {r[3]["rid"]: r for r in ring_since(since)
            if r[0] == "serve/request"}
    assert recs["late"][3]["finish_reason"] == "timeout"


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def _children(records, parent):
    """Direct children of ``parent`` (a record) among ``records``, in
    the order they opened (a kept one, the ``admit`` that compiled, is
    merged among the others by close time, and another file's hand-made
    record at a far clock reading may stand between them)."""
    path, t0, t1, _ = parent
    depth = path.count("/") + 1
    return sorted((r for r in records
                   if r[0].startswith(path + "/")
                   and r[0].count("/") == depth
                   and t0 <= r[1] and r[2] <= t1), key=lambda r: r[1])


def test_every_step_holds_its_children(served):
    _, records, sched = served
    # the spans: not the compile ledger's and the collector's records,
    # which fall where they fall (tests/unit/test_ring_records.py)
    records = [r for r in records
               if "/jax/" not in r[0] and not r[0].endswith("/gc")]
    steps = [r for r in records if r[0] == "serve/step"]
    # one span a return: one for every request admitted, and one for
    # every decode step or idle tick
    assert sum(r[3]["admitted"] for r in steps) == 6
    assert len(steps) == 6 + sched.step_count
    seen = set()
    last_round = 0
    for step in steps:
        kids = _children(records, step)
        names = [k[0].rsplit("/", 1)[1] for k in kids]
        # ``expire`` once a round, before its first admission, and on a
        # step that is no round's
        expired = step[3].get("round") != last_round
        last_round = step[3].get("round", last_round)
        if step[3]["admitted"]:
            assert names == ["expire"] * expired + ["admit"]
        else:
            assert ("expire" in names) == expired == \
                (names[0] == "expire")
            assert "admit" not in names
        assert sum(k[2] - k[1] for k in kids) <= step[2] - step[1]
        for a, b in zip(kids, kids[1:]):    # one thread: no overlap
            assert a[2] <= b[1]
        seen.update(k[0] for k in kids)
        for k in kids:
            if k[0] == "serve/step/decode":
                names = {g[0].rsplit("/", 1)[1]
                         for g in _children(records, k)}
                assert names == DECODE_CHILDREN
                inner = sum(g[2] - g[1] for g in _children(records, k))
                assert inner <= k[2] - k[1]
    want = {"serve/step/" + n
            for n in ("expire", "admit", "inputs", "decode", "book")}
    assert want <= seen and "serve/step/grow" in seen
    # no span of the serving path lies outside a step (but the
    # scheduler's construction)
    for path, t0, t1, _ in records:
        if path != "serve/request" and not path.startswith("setup/engine"):
            assert path.startswith("serve/step"), path


def test_no_step_copies_the_logits_home(served):
    """Since PR 37 ``engine.decode`` hands the logits back on the device
    and the scheduler drops them: no span of the copy, under any name a
    step could give it, and what is left of the decode call is its
    three children."""
    comps, records, _ = served
    assert sum(len(c.tokens) for c in comps) > len(comps)   # it decoded
    assert not [r[0] for r in records if "logits" in r[0]
                or r[0].endswith("d2h")]
    under = {r[0] for r in records
             if r[0].startswith("serve/step/decode/")
             and "/jax/" not in r[0] and not r[0].endswith("/gc")}
    assert under == {"serve/step/decode/" + n for n in DECODE_CHILDREN}


def test_step_attrs_are_the_steps_counters(served):
    comps, records, sched = served
    steps = [r[3] for r in records if r[0] == "serve/step"]
    # ``step`` counts decode steps: a round's admitting returns carry
    # the number of the decode that ends it
    decoded = [a for a in steps if not a["admitted"]]
    assert [a["step"] for a in decoded] == list(range(sched.step_count))
    assert [a["step"] for a in steps] == sorted(a["step"] for a in steps)
    assert sum(a["tokens"] for a in steps) == \
        sum(len(c.tokens) for c in comps)
    # a round: its admitting returns, then the decode that ends it and
    # says how many prefills it held
    rounds = {}
    for a in steps:
        if "round" in a:
            rounds.setdefault(a["round"], []).append(a)
    assert sorted(rounds) == list(range(1, sched.rounds + 1))
    for members in rounds.values():
        *admits, last = members
        assert admits and all(a["admitted"] == 1 == a["tokens"]
                              and a["batch"] == 0 for a in admits)
        assert last["admitted"] == 0
        assert last["round_prefills"] == len(admits)
        assert last["round_prefill_s"] >= 0.0
        assert {a["step"] for a in members} == {last["step"]}
    for a in decoded:
        if "round" not in a:
            assert a["round_prefills"] == 0 == a["round_prefill_s"]
    for a in steps:
        assert a["max_batch"] == 2
        assert 0 <= a["live_rows"] <= a["batch"] <= 2 or a["batch"] == 0
        assert a["queue_depth"] >= 0
        assert 0 <= a["pages_live"] <= a["pages_resident"] \
            <= a["pages_total"] == sched.engine.n_pages - 1
    # the flash kernel's counters ride the decode span, and only its
    decodes = [r[3] for r in records if r[0] == "serve/step/decode"]
    flash = sched.engine.attention_impl == "flash"
    assert decodes and all(
        (set(a or ()) == {"kv_blocks_live", "kv_blocks_launched",
                          "kv_rows_live", "kv_rows_written"})
        == flash for a in decodes)
    assert steps[-1]["live_rows"] == 0 == steps[-1]["queue_depth"]


def test_rid_joins_admit_prefill_and_request(served):
    comps, records, _ = served
    by_path = {}
    for path, t0, t1, attrs in records:
        if attrs and "rid" in attrs:
            by_path.setdefault(path, {})[attrs["rid"]] = (t0, t1, attrs)
    admits = by_path["serve/step/admit"]
    prefills = by_path["serve/step/admit/prefill"]
    requests = by_path["serve/request"]
    rids = {c.rid for c in comps}
    assert set(admits) == set(prefills) == set(requests) == rids
    for c in comps:
        a0, a1, _ = admits[c.rid]
        p0, p1, pattrs = prefills[c.rid]
        r0, r1, rattrs = requests[c.rid]
        assert a0 <= c.admit_t <= p0 <= p1 <= c.first_token_t <= a1
        assert pattrs["chunks"] == -(-c.prompt_len // 4)
        assert (r0, r1) == (c.arrival_t, c.finish_t)
        assert rattrs["token_t"] is c.token_t       # by reference
        assert rattrs["prompt_len"] == c.prompt_len
        assert rattrs["finish_reason"] == c.finish_reason
        for k in STAMPS:
            assert rattrs[k] == getattr(c, k)


def test_pages_live_is_the_sum_over_live_rows_tables():
    eng = build_engine(page_size=8)
    sched = ContinuousBatchingScheduler(eng)
    for r in stream(n=8, seed=3):
        sched.submit(r)
    seen = 0
    while sched.step():
        walked = sum(len(s.paging.pages) for s in sched.slots
                     if s is not None)
        assert sched.paging.pages_live == walked
        seen = max(seen, walked)
    assert seen > 0 and sched.paging.pages_live == 0
    assert sched.paging.facts()["pages_live"] == 0


def test_speculative_round_spans_draft_and_verify():
    eng = build_engine(speculative={"enabled": True, "k": 3,
                                    "draft_layers": 1})
    comps, records, _ = serve(eng, stream(n=3))
    paths = {r[0] for r in records}
    assert {"serve/step/draft", "serve/step/verify"} <= paths
    assert "serve/step/decode" not in paths
    for c in comps:
        assert len(c.token_t) == len(c.tokens)
        assert c.token_t == sorted(c.token_t)


# ---------------------------------------------------------------------------
# the session's share: events
# ---------------------------------------------------------------------------

def test_request_done_event_per_completion():
    session = TelemetrySession()
    comps, _, _ = serve(build_engine(session=session), stream())
    events = {e["rid"]: e for e in session.events.recent(
        event="request_done")}
    assert set(events) == {c.rid for c in comps}
    for c in comps:
        e = events[c.rid]
        assert e["tokens"] == len(c.tokens)
        assert e["finish_reason"] == c.finish_reason
        assert e["queue_wait_s"] == pytest.approx(c.admit_t - c.submit_t)
        assert e["ttft_s"] == pytest.approx(
            c.first_return_t - c.arrival_t)
        assert e["hold_s"] == pytest.approx(
            c.first_return_t - c.first_token_t)
        assert e["latency_s"] == pytest.approx(c.finish_t - c.arrival_t)
        assert 0 <= e["queue_wait_s"] <= e["ttft_s"] <= e["latency_s"]
        assert len(e["token_gaps_s"]) == max(0, len(c.tokens) - 2)
    # the series nothing read are gone; the one a test reads is not
    reg = session.registry
    assert reg.counter("decode_tokens_total").value > 0
    text = reg.to_prometheus()
    assert "decode_tokens_total" in text
    for gone in ("decode_step_seconds", "decode_batch_occupancy",
                 "decode_queue_depth", "kv_pages_free"):
        assert gone not in text


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def test_ring_drops_the_oldest_and_counts_it():
    ring = SpanRing(maxlen=4)
    for i in range(10):
        ring.append((f"p{i}", float(i), float(i) + 0.5, None))
    assert ring.dropped == 6
    assert [r[0] for r in ring.recent()] == ["p6", "p7", "p8", "p9"]
    assert [r[0] for r in ring.recent(since=8.5)] == ["p8", "p9"]
    assert spans.ring.records.maxlen == spans.RING_SIZE == 65536


def test_span_without_a_session_lands_in_the_ring():
    since = clock()
    attrs = {"rid": "x"}
    with Span("outer", attrs=attrs):
        assert spans.enclosing_attr("rid") == "x"
        with Span("inner") as inner:
            assert spans.enclosing_attr("rid") == "x"
            assert spans.enclosing_attr("nope", 7) == 7
            assert spans.live_phase_paths()[
                threading.get_ident()] == "outer/inner"
        attrs["late"] = 1       # filled in before the scope closes
    recs = ring_since(since)
    assert [r[0] for r in recs] == ["outer/inner", "outer"]
    (ipath, i0, i1, iattrs), (opath, o0, o1, oattrs) = recs
    assert o0 <= i0 <= i1 <= o1 and iattrs is None
    assert oattrs is attrs and oattrs["late"] == 1
    assert inner.duration_s == i1 - i0
    spans.record("by/hand", 1.0, 2.0, {"k": 1})
    assert spans.recent()[-1] == ("by/hand", 1.0, 2.0, {"k": 1})


def test_span_records_when_its_body_raises():
    since = clock()
    with pytest.raises(KeyError):
        with Span("boom"):
            raise KeyError("x")
    assert [r[0] for r in ring_since(since)] == ["boom"]
    assert "boom" not in spans.live_phase_paths().values()


def test_ring_recording_span_costs_under_5us():
    """A ``Span()`` with no session: stack, annotation, two clock
    reads, one ring append. The best of five batches, so that a busy
    test machine does not decide it."""
    n = 20000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with Span("cost"):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 5e-6, f"a ring-recording span costs {best * 1e6:.2f}us"


def test_epoch_offset_maps_the_clock_onto_unix_time():
    off = spans.epoch_offset()
    assert abs((clock() + off) - time.time()) < 1e-3
    assert abs(spans.epoch_offset() - off) < 1e-3
    assert clock is time.perf_counter


def test_flight_recorder_reads_the_ring(tmp_path):
    rec = FlightRecorder(tmp_path, history=8)
    assert not hasattr(rec, "_phases")
    session = TelemetrySession(flight=rec)
    with Span("no_session"):
        pass
    with session.span("dispatch"):
        with session.span("compile"):
            snap = rec.snapshot("probe")
    def spans_of(snapshot):
        # a collection may fall anywhere and leaves a record of its own
        return [p for p in snapshot["phase_log"]
                if not p["path"].endswith("/gc")]

    log = [(p["kind"], p["path"]) for p in spans_of(snap)]
    # closed: enter and exit; open: enter only
    assert log == [("enter", "no_session"), ("exit", "no_session"),
                   ("enter", "dispatch"), ("enter", "dispatch/compile")]
    assert abs(spans_of(snap)[-1]["t"] - time.time()) < 5.0
    assert spans_of(snap)[1]["duration_s"] >= 0
    log = spans_of(rec.snapshot("after"))
    assert [(p["kind"], p["path"]) for p in log][2:] == [
        ("enter", "dispatch"), ("enter", "dispatch/compile"),
        ("exit", "dispatch/compile"), ("exit", "dispatch")]
    for _ in range(20):
        with Span("many"):
            pass
    assert len(rec.snapshot("bounded")["phase_log"]) == 8

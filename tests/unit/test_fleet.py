"""Serving fleet resilience pins (`deepspeed_tpu/inference/router.py`,
`fleet.py`, plus the scheduler's robustness knobs — ISSUE 17).

Everything here runs on the no-jax ``StubEngine`` behind
:class:`ThreadReplica` (or scripted replica fakes for the router's
bookkeeping), so the whole file stays in the tier-1 fast lane; the real
subprocess/SIGKILL soak lives in ``tests/model/test_fleet_soak.py``.

Pinned contracts:

- scheduler: ``deadline_s``/``queue_timeout_s`` finish with the typed
  ``timeout`` reason (queued requests never take a row; live rows keep
  their partial tokens), ``run(max_steps)`` exhaustion finishes
  everything as ``incomplete`` with a ``scheduler_incomplete`` warning
  event.
- router: exactly-once completion over at-least-once execution —
  replica death drains in-flight requests and redispatches them with
  ``redispatched``/``restarts`` stamped; the redispatch budget turns
  into typed ``aborted`` completions (or :class:`RequestAbortedError`);
  shed/defer backpressure; duplicate replica reports are dropped.
- thread replicas: kill/preempt/hang map onto the supervisor's
  ``crash``/``preemption``/``hang`` vocabulary via ``classify_exit``.
"""

import threading
import time

import numpy as np
import pytest

from deepspeed_tpu.inference.fleet import (
    ThreadReplica,
    completion_dict,
    request_dict,
)
from deepspeed_tpu.inference.router import (
    FleetRouter,
    RequestAbortedError,
)
from deepspeed_tpu.inference.scheduler import (
    ContinuousBatchingScheduler,
    Request,
)
from deepspeed_tpu.runtime.supervisor.state import (
    CAUSE_CRASH,
    CAUSE_HANG,
    CAUSE_PREEMPTION,
)
from deepspeed_tpu.telemetry.session import TelemetrySession
from tests.unit.test_inference_engine import StubEngine


# ---------------------------------------------------------------------------
# scheduler robustness: deadlines, queue timeouts, max_steps exhaustion
# ---------------------------------------------------------------------------

class _SlowEngine(StubEngine):
    """Stub whose decode burns wall clock, so deadlines expire
    mid-generation without the test sleeping."""

    def __init__(self, decode_sleep_s, **kw):
        super().__init__(**kw)
        self.decode_sleep_s = decode_sleep_s

    def decode(self, tokens, positions, page_tables):
        time.sleep(self.decode_sleep_s)
        return super().decode(tokens, positions, page_tables)


class TestSchedulerRobustness:
    def test_queue_timeout_finishes_without_a_row(self):
        session = TelemetrySession()
        eng = StubEngine(max_batch=1, session=session)
        sched = ContinuousBatchingScheduler(eng)
        sched.submit(Request("hog", [1, 2], max_new_tokens=6))
        sched.submit(Request("late", [3], max_new_tokens=4,
                             queue_timeout_s=0.0))
        comps = {c.rid: c for c in sched.run()}
        assert comps["hog"].finish_reason == "max_new_tokens"
        late = comps["late"]
        assert late.finish_reason == "timeout"
        assert late.slot == -1 and late.tokens == []
        evts = session.events.recent(event="request_timeout")
        assert evts and evts[0]["where"] == "queue"

    def test_deadline_expires_mid_decode_keeps_partial_tokens(self):
        session = TelemetrySession()
        eng = _SlowEngine(0.05, max_batch=1, session=session)
        sched = ContinuousBatchingScheduler(eng)
        comps = sched.run([Request("d", [1, 2], max_new_tokens=50,
                                   deadline_s=0.001)])
        assert comps[0].finish_reason == "timeout"
        assert comps[0].slot == 0           # it held a row
        assert comps[0].tokens              # partial generation kept
        evts = session.events.recent(event="request_timeout")
        assert evts and evts[-1]["where"] == "decode"

    def test_max_steps_exhaustion_is_typed_incomplete(self):
        session = TelemetrySession()
        eng = StubEngine(max_batch=1, session=session)
        sched = ContinuousBatchingScheduler(eng)
        comps = sched.run([Request("live", [1, 2], max_new_tokens=50),
                           Request("queued", [3], max_new_tokens=50)],
                          max_steps=3)
        by = {c.rid: c for c in comps}
        assert by["live"].finish_reason == "incomplete"
        assert by["live"].tokens            # generated-so-far kept
        assert by["queued"].finish_reason == "incomplete"
        assert by["queued"].slot == -1 and by["queued"].tokens == []
        evts = session.events.recent(event="scheduler_incomplete")
        assert len(evts) == 1
        assert evts[0]["level"] == "warning"
        assert evts[0]["live_rows"] == 1 and evts[0]["queued"] == 1


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

class TestWireFormat:
    def test_request_dict_excludes_submit_t(self):
        r = Request("a", [1, 2], max_new_tokens=3, deadline_s=1.0,
                    redispatched=2, restarts=2)
        r.submit_t = 123.0
        d = request_dict(r)
        assert "submit_t" not in d
        assert d["rid"] == "a" and d["redispatched"] == 2
        assert d["deadline_s"] == 1.0

    def test_completion_dict_round_trips_scheduler_output(self):
        comps = ContinuousBatchingScheduler(StubEngine()).run(
            [Request("a", [1, 2], max_new_tokens=2)])
        d = completion_dict(comps[0])
        assert d["rid"] == "a"
        assert d["finish_reason"] == "max_new_tokens"
        assert d["redispatched"] == 0 and d["restarts"] == 0


# ---------------------------------------------------------------------------
# scripted replicas: deterministic router bookkeeping
# ---------------------------------------------------------------------------

class _InstantReplica:
    """Completes everything on the next poll."""

    def __init__(self, index):
        self.index = index
        self._pending = []
        self.stopped = False

    def submit(self, req):
        self._pending.append(req)

    def poll(self):
        out = [dict(completion_dict_for(req), slot=0)
               for req in self._pending]
        self._pending = []
        return out

    def check(self, now=None):
        return None

    def stop(self, timeout=None):
        self.stopped = True
        return {"compile_counts": {"prefill": 1, "decode": 1},
                "steps": 1, "completed": 1}

    def kill(self):
        pass

    def reap(self):
        pass


def completion_dict_for(req, reason="max_new_tokens"):
    return {"rid": req.rid, "prompt_len": len(req.prompt),
            "tokens": [7] * req.max_new_tokens, "finish_reason": reason,
            "bucket": 16, "slot": 0, "steps": req.max_new_tokens,
            "prefix_hit": False, "resumed": False, "prefill_chunks": 0,
            "prefill_chunks_skipped": 0,
            "redispatched": req.redispatched, "restarts": req.restarts}


class _HoldingReplica(_InstantReplica):
    """Accepts work, never completes it; optionally dies (with
    ``cause``) on the first health check after receiving work."""

    def __init__(self, index, die_with=None):
        super().__init__(index)
        self.die_with = die_with

    def poll(self):
        return []

    def check(self, now=None):
        if self.die_with is not None and self._pending:
            return self.die_with
        return None


def _reqs(n, **kw):
    return [Request(f"r{i}", [1, 2, 3], max_new_tokens=2, **kw)
            for i in range(n)]


class TestRouterBookkeeping:
    def test_happy_path_exactly_once(self):
        session = TelemetrySession()
        router = FleetRouter([_InstantReplica(0), _InstantReplica(1)],
                             session=session)
        fr = router.run(_reqs(5), timeout_s=10.0)
        assert fr.ok and len(fr.completions) == 5
        assert len({c["rid"] for c in fr.completions}) == 5
        assert fr.replicas_dead == 0 and fr.redispatched_total == 0
        assert len(fr.stats) == 2
        assert fr.latency_s["p99"] is not None
        done = session.events.recent(event="fleet_done")
        assert done and done[-1]["ok"]

    def test_death_drains_and_redispatches(self):
        session = TelemetrySession()
        router = FleetRouter(
            [_HoldingReplica(0, die_with=CAUSE_CRASH),
             _InstantReplica(1)],
            session=session, backoff_base_s=0.0)
        fr = router.run(_reqs(4), timeout_s=10.0)
        assert fr.ok and len(fr.completions) == 4
        assert fr.replicas_dead == 1
        assert router.dead == {0: CAUSE_CRASH}
        # replica 0 held half the fleet's requests; every one finished
        # elsewhere with the retry stamped on the completion
        redone = [c for c in fr.completions if c["redispatched"]]
        assert len(redone) == 2 == fr.redispatched_total
        assert all(c["restarts"] == 1 and c["replica"] == 1
                   for c in redone)
        assert session.events.recent(event="replica_dead")
        assert len(session.events.recent(event="fleet_redispatch")) == 2
        rec = session.events.recent(event="replica_recovered")
        assert rec and rec[-1]["time_to_recover_s"] >= 0.0

    def test_redispatch_budget_becomes_typed_abort(self):
        session = TelemetrySession()
        router = FleetRouter(
            [_HoldingReplica(0, die_with=CAUSE_CRASH),
             _HoldingReplica(1, die_with=CAUSE_CRASH)],
            session=session, max_redispatch=1, backoff_base_s=0.0)
        fr = router.run(_reqs(1), timeout_s=10.0)
        assert not fr.ok
        assert fr.completions[0]["finish_reason"] == "aborted"
        assert fr.aborted == 1 and fr.replicas_dead == 2
        evts = session.events.recent(event="request_aborted")
        assert evts and evts[0]["rid"] == "r0"

    def test_raise_on_abort(self):
        router = FleetRouter(
            [_HoldingReplica(0, die_with=CAUSE_CRASH)],
            max_redispatch=0, raise_on_abort=True, backoff_base_s=0.0)
        with pytest.raises(RequestAbortedError) as exc:
            router.run(_reqs(1), timeout_s=10.0)
        assert exc.value.rid == "r0"

    def test_shed_at_max_pending(self):
        session = TelemetrySession()
        router = FleetRouter([_InstantReplica(0)], session=session,
                             max_pending=1)
        reqs = _reqs(3)
        assert router.submit(reqs[0]) is True
        assert router.submit(reqs[1]) is False      # shed
        fr = router.run([reqs[2]], timeout_s=10.0)  # shed too
        assert fr.shed == 2
        shed = [c for c in fr.completions
                if c["finish_reason"] == "shed"]
        assert {c["rid"] for c in shed} == {"r1", "r2"}
        assert session.events.recent(event="fleet_shed")

    def test_duplicate_rid_rejected(self):
        router = FleetRouter([_InstantReplica(0)])
        router.submit(Request("a", [1], max_new_tokens=1))
        with pytest.raises(ValueError, match="duplicate rid"):
            router.submit(Request("a", [1], max_new_tokens=1))

    def test_defer_and_router_queue_timeout(self):
        session = TelemetrySession()
        router = FleetRouter([_HoldingReplica(0)], session=session,
                             max_queue_depth=1)
        reqs = _reqs(2, queue_timeout_s=0.05)
        fr = router.run(reqs, timeout_s=0.4)
        by = fr.by_rid()
        # r0 took the only queue-depth slot and was held forever
        # (fleet-level wall timeout truncates it); r1 could never
        # dispatch and timed out on the router's own queue.
        assert by["r1"]["finish_reason"] == "timeout"
        assert by["r0"]["finish_reason"] == "incomplete"
        assert fr.timeouts == 1 and fr.defers >= 1
        assert session.events.recent(event="fleet_defer")
        assert session.events.recent(event="request_timeout")
        assert session.events.recent(event="scheduler_incomplete")

    def test_duplicate_replica_report_dropped(self):
        class _DupReplica(_InstantReplica):
            def poll(self):
                out = super().poll()
                return out + [dict(c) for c in out]   # report twice

        router = FleetRouter([_DupReplica(0)])
        fr = router.run(_reqs(2), timeout_s=10.0)
        assert len(fr.completions) == 2
        assert len({c["rid"] for c in fr.completions}) == 2


# ---------------------------------------------------------------------------
# thread replicas: kill / preempt / hang / crash semantics
# ---------------------------------------------------------------------------

def _stub_factory(**kw):
    def factory():
        return StubEngine(**kw)
    return factory


class TestThreadReplica:
    def test_serves_and_reports_stats(self):
        rep = ThreadReplica(0, _stub_factory(max_batch=2)).start()
        rep.submit(Request("a", [1, 2], max_new_tokens=2))
        deadline = time.monotonic() + 5.0
        out = []
        while not out and time.monotonic() < deadline:
            out = rep.poll()
            time.sleep(0.001)
        assert out and out[0]["rid"] == "a"
        assert rep.check() is None
        stats = rep.stop()
        assert stats["completed"] == 1 and stats["steps"] >= 1

    def test_crash_classification(self):
        def exploding():
            eng = StubEngine()

            def boom(tokens, positions, page_tables):
                raise RuntimeError("injected decode fault")
            eng.decode = boom
            return eng

        rep = ThreadReplica(0, exploding).start()
        rep.submit(Request("a", [1, 2], max_new_tokens=4))
        deadline = time.monotonic() + 5.0
        while rep.check() is None and time.monotonic() < deadline:
            time.sleep(0.001)
        assert rep.check() == CAUSE_CRASH

    def test_kill_classification(self):
        rep = ThreadReplica(0, _stub_factory()).start()
        rep.kill()
        deadline = time.monotonic() + 5.0
        while rep.check() is None and time.monotonic() < deadline:
            time.sleep(0.001)
        assert rep.check() == CAUSE_CRASH

    def test_preempt_classification(self):
        rep = ThreadReplica(0, _stub_factory()).start()
        rep.preempt()
        deadline = time.monotonic() + 5.0
        while rep.check() is None and time.monotonic() < deadline:
            time.sleep(0.001)
        assert rep.check() == CAUSE_PREEMPTION

    def test_hang_detection(self):
        gate = threading.Event()

        def gated():
            eng = StubEngine()
            real = eng.decode

            def stuck(tokens, positions, page_tables):
                gate.wait(timeout=30.0)
                return real(tokens, positions, page_tables)
            eng.decode = stuck
            return eng

        rep = ThreadReplica(0, gated, step_timeout_s=0.05).start()
        rep.submit(Request("a", [1, 2], max_new_tokens=2))
        try:
            deadline = time.monotonic() + 5.0
            cause = None
            while cause is None and time.monotonic() < deadline:
                cause = rep.check()
                time.sleep(0.005)
            assert cause == CAUSE_HANG
        finally:
            gate.set()          # release the daemon thread

    def test_fleet_of_thread_replicas_survives_a_kill(self):
        session = TelemetrySession()
        reps = [ThreadReplica(i, _stub_factory(max_batch=2)).start()
                for i in range(2)]
        router = FleetRouter(reps, session=session, backoff_base_s=0.0,
                             max_queue_depth=2)
        # kill replica 0 shortly after dispatch starts
        killer = threading.Timer(0.05, reps[0].kill)
        killer.start()
        try:
            fr = router.run(_reqs(6), timeout_s=30.0)
        finally:
            killer.cancel()
        assert len(fr.completions) == 6
        assert all(c["finish_reason"] == "max_new_tokens"
                   for c in fr.completions)
        assert fr.ok
        # token streams are deterministic: every request decoded the
        # same StubEngine sequence regardless of which replica ran it
        tokens = {tuple(c["tokens"]) for c in fr.completions}
        assert len(tokens) == 1
        if fr.replicas_dead:
            assert router.dead.get(0) == CAUSE_CRASH
            assert fr.redispatched_total >= 1


# ---------------------------------------------------------------------------
# numpy import guard: the file must not require jax at collection
# ---------------------------------------------------------------------------

def test_module_surface_is_jax_free():
    """router.py and fleet.py must import without jax so thread-backend
    unit tests (and the router itself) stay in the fast lane."""
    import deepspeed_tpu.inference.fleet as fleet
    import deepspeed_tpu.inference.router as router
    for mod in (fleet, router):
        assert "jax" not in getattr(mod, "__dict__", {})
    assert isinstance(np.zeros(1), np.ndarray)

"""Disaggregated prefill/decode serving (`inference/disagg.py` +
`inference/router.py` DisaggRouter + the satellite surfaces).

The contract under test, layer by layer:

- Handoff stores: `park`/`install`/`parked`/`peek`/`drop` over both
  transports. DeviceHandoffStore is consume-once and never parked (a
  dead decode worker must re-prefill); FileHandoffStore is durable,
  CRC-verified at install, and deletes a rotted snapshot before
  raising.
- Tier pins: a prefill-tier engine hard-raises on `decode`, a
  decode-tier engine hard-raises on `prefill`, and after a full stream
  each tier's jit cache holds exactly ONE program.
- Token parity: the disaggregated stream (DisaggCoordinator and the
  threaded DisaggRouter) is greedy-token-identical to the colocated
  single-engine oracle — the handoff is admission metadata, never
  math. f32+dense runs in the fast lane; the other {dtype, impl}
  combos are slow-marked.
- Failure typing: geometry mismatch -> `handoff_error`, missing
  snapshot -> `handoff_missing`, CRC rot -> cold re-prefill with the
  tokens still oracle-identical.
- Tier-aware drain: a dead decode worker's requests resume from a
  parked handoff (no re-prefill) or fall back to the prefill queue,
  bounded by the redispatch budget — exercised on scripted fakes so
  the branch logic is deterministic.
- Satellites: config validation, `rule_decode` tier-pin/geometry
  findings, `ds_tpu_tune --serving` chunk/batch dimensions with typed
  build rejections, and the metrics CLI's per-tier summary block.
"""

import io

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.disagg import (
    META_FIELDS, DecodeWorker, DeviceHandoffStore, DisaggCoordinator,
    FileHandoffStore, HandoffMeta, PrefillWorker)
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.paging import HostPageCorruptError
from deepspeed_tpu.inference.router import DisaggRouter
from deepspeed_tpu.inference.scheduler import (
    ContinuousBatchingScheduler, Request)
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from deepspeed_tpu.runtime.resilience import fault_injection

_slow = pytest.mark.slow

PREFILL_PIN = {"prefill": 1, "decode": 0}
DECODE_PIN = {"prefill": 0, "decode": 1}

# the shared request stream: prompt lengths straddle both seq buckets
# and the page boundary, so handoffs carry 1..3 pages
_rng = np.random.default_rng(7)
PROTOS = [(f"r{i}", _rng.integers(0, 64, 3 + 4 * i).tolist(), 4)
          for i in range(4)]


def _requests():
    return [Request(rid, list(prompt), max_new_tokens=m)
            for rid, prompt, m in PROTOS]


def _build(kvdt=None, impl="dense", **knobs):
    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=2, n_head=4, dtype=jnp.float32)
    model = GPT2LMHead(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(model, params, config={
        "max_batch": 2, "seq_buckets": (16, 32), "prefill_chunk": 4,
        "kv_cache_dtype": kvdt, "attention_impl": impl,
        "attention_block_k": 8, **knobs})
    return eng


def _oracle(kvdt=None, impl="dense"):
    """Colocated single-engine greedy stream: {rid: tokens}."""
    sched = ContinuousBatchingScheduler(_build(kvdt, impl))
    for r in _requests():
        sched.submit(r)
    sched.run()
    return {c.rid: list(c.tokens) for c in sched.completions}


@pytest.fixture(scope="module")
def oracle_f32():
    return _oracle()


# ---------------------------------------------------------------------------
# HandoffMeta + store contract
# ---------------------------------------------------------------------------

def test_handoff_meta_roundtrip():
    meta = HandoffMeta(rid=17, prompt_len=12.0, first_token=5,
                       next_pos=12, page_size=8, pages_per_row=4,
                       n_pages=2, parked=1)
    d = meta.to_dict()
    assert set(d) == set(META_FIELDS)
    back = HandoffMeta.from_dict(d)
    # constructor coerces: rid -> str, counts -> int, parked -> bool
    assert back.rid == "17" and back.prompt_len == 12
    assert back.parked is True
    assert back.to_dict() == d


class _PoolEngine:
    """Just enough engine for the store contract: a page-pool pytree
    plus gather/scatter over page ids (same structure contract the
    real engine's host tier exposes)."""

    def __init__(self, n_pages=6, width=3, fill=0.0):
        self.cache = {
            "k": np.full((n_pages, width), fill, np.float32),
            "v": np.full((n_pages, width), fill + 1.0, np.float32)}

    def gather_pages(self, page_ids):
        ids = list(page_ids)
        return {k: np.array(v[ids]) for k, v in self.cache.items()}

    gather_pages_device = gather_pages

    def scatter_pages(self, page_ids, vals):
        ids = list(page_ids)
        for k in self.cache:
            self.cache[k][ids] = np.asarray(vals[k])


def _meta(rid="a", n_pages=2):
    return HandoffMeta(rid=rid, prompt_len=7, first_token=3, next_pos=7,
                       page_size=8, pages_per_row=4, n_pages=n_pages,
                       parked=False)


def test_device_store_consume_once():
    src = _PoolEngine(fill=2.0)
    dst = _PoolEngine(fill=0.0)
    store = DeviceHandoffStore()
    assert not store.parked("a")
    nbytes = store.park("a", src, [1, 2], _meta())
    # 2 leaves x 2 pages x 3 f32
    assert nbytes == 2 * 2 * 3 * 4
    assert len(store) == 1
    assert store.parked("a") is False       # device arrays never park
    assert store.peek("a").rid == "a"
    meta = store.install("a", dst, [3, 4])
    assert meta.first_token == 3
    np.testing.assert_array_equal(dst.cache["k"][3:5],
                                  src.cache["k"][1:3])
    np.testing.assert_array_equal(dst.cache["v"][3:5],
                                  src.cache["v"][1:3])
    # consume-once: the snapshot left with the install
    assert store.peek("a") is None
    with pytest.raises(KeyError):
        store.install("a", dst, [3, 4])
    store.drop("a")                          # idempotent no-op


def test_file_store_durable_roundtrip(tmp_path):
    src = _PoolEngine(fill=5.0)
    dst = _PoolEngine(fill=0.0)
    store = FileHandoffStore(str(tmp_path))
    assert store.durable
    store.park("b", src, [0, 3], _meta("b"))
    assert store.parked("b")
    assert store.peek("b").prompt_len == 7
    meta = store.install("b", dst, [1, 2])
    assert meta.rid == "b"
    np.testing.assert_array_equal(dst.cache["k"][[1, 2]],
                                  src.cache["k"][[0, 3]])
    # durable: RETAINED after install (a dead decode worker resumes)
    assert store.parked("b")
    store.install("b", dst, [1, 2])
    store.drop("b")
    assert not store.parked("b")
    with pytest.raises(KeyError):
        store.install("b", dst, [1, 2])


def test_file_store_crc_rot_detected_and_deleted(tmp_path):
    fault_injection.clear_faults()
    src = _PoolEngine(fill=1.0)
    dst = _PoolEngine(fill=0.0)
    store = FileHandoffStore(str(tmp_path))
    try:
        fault_injection.inject_page_corruption(session_id="rot",
                                               times=1)
        store.park("rot", src, [1, 2], _meta("rot"))
        assert store.parked("rot")
        with pytest.raises(HostPageCorruptError):
            store.install("rot", dst, [3, 4])
        # rotted bytes help nobody: the snapshot is gone
        assert not store.parked("rot")
        # the destination pool was never scattered into
        np.testing.assert_array_equal(
            dst.cache["k"], _PoolEngine(fill=0.0).cache["k"])
    finally:
        fault_injection.clear_faults()


# ---------------------------------------------------------------------------
# tier pins: each engine runs exactly one of the two programs
# ---------------------------------------------------------------------------

def test_tier_engine_pins_other_program_off():
    pre = _build(tier="prefill")
    with pytest.raises(RuntimeError, match="decode program is pinned"):
        pre.decode(np.zeros(2, np.int32), np.zeros(2, np.int32),
                   page_tables=np.zeros((2, 4), np.int32))
    dec = _build(tier="decode")
    with pytest.raises(RuntimeError, match="prefill program is pinned"):
        dec.prefill(0, [1, 2, 3],
                    page_table=np.zeros(4, np.int32))
    # the guard fires before any trace: both caches stay empty
    assert pre.compile_counts() == {"prefill": 0, "decode": 0}
    assert dec.compile_counts() == {"prefill": 0, "decode": 0}


def test_a_tier_is_refused_a_ring_layout_like_any_engine():
    with pytest.raises(ValueError, match="only KV layout since PR 28"):
        _build(tier="prefill", kv_layout="ring")


def test_workers_reject_wrong_tier_engine():
    store = DeviceHandoffStore()
    with pytest.raises(ValueError, match="prefill-tier"):
        PrefillWorker(_build(tier="decode"), store)
    with pytest.raises(ValueError, match="decode-tier"):
        DecodeWorker(_build(tier="prefill"), store)


# ---------------------------------------------------------------------------
# coordinator parity vs the colocated oracle
# ---------------------------------------------------------------------------

def _run_coordinator(kvdt=None, impl="dense", store=None):
    pre = _build(kvdt, impl, tier="prefill")
    dec = _build(kvdt, impl, tier="decode", max_batch=3)
    coord = DisaggCoordinator([pre], [dec], store=store)
    comps = coord.run(_requests())
    return coord, comps


PARITY_CASES = [
    pytest.param(None, "dense", id="dense-f32"),
    pytest.param("int8", "dense", id="dense-int8", marks=_slow),
    pytest.param(None, "flash", id="flash-f32", marks=_slow),
    pytest.param("int8", "flash", id="flash-int8", marks=_slow),
]


@pytest.mark.parametrize("kvdt,impl", PARITY_CASES)
def test_disagg_stream_matches_colocated_oracle(kvdt, impl, oracle_f32):
    oracle = oracle_f32 if (kvdt, impl) == (None, "dense") \
        else _oracle(kvdt, impl)
    coord, comps = _run_coordinator(kvdt, impl)
    assert {c["rid"]: c["tokens"] for c in comps} == oracle
    # every request crossed the handoff and finished decode-side
    assert all(c["tier"] == "decode" for c in comps)
    stats = coord.tier_stats()
    assert stats["handoffs"] == len(PROTOS)
    assert stats["handoff_bytes_per_session"] > 0
    assert stats["reprefills"] == 0
    # the 2-program contract: one compiled program per tier, total 2
    assert stats["prefill"]["compile_counts"] == PREFILL_PIN
    assert stats["decode"]["compile_counts"] == DECODE_PIN


@_slow
def test_disagg_tiers_scale_independently(oracle_f32):
    """2 prefill workers against 2 decode workers (different
    max_batch per tier): same tokens, and EVERY worker still pins
    exactly its own single program."""
    pres = [_build(tier="prefill") for _ in range(2)]
    decs = [_build(tier="decode", max_batch=3) for _ in range(2)]
    coord = DisaggCoordinator(pres, decs)
    comps = coord.run(_requests())
    assert {c["rid"]: c["tokens"] for c in comps} == oracle_f32
    stats = coord.tier_stats()
    for w in stats["prefill"]["per_worker"]:
        assert w["compile_counts"] == PREFILL_PIN
    for w in stats["decode"]["per_worker"]:
        assert w["compile_counts"] == DECODE_PIN


def test_corrupt_handoff_cold_reprefills(tmp_path, oracle_f32):
    """A CRC-rotted file handoff surfaces as `handoff_corrupt`; the
    coordinator recycles the request through a cold re-prefill and the
    final tokens are still oracle-identical (never serve from a rotten
    page)."""
    fault_injection.clear_faults()
    try:
        fault_injection.inject_page_corruption(session_id="r1",
                                               times=1)
        coord, comps = _run_coordinator(
            store=FileHandoffStore(str(tmp_path)))
        assert coord.reprefills == 1
        assert {c["rid"]: c["tokens"] for c in comps} == oracle_f32
        by_rid = {c["rid"]: c for c in comps}
        assert by_rid["r1"]["restarts"] == 1
        stats = coord.tier_stats()
        assert stats["prefill"]["compile_counts"] == PREFILL_PIN
        assert stats["decode"]["compile_counts"] == DECODE_PIN
    finally:
        fault_injection.clear_faults()


def test_prefill_tier_completes_one_token_requests():
    """A request whose first token finishes it never travels: it
    completes on the prefill tier with no handoff parked."""
    store = DeviceHandoffStore()
    worker = PrefillWorker(_build(tier="prefill"), store)
    worker.submit(Request("one", [1, 2, 3], max_new_tokens=1))
    worker.step()
    outs = worker.drain_outputs()
    assert len(outs) == 1
    comp = outs[0]
    assert comp["kind"] == "completion" and comp["tier"] == "prefill"
    assert comp["finish_reason"] == "max_new_tokens"
    assert len(comp["tokens"]) == 1
    assert len(store) == 0 and worker.handoffs == 0


def test_prefill_worker_rejects_malformed_requests():
    worker = PrefillWorker(_build(tier="prefill"), DeviceHandoffStore())
    with pytest.raises(ValueError, match="empty prompt"):
        worker.submit(Request("e", [], max_new_tokens=2))
    with pytest.raises(ValueError, match="does not fit"):
        worker.submit(Request("l", list(range(40)), max_new_tokens=2))


# ---------------------------------------------------------------------------
# decode worker failure typing
# ---------------------------------------------------------------------------

def test_decode_worker_types_handoff_failures():
    eng = _build(tier="decode")
    worker = DecodeWorker(eng, DeviceHandoffStore())
    with pytest.raises(ValueError, match="only accepts handoffs"):
        worker.submit(Request("no-meta", [1, 2], max_new_tokens=2))

    # geometry mismatch: a config bug re-prefill can't fix
    bad = HandoffMeta(rid="geo", prompt_len=4, first_token=1,
                      next_pos=4, page_size=eng.page_size * 2,
                      pages_per_row=eng.pages_per_row, n_pages=1,
                      parked=False)
    worker.submit(Request("geo", [1, 2, 3, 4], max_new_tokens=2), bad)
    worker.step()
    outs = worker.drain_outputs()
    assert [o["kind"] for o in outs] == ["handoff_error"]
    assert "geometry mismatch" in outs[0]["error"]

    # missing snapshot (consumed with a dead worker): re-prefillable
    gone = HandoffMeta(rid="gone", prompt_len=4, first_token=1,
                       next_pos=4, page_size=eng.page_size,
                       pages_per_row=eng.pages_per_row, n_pages=1,
                       parked=False)
    worker.submit(Request("gone", [1, 2, 3, 4], max_new_tokens=2), gone)
    worker.step()
    outs = worker.drain_outputs()
    assert [o["kind"] for o in outs] == ["handoff_missing"]
    assert worker.installed == 0


# ---------------------------------------------------------------------------
# tier-aware drain: scripted fakes, deterministic branches
# ---------------------------------------------------------------------------

class _FakeReplica:
    def __init__(self, index):
        self.index = index
        self.submitted = []

    def submit(self, request, meta=None):
        self.submitted.append((request, meta))

    def poll(self):
        return []

    def check(self, now):
        return None

    def stop(self):
        return None

    def kill(self):
        pass

    def reap(self):
        pass


class _FakeStore:
    durable = True

    def __init__(self, parked_rids=()):
        self._parked = set(parked_rids)
        self.dropped = []

    def parked(self, rid):
        return rid in self._parked

    def drop(self, rid):
        self.dropped.append(rid)
        self._parked.discard(rid)


def _fake_router(store, **kwargs):
    pre = [_FakeReplica(0)]
    dec = [_FakeReplica(1), _FakeReplica(2)]
    return DisaggRouter(pre, dec, store, **kwargs), pre, dec


def test_drain_dead_decode_resumes_from_park():
    store = _FakeStore(parked_rids={"a"})
    router, _, _ = _fake_router(store)
    req = Request("a", [1, 2, 3], max_new_tokens=4)
    router._metas["a"] = {"page_size": 8}
    router.assigned[1]["a"] = req
    router._drain(1, now=100.0)
    # durable handoff survived the worker: resume, don't re-prefill
    assert router.resumed_from_park == 1
    assert len(router.decode_queue) == 1
    item = router.decode_queue[0]
    assert item.meta == {"page_size": 8}
    assert item.not_before > 100.0          # backoff gate
    assert len(router.queue) == 0
    assert store.dropped == []
    assert req.redispatched == 1 and req.restarts == 1


def test_drain_dead_decode_unparked_reprefills():
    store = _FakeStore()                    # nothing parked
    router, _, _ = _fake_router(store)
    req = Request("a", [1, 2, 3], max_new_tokens=4, arrival_step=5)
    router._metas["a"] = {"page_size": 8}
    router.assigned[1]["a"] = req
    router._drain(1, now=100.0)
    # only the prompt survived: back to the prefill tier from scratch
    assert router.resumed_from_park == 0
    assert len(router.decode_queue) == 0
    assert len(router.queue) == 1
    assert "a" in store.dropped
    assert "a" not in router._metas
    assert req.arrival_step == 0            # admit immediately


def test_drain_dead_decode_over_budget_aborts():
    import time as _time
    store = _FakeStore(parked_rids={"a"})
    router, _, _ = _fake_router(store, max_redispatch=0)
    req = Request("a", [1, 2, 3], max_new_tokens=4)
    router._submit_t["a"] = _time.monotonic()
    router.assigned[1]["a"] = req
    router._drain(1, now=100.0)
    assert router.aborted == 1
    assert len(router.decode_queue) == 0 and len(router.queue) == 0
    assert router.completions[0]["finish_reason"] == "aborted"


def test_drain_dead_prefill_requeues_to_prefill_tier():
    router, _, _ = _fake_router(_FakeStore())
    req = Request("a", [1, 2, 3], max_new_tokens=4)
    router.assigned[0]["a"] = req
    router._drain(0, now=100.0)
    assert len(router.queue) == 1 and len(router.decode_queue) == 0
    assert router.redispatched_total == 1


def test_requeue_prefill_bounded_like_a_redispatch():
    import time as _time
    router, _, _ = _fake_router(_FakeStore(), max_redispatch=1)
    req = Request("a", [1, 2, 3], max_new_tokens=4)
    router._submit_t["a"] = _time.monotonic()
    router._metas["a"] = {"page_size": 8}
    router._requeue_prefill(req, now=0.0, why="handoff_corrupt")
    assert len(router.queue) == 1 and req.restarts == 1
    assert "a" not in router._metas
    # budget: restarts may reach max_redispatch + 1, not beyond
    req2 = Request("b", [1], max_new_tokens=2, restarts=2)
    router._submit_t["b"] = _time.monotonic()
    router._requeue_prefill(req2, now=0.0, why="handoff_missing")
    assert router.aborted == 1
    assert router.completions[0]["rid"] == "b"


# ---------------------------------------------------------------------------
# threaded end-to-end: DisaggRouter over TierThreadReplicas
# ---------------------------------------------------------------------------

def test_disagg_router_thread_backend_end_to_end(oracle_f32):
    from deepspeed_tpu.inference.fleet import TierThreadReplica

    store = DeviceHandoffStore()

    def prefill_factory():
        return PrefillWorker(_build(tier="prefill"), store)

    def decode_factory():
        return DecodeWorker(_build(tier="decode", max_batch=3), store)

    pre = TierThreadReplica(0, prefill_factory).start()
    dec = TierThreadReplica(1, decode_factory).start()
    router = DisaggRouter([pre], [dec], store, max_redispatch=2)
    result = router.run(requests=_requests(), timeout_s=120.0)
    assert result.ok
    assert {c["rid"]: c["tokens"]
            for c in result.completions} == oracle_f32
    assert result.handoffs == len(PROTOS)
    assert result.handoff_bytes > 0
    assert result.replicas_dead == 0
    assert result.ttft_s["p50"] is not None
    # per-tier stats ride the result, tagged with their tier, and the
    # fleet-wide jit census is exactly 2 programs
    by_tier = {s["tier"]: s for s in result.stats}
    assert by_tier["prefill"]["compile_counts"] == PREFILL_PIN
    assert by_tier["decode"]["compile_counts"] == DECODE_PIN
    comps = result.by_rid()
    assert all(c["tier"] == "decode" for c in comps.values())
    assert all(c.get("ttft_s") is not None for c in comps.values())


# ---------------------------------------------------------------------------
# satellites: config, rules, tune, metrics
# ---------------------------------------------------------------------------

def test_disagg_config_block_and_validation():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    cfg = DeepSpeedConfig({"train_batch_size": 16, "inference": {
        "kv_layout": "paged", "disaggregated": True,
        "prefill_workers": 2, "decode_workers": 3,
        "prefill_max_batch": 4}}, world_size=1)
    inf = cfg.inference
    assert inf.disaggregated is True
    assert (inf.prefill_workers, inf.decode_workers) == (2, 3)
    assert (inf.prefill_max_batch, inf.decode_max_batch) == (4, 0)
    # defaults: colocated
    inf0 = DeepSpeedConfig({"train_batch_size": 16},
                           world_size=1).inference
    assert inf0.disaggregated is False
    assert (inf0.prefill_workers, inf0.decode_workers) == (1, 1)

    def bad(block, match):
        with pytest.raises(ValueError, match=match):
            DeepSpeedConfig({"train_batch_size": 16,
                             "inference": block}, world_size=1)

    bad({"kv_layout": "ring", "disaggregated": True},
        "only KV layout since PR 28")
    bad({"disaggregated": True, "replicas": 2}, "replicas")
    bad({"kv_layout": "paged", "disaggregated": True,
         "speculative": {"enabled": True}}, "speculative")
    bad({"disaggregated": 1}, "bool")
    bad({"prefill_workers": 0}, "prefill_workers")
    bad({"decode_max_batch": -1}, "decode_max_batch")


def test_rule_decode_tier_pins_and_geometry():
    from deepspeed_tpu.analysis.rules import (
        SEV_ERROR, StepContext, rule_decode)

    clean = StepContext(
        hlo_text="",
        disagg_tier_counts={"prefill": PREFILL_PIN,
                            "decode": DECODE_PIN},
        disagg_page_facts={
            "prefill": {"page_size": 8, "pages_per_row": 4},
            "decode": {"page_size": 8, "pages_per_row": 4}})
    assert rule_decode(clean) == []

    # seeded violations: both tiers leak the other program AND the
    # page geometry disagrees across the handoff -> 3 errors
    dirty = StepContext(
        hlo_text="",
        disagg_tier_counts={"prefill": {"prefill": 1, "decode": 1},
                            "decode": {"prefill": 1, "decode": 1}},
        disagg_page_facts={
            "prefill": {"page_size": 8, "pages_per_row": 4},
            "decode": {"page_size": 16, "pages_per_row": 4}})
    findings = rule_decode(dirty)
    assert len(findings) == 3
    assert all(f.severity == SEV_ERROR for f in findings)
    msgs = " | ".join(f.message for f in findings)
    assert "prefill tier holds compile counts" in msgs
    assert "decode tier holds compile counts" in msgs
    assert "geometry mismatch" in msgs


@pytest.mark.full_compile
def test_audit_disagg_flavor_is_clean():
    from deepspeed_tpu.analysis.audit import audit_disagg

    report = audit_disagg()
    assert report.findings == []
    stats = report.stats
    assert stats["tier_compile_counts"]["prefill"] == PREFILL_PIN
    assert stats["tier_compile_counts"]["decode"] == DECODE_PIN
    assert stats["completions"] == 4


@pytest.mark.full_compile
def test_serving_dimensions_include_tier_knobs():
    from deepspeed_tpu.analysis.tune import (
        SERVING_DIMENSION_NAMES, serving_dimensions)

    dims = dict(serving_dimensions(
        {"inference": {"prefill_chunk": 4, "seq_buckets": [16, 32]}}))
    assert {"page", "chunk", "batch", "park", "block"} <= set(dims)
    assert set(dims) <= set(SERVING_DIMENSION_NAMES)
    assert [c.label for c in dims["chunk"]] == \
        ["chunk2", "chunk4", "chunk8"]
    assert [c.label for c in dims["batch"]] == \
        ["batch1", "batch2", "batch4"]


@pytest.mark.full_compile
@_slow
def test_bad_chunk_candidate_is_typed_rejection():
    """`prefill_chunk` 8 against page_size 12 cannot build (neither
    divides the other; a chunk of whole pages can, since PR 31) — the
    tuner reports the typed `candidate_build_error`, never a silent
    skip."""
    from deepspeed_tpu.analysis.tune import (
        REJECT_BUILD_ERROR, evaluate_serving_candidate)

    res = evaluate_serving_candidate(
        {"train_batch_size": 8,
         "inference": {"seq_buckets": [24, 48], "prefill_chunk": 8,
                       "page_size": 12, "max_batch": 2}},
        model_overrides={"n_embd": 32},
        label="chunk8", dimension="chunk")
    assert res.reject_reason == REJECT_BUILD_ERROR
    assert "page_size" in (res.reject_detail or "")


def test_metrics_summarize_disagg_block():
    from deepspeed_tpu.telemetry.cli import (
        _summarize_disagg, print_disagg_block)

    def ev(event, **f):
        return dict(event=event, **f)

    events = [
        ev("fleet_dispatch", tier="prefill", rid="a"),
        ev("fleet_dispatch", tier="decode", rid="a"),
        ev("fleet_redispatch", tier="decode", rid="a"),
        ev("prefill_step", tier="prefill", rid="a", wall_s=0.01),
        ev("decode_step", wall_s=0.002),
        ev("request_prefilled", rid="a", tier="prefill", ttft_s=0.05,
           queue_wait_s=0.004, handoff_bytes=2048, parked=True),
        ev("request_complete", rid="a", tier="decode", ttft_s=0.05,
           decode_queue_wait_s=0.003, finish_reason="max_new_tokens"),
        ev("disagg_done", ok=True, handoffs=1, handoff_bytes=2048,
           handoff_corrupt=0, resumed_from_park=1,
           dead_by_tier={"prefill": 0, "decode": 1}),
    ]
    dg = _summarize_disagg(events)
    assert dg is not None
    assert dg["handoffs"] == 1 and dg["handoff_bytes"] == 2048
    assert dg["ttft_s"]["p50"] == 0.05
    tiers = dg["tiers"]
    assert tiers["prefill"]["dispatched"] == 1
    assert tiers["prefill"]["steps"] == 1
    assert tiers["prefill"]["queue_wait_s"]["p50"] == 0.004
    assert tiers["decode"]["redispatched"] == 1
    assert tiers["decode"]["queue_wait_s"]["p50"] == 0.003

    # a log with no disaggregation events gets no block
    assert _summarize_disagg(
        [ev("decode_step", wall_s=0.1)]) is None

    buf = io.StringIO()
    print_disagg_block(dg, out=buf)
    text = buf.getvalue()
    assert "prefill tier" in text and "decode tier" in text
    assert "ttft" in text

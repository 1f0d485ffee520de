"""Disaggregated serving soak (ISSUE 20 acceptance): tier-worker
deaths mid-stream → tier-aware drain → bit-exact completions.

Each scenario runs a REAL disaggregated process fleet — subprocess
tier workers (`inference/fleet_worker.py` driving `inference/disagg.py`
PrefillWorker/DecodeWorker over a shared FileHandoffStore) routed by
`inference/router.py:DisaggRouter` — and checks:

- an injected SIGKILL in one prefill worker's chunk train (the
  ``inject_kill("prefill_chunk")`` seam) is classified as a crash; its
  in-flight requests re-prefill on the surviving prefill worker;
  EVERY request still completes on the decode tier, tokens BIT-EXACT
  against an uninterrupted colocated single-engine oracle (greedy
  decode is request-local deterministic, so at-least-once prefill
  surfaces as exactly-once completion);
- a SIGKILLed decode worker's in-flight requests RESUME from their
  durable file handoffs on the surviving decode worker — no
  re-prefill (``resumed_from_park``), same tokens;
- every surviving tier worker honours its one-program pin
  (prefill ``{"prefill": 1, "decode": 0}``, decode
  ``{"prefill": 0, "decode": 1}``) through the recovery.
"""

import os

import pytest

from deepspeed_tpu.runtime.supervisor import CAUSE_CRASH

# slow: each scenario boots three jax subprocess tier workers (engine
# build + compile warmup per worker) plus an in-process oracle engine —
# the CI disagg-smoke / slow lane, not the per-commit fast lane.
pytestmark = [pytest.mark.model, pytest.mark.faultinject,
              pytest.mark.slow]

PREFILL_PIN = {"prefill": 1, "decode": 0}
DECODE_PIN = {"prefill": 0, "decode": 1}

# One engine recipe everywhere — tier workers and the oracle must build
# byte-identical engines for the token-identity check to mean anything.
# seq_buckets as a list: the spec travels through JSON.
INF_CFG = {"max_batch": 2, "seq_buckets": [16, 32], "prefill_chunk": 4,
           "temperature": 0.0}


def _requests(n=4, max_new=8):
    from deepspeed_tpu.inference.scheduler import Request
    reqs = []
    for i in range(n):
        prompt = [(7 * i + 3 * j + 1) % 256 for j in range(3 + i)]
        reqs.append(Request(rid=f"s{i}", prompt=prompt,
                            max_new_tokens=max_new))
    return reqs


def _oracle_tokens(requests):
    """Uninterrupted colocated run on one paged engine."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny

    cfg = gpt2_tiny(n_embd=32, dtype=jnp.float32, scan_layers=False)
    model = GPT2LMHead(cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    engine = InferenceEngine(model, params, config=dict(INF_CFG))
    comps = ContinuousBatchingScheduler(engine).run(requests)
    return {c.rid: list(c.tokens) for c in comps}


def _start_tiers(workdir, n_prefill, n_decode, inject=None,
                 inject_index=None):
    """Tier process replicas over a shared handoff directory, with
    globally-unique indices (prefill 0..N-1, decode N..N+M-1)."""
    from deepspeed_tpu.inference.disagg import FileHandoffStore
    from deepspeed_tpu.inference.fleet import TierProcessReplica

    handoff_dir = os.path.join(workdir, "handoff")
    store = FileHandoffStore(handoff_dir)
    total = n_prefill + n_decode

    def spawn(index, tier, tag):
        rspec = {"inf_cfg": dict(INF_CFG), "seed": 0,
                 "scan_layers": False, "tier": tier,
                 "handoff_dir": handoff_dir,
                 "jsonl": os.path.join(workdir, f"{tag}.jsonl")}
        return TierProcessReplica(
            index, rspec, workdir, num_replicas=total,
            inject=inject if index == inject_index else None).start()

    prefill = [spawn(i, "prefill", f"prefill{i}")
               for i in range(n_prefill)]
    decode = [spawn(n_prefill + j, "decode", f"decode{j}")
              for j in range(n_decode)]
    for r in prefill + decode:
        r.wait_ready(timeout=180.0)
    return prefill, decode, store


def _pins(result):
    return {s["replica"]: (s["tier"], s["compile_counts"])
            for s in result.stats}


def test_sigkill_prefill_worker_midchunk_bit_exact(tmp_path):
    """SIGKILL one of two prefill workers inside its chunk train: the
    router classifies a crash, drains its in-flight requests back to
    the surviving prefill worker, and every request still completes on
    the decode tier bit-exact against the colocated oracle."""
    from deepspeed_tpu.inference.router import DisaggRouter
    workdir = str(tmp_path)
    prefill, decode, store = _start_tiers(
        workdir, n_prefill=2, n_decode=1,
        inject={"kill": {"op": "prefill_chunk", "at_step": 1}},
        inject_index=0)
    router = DisaggRouter(prefill, decode, store, backoff_base_s=0.01)
    result = router.run(_requests(), timeout_s=240.0)

    assert result.ok, [c["finish_reason"] for c in result.completions]
    assert router.dead == {0: CAUSE_CRASH}
    assert result.dead_by_tier == {"prefill": 1, "decode": 0}
    assert result.redispatched_total >= 1

    # the drained requests record their retry history and land on the
    # surviving prefill worker before finishing decode-side
    redone = [c for c in result.completions if c["redispatched"]]
    assert redone
    assert all(c["restarts"] >= 1 and c["tier"] == "decode"
               for c in redone)

    # every request crossed the handoff; ttft was stamped prefill-side
    assert result.handoffs >= len(result.completions)
    assert result.handoff_bytes > 0
    assert result.ttft_s["p50"] is not None

    # one-program pins hold through the recovery: surviving prefill
    # worker never decoded, decode worker never prefilled
    pins = _pins(result)
    assert pins[1] == ("prefill", PREFILL_PIN)
    assert pins[2] == ("decode", DECODE_PIN)

    oracle = _oracle_tokens(_requests())
    got = {c["rid"]: c["tokens"] for c in result.completions}
    assert got == oracle


def test_sigkill_decode_worker_resumes_from_parked_handoff(tmp_path):
    """SIGKILL one of two decode workers mid-decode: its in-flight
    requests' file handoffs are durable (parked), so they RESUME on the
    surviving decode worker without re-prefilling — and the tokens are
    still bit-exact (the resumed decode replays from the handoff
    frontier deterministically)."""
    from deepspeed_tpu.inference.router import DisaggRouter
    workdir = str(tmp_path)
    prefill, decode, store = _start_tiers(
        workdir, n_prefill=1, n_decode=2,
        inject={"kill": {"op": "decode_step", "at_step": 2}},
        inject_index=1)
    router = DisaggRouter(prefill, decode, store, backoff_base_s=0.01)
    result = router.run(_requests(max_new=12), timeout_s=240.0)

    assert result.ok, [c["finish_reason"] for c in result.completions]
    assert router.dead == {1: CAUSE_CRASH}
    assert result.dead_by_tier == {"prefill": 0, "decode": 1}

    # the durable-handoff contract: drained decode requests resumed
    # from their parked snapshots instead of re-prefilling
    assert result.resumed_from_park >= 1
    assert result.handoff_corrupt == 0

    pins = _pins(result)
    assert pins[0] == ("prefill", PREFILL_PIN)
    assert pins[2] == ("decode", DECODE_PIN)

    oracle = _oracle_tokens(_requests(max_new=12))
    got = {c["rid"]: c["tokens"] for c in result.completions}
    assert got == oracle

"""Serving engine + continuous-batching scheduler pins
(`deepspeed_tpu/inference/engine.py`, `scheduler.py`).

Two halves:

- scheduler logic against a stub engine (no jax): bucket assignment,
  slot recycling, eos/max_new/length finishes, open-loop arrival
  gating, and the ``decode_step`` telemetry stream.
- the real engine's recompile contract: one tiny-model engine driven
  through admit/evict across BOTH seq buckets must hold
  ``{"prefill": 1, "decode": 1}`` — the acceptance criterion the whole
  bucketed-shapes design exists for — plus the in-engine detector's
  negative case and config validation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.scheduler import (
    ContinuousBatchingScheduler,
    Request,
)
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from deepspeed_tpu.telemetry.session import TelemetrySession


def identity_tables(eng):
    """``[max_batch, pages_per_row]`` page tables handing row ``r`` the
    pages ``1 + r * pages_per_row ...`` in order (page 0 is the trash
    page): what a test that calls ``prefill`` / ``decode`` itself, with
    no scheduler and no allocator, passes."""
    ppr = eng.pages_per_row
    return 1 + np.arange(eng.max_batch * ppr, dtype=np.int32).reshape(
        eng.max_batch, ppr)


class StubEngine:
    """Scheduler-facing engine surface without jax: prefill returns
    logits argmaxing to token 7; decode echoes position+1 as the next
    token so generations are deterministic and inspectable. The pool
    facts are a default engine's (pages of two prefill chunks, every
    row at full length plus the trash page); the pool itself is one
    float a page."""

    def __init__(self, max_batch=2, seq_buckets=(16, 32), session=None):
        self.max_batch = max_batch
        self.seq_buckets = tuple(sorted(seq_buckets))
        self.max_seq = max(self.seq_buckets)
        self.session = session
        self.prefill_chunk = 4
        self.page_size = 8
        self.pages_per_row = self.max_seq // self.page_size
        self.n_pages = max_batch * self.pages_per_row + 1
        self.prefix_cache = True
        self.host_park_threshold = 0.0
        self.cache = {"k": np.zeros((self.n_pages, 1), np.float32)}
        self.prefills = []
        self.decodes = 0

    def prefill(self, slot, prompt, page_table, start=0):
        assert len(page_table) == self.pages_per_row
        self.prefills.append((slot, tuple(prompt)))
        logits = np.zeros(64, np.float32)
        logits[7] = 1.0
        return logits

    def sample_first(self, last_logits):
        return int(np.argmax(last_logits))

    def decode(self, tokens, positions, page_tables):
        assert page_tables.shape == (self.max_batch, self.pages_per_row)
        self.decodes += 1
        nxt = (np.asarray(positions) + 1).astype(np.int32)
        return nxt, np.zeros((self.max_batch, 64), np.float32)


class TestSchedulerLogic:
    def test_bucket_assignment_smallest_fit_and_clamp(self):
        eng = StubEngine(seq_buckets=(16, 32))
        sched = ContinuousBatchingScheduler(eng)
        assert sched._bucket_for(Request("a", [0] * 4,
                                         max_new_tokens=4)) == 16
        assert sched._bucket_for(Request("b", [0] * 13,
                                         max_new_tokens=4)) == 32
        # over the largest bucket: clamps (generation truncates there)
        assert sched._bucket_for(Request("c", [0] * 30,
                                         max_new_tokens=10)) == 32

    def test_submit_validation(self):
        sched = ContinuousBatchingScheduler(StubEngine())
        with pytest.raises(ValueError, match="empty prompt"):
            sched.submit(Request("a", []))
        with pytest.raises(ValueError, match="does not fit"):
            sched.submit(Request("b", [0] * 40))
        with pytest.raises(ValueError, match="max_new_tokens"):
            sched.submit(Request("c", [0], max_new_tokens=0))

    def test_max_new_tokens_finish_and_slot_recycling(self):
        eng = StubEngine(max_batch=2)
        sched = ContinuousBatchingScheduler(eng)
        reqs = [Request(f"r{i}", [1, 2], max_new_tokens=3)
                for i in range(4)]
        comps = sched.run(reqs)
        assert [c.rid for c in comps] == ["r0", "r1", "r2", "r3"]
        assert all(c.finish_reason == "max_new_tokens" for c in comps)
        assert all(len(c.tokens) == 3 for c in comps)
        # 2 rows served 4 requests: later requests reused slots 0/1
        assert {c.slot for c in comps} == {0, 1}

    def test_eos_finish(self):
        eng = StubEngine()
        sched = ContinuousBatchingScheduler(eng)
        # prefill's first sampled token is 7 -> immediate eos finish
        comps = sched.run([Request("a", [1, 2], max_new_tokens=8,
                                   eos_id=7)])
        assert comps[0].finish_reason == "eos"
        assert comps[0].tokens == [7]
        assert eng.decodes == 0

    def test_length_eviction_at_bucket_edge(self):
        eng = StubEngine(seq_buckets=(16, 32))
        sched = ContinuousBatchingScheduler(eng)
        comps = sched.run([Request("a", [1] * 30, max_new_tokens=10)])
        assert comps[0].finish_reason == "length"
        assert comps[0].bucket == 32
        # positions 30 and 31 were decodable; the prefill token plus
        # two decode outputs landed before the budget ran out
        assert len(comps[0].tokens) == 3

    def test_open_loop_arrival_gating(self):
        eng = StubEngine(max_batch=4)
        sched = ContinuousBatchingScheduler(eng)
        sched.submit(Request("later", [1, 2], max_new_tokens=2,
                             arrival_step=5))
        sched.step()
        assert sched.slots == [None] * 4     # not admitted yet
        assert sched.step_count == 1
        comps = sched.run(max_steps=50)
        assert comps[0].rid == "later"
        assert comps[0].steps <= 2

    def test_decode_step_events_and_metrics(self):
        session = TelemetrySession()
        eng = StubEngine(max_batch=2, session=session)
        sched = ContinuousBatchingScheduler(eng)
        sched.run([Request("a", [1, 2], max_new_tokens=3),
                   Request("b", [3], max_new_tokens=2)])
        evts = session.events.recent(event="decode_step")
        assert evts and eng.decodes == len(evts)
        for e in evts:
            assert set(e) >= {"step", "tokens", "batch", "occupancy",
                              "queue_depth", "wall_s"}
        assert evts[0]["batch"] == 2 and evts[0]["occupancy"] == 1.0
        assert session.registry.counter("decode_tokens_total").value > 0


def _tiny_engine(cls=InferenceEngine, **cfg_kw):
    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=2, n_head=4, dtype=jnp.float32)
    model = GPT2LMHead(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    inf = {"max_batch": 2, "seq_buckets": (16, 32), "prefill_chunk": 4}
    inf.update(cfg_kw)
    return cls(model, params, config=inf)


class TestEngineValidation:
    def test_bucket_chunk_mismatch_rejected(self):
        with pytest.raises(ValueError, match="multiple of"):
            _tiny_engine(seq_buckets=(10, 32))

    def test_bad_max_batch_rejected(self):
        with pytest.raises(ValueError, match="max_batch"):
            _tiny_engine(max_batch=0)

    def test_empty_buckets_rejected(self):
        with pytest.raises(ValueError, match="seq_buckets"):
            _tiny_engine(seq_buckets=())

    @pytest.mark.parametrize("layout", ["ring", "", "Paged"])
    def test_kv_layout_other_than_paged_is_refused(self, layout):
        with pytest.raises(ValueError, match="only KV layout since PR 28"):
            _tiny_engine(kv_layout=layout)

    def test_kv_layout_paged_is_accepted_and_changes_nothing(self):
        assert _tiny_engine(kv_layout="paged").cache_facts() == \
            _tiny_engine().cache_facts()

    @pytest.mark.parametrize("chunk,buckets", [(4, (16, 32)), (16, (32,)),
                                               (32, (32,))])
    def test_default_pool_holds_the_bytes_a_row_buffer_held(self, chunk,
                                                            buckets):
        """No page keys: `page_size` is two prefill chunks (at most
        `max_seq`) and `n_pages` every row at full length plus the trash
        page, so the pool is `max_batch x max_seq` positions of K and V
        a layer (the ring layout's `[max_batch, max_seq, H, D]`) and one
        page more."""
        eng = _tiny_engine(prefill_chunk=chunk, seq_buckets=buckets)
        assert eng.page_size == min(2 * chunk, 32)
        assert eng.n_pages == 2 * (32 // eng.page_size) + 1
        position = 2 * 4 * 8 * 4         # K and V, 4 heads x 8, float32
        assert eng.cache_facts()["bytes"] == \
            2 * position * (2 * 32 + eng.page_size)

    def test_prompt_length_bounds(self):
        eng = _tiny_engine()
        table = identity_tables(eng)[0]
        with pytest.raises(ValueError, match="prompt length"):
            eng.prefill(0, [], table)
        with pytest.raises(ValueError, match="prompt length"):
            eng.prefill(0, [1] * 33, table)


class TestRecompileContract:
    def test_two_compiles_across_buckets_with_admit_evict(self):
        """THE acceptance pin: a stream that exercises admission,
        eviction, slot recycling, and both seq buckets compiles the
        prefill and decode programs exactly once each."""
        eng = _tiny_engine()
        sched = ContinuousBatchingScheduler(eng)
        rng = np.random.default_rng(0)
        reqs = [
            Request("small", rng.integers(0, 64, 3).tolist(),
                    max_new_tokens=4),                    # bucket 16
            Request("large", rng.integers(0, 64, 20).tolist(),
                    max_new_tokens=6),                    # bucket 32
            Request("late", rng.integers(0, 64, 2).tolist(),
                    max_new_tokens=3, arrival_step=4),    # recycles a row
            Request("clamped", rng.integers(0, 64, 30).tolist(),
                    max_new_tokens=10),                   # length-evicts
        ]
        comps = sched.run(reqs)
        assert len(comps) == 4
        assert {c.bucket for c in comps} == {16, 32}
        assert eng.compile_counts() == {"prefill": 1, "decode": 1}
        assert eng.recompile_findings() == []
        # reset must not cost a compile either
        eng.reset()
        more = ContinuousBatchingScheduler(eng).run(
            [Request("again", [5, 6, 7], max_new_tokens=2)])
        assert len(more) == 1
        assert eng.compile_counts() == {"prefill": 1, "decode": 1}

    def test_detector_negative_case(self):
        """With baseline=0 every compiled program is a finding — the
        detector actually reads the jit caches."""
        eng = _tiny_engine()
        ContinuousBatchingScheduler(eng).run(
            [Request("a", [1, 2, 3], max_new_tokens=2)])
        findings = eng.recompile_findings(baseline=0)
        assert {f.details["program"] for f in findings} == \
            {"prefill", "decode"}
        assert all(f.severity == "error" for f in findings)

    def test_cache_facts_shape(self):
        eng = _tiny_engine(kv_cache_dtype="int8")
        facts = eng.cache_facts()
        assert facts["kv_cache_dtype"] == "int8"
        assert facts["dtype_census"] == {"int8": 4}
        assert facts["seq_buckets"] == [16, 32]
        assert facts["max_seq"] == 32 and not facts["stacked"]
        # no page keys given: pages of two prefill chunks, every row at
        # full length and the trash page
        assert (facts["page_size"], facts["pages_per_row"],
                facts["n_pages"]) == (8, 4, 2 * 4 + 1)
        assert "kv_layout" not in facts


# ---------------------------------------------------------------------------
# decode() brings home the tokens; the logits stay on the device (PR 37)
# ---------------------------------------------------------------------------

SAMPLING = {
    "greedy": {},
    "temperature": {"temperature": 0.8, "top_k": 16, "top_p": 0.9,
                    "sampling_seed": 3},
}


class CopiesLogits(InferenceEngine):
    """``decode`` as it was before PR 37: the logits copied to the host
    on every step, whoever reads them."""

    def decode(self, tokens, positions, page_tables):
        nxt, logits = super().decode(tokens, positions, page_tables)
        return nxt, np.asarray(logits)


def _decode_steps(eng, steps=10):
    """Prefill row 0 (row 1 holds no request) and decode ``steps``
    tokens, each the step before's; returns the tokens the row was fed
    (the prompt, then one a step) and ``[(tokens, logits)]`` as
    ``decode`` handed them back."""
    tables = identity_tables(eng)
    tables[1] = 0
    fed = [5, 9, 2, 40, 11]
    tok = eng.sample_first(eng.prefill(0, fed, tables[0]))
    out = []
    for _ in range(steps):
        t = np.asarray([tok, 0], np.int32)
        p = np.asarray([len(fed), 0], np.int32)
        fed = [*fed, tok]
        nxt, logits = eng.decode(t, p, tables)
        out.append((nxt, logits))
        tok = int(nxt[0])
    return fed, out


@pytest.mark.parametrize("sampling", sorted(SAMPLING))
class TestDecodeLeavesLogitsOnTheDevice:
    def test_tokens_are_numpy_and_logits_the_programs_array(self,
                                                            sampling):
        eng = _tiny_engine(**SAMPLING[sampling])
        program, made = eng._decode, []

        def watched(*args):
            made.append(program(*args))
            return made[-1]

        eng._decode = watched
        _, steps = _decode_steps(eng, steps=3)
        assert len(made) == 3
        for (nxt, logits), result in zip(steps, made):
            assert type(nxt) is np.ndarray
            assert nxt.shape == (2,) and nxt.dtype == np.int32
            assert isinstance(logits, jax.Array)
            assert logits is result[1]          # untouched: no copy made
            assert logits.shape == (2, 64)
            assert logits.dtype == jnp.float32

    def test_logits_read_as_the_copied_ones_did(self, sampling):
        """Bit for bit what the previous form handed back, and within
        the parity tests' limit of their reference: the full forward
        over the tokens the engine itself drew."""
        eng = _tiny_engine(**SAMPLING[sampling])
        old = _tiny_engine(CopiesLogits, **SAMPLING[sampling])
        fed, new_steps = _decode_steps(eng)
        old_fed, old_steps = _decode_steps(old)
        assert fed == old_fed
        ref = np.asarray(eng.model.apply(
            {"params": eng.params}, jnp.asarray([fed], jnp.int32),
            deterministic=True)[0], np.float32)
        first = len(fed) - len(new_steps)
        for i, ((nxt, logits), (old_nxt, old_logits)) in enumerate(
                zip(new_steps, old_steps)):
            assert type(old_logits) is np.ndarray
            np.testing.assert_array_equal(nxt, old_nxt)
            np.testing.assert_array_equal(np.asarray(logits), old_logits)
            # a row and its argmax answer with no copy of the whole
            np.testing.assert_array_equal(logits[0], old_logits[0])
            assert int(logits[0].argmax()) == int(old_logits[0].argmax())
            np.testing.assert_allclose(logits[0], ref[first + i],
                                       atol=2e-6)

    def test_ten_steps_compile_each_program_once(self, sampling):
        eng = _tiny_engine(**SAMPLING[sampling])
        _decode_steps(eng, steps=10)
        assert eng.compile_counts() == {"prefill": 1, "decode": 1}
        assert eng.recompile_findings() == []

    def test_a_fixed_stream_draws_the_same_tokens(self, sampling):
        def run(eng):
            rng = np.random.default_rng(0)
            reqs = [Request(f"r{i}",
                            rng.integers(0, 64,
                                         int(rng.integers(2, 20))).tolist(),
                            max_new_tokens=int(rng.integers(2, 8)))
                    for i in range(5)]
            comps = ContinuousBatchingScheduler(eng).run(reqs)
            return {c.rid: c.tokens for c in comps}

        new = run(_tiny_engine(**SAMPLING[sampling]))
        old = run(_tiny_engine(CopiesLogits, **SAMPLING[sampling]))
        assert len(new) == 5 and new == old

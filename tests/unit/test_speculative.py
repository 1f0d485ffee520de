"""Speculative decoding subsystem (`inference/speculative.py`).

The PR's acceptance criteria, as pins:

- **Greedy parity**: a speculative serve's outputs are BIT-IDENTICAL
  to the non-speculative engine's over the same stream — drafting and
  verify-accept are an execution strategy, not a model change. Runs
  across {unrolled, scan} x {dense, flash+int8} and under 4-way TP.
- **Three pinned programs**: prefill + draft + verify each compile
  exactly once through bucket churn, and the plain decode program is
  never entered (0 jit-cache entries). Degenerate configs (k == 0,
  draft_layers >= n_layer) disable speculation and fall back to the
  exact 2-program engine.
- **Accept rules** are module-level pure functions with unit math
  pins (longest-matching-prefix for greedy; Leviathan rejection
  sampling with residual corrections for temperature > 0 — the
  empirical accept rate matches sum min(p, q)).
- The scheduler **length-finishes** any row whose verify window would
  cross max_seq (the page-table lookup would clamp onto the row's
  last page and overwrite valid history otherwise), the adaptive
  window controller moves draft_len as traced data only, and the
  `speculative` audit flavor comes back with zero findings after its
  churn stream.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis.audit import EXTRA_FLAVORS, audit_speculative
from deepspeed_tpu.analysis.rules import (
    RULE_IDS,
    SEV_ERROR,
    StepContext,
    rule_speculative,
)
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.scheduler import (
    ContinuousBatchingScheduler,
    Request,
)
from deepspeed_tpu.inference.speculative import (
    SpeculativeDecoder,
    build_speculative,
    greedy_accept,
    rejection_accept,
)
from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny

SPEC = {"enabled": True, "k": 3, "draft_layers": 1}


def build_engine(speculative=SPEC, scan_layers=False, mesh=None,
                 **overrides):
    cfg = gpt2_tiny(n_embd=32, dtype=jnp.float32,
                    scan_layers=scan_layers)
    model = GPT2LMHead(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    inf_cfg = {"max_batch": 2, "seq_buckets": (16, 32),
               "prefill_chunk": 4}
    if speculative is not None:
        inf_cfg["speculative"] = speculative
    inf_cfg.update(overrides)
    return InferenceEngine(model, params, config=inf_cfg, mesh=mesh)


def stream(n=5, seed=1, max_new=5, vocab=256):
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}",
                    rng.integers(0, vocab,
                                 int(rng.integers(2, 20))).tolist(),
                    max_new_tokens=max_new)
            for i in range(n)]


def run_tokens(engine, **stream_kw):
    comps = ContinuousBatchingScheduler(engine).run(stream(**stream_kw))
    return {c.rid: (c.tokens, c.finish_reason) for c in comps}


def assert_parity(spec_engine, plain_engine, **stream_kw):
    spec_out = run_tokens(spec_engine, **stream_kw)
    plain_out = run_tokens(plain_engine, **stream_kw)
    assert spec_out == plain_out
    assert spec_engine.compile_counts() == \
        {"prefill": 1, "decode": 0, "draft": 1, "verify": 1}
    assert plain_engine.compile_counts() == {"prefill": 1, "decode": 1}


class TestGreedyAccept:
    def test_all_match_emits_bonus(self):
        # pred[t] is the model's token for position t; all drafts agree
        pred = jnp.array([[5, 6, 7, 9]])
        tokens = jnp.array([[1, 5, 6, 7]])   # pending=1, drafts 5,6,7
        acc, out = greedy_accept(pred, tokens, jnp.array([3]))
        assert int(acc[0]) == 3
        assert out[0, :4].tolist() == [5, 6, 7, 9]   # drafts + bonus

    def test_first_mismatch_emits_correction_only(self):
        pred = jnp.array([[5, 6, 7, 9]])
        tokens = jnp.array([[1, 4, 6, 7]])   # d1=4 != pred 5
        acc, out = greedy_accept(pred, tokens, jnp.array([3]))
        assert int(acc[0]) == 0
        assert out[0, 0].tolist() == 5       # the correction
        assert out[0, 1:].tolist() == [0, 0, 0]

    def test_partial_prefix(self):
        pred = jnp.array([[5, 6, 7, 9]])
        tokens = jnp.array([[1, 5, 6, 8]])   # d3=8 != pred 7
        acc, out = greedy_accept(pred, tokens, jnp.array([3]))
        assert int(acc[0]) == 2
        assert out[0, :3].tolist() == [5, 6, 7]

    def test_draft_len_masks_padding(self):
        # padding happens to equal pred but sits past draft_len=1
        pred = jnp.array([[5, 6, 7, 9]])
        tokens = jnp.array([[1, 5, 6, 7]])
        acc, out = greedy_accept(pred, tokens, jnp.array([1]))
        assert int(acc[0]) == 1
        assert out[0, :2].tolist() == [5, 6]  # accepted draft + bonus

    def test_rows_independent(self):
        pred = jnp.array([[5, 6, 7, 9], [5, 6, 7, 9]])
        tokens = jnp.array([[1, 5, 6, 7], [1, 4, 6, 7]])
        acc, _ = greedy_accept(pred, tokens, jnp.array([3, 3]))
        assert acc.tolist() == [3, 0]


class TestRejectionAccept:
    def test_identical_distributions_always_accept(self):
        # q == p one-hot: u * 1 <= 1 always accepts; the bonus slot
        # samples p (also one-hot), so the output is deterministic
        V = 4
        p = jax.nn.one_hot(jnp.array([2, 1, 3]), V)[None]  # [1, 3, V]
        q = p[:, :2]
        tokens = jnp.array([[0, 2, 1]])   # drafts exactly the one-hots
        acc, out, _ = rejection_accept(
            p, tokens, jnp.array([2]), q, jax.random.PRNGKey(0))
        assert int(acc[0]) == 2
        assert out[0].tolist() == [2, 1, 3]

    def test_zero_target_mass_always_rejects(self):
        # p(d1) = 0: u * q > 0 >= p rejects; the correction samples
        # the residual max(p - q, 0), which is p's support alone
        V = 4
        p = jnp.tile(jax.nn.one_hot(jnp.array([3]), V)[None], (1, 2, 1))
        q = jax.nn.one_hot(jnp.array([1]), V)[None]        # [1, 1, V]
        tokens = jnp.array([[0, 1]])                       # draft d1=1
        acc, out, _ = rejection_accept(
            p, tokens, jnp.array([1]), q, jax.random.PRNGKey(0))
        assert int(acc[0]) == 0
        assert out[0, 0].tolist() == 3     # residual == p, token 3

    def test_empirical_accept_rate_matches_min_mass(self):
        """The rejection test accepts d ~ q with total probability
        sum_x min(p(x), q(x)) — the textbook identity, measured over
        4096 i.i.d. rows."""
        B, V = 4096, 4
        p_row = jnp.array([0.5, 0.3, 0.1, 0.1])
        q_row = jnp.array([0.1, 0.3, 0.5, 0.1])
        key = jax.random.PRNGKey(7)
        kd, ka = jax.random.split(key)
        drafts = jax.random.categorical(
            kd, jnp.log(jnp.tile(q_row[None], (B, 1))), axis=-1)
        tokens = jnp.stack(
            [jnp.zeros(B, jnp.int32), drafts.astype(jnp.int32)], axis=1)
        probs = jnp.tile(p_row[None, None], (B, 2, 1))
        q = jnp.tile(q_row[None, None], (B, 1, 1))
        acc, _, _ = rejection_accept(
            probs, tokens, jnp.ones(B, jnp.int32), q, ka)
        expected = float(jnp.sum(jnp.minimum(p_row, q_row)))
        rate = float(jnp.mean((acc == 1).astype(jnp.float32)))
        assert rate == pytest.approx(expected, abs=0.03)


class TestGreedyParity:
    def test_unrolled(self):
        assert_parity(build_engine(), build_engine(speculative=None))

    @pytest.mark.slow
    def test_scan_layers(self):
        assert_parity(build_engine(scan_layers=True),
                      build_engine(speculative=None, scan_layers=True))

    @pytest.mark.slow
    def test_flash_int8_draft_vs_dense_oracle(self):
        # flash runs the T=1 draft; verify is dense by design. The
        # oracle is the plain dense engine — outputs must still match.
        spec = build_engine(attention_impl="flash", attention_block_k=8,
                            kv_cache_dtype="int8")
        plain = build_engine(speculative=None, attention_impl="dense",
                             kv_cache_dtype="int8")
        assert_parity(spec, plain)

    @pytest.mark.slow
    def test_tensor_parallel_mesh(self):
        from deepspeed_tpu.parallel.mesh import build_mesh
        if len(jax.devices()) < 4:
            pytest.skip("needs the 8-device CPU mesh")
        mesh = build_mesh({"model": 4}, devices=jax.devices()[:4])
        assert_parity(build_engine(mesh=mesh),
                      build_engine(speculative=None, mesh=mesh))


class TestSampledServe:
    @pytest.mark.slow
    def test_three_programs_and_support(self):
        """Sampled speculative serve: the q-dist plumbing adds no
        programs, every emitted token is inside the engine's top-k
        filter support (the verify distribution is filtered before
        the accept test), and at least one token emits per round."""
        eng = build_engine(temperature=0.8, top_k=16, top_p=0.9,
                           sampling_seed=3)
        comps = ContinuousBatchingScheduler(eng).run(stream(n=4))
        assert len(comps) == 4
        assert eng.compile_counts() == \
            {"prefill": 1, "decode": 0, "draft": 1, "verify": 1}
        facts = eng.speculative.facts()
        assert facts["mean_accepted"] >= 1.0
        assert 0.0 <= facts["draft_efficiency"] <= 1.0


class TestDegenerateFallback:
    def test_k_zero_disables(self):
        eng = build_engine(speculative={"enabled": True, "k": 0})
        assert eng.speculative is None
        assert eng.compile_counts() == {"prefill": 0, "decode": 0}

    def test_full_depth_draft_disables(self):
        eng = build_engine(speculative={
            "enabled": True, "k": 3, "draft_layers": 2})  # == n_layer
        assert eng.speculative is None

    def test_absent_block_disables(self):
        assert build_engine(speculative=None).speculative is None

    def test_disabled_block_disables(self):
        eng = build_engine(speculative={"enabled": False, "k": 3})
        assert eng.speculative is None

    def test_fallback_serves_two_programs(self):
        eng = build_engine(speculative={"enabled": True, "k": 0})
        out = run_tokens(eng)
        assert len(out) == 5
        assert eng.compile_counts() == {"prefill": 1, "decode": 1}

    def test_negative_k_raises(self):
        with pytest.raises(ValueError, match="k"):
            build_engine(speculative={"enabled": True, "k": -1})

    def test_decoder_validates_draft_layers(self):
        eng = build_engine(speculative=None)
        with pytest.raises(ValueError, match="draft_layers"):
            SpeculativeDecoder(eng, k=2, draft_layers=2)
        with pytest.raises(ValueError, match="draft_layers"):
            SpeculativeDecoder(eng, k=2, draft_layers=0)

    def test_decoder_validates_window_headroom(self):
        eng = build_engine(speculative=None)
        with pytest.raises(ValueError, match="max_seq"):
            SpeculativeDecoder(eng, k=eng.max_seq, draft_layers=1)


class TestAdaptiveController:
    def test_fixed_window_by_default(self):
        eng = build_engine()
        spec = eng.speculative
        assert spec.draft_len() == spec.k
        spec.observe(2, 6, 0, 2)     # terrible round
        assert spec.draft_len() == spec.k

    def test_grow_and_shrink(self):
        eng = build_engine(speculative={
            "enabled": True, "k": 3, "draft_layers": 1,
            "min_accept_to_grow": 1.0})
        spec = eng.speculative
        spec._j = 2
        spec.observe(2, 4, 2, 4)     # mean accepted 1.0 -> grow
        assert spec.draft_len() == 3
        spec.observe(2, 6, 6, 8)     # still good: capped at k
        assert spec.draft_len() == 3
        spec.observe(2, 6, 0, 2)     # bad round -> shrink
        assert spec.draft_len() == 2
        spec.observe(2, 4, 0, 2)
        spec.observe(2, 2, 0, 2)
        spec.observe(2, 2, 0, 2)     # floor at 1
        assert spec.draft_len() == 1

    def test_facts_counters(self):
        eng = build_engine()
        run_tokens(eng)
        facts = eng.speculative.facts()
        assert facts["k"] == 3 and facts["draft_layers"] == 1
        assert facts["n_layer"] == 2
        assert facts["rounds"] > 0
        assert facts["row_rounds"] >= facts["rounds"]
        assert facts["emitted_total"] >= facts["row_rounds"]
        assert facts["mean_accepted"] >= 1.0
        assert 0.0 <= facts["draft_efficiency"] <= 1.0


class TestSchedulerWindowGuard:
    def test_length_finish_before_max_seq_overrun(self):
        """A row whose verify window would cross max_seq is finished
        with the length reason BEFORE the round — the chunk's writes
        past max_seq would otherwise clamp onto the row's last page,
        over valid history."""
        eng = build_engine(seq_buckets=(16,))
        comps = ContinuousBatchingScheduler(eng).run(
            [Request("r0", list(range(8)), max_new_tokens=12)])
        (c,) = comps
        assert c.finish_reason == "length"
        # kv_tokens = prompt + generated[:-1] never reaches max_seq
        assert 8 + len(c.tokens) <= eng.max_seq

    def test_truncation_is_at_most_k_early_and_prefix_exact(self):
        eng = build_engine(seq_buckets=(16,))
        plain = build_engine(speculative=None, seq_buckets=(16,))
        req = [Request("r0", list(range(8)), max_new_tokens=12)]
        spec_c = ContinuousBatchingScheduler(eng).run(list(req))[0]
        plain_c = ContinuousBatchingScheduler(plain).run(list(req))[0]
        k = eng.speculative.k
        assert len(plain_c.tokens) - len(spec_c.tokens) <= k + 1
        assert spec_c.tokens == plain_c.tokens[:len(spec_c.tokens)]


class TestRuleSpeculative:
    def test_registered(self):
        assert "speculative" in RULE_IDS
        assert "speculative" in EXTRA_FLAVORS

    def test_skips_without_facts(self):
        assert rule_speculative(StepContext(hlo_text="")) == []

    def _facts(self, **over):
        f = {"k": 3, "draft_layers": 1, "n_layer": 4, "rounds": 10,
             "row_rounds": 20, "mean_accepted": 1.5,
             "draft_efficiency": 0.4}
        f.update(over)
        return f

    def _counts(self, **over):
        c = {"prefill": 1, "decode": 0, "draft": 1, "verify": 1}
        c.update(over)
        return c

    def test_clean_context_passes(self):
        ctx = StepContext(
            hlo_text="", spec_facts=self._facts(),
            spec_compile_counts=self._counts(),
            spec_draft_flops=25.0, spec_full_flops=100.0)
        assert rule_speculative(ctx) == []

    def test_decode_entry_is_silent_fallback_error(self):
        ctx = StepContext(
            hlo_text="", spec_facts=self._facts(),
            spec_compile_counts=self._counts(decode=1))
        (f,) = rule_speculative(ctx)
        assert f.severity == SEV_ERROR
        assert "fell back" in f.message
        assert f.details["program"] == "decode"

    def test_extra_draft_program_is_error(self):
        ctx = StepContext(
            hlo_text="", spec_facts=self._facts(),
            spec_compile_counts=self._counts(draft=2))
        (f,) = rule_speculative(ctx)
        assert "draft" in f.message and "leaked" in f.message

    def test_untruncated_draft_flops_is_error(self):
        ctx = StepContext(
            hlo_text="", spec_facts=self._facts(),
            spec_compile_counts=self._counts(),
            spec_draft_flops=98.0, spec_full_flops=100.0)
        (f,) = rule_speculative(ctx)
        assert "truncation" in f.message
        assert f.details["ratio"] == pytest.approx(0.98)

    def test_mean_accepted_below_one_is_error(self):
        ctx = StepContext(
            hlo_text="",
            spec_facts=self._facts(mean_accepted=0.6),
            spec_compile_counts=self._counts())
        (f,) = rule_speculative(ctx)
        assert "dropping tokens" in f.message

    def test_degenerate_depth_is_error(self):
        ctx = StepContext(
            hlo_text="",
            spec_facts=self._facts(draft_layers=4),  # == n_layer
            spec_compile_counts=self._counts())
        (f,) = rule_speculative(ctx)
        assert "degenerate" in f.message

    def test_paged_host_transfer_in_draft_is_error(self):
        ctx = StepContext(
            hlo_text="", spec_facts=self._facts(),
            spec_compile_counts=self._counts(),
            spec_draft_hlo='  infeed = (s32[2]) infeed(token[] %t)\n',
            spec_verify_hlo="")
        (f,) = rule_speculative(ctx)
        assert f.details["program"] == "draft"
        assert "host transfer" in f.message


class TestAuditSpeculative:
    @pytest.mark.full_compile
    @pytest.mark.slow
    def test_zero_findings(self):
        """The acceptance criterion: the speculative flavor churns the
        serve stream (park + resume included) and the whole catalog
        comes back empty; the measured draft flop ratio shows real
        truncation."""
        report = audit_speculative()
        assert report.findings == []
        st = report.stats
        assert st["compile_counts"] == \
            {"prefill": 1, "decode": 0, "draft": 1, "verify": 1}
        assert st["speculative"]["mean_accepted"] >= 1.0
        ratio = st["draft_flops_ratio"]
        dl = st["speculative"]["draft_layers"]
        nl = st["speculative"]["n_layer"]
        assert dl / nl <= ratio < (dl / nl + 1.0) / 2.0
        assert st["paging"]["sessions_resumed"] >= 1

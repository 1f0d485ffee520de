"""Decode audit flavor (`deepspeed_tpu/analysis/audit.py:audit_decode`
+ `analysis/rules.py:rule_decode` + ``rule_flash_decode``).

The rule negatives are pure-python — a StepContext with faked compile
counts / cache censuses / HLO snippets, no jax programs — so every
failure mode of the serving contract (mid-stream recompile, mixed
cache dtypes, silently-skipped quantization, a dense attention dot
surviving a flash rewrite) has a cheap pin. The real end-to-end audit
(tiny engine, scripted stream, lowered decode HLO, full rule catalog →
zero findings) is the PR's acceptance criterion and runs plain,
quantized, and on the dense fallback.
"""

import pytest

from deepspeed_tpu.analysis.audit import EXTRA_FLAVORS, audit_decode
from deepspeed_tpu.analysis.rules import (
    SEV_ERROR,
    RULE_IDS,
    StepContext,
    rule_decode,
    rule_flash_decode,
)

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile


class TestRuleDecode:
    def test_registered(self):
        assert "decode" in RULE_IDS
        assert "decode" in EXTRA_FLAVORS

    def test_skips_when_no_decode_facts(self):
        assert rule_decode(StepContext(hlo_text="")) == []

    def test_clean_counts_and_census_pass(self):
        ctx = StepContext(
            hlo_text="", decode_compile_counts={"prefill": 1, "decode": 1},
            decode_cache_census={"float32": 4})
        assert rule_decode(ctx) == []

    def test_midstream_recompile_is_error(self):
        ctx = StepContext(
            hlo_text="", decode_compile_counts={"prefill": 1, "decode": 3})
        findings = rule_decode(ctx)
        assert len(findings) == 1
        f = findings[0]
        assert f.rule == "decode" and f.severity == SEV_ERROR
        assert f.details["program"] == "decode"
        assert f.details["cache_size"] == 3
        assert "recompiled mid-stream" in f.message

    def test_raised_expectation_tolerates_more_programs(self):
        ctx = StepContext(
            hlo_text="", decode_compile_counts={"prefill": 2, "decode": 2},
            decode_expected_compiles=2)
        assert rule_decode(ctx) == []

    def test_unknown_count_not_flagged(self):
        ctx = StepContext(
            hlo_text="",
            decode_compile_counts={"prefill": None, "decode": 1})
        assert rule_decode(ctx) == []

    def test_mixed_cache_dtypes_is_error(self):
        ctx = StepContext(
            hlo_text="",
            decode_cache_census={"float32": 3, "bfloat16": 1})
        findings = rule_decode(ctx)
        assert [f.severity for f in findings] == [SEV_ERROR]

    def test_skipped_quantization_is_error(self):
        # configured int8 but the cache stores float32: the quantized
        # path silently never engaged
        ctx = StepContext(
            hlo_text="", decode_kv_cache_dtype="int8",
            decode_cache_census={"float32": 4})
        findings = rule_decode(ctx)
        assert len(findings) == 1
        assert findings[0].severity == SEV_ERROR
        assert "int8" in findings[0].message

    def test_honoured_quantization_passes(self):
        ctx = StepContext(
            hlo_text="", decode_kv_cache_dtype="int8",
            decode_cache_census={"int8": 4})
        assert rule_decode(ctx) == []


_PAYLOAD = (2, 32, 4, 8)
# A dense decode attention contraction: an operand dim multiset
# containing every cache payload dim (max_batch, max_seq, n_head,
# head_dim) in einsum-permuted order.
_DENSE_DOT = ("%dot.1 = f32[2,4,1,32]{3,2,1,0} dot(f32[2,4,1,8]{3,2,1,0} "
              "%a, f32[2,4,8,32]{3,2,1,0} %b), lhs_batch_dims={0,1}")
# A kernel-sized dot: block_k slices never carry all four payload dims.
_BLOCK_DOT = ("%dot.2 = f32[1,8]{1,0} dot(f32[1,8]{1,0} %q, "
              "f32[8,8]{1,0} %k)")


class TestRuleFlashDecode:
    def test_registered(self):
        assert "flash_decode" in RULE_IDS

    def test_skips_unless_flash_promised(self):
        ctx = StepContext(hlo_text=_DENSE_DOT,
                          decode_attention_impl="dense",
                          decode_cache_payload_shape=_PAYLOAD)
        assert rule_flash_decode(ctx) == []

    def test_surviving_dense_dot_is_error(self):
        ctx = StepContext(hlo_text=_DENSE_DOT + "\n" + _BLOCK_DOT,
                          decode_attention_impl="flash",
                          decode_cache_payload_shape=_PAYLOAD)
        findings = rule_flash_decode(ctx)
        assert [f.severity for f in findings] == [SEV_ERROR]
        assert "dense attention softmax survived" in findings[0].message
        assert findings[0].details["dots"] == [_DENSE_DOT]

    def test_block_sized_dots_pass(self):
        ctx = StepContext(hlo_text=_BLOCK_DOT,
                          decode_attention_impl="flash",
                          decode_cache_payload_shape=_PAYLOAD)
        assert rule_flash_decode(ctx) == []

    def test_f32_cache_copy_under_quantization_is_error(self):
        # a dequantized full-cache f32 value (dims ⊇ payload multiset)
        hlo = "%convert.9 = f32[2,32,4,8]{3,2,1,0} convert(s8[2,32,4,8] %c)"
        ctx = StepContext(hlo_text=hlo, decode_attention_impl="flash",
                          decode_kv_cache_dtype="int8",
                          decode_cache_payload_shape=_PAYLOAD)
        findings = rule_flash_decode(ctx)
        assert [f.severity for f in findings] == [SEV_ERROR]
        assert findings[0].details["f32_payload_values"] == 1

    def test_scale_planes_are_not_flagged(self):
        # per-head scales are f32[B, S, H] — no head_dim, not a copy
        hlo = "%p.3 = f32[2,32,4]{2,1,0} parameter(3)"
        ctx = StepContext(hlo_text=hlo, decode_attention_impl="flash",
                          decode_kv_cache_dtype="int8",
                          decode_cache_payload_shape=_PAYLOAD)
        assert rule_flash_decode(ctx) == []

    @pytest.mark.parametrize("hlo,n", [
        # XLA's relayout of the pool, layout and tiling after the shape
        ("%copy.7 = f32[2,32,4,8]{3,1,2,0:T(8,128)} copy(%p)\n"
         "%copy.8 = f32[2,32,4,8]{2,3,1,0:T(8,128)} copy(%copy.7)", 2),
        # other shapes and other ops of that shape are not it
        ("%copy.1 = f32[2,32,4]{2,1,0} copy(%s)\n"
         "%copy.2 = f32[8,32,8]{2,1,0} copy(%m)\n"
         "%fusion.3 = f32[2,32,4,8]{3,2,1,0} fusion(%p)", 0),
    ], ids=["relayout", "not-the-pool"])
    def test_pool_shaped_copy_is_error(self, hlo, n):
        ctx = StepContext(hlo_text=hlo, decode_attention_impl="flash",
                          decode_cache_payload_shape=_PAYLOAD)
        fs = rule_flash_decode(ctx)
        assert len(fs) == (1 if n else 0)
        if n:
            assert fs[0].severity == "error"
            assert fs[0].details["pool_shaped_copies"] == n

    @pytest.mark.parametrize("platform,n", [("tpu", 1), (None, 1),
                                            ("cpu", 0)])
    def test_pool_shaped_copy_is_judged_where_the_kernel_is_one(
            self, platform, n):
        # off-TPU the paged kernel is interpret mode's plain HLO, which
        # carries the pool (an `ANY`-memory operand) through its
        # emulated grid loop by copy: that says nothing about the chip
        hlo = ("%copy.7 = f32[2,32,4,8]{3,2,1,0} copy(%get-tuple-element.9)\n"
               "%k = f32[2,4,8]{2,1,0} custom-call(%p, %copy.7)")
        ctx = StepContext(hlo_text=hlo, decode_attention_impl="flash",
                          decode_platform=platform,
                          decode_cache_payload_shape=_PAYLOAD)
        assert len(rule_flash_decode(ctx)) == n

    def test_missing_custom_call_only_errors_on_tpu(self):
        ctx_cpu = StepContext(hlo_text=_BLOCK_DOT,
                              decode_attention_impl="flash",
                              decode_platform="cpu",
                              decode_cache_payload_shape=_PAYLOAD)
        assert rule_flash_decode(ctx_cpu) == []
        ctx_tpu = StepContext(hlo_text=_BLOCK_DOT,
                              decode_attention_impl="flash",
                              decode_platform="tpu",
                              decode_cache_payload_shape=_PAYLOAD)
        findings = rule_flash_decode(ctx_tpu)
        assert [f.severity for f in findings] == [SEV_ERROR]
        assert "custom-call" in findings[0].message


class TestAuditDecodeEndToEnd:
    def test_zero_findings(self):
        report = audit_decode()
        assert report.findings == []
        assert report.stats["compile_counts"] == \
            {"prefill": 1, "decode": 1}
        # five requests and the parked session's follow-up
        assert report.stats["completions"] == 6
        assert set(report.stats["finish_reasons"]) >= \
            {"max_new_tokens", "length"}
        # the stock decode flavor serves flash attention
        assert report.stats["attention"]["impl"] == "flash"

    def test_zero_findings_quantized(self):
        report = audit_decode(kv_cache_dtype="int8")
        assert report.findings == []
        assert report.stats["cache"]["dtype_census"] == {"int8": 4}
        assert report.stats["paging"]["prefix_hits"] >= 1

    @pytest.mark.slow
    def test_dense_fallback_still_audits_clean(self):
        # the oracle path keeps working under the same catalog — the
        # flash_decode rule is inert when dense is what was promised
        report = audit_decode(attention_impl="dense")
        assert report.findings == []
        assert report.stats["attention"]["impl"] == "dense"

    @pytest.mark.slow
    def test_flash_lowering_deleted_the_dense_work(self):
        """The acceptance pin, measured off the real lowered programs:
        dense decode carries payload-shaped attention dots (and, when
        quantized, f32 cache-sized dequant values); flash carries
        neither."""
        from deepspeed_tpu.analysis.hlo import (payload_shaped_dots,
                                                payload_shaped_values)
        dense = audit_decode(kv_cache_dtype="int8",
                             attention_impl="dense")
        flash = audit_decode(kv_cache_dtype="int8")
        assert len(payload_shaped_dots(dense.hlo_text, _PAYLOAD)) > 0
        assert payload_shaped_values(dense.hlo_text, "f32", _PAYLOAD) > 0
        assert payload_shaped_dots(flash.hlo_text, _PAYLOAD) == []
        assert payload_shaped_values(flash.hlo_text, "f32", _PAYLOAD) == 0


class TestAuditDecodePaged:
    """The pool's acceptance pin: audit_decode's stream
    exercises the whole admission ladder (radix hits, a parked session
    evacuated to host RAM, a resume that pages it back in) and the
    full rule catalog must still come back empty on the post-churn
    decode HLO — page tables are data, parking is host-side, the two
    compiled programs never change."""

    def test_zero_findings_paged_with_churn(self):
        report = audit_decode()
        assert report.findings == []
        assert report.stats["compile_counts"] == \
            {"prefill": 1, "decode": 1}
        assert report.stats["cache"]["page_size"] == 8
        pg = report.stats["paging"]
        assert pg["prefix_hits"] >= 1            # shared-prefix stream
        assert pg["sessions_resumed"] >= 1       # parked -> followed up
        assert pg["pages_evacuated"] >= 1        # host tier engaged
        assert pg["pages_paged_in"] >= 1
        assert pg["pages_free"] + pg["pages_resident"] == \
            pg["n_pages"] - 1                    # trash page accounting

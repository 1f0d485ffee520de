"""Roofline cost model (`deepspeed_tpu/analysis/cost.py`).

Absolute seconds from the datasheet constants are not the contract —
*rankings* between candidates lowered the same way are. The pins here
are the ones the autotuner's correctness rests on: chunked-ring overlap
never scores worse than blocking on the `pipeline_tp` flavor, the fp8
quantized wire moves fewer interconnect bytes than the same config at
full precision, and an over-budget static peak is a typed rejection,
not a score.
"""

import math

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis.audit import audit_engine, build_flavor_engine
from deepspeed_tpu.analysis.cost import (
    PLATFORMS,
    REJECT_PEAK_MEMORY,
    Platform,
    dot_flops,
    estimate_step_cost,
    resolve_platform,
)

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = [pytest.mark.filterwarnings("ignore::DeprecationWarning"),
              pytest.mark.full_compile]


def _audit(flavor, config_overrides=None):
    engine, batch = build_flavor_engine(
        flavor, config_overrides=config_overrides)
    report = audit_engine(engine, batch)
    sites = (report.stats.get("jaxpr") or {}).get(
        "collective_sites") or []
    return report, sites, engine.mesh.size


# ---------------------------------------------------------------------------
# dot_flops
# ---------------------------------------------------------------------------

def test_dot_flops_matmul_exact():
    """A single [8,16]x[16,32] matmul is 2*8*32*16 = 8192 FLOPs, on both
    the compiled text and the pre-optimization dump."""
    a = jnp.ones((8, 16), jnp.float32)
    b = jnp.ones((16, 32), jnp.float32)
    lowered = jax.jit(jnp.dot).lower(a, b)
    assert dot_flops(lowered.compile().as_text()) == 2 * 8 * 32 * 16
    assert dot_flops(lowered.as_text(dialect="hlo")) == 2 * 8 * 32 * 16


def test_dot_flops_grad_counts_both_passes():
    """value_and_grad of sum(a@b) adds the backward dgrad dot: the total
    strictly exceeds the forward-only count."""
    a = jnp.ones((8, 16), jnp.float32)
    b = jnp.ones((16, 32), jnp.float32)

    def loss(a, b):
        return jnp.sum(jnp.dot(a, b))

    fwd = dot_flops(jax.jit(jnp.dot).lower(a, b).compile().as_text())
    both = dot_flops(jax.jit(jax.grad(loss, argnums=(0, 1)))
                     .lower(a, b).compile().as_text())
    assert both > fwd


def test_dot_flops_scan_body_weighted_by_trips():
    """A dot inside a 5-trip scan counts 5x (same trip-aware accounting
    as the collective-bytes parser)."""
    w = jnp.ones((16, 16), jnp.float32)
    x = jnp.ones((5, 8, 16), jnp.float32)

    def f(w, xs):
        def body(carry, x):
            return carry + jnp.sum(jnp.dot(x, w)), None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        return out

    hlo = jax.jit(f).lower(w, x).compile().as_text()
    assert dot_flops(hlo) == 5 * 2 * 8 * 16 * 16


# ---------------------------------------------------------------------------
# platform table
# ---------------------------------------------------------------------------

def test_resolve_platform():
    assert resolve_platform("tpu_v5e") is PLATFORMS["tpu_v5e"]
    p = Platform("x", 1e12, 1e9, 1e9, 1e-6, 2 ** 30)
    assert resolve_platform(p) is p
    with pytest.raises(ValueError, match="tpu_v5e"):
        resolve_platform("tpu_v9000")


def test_platform_constants_sane():
    for p in PLATFORMS.values():
        assert p.flops_per_second > 0
        assert p.ici_bytes_per_second > 0
        assert p.ici_latency_seconds > 0
        assert p.hbm_bytes > 0


# ---------------------------------------------------------------------------
# ranking pins (the tuner's contract)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pipeline_tp_overlapped():
    return _audit("pipeline_tp")


@pytest.fixture(scope="module")
def pipeline_tp_blocking():
    return _audit("pipeline_tp", config_overrides={
        "tensor_parallel": {"overlap": {"enabled": False}}})


def test_chunked_overlap_scores_at_most_blocking(
        pipeline_tp_overlapped, pipeline_tp_blocking):
    """chunks=4 overlapped rings never rank WORSE than the blocking
    lowering of the same step: the SiteRecord-driven overlap credit
    must at least offset the extra per-chunk permute launches."""
    rep_o, sites_o, n = pipeline_tp_overlapped
    rep_b, sites_b, _ = pipeline_tp_blocking
    cost_o = estimate_step_cost(rep_o.hlo_text, n_devices=n,
                                collective_sites=sites_o)
    cost_b = estimate_step_cost(rep_b.hlo_text, n_devices=n,
                                collective_sites=sites_b)
    assert cost_o.overlap_chunks == 4
    assert cost_o.overlap_credit_seconds > 0
    assert cost_b.overlap_credit_seconds == 0
    assert cost_o.score <= cost_b.score
    assert cost_o.ok and cost_b.ok


def test_overlap_credit_only_discounts_permutes(pipeline_tp_overlapped):
    rep, sites, n = pipeline_tp_overlapped
    cost = estimate_step_cost(rep.hlo_text, n_devices=n,
                              collective_sites=sites)
    assert 0 < cost.exposed_interconnect_seconds <= \
        cost.interconnect_seconds
    assert cost.step_seconds == pytest.approx(
        cost.compute_seconds + cost.exposed_interconnect_seconds)
    # without the site records there is no credit
    bare = estimate_step_cost(rep.hlo_text, n_devices=n)
    assert bare.overlap_chunks == 1
    assert bare.overlap_credit_seconds == 0
    assert bare.score >= cost.score


@pytest.fixture(scope="module")
def fp8_pair():
    """The fp8 flavor (zero3 + quantized f8 gather wire) vs the same
    config with fp8 off (full-precision wire)."""
    with_fp8 = _audit("fp8")
    without = _audit("fp8", config_overrides={"fp8": {"enabled": False}})
    return with_fp8, without


@pytest.mark.slow
def test_fp8_wire_moves_fewer_interconnect_bytes(fp8_pair):
    (rep_f8, sites_f8, n), (rep_fp, sites_fp, _) = fp8_pair
    cost_f8 = estimate_step_cost(rep_f8.hlo_text, n_devices=n,
                                 collective_sites=sites_f8)
    cost_fp = estimate_step_cost(rep_fp.hlo_text, n_devices=n,
                                 collective_sites=sites_fp)
    assert cost_f8.wire_bytes < cost_fp.wire_bytes
    # the quantized wire shows up as 1-byte dtypes in the breakdown
    quant = sum(b for dt, b in cost_f8.wire_bytes_by_dtype.items()
                if dt.startswith(("u8", "s8", "f8")))
    assert quant > 0


@pytest.mark.slow
def test_over_budget_peak_is_typed_rejection(fp8_pair):
    (rep, sites, n), _ = fp8_pair
    cost = estimate_step_cost(rep.hlo_text, n_devices=n,
                              collective_sites=sites,
                              peak_budget_bytes=1)
    assert cost.reject_reason == REJECT_PEAK_MEMORY
    assert not cost.ok
    assert math.isinf(cost.score)
    assert cost.to_dict()["score"] is None
    # a generous budget scores normally
    ok = estimate_step_cost(rep.hlo_text, n_devices=n,
                            collective_sites=sites,
                            peak_budget_bytes=1 << 40)
    assert ok.ok and ok.score < math.inf

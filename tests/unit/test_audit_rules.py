"""Audit-rule pins (`deepspeed_tpu/analysis/`).

Two halves:

- zero-findings pins: every stock compiled-step flavor must audit clean,
  with full donation coverage — a future change that drops a
  ``donate_argnums`` (``donated_expected`` collapses to 0) or breaks
  aliasing/byte budgets fails here, in tier-1.
- seeded violations: each rule class is fed a program that *should*
  fail — a donation that doesn't alias, an fp32 all-reduce in a bf16
  context, a host callback inside the step, an unaccountable loop, a
  forced recompile — and must produce its finding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis import (
    AuditError,
    StepContext,
    audit_engine,
    audit_hlo,
    build_flavor_engine,
    check_recompile,
    donated_jit,
)
from deepspeed_tpu.analysis.audit import STEP_FLAVORS, _lower_step
from deepspeed_tpu.analysis.rules import (
    SEV_ERROR,
    SEV_WARNING,
    rule_donation,
    rule_peak_memory,
    rule_resharding,
    rule_trip_count,
)

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

# Donated buffers per flavor: params + opt m/v (+ dstate); a floor, not
# an exact count, so model tweaks don't churn the pin. The offload grad
# step donates only device_state (params stay, masters live on host).
_MIN_DONATED = {"dense": 8, "zero1": 8, "zero2": 8, "zero3": 8,
                "offload": 1, "quantized": 8, "pipeline": 8}


@pytest.mark.parametrize("flavor", STEP_FLAVORS)
def test_stock_flavor_audits_clean(flavor):
    engine, batch = build_flavor_engine(flavor)
    report = audit_engine(engine, batch)
    assert report.flavor == flavor
    assert report.findings == [], report.to_text()
    # donation pin: the flavor must still DECLARE donations (a dropped
    # donate_argnums empties the expectation and fails here) and every
    # declared one must alias.
    assert report.stats["donated_expected"] >= _MIN_DONATED[flavor]
    assert report.stats["donated_aliased"] == \
        report.stats["donated_expected"]
    assert report.stats["compile_cache_size"] == 1
    # the trace-time passes ran (not merely skipped) and came back clean
    assert report.stats["jaxpr"]["divergent_collectives"] == 0
    assert report.stats["jaxpr"]["unordered_permutes"] == 0
    # and the static peak estimate is populated for the memory rule
    assert report.stats["peak_memory"]["peak_bytes"] > 0
    if flavor == "pipeline":
        # the executed-1F1B loops must be statically accountable — this
        # is what makes the collective-permute volume pinnable at all.
        assert report.stats["while_loops"] >= 1
        assert report.stats["unknown_trip_counts"] == 0


def test_zero3_flavor_wire_volume_pins():
    """The gather-on-use stage-3 step (gather_chunks=2) must move params
    as ppermute ring stripes — per-leaf, per-layer — never as a bulk
    all-gather, and its total wire volume must stay inside the ZeRO
    paper's envelope."""
    engine, batch = build_flavor_engine("zero3")
    report = audit_engine(engine, batch)
    assert report.findings == [], report.to_text()
    plan = engine._zero3_plan
    assert plan is not None and plan.gather_chunks == 2
    assert plan.gather_leaves == 8       # 4 toy layers x (kernel, bias)
    cb = report.stats["collective_bytes"]
    m = report.stats["param_bytes"]
    # every gather became a ring: zero whole-leaf all-gathers remain
    assert cb.get("all-gather", 0) == 0, cb
    # ring volume = one param-sized pass (f32-widened worst case on the
    # CPU partitioner, which sinks the 16-bit cast through the permute)
    assert 0 < cb["collective-permute"] <= m + m // 4, (cb, m)
    # ring op count: leaves x chunks x (n_devices - 1) hops, counted
    # from a fresh lowering (report stats don't carry the HLO text)
    from deepspeed_tpu.analysis.audit import _engine_fn_args
    from deepspeed_tpu.analysis.hlo import collective_counts
    placed = engine._shard_batch(batch)
    fn, args = _engine_fn_args(engine, placed, jax.random.PRNGKey(0),
                               jnp.asarray(1e-3, jnp.float32))
    counts = collective_counts(fn.lower(*args).compile().as_text())
    n = 8
    assert counts.get("collective-permute", 0) == \
        plan.gather_leaves * plan.gather_chunks * (n - 1), counts
    # grand total inside the 3Psi-ish stage-3 budget the rule enforces
    assert cb["total"] <= int(3.2 * m), (cb, m)


def test_pipeline_permute_volume_trip_aware():
    """The 1F1B collective-permute rides inside while loops; flat
    counting used to see (at most) one tick of it."""
    engine, batch = build_flavor_engine("pipeline")
    report = audit_engine(engine, batch)
    aware = report.stats["collective_bytes"].get("collective-permute", 0)
    flat = report.stats["collective_bytes_flat"].get(
        "collective-permute", 0)
    assert aware > 0
    assert aware >= flat


# ---------------------------------------------------------------------------
# seeded violations — each rule must catch its class
# ---------------------------------------------------------------------------

def _toy_update(params, grads):
    return jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)


def test_dropped_donation_is_reported():
    params = {"w": jnp.ones((512, 512)), "b": jnp.ones((512,))}
    grads = jax.tree_util.tree_map(jnp.ones_like, params)

    donated = donated_jit(_toy_update, (0,))
    plain = jax.jit(_toy_update)     # the "regression": donation dropped
    _, expected, pinfo = _lower_step(donated, (params, grads))
    assert expected, "donated lowering must produce an expectation"
    hlo_plain = plain.lower(params, grads).compile().as_text()

    findings = rule_donation(StepContext(
        hlo_text=hlo_plain, expected_donated_params=expected,
        donated_param_info=pinfo,
        declared_donate_argnums=donated._ds_donate_argnums))
    assert len(findings) == 1 and findings[0].severity == SEV_ERROR
    assert findings[0].details["missing_count"] == len(expected)
    assert findings[0].details["missing_bytes"] >= 512 * 512 * 4


def test_f32_all_reduce_in_bf16_run_is_reported():
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
    mapped = shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
                       in_specs=(P("d"),), out_specs=P(None),
                       check_vma=False)
    # 64KB fp32 all-reduce, declared compute dtype bf16, no fp32-master
    # allowance (param_bytes=0): a silent upcast by construction.
    hlo = jax.jit(mapped).lower(
        jnp.ones((2, 8192), jnp.float32)).compile().as_text()
    report = audit_hlo(hlo, rules=["dtype_hygiene"], compute_dtype="bf16")
    assert any(f.rule == "dtype_hygiene" and f.severity == SEV_ERROR
               for f in report.findings), report.to_text()
    # the same program audits clean when the run really is fp32
    assert audit_hlo(hlo, rules=["dtype_hygiene"],
                     compute_dtype="f32").findings == []


@pytest.mark.parametrize("stage", [1, 2])
def test_f32_param_gather_at_zero12_is_the_upcast_it_now_is(stage):
    """Stages 1 and 2 gather the 16-bit copy of sharded masters
    (`zero/sharding.py:make_param_caster`), so a parameter-sized fp32
    all-gather in a bf16 step is no longer the masters' refresh but a
    silent upcast — except in the step kinds that keep replicated fp32
    masters, and in a program the CPU backend compiled (it re-widens
    the 16-bit gather)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    mesh = Mesh(np.array(jax.devices()[:4]), ("d",))

    def gather_of(dtype):
        mapped = shard_map(
            lambda x: jax.lax.all_gather(x, "d", axis=0, tiled=True),
            mesh=mesh, in_specs=(P("d"),), out_specs=P(None),
            check_vma=False)
        return jax.jit(mapped).lower(
            jnp.ones((512, 512), dtype)).compile().as_text()

    M = 512 * 512 * 4
    kw = dict(rules=["dtype_hygiene"], compute_dtype="bf16",
              zero_stage=stage, param_bytes=M, n_devices=4)
    report = audit_hlo(gather_of(jnp.float32), **kw)
    assert [f.details["family"] for f in report.findings] == \
        ["all-gather"], report.to_text()
    assert report.findings[0].details["f32_bytes"] == M
    # what stays allowed one parameter-sized fp32 gather
    for allowed in ({"platform": "cpu"}, {"comm_quantized": True},
                    {"pipeline": True}, {"flavor": "sparse"}):
        assert audit_hlo(gather_of(jnp.float32),
                         **kw, **allowed).findings == [], allowed
    # and the 16-bit gather is clean under the strict budget (as a
    # native-bf16 backend spells it; the CPU would re-widen it)
    bf16 = gather_of(jnp.float32).replace("f32[", "bf16[")
    assert "bf16[512,512]" in bf16 and "f32[" not in bf16
    assert audit_hlo(bf16, **kw).findings == []


def test_host_callback_in_step_is_reported():
    def on_host(x):
        return np.asarray(x) + 1.0

    @jax.jit
    def step(x):
        return jax.pure_callback(
            on_host, jax.ShapeDtypeStruct(x.shape, x.dtype), x) * 2.0

    hlo = step.lower(jnp.ones((16,))).compile().as_text()
    report = audit_hlo(hlo, rules=["host_transfer"])
    assert [f.rule for f in report.findings] == ["host_transfer"]
    assert report.findings[0].severity == SEV_ERROR


def test_unaccountable_loop_is_reported():
    synth = """\
HloModule synth, entry_computation_layout={(f32[64])->f32[64]}

%body.1 (p: f32[64]) -> f32[64] {
  %p = f32[64]{0} parameter(0)
  ROOT %ar = f32[64]{0} all-reduce(f32[64]{0} %p), to_apply=%add
}

%cond.1 (p: f32[64]) -> pred[] {
  %p2 = f32[64]{0} parameter(0)
  ROOT %lt = pred[] custom-call(), custom_call_target="dyn"
}

ENTRY %main (a: f32[64]) -> f32[64] {
  %a = f32[64]{0} parameter(0)
  ROOT %w = f32[64]{0} while(f32[64]{0} %a), condition=%cond.1, \
body=%body.1
}
"""
    findings = rule_trip_count(StepContext(hlo_text=synth))
    assert len(findings) == 1 and findings[0].rule == "trip_count"


def test_recompile_detected_and_raises_when_configured():
    engine, batch = build_flavor_engine("dense", config_overrides={
        "analysis": {"enabled": True, "fail_on_findings": True}})
    engine.train_batch(batch)
    # opt-in compile-time audit ran and was clean
    assert engine.last_audit_report is not None
    assert engine.last_audit_report.ok
    assert check_recompile(engine) == []

    # Aval drift: a weak-typed python lr instead of the engine's f32
    # array adds a second cache entry (donate copies so the engine's
    # own buffers survive the extra call).
    placed = engine._shard_batch(batch)
    copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
    engine._compiled_train_step(
        copy(engine.params), copy(engine.opt_state),
        copy(engine.device_state), placed, jax.random.PRNGKey(0), 0.001)
    assert [f.rule for f in check_recompile(engine)] == ["recompile"]
    with pytest.raises(AuditError, match="recompile"):
        engine.train_batch(batch)


def test_peak_memory_budget_violation_reported():
    """The per-stage budget formula: dense (stage 0) allows params +
    3M optimizer + 3M headroom; ZeRO-1 shards the optimizer term by N.
    An estimate past the budget is an error; under it, silence."""
    M = 10 << 20
    est = {"peak_bytes": 12 * M, "temp_peak_bytes": 11 * M,
           "parameter_bytes": M, "output_bytes": M,
           "donated_output_bytes": M}
    # stage 0 budget = M * (1 + 3 + 3) + slack = ~7M -> 12M violates
    findings = rule_peak_memory(StepContext(
        hlo_text="", param_bytes=M, zero_stage=0, peak_memory=est))
    assert len(findings) == 1 and findings[0].severity == SEV_ERROR
    assert findings[0].details["budget_bytes"] < 12 * M

    # same estimate under an explicit generous budget: clean
    assert rule_peak_memory(StepContext(
        hlo_text="", param_bytes=M, peak_memory=est,
        peak_budget_bytes=16 * M)) == []

    # ZeRO-1 over 8 devices tightens the optimizer term: a peak that
    # fits the stage-0 budget can still violate the stage-1 one.
    est_ok0 = dict(est, peak_bytes=5 * M, temp_peak_bytes=4 * M)
    assert rule_peak_memory(StepContext(
        hlo_text="", param_bytes=M, zero_stage=0,
        peak_memory=est_ok0)) == []
    assert rule_peak_memory(StepContext(
        hlo_text="", param_bytes=M, zero_stage=1, n_devices=8,
        peak_memory=est_ok0))

    # no estimate / no param baseline: rule not applicable
    assert rule_peak_memory(StepContext(hlo_text="", param_bytes=M)) == []
    assert rule_peak_memory(StepContext(hlo_text="",
                                        peak_memory=est)) == []


def test_replicated_optimizer_state_reported_under_zero():
    """A ZeRO run whose optimizer state holds large fully-replicated
    leaves is paying stage-0 memory while claiming otherwise."""
    leaves = [{"path": ".m.w", "bytes": 4 << 20, "shape": [1024, 1024]}]
    findings = rule_resharding(StepContext(
        hlo_text="", zero_stage=2, n_devices=8,
        replicated_leaves=leaves))
    assert len(findings) == 1 and findings[0].severity == SEV_ERROR
    assert findings[0].details["total_bytes"] == 4 << 20
    # same leaves are legitimate on a single device or at stage 0
    assert rule_resharding(StepContext(
        hlo_text="", zero_stage=0, n_devices=8,
        replicated_leaves=leaves)) == []
    assert rule_resharding(StepContext(
        hlo_text="", zero_stage=2, n_devices=1,
        replicated_leaves=leaves)) == []
    # and small replicated leaves are the partitioner's own choice
    assert rule_resharding(StepContext(
        hlo_text="", zero_stage=2, n_devices=8,
        replicated_leaves=[{"path": ".m.b", "bytes": 4096,
                            "shape": [1024]}])) == []


def test_reshard_conflicts_below_threshold_are_noise():
    events = [{"kind": "conflict", "bytes": 4096, "path": [],
               "primitive": "add", "dim": 0, "specs": []}]
    assert rule_resharding(StepContext(
        hlo_text="", reshard_events=events)) == []
    findings = rule_resharding(StepContext(
        hlo_text="", reshard_events=[dict(events[0], bytes=2 << 20)]))
    assert findings and findings[0].severity == SEV_WARNING


def test_zero3_upfront_full_gather_is_reported():
    """A stage-3 program that all-gathers the whole param tree in one op
    (the spec-sharded regression the explicit schedule exists to
    prevent) must trip the per-leaf gather allowance; a layer-by-layer
    schedule of the declared shape audits clean."""
    M = 1 << 20   # fp32 master bytes
    leaf = 64 << 10   # largest declared per-leaf gather (compute dtype)
    # one monolithic bf16 gather moving ~the whole tree at once
    upfront = """
  %ag = bf16[524288]{0} all-gather(bf16[65536]{0} %p0)
"""
    report = audit_hlo(upfront, rules=["zero_budget"], zero_stage=3,
                       param_bytes=M, n_devices=8,
                       zero3_gather_leaves=8, zero3_gather_chunks=1,
                       zero3_max_gather_bytes=leaf)
    assert any("up-front full-param gather" in f.message
               and f.severity == SEV_ERROR
               for f in report.findings), report.to_text()

    # eight per-leaf gathers of the declared size: clean
    per_leaf = "".join(
        f"\n  %ag{i} = bf16[32768]{{0}} all-gather(bf16[4096]{{0}} %p{i})"
        for i in range(8))
    assert audit_hlo(per_leaf, rules=["zero_budget"], zero_stage=3,
                     param_bytes=M, n_devices=8,
                     zero3_gather_leaves=8, zero3_gather_chunks=1,
                     zero3_max_gather_bytes=leaf).findings == []

    # fewer gather-family ops than declared leaves: the schedule was
    # coalesced away — reported even when each op is small enough.
    coalesced = """
  %ag = bf16[32768]{0} all-gather(bf16[4096]{0} %p0)
"""
    report = audit_hlo(coalesced, rules=["zero_budget"], zero_stage=3,
                       param_bytes=M, n_devices=8,
                       zero3_gather_leaves=8, zero3_gather_chunks=1,
                       zero3_max_gather_bytes=leaf)
    assert any(f.severity == SEV_ERROR for f in report.findings), \
        report.to_text()


def test_zero3_ring_chunking_must_reach_hlo():
    """gather_chunks > 1 promises ppermute ring stripes; a lowered step
    with no collective-permutes regressed to monolithic gathers."""
    no_rings = """
  %ag = bf16[32768]{0} all-gather(bf16[4096]{0} %p0)
"""
    report = audit_hlo(no_rings, rules=["overlap"], zero_stage=3,
                       n_devices=8, zero3_gather_leaves=8,
                       zero3_gather_chunks=2,
                       zero3_max_gather_bytes=64 << 10)
    assert any(f.rule == "overlap" and f.severity == SEV_ERROR
               for f in report.findings), report.to_text()
    # chunks=1 promises no rings: nothing to check
    assert audit_hlo(no_rings, rules=["overlap"], zero_stage=3,
                     n_devices=8, zero3_gather_leaves=8,
                     zero3_gather_chunks=1,
                     zero3_max_gather_bytes=64 << 10).findings == []


def test_zero3_registered_gather_sites_exempt_resharding():
    """Satellite contract: conflict-sized reshard events attributable to
    the *registered* zero3 gather/re-shard schedule (SiteRecord log) are
    exempt; the same events on a stage-3 trace that registered NO zero3
    sites still fire — an unregistered gather is exactly the regression
    the rule polices."""
    leaf = 2 << 20   # declared max per-leaf gather, above the rule's
    # 1MB conflict-noise threshold so the events are reportable at all
    events = [{"kind": "conflict", "bytes": leaf, "path": [],
               "primitive": "dot_general", "dim": 0, "specs": []}]
    sites = [{"site": "zero3_gather", "axis": "data",
              "primitive": "all_gather", "chunks": 1, "hops": 1,
              "chained": True}]
    # registered: attributed and exempt
    assert rule_resharding(StepContext(
        hlo_text="", zero_stage=3, n_devices=8,
        zero3_max_gather_bytes=leaf,
        collective_sites=sites, reshard_events=events)) == []
    # same events, no zero3 sites in the trace: fires
    findings = rule_resharding(StepContext(
        hlo_text="", zero_stage=3, n_devices=8,
        zero3_max_gather_bytes=leaf,
        collective_sites=[], reshard_events=events))
    assert findings and findings[0].severity == SEV_WARNING
    # registered but the event is bigger than the declared schedule
    # accounts for: still fires
    big = [dict(events[0], bytes=4 * leaf)]
    findings = rule_resharding(StepContext(
        hlo_text="", zero_stage=3, n_devices=8,
        zero3_max_gather_bytes=leaf,
        collective_sites=sites, reshard_events=big))
    assert findings and findings[0].severity == SEV_WARNING


def test_unknown_rule_id_rejected_by_config():
    params = {"w": jnp.ones((8, 8))}
    with pytest.raises((ValueError, AssertionError),
                       match="unknown rule id"):
        deepspeed_tpu.initialize(
            config={"train_batch_size": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "analysis": {"enabled": True, "rules": ["no_such"]}},
            loss_fn=lambda p, b, rng=None: jnp.sum(p["w"]),
            params=params)


_M = 4 << 20        # fp32 master bytes of a 1,048,576-parameter model
_REFRESH = "\n  %ag = f32[1048576]{0} all-gather(f32[262144]{0} %p)"


@pytest.mark.parametrize("spelling,hlo,severities", [
    # CPU: the whole fp32 gradient in one all-reduce (then a slice)
    ("all-reduce",
     "\n  %ar = f32[1048576]{0} all-reduce(f32[1048576]{0} %g)",
     []),
    # the op by its own name: each device keeps a 1/4 shard, in bf16
    ("reduce-scatter",
     "\n  %rs = bf16[262144]{0} reduce-scatter(bf16[1048576]{0} %g)",
     []),
    # v5e (PR 21's four-chip run): a ring of N-1 = 3 shard-sized bf16
    # permutes and no op named reduce-scatter
    ("permute-ring",
     "".join(f"\n  %cp{i} = bf16[262144]{{0}} collective-permute("
             f"bf16[262144]{{0}} %s{i})" for i in range(3)),
     []),
    ("missing", "", [SEV_WARNING]),
    ("doubled-ring",
     "".join(f"\n  %cp{i} = f32[262144]{{0}} collective-permute("
             f"f32[262144]{{0}} %s{i})" for i in range(6)),
     [SEV_ERROR]),
])
def test_zero2_gradient_exchange_in_every_spelling(spelling, hlo,
                                                   severities):
    """`zero_budget` holds a stage-2 step to ONE gradient exchange and
    one refresh gather whatever XLA calls the exchange: the rule used
    to add up all-reduce and reduce-scatter outputs only, so on the v5e
    it saw 2.7 MB of a 590 MB exchange and warned that the sync was
    missing."""
    report = audit_hlo(hlo + _REFRESH, rules=["zero_budget"],
                       zero_stage=2, param_bytes=_M, n_devices=4,
                       compute_dtype="bf16")
    assert [f.severity for f in report.findings] == severities, \
        report.to_text()

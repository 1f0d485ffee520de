"""Trace-time pass pins (`deepspeed_tpu/analysis/jaxpr.py`).

Three halves:

- synthetic programs: each jaxpr pass is fed a minimal shard_map program
  that *should* fail (a ppermute under a `lax.cond` whose predicate
  derives from ``axis_index``; two concurrent un-chained ppermutes) and
  a near-identical one that shouldn't (uniform predicate; taint erased
  by a psum; the ``barrier_after`` chain) — the rule must separate them.
- the PR 5 regression, through the production code path:
  ``pipeline_trace_fixture`` rebuilds the pre-fix stage-divergent /
  un-chained tick schedules inside the real 1F1B step, and the passes
  must flag both at trace time WITHOUT executing (the failure mode is a
  hang, so these programs are traced and never run).
- rule plumbing: the jaxpr facts reach ``rule_deadlock`` /
  ``rule_resharding`` through :class:`StepContext` fields.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.analysis import audit as A
from deepspeed_tpu.analysis.jaxpr import (
    check_divergent_collectives,
    check_unordered_permutes,
    collect_collectives,
    input_specs_of,
    propagate_partition_specs,
    trace_jaxpr,
)
from deepspeed_tpu.analysis.rules import (
    SEV_ERROR,
    StepContext,
    rule_deadlock,
    rule_resharding,
)
from deepspeed_tpu.parallel.collectives import (
    barrier_after,
    record_collective_sites,
)
from deepspeed_tpu.runtime.pipe import pipeline as pl
from jax import shard_map


def _mesh():
    return Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                ("pipe", "data"))


def _trace(fn, *args):
    return trace_jaxpr(fn, args)


# ---------------------------------------------------------------------------
# divergent-collective detection (synthetic)
# ---------------------------------------------------------------------------

def test_divergent_ppermute_flagged():
    """The PR 5 bug in miniature: a ppermute inside a branch selected by
    ``axis_index`` strands part of its global rendezvous."""
    mesh = _mesh()

    def f(x):
        def inner(x):
            s = lax.axis_index("pipe")
            def send(x):
                return lax.ppermute(x, "pipe", [(0, 1), (1, 0)])
            return lax.cond(s == 0, send, lambda x: x, x)
        return shard_map(inner, mesh=mesh, in_specs=P("pipe"),
                         out_specs=P("pipe"), check_vma=False)(x)

    findings = check_divergent_collectives(_trace(f, jnp.zeros((8, 4))))
    assert findings, "divergent ppermute must be flagged"
    assert findings[0]["kind"] == "deadlock"
    assert findings[0]["primitive"] == "ppermute"
    assert "pipe" in findings[0]["divergent_axes"]


def test_divergent_psum_over_other_axis_clean():
    """How the seed 'got away with it': a grouped collective whose axis
    the divergence does NOT split still has a full replica group on
    every branch — no finding."""
    mesh = _mesh()

    def f(x):
        def inner(x):
            s = lax.axis_index("pipe")
            return lax.cond(s == 0, lambda x: lax.psum(x, "data"),
                            lambda x: x, x)
        return shard_map(inner, mesh=mesh, in_specs=P("pipe", "data"),
                         out_specs=P("pipe", None), check_vma=False)(x)

    assert check_divergent_collectives(_trace(f, jnp.zeros((8, 4)))) == []


def test_divergent_psum_over_same_axis_flagged():
    mesh = _mesh()

    def f(x):
        def inner(x):
            s = lax.axis_index("pipe")
            return lax.cond(s == 0, lambda x: lax.psum(x, "pipe"),
                            lambda x: x, x)
        return shard_map(inner, mesh=mesh, in_specs=P("pipe"),
                         out_specs=P(None), check_vma=False)(x)

    assert check_divergent_collectives(_trace(f, jnp.zeros((8, 4))))


def test_uniform_cond_clean():
    """Branching on a scalar *argument* is uniform across devices — a
    collective inside is safe."""
    mesh = _mesh()

    def f(x, flag):
        def inner(x, flag):
            def send(x):
                return lax.ppermute(x, "pipe", [(0, 1), (1, 0)])
            return lax.cond(flag > 0, send, lambda x: x, x)
        return shard_map(inner, mesh=mesh, in_specs=(P("pipe"), P()),
                         out_specs=P("pipe"), check_vma=False)(x, flag)

    closed = _trace(f, jnp.zeros((8, 4)), jnp.int32(1))
    assert check_divergent_collectives(closed) == []


def test_taint_erased_by_psum_clean():
    """``psum(axis_index(a), a)`` is the same value everywhere — the
    reduction launders the device-varying taint."""
    mesh = _mesh()

    def f(x):
        def inner(x):
            s = lax.psum(lax.axis_index("pipe"), "pipe")
            def send(x):
                return lax.ppermute(x, "pipe", [(0, 1), (1, 0)])
            return lax.cond(s > 0, send, lambda x: x, x)
        return shard_map(inner, mesh=mesh, in_specs=P("pipe"),
                         out_specs=P("pipe"), check_vma=False)(x)

    assert check_divergent_collectives(_trace(f, jnp.zeros((8, 4)))) == []


def test_divergent_while_trip_count_flagged():
    """A while loop whose trip count depends on ``axis_index`` runs a
    different number of iterations per device — any collective in its
    body rendezvouses a different number of times."""
    mesh = _mesh()

    def f(x):
        def inner(x):
            s = lax.axis_index("pipe")
            def cond(c):
                i, _ = c
                return i < s + 1
            def body(c):
                i, x = c
                return i + 1, lax.psum(x, "data")
            return lax.while_loop(cond, body, (jnp.int32(0), x))[1]
        return shard_map(inner, mesh=mesh, in_specs=P("pipe", "data"),
                         out_specs=P("pipe", None), check_vma=False)(x)

    assert check_divergent_collectives(_trace(f, jnp.zeros((8, 4))))


# ---------------------------------------------------------------------------
# unordered-permute detection (synthetic)
# ---------------------------------------------------------------------------

def _two_permutes(chain):
    mesh = _mesh()

    def f(xy):
        x, y = xy

        def inner(x, y):
            a = lax.ppermute(x, "pipe", [(0, 1), (1, 0)])
            src = barrier_after(y, a) if chain else y
            b = lax.ppermute(src, "pipe", [(0, 1), (1, 0)])
            return a + b
        return shard_map(inner, mesh=mesh, in_specs=(P("pipe"), P("pipe")),
                         out_specs=P("pipe"), check_vma=False)(x, y)

    x = jnp.zeros((8, 4))
    return _trace(f, (x, x))


def test_unordered_concurrent_permutes_flagged():
    findings = check_unordered_permutes(_two_permutes(chain=False))
    assert findings, "concurrent un-chained ppermutes must be flagged"
    assert findings[0]["kind"] == "unordered_permutes"


def test_barrier_after_chain_clean():
    """The ``barrier_after`` invariant, checked instead of assumed: the
    optimization_barrier edge makes the second permute an ancestor-
    ordered successor of the first."""
    assert check_unordered_permutes(_two_permutes(chain=True)) == []


def test_collect_collectives_inventory():
    sites = collect_collectives(_two_permutes(chain=False))
    permutes = [s for s in sites if s.primitive == "ppermute"]
    assert len(permutes) == 2
    assert all(s.axes == ("pipe",) for s in permutes)


# ---------------------------------------------------------------------------
# sharding-flow lint (synthetic)
# ---------------------------------------------------------------------------

def test_spec_conflict_detected():
    mesh = _mesh()
    a = jax.device_put(jnp.ones((8, 8)),
                       NamedSharding(mesh, P("pipe", None)))
    b = jax.device_put(jnp.ones((8, 8)),
                       NamedSharding(mesh, P("data", None)))
    closed = _trace(lambda a, b: a * b, a, b)
    specs = input_specs_of((a, b))
    _, events = propagate_partition_specs(closed, specs)
    assert len(events) == 1 and events[0].kind == "conflict"

    # and the rule turns a big-enough conflict into a finding
    ctx = StepContext(hlo_text="", reshard_events=[
        {"kind": "conflict", "bytes": 2 << 20, "path": [],
         "primitive": "mul", "dim": 0, "specs": []}])
    findings = rule_resharding(ctx)
    assert [f.rule for f in findings] == ["resharding"]


def test_matching_specs_clean_and_propagated():
    mesh = _mesh()
    sh = NamedSharding(mesh, P("pipe", None))
    a = jax.device_put(jnp.ones((8, 8)), sh)
    b = jax.device_put(jnp.ones((8, 8)), sh)
    closed = _trace(lambda a, b: a * b, a, b)
    out, events = propagate_partition_specs(closed, input_specs_of((a, b)))
    assert events == []
    assert out[0] == (("pipe",), None)


# ---------------------------------------------------------------------------
# the PR 5 regression, through the production 1F1B step
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pipeline_step():
    """The real pipeline flavor's compiled-step callable + exact args
    (compile paid once for the whole module)."""
    engine, batch = A.build_flavor_engine("pipeline")
    engine.train_batch(batch)
    placed = engine._shard_batch(batch)
    fn, args = A._engine_fn_args(
        engine, placed, jax.random.PRNGKey(0),
        jnp.asarray(1e-3, jnp.float32))
    return fn, args


def test_pipeline_baseline_traces_clean_with_chained_sites(pipeline_step):
    fn, args = pipeline_step
    facts = A._jaxpr_facts(fn, args)
    assert facts["divergent"] == []
    assert facts["unordered"] == []
    transfers = [s for s in facts["collective_sites"]
                 if s["site"] == "pipeline.stage_transfer"]
    assert transfers, "stage transfers must self-report their site"
    assert all(s["chained"] for s in transfers)


def test_stage_divergent_transfer_flagged_without_executing(pipeline_step):
    """Re-introduce the PR 5 deadlock (transfer gated on ``valid_f``,
    which derives from ``axis_index('pipe')``) and prove the analyzer
    catches it from the trace alone — the program is NEVER run."""
    fn, args = pipeline_step
    with pl.pipeline_trace_fixture(divergent_transfer=True):
        closed = trace_jaxpr(fn, args)
    findings = check_divergent_collectives(closed)
    assert findings, "stage-divergent transfer must be flagged"
    assert any(d["primitive"] == "ppermute"
               and "pipe" in d["divergent_axes"] for d in findings)

    # and rule_deadlock surfaces them as error findings
    rf = rule_deadlock(StepContext(hlo_text="", jaxpr_divergent=findings))
    assert rf and all(f.rule == "deadlock" and f.severity == SEV_ERROR
                      for f in rf)


def test_unchained_transfer_flagged_without_executing(pipeline_step):
    """Drop the ``barrier_after``/optimization_barrier dep-chain between
    the forward and backward stage transfers: the permute-ordering pass
    must flag the race, and the site log must record the confession."""
    fn, args = pipeline_step
    with pl.pipeline_trace_fixture(unchained_transfer=True):
        with record_collective_sites() as sites:
            closed = trace_jaxpr(fn, args)
    assert check_unordered_permutes(closed), \
        "un-chained concurrent stage transfers must be flagged"
    unchained = [s for s in sites if not s.chained]
    assert unchained, "site log must record chained=False"

    # the unchained_site clause of rule_deadlock fires on the records
    import dataclasses
    rf = rule_deadlock(StepContext(
        hlo_text="",
        collective_sites=[dataclasses.asdict(s) for s in unchained]))
    assert rf and rf[0].details["kind"] == "unchained_site"


def test_fixture_restores_production_schedule(pipeline_step):
    """The fixture is scoped: after the context exits, a fresh trace is
    clean again (no leaked module state)."""
    fn, args = pipeline_step
    with pl.pipeline_trace_fixture(divergent_transfer=True):
        pass
    closed = trace_jaxpr(fn, args)
    assert check_divergent_collectives(closed) == []
    assert check_unordered_permutes(closed) == []

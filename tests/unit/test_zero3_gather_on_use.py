"""Explicit ZeRO-3 gather-on-use schedule (`zero/stage3.py`).

Four contracts:

- ``gather_chunks=1`` is bit-identical to the legacy spec-sharded
  caster (`zero/sharding.py:make_param_caster`) — same losses, same
  params, step for step: the explicit path only pins *placement*.
- ``gather_chunks>1`` replaces every whole-leaf all-gather with
  ppermute ring stripes (pinned in the compiled HLO) while matching
  the legacy numerics to float precision.
- the backward *re-gathers*: the remat policy drops the gathered
  16-bit copies at the fwd/bwd boundary, so the pre-optimization
  StableHLO carries 2x leaves all_gathers (one forward pass + one
  backward recompute, kept apart by remat's optimization_barriers)
  and the jaxpr carries the ``zero3_gathered`` checkpoint_name tags
  that make the drop targetable. Pinned pre-optimization because the
  CPU backend strips the barriers and CSEs the recompute away — on
  TPU the barriers survive.
- both emitters confess to the trace-time ``SiteRecord`` log
  (``zero3_gather`` / ``zero3_reshard``) — what the audit's
  deadlock/resharding attribution runs on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.hlo import collective_bytes, collective_counts
from deepspeed_tpu.analysis.jaxpr import trace_jaxpr
from deepspeed_tpu.parallel.collectives import record_collective_sites
from deepspeed_tpu.runtime.zero.stage3 import GATHERED_NAME
from tests.unit.simple_model import base_config
from tests.unit.zero_fixtures import init_params, loss_fn, make_batch

N_DEV = 8


def build_engine3(**zero_overrides):
    zo = {"stage": 3}
    zo.update(zero_overrides)
    cfg = base_config(train_batch_size=16, bf16={"enabled": True},
                      zero_optimization=zo)
    params = init_params(jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg, loss_fn=loss_fn, params=params)
    return engine


def _param_leaves(engine):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(engine.params)]


def _step_fn_args(engine, batch):
    placed = engine._shard_batch(batch)
    return engine._compiled_train_step, (
        engine.params, engine.opt_state, engine.device_state, placed,
        jax.random.PRNGKey(1), jnp.asarray(1e-3, jnp.float32))


def test_chunks1_bit_identical_to_legacy_caster():
    b = make_batch()
    legacy = build_engine3(gather_on_use=False)
    explicit = build_engine3()   # gather_on_use defaults True, chunks 1
    for _ in range(3):
        l_old = float(legacy.train_batch(b))
        l_new = float(explicit.train_batch(b))
        assert l_old == l_new, (l_old, l_new)
    plan = explicit._zero3_plan
    assert plan is not None
    assert plan.gather_chunks == 1 and plan.prefetch
    assert plan.gather_leaves == 16      # 8 layers x (kernel, bias)
    assert legacy._zero3_plan is None    # legacy path declares no plan
    for a, b_ in zip(_param_leaves(legacy), _param_leaves(explicit)):
        assert np.array_equal(a, b_)


@pytest.mark.full_compile
def test_chunked_rings_match_legacy_and_lower_to_permutes():
    b = make_batch()
    legacy = build_engine3(gather_on_use=False)
    ringed = build_engine3(gather_chunks=2)
    for _ in range(3):
        l_old = float(legacy.train_batch(b))
        l_new = float(ringed.train_batch(b))
        assert l_old == pytest.approx(l_new, rel=1e-6), (l_old, l_new)
    for a, b_ in zip(_param_leaves(legacy), _param_leaves(ringed)):
        assert np.allclose(a, b_, rtol=2e-5, atol=1e-6)
    plan = ringed._zero3_plan
    assert plan is not None and plan.gather_chunks == 2

    fn, args = _step_fn_args(ringed, b)
    hlo = fn.lower(*args).compile().as_text()
    counts = collective_counts(hlo)
    # every whole-leaf gather became ring stripes:
    # leaves x chunks x (n-1) hops, and zero all-gathers remain
    assert counts.get("all-gather", 0) == 0, counts
    assert counts.get("collective-permute", 0) == \
        plan.gather_leaves * plan.gather_chunks * (N_DEV - 1), counts
    # ring wire volume stays a single param-sized pass (f32-widened
    # worst case — the CPU partitioner sinks the cast into the ring)
    v = collective_bytes(hlo)
    m = plan.total_gather_bytes * 2      # fp32 bytes of gathered leaves
    assert 0 < v["collective-permute"] <= 2 * m, (v, m)


def test_backward_regathers_at_jaxpr_level():
    b = make_batch()
    engine = build_engine3()
    engine.train_batch(b)
    fn, args = _step_fn_args(engine, b)
    with record_collective_sites() as sites:
        closed = trace_jaxpr(fn, args)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for val in eqn.params.values():
                for v in val if isinstance(val, (list, tuple)) else [val]:
                    if hasattr(v, "jaxpr"):        # ClosedJaxpr
                        yield from walk(v.jaxpr)
                    elif hasattr(v, "eqns"):       # raw Jaxpr
                        yield from walk(v)

    eqns = list(walk(closed.jaxpr))
    leaves = engine._zero3_plan.gather_leaves
    gathers = [e for e in eqns if e.primitive.name == "all_gather"]
    # forward schedule: exactly one gather per sharded leaf — no bulk
    # up-front gather (the backward recompute stays abstract inside the
    # remat eqn at this level; it is pinned below, pre-optimization)
    assert len(gathers) == leaves, len(gathers)
    remats = [e for e in eqns if e.primitive.name.startswith("remat")
              and e.params.get("differentiated")]
    assert remats, "gathered-params remat boundary missing from the step"
    tags = [e for e in eqns if e.primitive.name == "name"
            and e.params.get("name") == GATHERED_NAME]
    assert len(tags) >= leaves, len(tags)

    # backward re-gather, pinned where it is backend-independent: the
    # pre-optimization StableHLO carries forward + recompute gathers,
    # separated by the remat's CSE-prevention barriers. (The CPU
    # backend strips the barriers and CSEs the recompute back into the
    # forward; a native-16-bit backend keeps both passes.)
    txt = fn.lower(*args).as_text()
    assert txt.count("all_gather") == 2 * leaves, \
        txt.count("all_gather")
    assert txt.count("optimization_barrier") >= leaves

    # trace-time confession: the gather and re-shard emitters registered
    kinds = {(s.site, s.primitive) for s in sites}
    assert ("zero3_gather", "all_gather") in kinds, kinds
    assert ("zero3_reshard", "reduce_scatter") in kinds, kinds


def test_ring_site_records_register_chunking():
    b = make_batch()
    engine = build_engine3(gather_chunks=2)
    engine.train_batch(b)
    fn, args = _step_fn_args(engine, b)
    with record_collective_sites() as sites:
        trace_jaxpr(fn, args)
    rings = [s for s in sites
             if s.site == "zero3_gather" and s.primitive == "ppermute"]
    assert rings, [(s.site, s.primitive) for s in sites]
    assert all(s.chunks == 2 and s.hops == N_DEV - 1 and s.chained
               for s in rings)


@pytest.mark.parametrize("overrides,match", [
    ({"gather_chunks": 0}, "gather_chunks"),
    ({"gather_chunks": -2}, "gather_chunks"),
    ({"gather_chunks": True}, "gather_chunks"),
    ({"gather_chunks": 2, "prefetch": False}, "requires prefetch"),
    ({"gather_chunks": 2, "gather_on_use": False},
     "requires gather_on_use"),
    ({"gather_on_use": "yes"}, "must be a bool"),
    ({"bidirectional": 1}, "must be a bool"),
])
def test_zero3_config_validation(overrides, match):
    zo = {"stage": 3}
    zo.update(overrides)
    cfg = base_config(train_batch_size=16, bf16={"enabled": True},
                      zero_optimization=zo)
    with pytest.raises(ValueError, match=match):
        deepspeed_tpu.initialize(
            config=cfg, loss_fn=loss_fn,
            params=init_params(jax.random.PRNGKey(0)))

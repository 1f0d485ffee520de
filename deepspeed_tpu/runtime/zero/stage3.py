"""Explicit ZeRO-3: gather-on-use parameter sharding with prefetch.

The GSPMD spec-sharded stage 3 (`zero/sharding.py:make_param_caster`)
leaves gather *placement* to XLA: nothing stops the scheduler from
hoisting every param all-gather to the top of the step (peak = all
gathered copies live at once) and nothing re-gathers in the backward —
XLA saves the gathered 16-bit copies as residuals, paying the gathered
footprint across the whole fwd+bwd interval. This module pins the
schedule instead (the DeepCompile argument, arXiv:2504.09983):

- one ``shard_map`` over ALL sharded leaves runs per-leaf cast-then-
  gathers through :func:`parallel.collectives.ring_all_gather`,
  dep-chained in leaf order — leaf *i+1*'s gather issues behind leaf
  *i*'s (the prefetch schedule), and with ``gather_chunks > 1`` each
  leaf moves as ppermute ring stripes that interleave with the
  consuming matmuls;
- every gathered leaf is tagged :func:`jax.ad_checkpoint.checkpoint_name`
  so the engine's remat policy (:func:`zero3_remat_policy`) drops the
  gathered copy at the fwd/bwd boundary and the backward *re-gathers*
  from the always-live fp32 shards — the gathered footprint is
  per-use, never saved;
- the ``custom_vjp`` backward casts the compute-dtype cotangents to
  fp32 and constrains them straight back to the sharded layout
  (GSPMD lowers that to the reduce-scatter; an explicit in-graph
  ``psum_scatter`` would double-count — at the jit level the cotangent
  is one logical array, and GSPMD would materialize it with its own
  all-reduce first) — the full fp32 param gradient never exists
  replicated;
- both emitters register in the PR 6 ``SiteRecord`` trace-time log
  (sites ``zero3_gather`` / ``zero3_reshard``) so the audit's
  deadlock/resharding rules can attribute the traffic.

``gather_chunks=1`` lowers each leaf to the same tiled ``all_gather``
as the legacy caster — bit-identical numerics, schedule still pinned.
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.parallel.collectives import (
    log_collective_site,
    ring_all_gather,
)
from jax import shard_map

# The checkpoint_name tag on every gathered leaf; the remat policy
# excludes exactly this name from the saved residuals.
GATHERED_NAME = "zero3_gathered"


def zero3_remat_policy():
    """Remat policy for the stage-3 step: save every residual EXCEPT the
    gathered 16-bit params. Forward activations stay saved (no compute
    is re-done beyond the gathers); the backward re-gathers each leaf
    from its fp32 shard right where the transposed matmul needs it."""
    return jax.checkpoint_policies.save_anything_except_these_names(
        GATHERED_NAME)


@dataclasses.dataclass(frozen=True)
class Zero3Plan:
    """Static facts about the gather-on-use schedule, produced next to
    the caster and consumed by the audit (`analysis/audit.py` feeds them
    into ``StepContext`` so `analysis/rules.py` can pin per-leaf gather
    sizes/counts against the HLO)."""
    gather_leaves: int           # sharded leaves gathered per use
    gather_chunks: int           # ring stripes per leaf (1 = all-gather)
    prefetch: bool               # dep-chained leaf order
    bidirectional: bool          # alternate ring direction per stripe
    max_gather_bytes: int        # largest single gathered leaf (compute dtype)
    total_gather_bytes: int      # all gathered leaves (compute dtype)
    wire_dtype: str = None       # codec name when gathers move quantized

    def to_dict(self):
        return dataclasses.asdict(self)


def make_gather_on_use_caster(params, param_shardings, mesh, dtype,
                              axis="data", chunks=1, prefetch=True,
                              bidirectional=False, wire_dtype=None,
                              wire_chunk=512):
    """``(cast, Zero3Plan)`` for the explicit stage-3 step, or
    ``(None, None)`` when nothing is sharded over ``axis`` (callers keep
    the default cast, exactly like ``make_param_caster``).

    ``cast(params)`` returns the compute-dtype param tree: leaves
    sharded over ``axis`` ride the single-shard_map gather described in
    the module docstring; everything else is a plain ``astype``.

    ``wire_dtype`` (a codec name from ``runtime/comm/codecs.py``) moves
    each gather's payload quantized — per-chunk scales packed into the
    same collective operand, the local shard placed exactly; the
    backward reduce-scatter stays full precision (grad accumulation
    numerics are never quantized here).
    """
    assert chunks <= 1 or prefetch, (
        "zero3: gather_chunks > 1 requires the prefetch dep-chain "
        "(rendezvous-safety invariant; enforced by config validation)")
    if mesh.shape.get(axis, 1) == 1:
        return None, None

    leaves, treedef = jax.tree_util.tree_flatten(params)
    shard_leaves = treedef.flatten_up_to(param_shardings)
    gathered_idx, in_specs, out_specs, dims = [], [], [], []
    for i, (leaf, sharding) in enumerate(zip(leaves, shard_leaves)):
        spec = tuple(sharding.spec)
        # Only plain `axis` entries are gathered; tuple sub-specs (e.g.
        # ("data", "model") on one dim) fall back to the default cast.
        if axis in spec:
            gathered_idx.append(i)
            in_specs.append(PartitionSpec(*spec))
            out_specs.append(PartitionSpec(
                *[None if s == axis else s for s in spec]))
            dims.append(spec.index(axis))
    if not gathered_idx:
        return None, None

    itemsize = jnp.dtype(dtype).itemsize
    sizes = [int(leaves[i].size) * itemsize for i in gathered_idx]
    plan = Zero3Plan(
        gather_leaves=len(gathered_idx), gather_chunks=int(chunks),
        prefetch=bool(prefetch), bidirectional=bool(bidirectional),
        max_gather_bytes=max(sizes), total_gather_bytes=sum(sizes),
        wire_dtype=(str(wire_dtype) if wire_dtype else None))

    def inner(shards):
        # Per-leaf cast-then-gather, dep-chained in leaf order: the chain
        # is the prefetch schedule (leaf i+1's transfer issues behind
        # leaf i's, ahead of leaf i+1's consumer) and — for the ring
        # form — the invariant that keeps concurrent ppermutes off the
        # in-process rendezvous.
        outs, dep = [], None
        for buf, dim in zip(shards, dims):
            full, d = ring_all_gather(
                buf.astype(dtype), axis, axis=dim, chunks=chunks,
                bidirectional=bidirectional,
                dep=dep if prefetch else None, site="zero3_gather",
                wire_dtype=wire_dtype, wire_chunk=wire_chunk)
            if prefetch:
                dep = d
            outs.append(full)
        return tuple(outs)

    gather_impl = shard_map(inner, mesh=mesh, in_specs=(tuple(in_specs),),
                            out_specs=tuple(out_specs), check_vma=False)

    @jax.custom_vjp
    def gather16(shards):
        return gather_impl(shards)

    def fwd(shards):
        return gather_impl(shards), None

    def bwd(_, cts):
        # Reduce-scatter straight into the sharded fp32 layout: cast the
        # 16-bit cotangent up FIRST (wire precision never touches grad
        # accumulation numerics), then let GSPMD lower the replicated->
        # sharded constraint to its reduce-scatter. The full fp32 param
        # gradient never materializes replicated.
        log_collective_site("zero3_reshard", axis, "reduce_scatter",
                            chunks=len(in_specs))
        return (tuple(
            jax.lax.with_sharding_constraint(
                ct.astype(jnp.float32), NamedSharding(mesh, spec))
            for ct, spec in zip(cts, in_specs)),)

    gather16.defvjp(fwd, bwd)

    n_axis = int(mesh.shape[axis])

    def declare_sites():
        # SiteRecord registration for the whole schedule, exposed as a
        # hook the engine's accumulator calls OUTSIDE the remat
        # boundary: jax.checkpoint memoizes its body trace (and jax
        # caches the shard_map/custom_vjp traces on the fn objects), so
        # trace-time logging inside any of them goes quiet on an
        # audit's retrace of the long-lived step.
        if chunks > 1:
            log_collective_site("zero3_gather", axis, "ppermute",
                                chunks=int(chunks), hops=n_axis - 1)
        else:
            log_collective_site("zero3_gather", axis, "all_gather")
        log_collective_site("zero3_reshard", axis, "reduce_scatter",
                            chunks=len(in_specs))

    def cast(p):
        p_leaves = treedef.flatten_up_to(p)
        full = gather16(tuple(p_leaves[i] for i in gathered_idx))
        out = [x.astype(dtype) for x in p_leaves]
        for j, i in enumerate(gathered_idx):
            # The name tag is what lets zero3_remat_policy drop the
            # gathered copy at the fwd/bwd boundary (backward re-gathers).
            out[i] = checkpoint_name(full[j], GATHERED_NAME)
        return jax.tree_util.tree_unflatten(treedef, out)

    cast.declare_sites = declare_sites
    return cast, plan

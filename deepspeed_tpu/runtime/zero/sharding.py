"""ZeRO as GSPMD sharding declarations.

The reference implements ZeRO-1/2 with hand-coded flatten/partition/
reduce-scatter/all-gather machinery driven by per-param backward hooks
(`runtime/zero/stage1.py:104`, `stage2.py:92`). On TPU the same capabilities
are sharding *declarations* over the ``data`` mesh axis (the ZeRO-DP ≡
weight-update-sharding equivalence; see PAPERS.md "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training"):

- stage 1 — optimizer state (fp32 masters + moments) sharded over ``data``;
  XLA emits a reduce-scatter of grads into the shard, exactly the collective
  stage1.py hand-codes at :533. Under 16-bit compute the masters STAY
  sharded between steps and the step begins with one all-gather of their
  compute-dtype copy (:func:`make_param_caster`) — stage1.py:692, which
  gathers the updated fp16 shards and never the fp32 masters. Under fp32
  compute the copy and the master are the same bytes, so the masters lie
  replicated and the update ends with the all-gather of updated params.
- stage 2 — gradients additionally constrained to the sharded layout inside
  the step (``with_sharding_constraint``), so the full replicated gradient
  never materializes — the IPG-bucket capability of stage2.py:613.
- stage 3 — parameters sharded over ``data`` whatever the compute dtype
  (beyond the reference, which caps at stage 2), gathered just-in-time per
  layer and again in the backward (`zero/stage3.py`) instead of once a
  step: the 16-bit copy is never whole on a device.

Overlap of grad communication with backward compute (stage2's
``overlap_comm``) falls out of XLA's latency-hiding scheduler rather than a
dedicated reduction stream.
"""

from jax.sharding import NamedSharding, PartitionSpec

import jax
import jax.numpy as jnp

from jax import shard_map


def zero_partition_spec(shape, base_spec, mesh, axis="data"):
    """Augment ``base_spec`` by sharding one more dimension over ``axis``.

    Picks the largest dimension that (a) is not already sharded by
    ``base_spec`` and (b) divides evenly by the axis size; returns the base
    spec unchanged when nothing qualifies (small params stay replicated —
    the analog of the reference's padding of sub-partitions, without the
    padding).

    ``mesh`` may also be a plain int axis size: the offline resharder
    (`runtime/elastic/reshard.py`) re-solves specs for a world size that
    has no live mesh. The decision depends only on the axis size, so the
    int form is exactly equivalent.
    """
    axis_size = mesh if isinstance(mesh, int) else mesh.shape[axis]
    if axis_size == 1 or not shape:
        return base_spec
    spec = tuple(base_spec) if base_spec else ()
    spec = spec + (None,) * (len(shape) - len(spec))
    if any(axis == e or (isinstance(e, tuple) and axis in e) for e in spec):
        return _canonical(spec)     # the base spec spent the axis already
    best_dim, best_size = None, 0
    for dim, size in enumerate(shape):
        if spec[dim] is not None:
            continue
        if size % axis_size == 0 and size > best_size:
            best_dim, best_size = dim, size
    if best_dim is None:
        return _canonical(spec)
    new_spec = list(spec)
    new_spec[best_dim] = axis
    return _canonical(new_spec)


def _canonical(spec):
    # Strip trailing Nones: jit canonicalizes output shardings the same
    # way, and an equivalent-but-unequal spec (('data', None) vs
    # ('data',)) on the placed optimizer state forces a full retrace +
    # recompile on the second step.
    spec = list(spec)
    while spec and spec[-1] is None:
        spec.pop()
    return PartitionSpec(*spec)


def build_zero_shardings(params, base_specs, mesh, stage, axis="data",
                         sharded_masters=False):
    """Per-leaf NamedShardings for params / optimizer state / gradients.

    Returns a dict with ``param``, ``opt``, ``grad`` pytrees of NamedSharding.
    ``sharded_masters`` gives the parameters the optimizer state's layout
    at stages 1 and 2 too (stage 3 always has it): for an engine whose
    step gathers a 16-bit copy of them (:func:`make_param_caster`).
    """
    def base_of(path_leaf_spec):
        return path_leaf_spec if path_leaf_spec is not None else PartitionSpec()

    def param_spec(leaf, spec):
        if stage >= 3 or (stage >= 1 and sharded_masters):
            return zero_partition_spec(leaf.shape, base_of(spec), mesh, axis)
        return base_of(spec)

    def opt_spec(leaf, spec):
        if stage >= 1:
            return zero_partition_spec(leaf.shape, base_of(spec), mesh, axis)
        return base_of(spec)

    def grad_spec(leaf, spec):
        if stage >= 2:
            return zero_partition_spec(leaf.shape, base_of(spec), mesh, axis)
        return base_of(spec)

    def shard(fn):
        # base_specs has PartitionSpec leaves at params' leaf positions;
        # flatten_up_to keeps each spec whole (PartitionSpec is a tuple
        # subclass, so a plain tree_map over it would descend into it).
        treedef = jax.tree_util.tree_structure(params)
        leaves = treedef.flatten_up_to(base_specs)
        spec_tree = jax.tree_util.tree_unflatten(treedef, leaves)
        return jax.tree_util.tree_map(
            lambda leaf, spec: NamedSharding(mesh, fn(leaf, spec)),
            params, spec_tree)

    return {
        "param": shard(param_spec),
        "opt": shard(opt_spec),
        "grad": shard(grad_spec),
    }


def constrain_tree(tree, sharding_tree):
    """Apply with_sharding_constraint leaf-wise (inside jit)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s),
        tree, sharding_tree)


def make_param_caster(params, param_shardings, mesh, dtype, axis="data"):
    """``cast(params) -> compute-dtype params`` for float32 masters that
    lie sharded over ``axis``: cast-then-gather.

    Each fp32 shard is cast to the compute dtype LOCALLY and the
    all-gather moves the 16-bit copy, halving the parameter traffic of
    XLA's default gather-then-cast (a plain ``with_sharding_constraint``
    cannot express this: sharding propagation walks the replicated
    constraint back through the convert and gathers fp32). Bitwise-exact
    — cast is elementwise, so cast∘gather == gather∘cast. It is what the
    reference does at stages 1 and 2 (`stage1.py:692`: updated fp16
    shards, not fp32 masters, ride NCCL), and what stages 1, 2 and the
    ``gather_on_use: false`` stage 3 do here.

    The whole tree goes through ONE ``shard_map`` under ONE
    ``custom_vjp``: a model of several hundred leaves traces and lowers
    one manual region, not one a leaf. Backward is pinned to the EXACT
    path: each compute-dtype cotangent is cast to fp32 first, then
    reduced/resharded in fp32 — the 16-bit wire never touches gradient
    accumulation numerics.

    Leaves with a plain ``axis`` entry in their spec (per
    ``param_shardings``) take the gather; the rest — no dimension the
    axis divides, or a tuple sub-spec such as ``("data", "model")`` on
    one dim — are a plain astype. Returns None when nothing is sharded
    over ``axis`` (replicated masters, or a 1-device data axis) so
    callers keep the default cast. ``cast.plan`` is the static split:
    how many leaves and 16-bit bytes ride the gather, how many leaves
    and bytes stay as they lie.
    """
    if mesh.shape.get(axis, 1) == 1:
        return None
    leaves, treedef = jax.tree_util.tree_flatten(params)
    specs = [PartitionSpec(*s.spec)
             for s in treedef.flatten_up_to(param_shardings)]
    gathered = [i for i, spec in enumerate(specs) if axis in tuple(spec)]
    if not gathered:
        return None
    in_specs = tuple(specs[i] for i in gathered)
    dims = [tuple(spec).index(axis) for spec in in_specs]
    out_specs = tuple(
        PartitionSpec(*[None if s == axis else s for s in spec])
        for spec in in_specs)
    back = [NamedSharding(mesh, spec) for spec in in_specs]

    def inner(*shards):
        return tuple(
            jax.lax.all_gather(x.astype(dtype), axis, axis=dim, tiled=True)
            for x, dim in zip(shards, dims))

    fwd_impl = shard_map(inner, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    @jax.custom_vjp
    def gather16(shards):
        return fwd_impl(*shards)

    def fwd(shards):
        return fwd_impl(*shards), None

    def bwd(_, cts):
        return (tuple(
            jax.lax.with_sharding_constraint(ct.astype(jnp.float32), s)
            for ct, s in zip(cts, back)),)

    gather16.defvjp(fwd, bwd)

    def cast(p):
        flat = treedef.flatten_up_to(p)
        whole = dict(zip(gathered,
                         gather16(tuple(flat[i] for i in gathered))))
        return jax.tree_util.tree_unflatten(
            treedef, [whole[i] if i in whole else x.astype(dtype)
                      for i, x in enumerate(flat)])

    width = jnp.dtype(dtype).itemsize
    sizes = [int(x.size) for x in leaves]
    on_wire = sum(sizes[i] for i in gathered)
    cast.plan = {
        "gather_leaves": len(gathered),
        "gather_bytes": on_wire * width,
        "replicated_leaves": len(leaves) - len(gathered),
        "replicated_bytes": (sum(sizes) - on_wire) * width,
    }
    return cast

"""ZeRO as GSPMD sharding declarations.

The reference implements ZeRO-1/2 with hand-coded flatten/partition/
reduce-scatter/all-gather machinery driven by per-param backward hooks
(`runtime/zero/stage1.py:104`, `stage2.py:92`). On TPU the same capabilities
are sharding *declarations* over the ``data`` mesh axis (the ZeRO-DP ≡
weight-update-sharding equivalence; see PAPERS.md "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training"):

- stage 1 — optimizer state (fp32 masters + moments) sharded over ``data``;
  XLA emits a reduce-scatter of grads into the shard and an all-gather of
  updated params, exactly the collectives stage1.py hand-codes at :533,:692.
- stage 2 — gradients additionally constrained to the sharded layout inside
  the step (``with_sharding_constraint``), so the full replicated gradient
  never materializes — the IPG-bucket capability of stage2.py:613.
- stage 3 — parameters themselves sharded over ``data`` (beyond the
  reference, which caps at stage 2); XLA all-gathers weights just-in-time
  per layer.

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


def build_zero_shardings(params, base_specs, mesh, stage, axis="data"):
    """Per-leaf NamedShardings for params / optimizer state / gradients.

    Returns a dict with ``param``, ``opt``, ``grad`` pytrees of NamedSharding.
    """
    def base_of(path_leaf_spec):
        return path_leaf_spec if path_leaf_spec is not None else PartitionSpec()

    def param_spec(leaf, spec):
        if stage >= 3:
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


def _gather_cast_leaf(mesh, spec, dtype, axis):
    """Cast-then-gather for one stage-3 param leaf: the fp32 shard is cast
    to the compute dtype LOCALLY and the all-gather moves the 16-bit
    copy, halving per-use param traffic vs XLA's default gather-then-cast
    (a plain ``with_sharding_constraint`` cannot express this: sharding
    propagation walks the replicated constraint back through the convert
    and gathers fp32). Bitwise-exact — cast is elementwise, so
    cast∘gather == gather∘cast. The reference's analog is stage 1's fp16
    param all-gather (`stage1.py:692`: updated fp16 shards, not fp32
    masters, ride NCCL).

    Backward is pinned by custom_vjp to the EXACT path: the compute-dtype
    cotangent is cast to fp32 first, then reduced/resharded in fp32 —
    the 16-bit wire never touches gradient accumulation numerics.
    """
    dim = list(spec).index(axis)
    out_spec = PartitionSpec(*[None if s == axis else s for s in spec])

    def inner(xs):
        return jax.lax.all_gather(xs.astype(dtype), axis, axis=dim,
                                  tiled=True)

    fwd_impl = shard_map(inner, mesh=mesh, in_specs=(spec,),
                             out_specs=out_spec, check_vma=False)

    @jax.custom_vjp
    def gather16(x):
        return fwd_impl(x)

    def fwd(x):
        return fwd_impl(x), None

    def bwd(_, ct):
        ctf = ct.astype(jnp.float32)
        return (jax.lax.with_sharding_constraint(
            ctf, NamedSharding(mesh, spec)),)

    gather16.defvjp(fwd, bwd)
    return gather16


def make_param_caster(params, param_shardings, mesh, dtype, axis="data"):
    """``cast(params) -> compute-dtype params`` for ZeRO-3 train steps.

    Leaves sharded over ``axis`` (per ``param_shardings``) take the
    cast-then-gather path; everything else is a plain astype. Returns
    None when nothing is sharded over ``axis`` (stages < 3, fp32
    compute, or a 1-device data axis) so callers can keep the default
    cast.
    """
    if mesh.shape.get(axis, 1) == 1:
        return None

    found = {"gather": False}

    def leaf_fn(leaf, sharding):
        spec = tuple(sharding.spec)
        # Only plain `axis` entries are handled; tuple sub-specs (e.g.
        # ("data", "model") on one dim) fall back to the default cast.
        if axis in spec:
            found["gather"] = True
            return _gather_cast_leaf(mesh, PartitionSpec(*spec), dtype, axis)
        return lambda x: x.astype(dtype)

    fns = jax.tree_util.tree_map(leaf_fn, params, param_shardings)
    if not found["gather"]:
        return None

    def cast(p):
        return jax.tree_util.tree_map(lambda f, x: f(x), fns, p)

    return cast

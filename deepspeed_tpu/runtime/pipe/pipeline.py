"""Compiled pipeline execution over the ``pipe`` mesh axis.

The reference interprets instruction lists rank-by-rank, sending activations
through 2-rank NCCL groups (`runtime/pipe/engine.py:1144`, `pipe/p2p.py`).
The TPU-native execution model compiles the whole train batch into ONE XLA
program: stages live at coordinates of the ``pipe`` mesh axis and
microbatch activations rotate stage-to-stage with ``lax.ppermute`` over
ICI. Two programs are provided:

- :func:`make_pipeline_loss_fn` — a GPipe fill-drain wavefront; the
  backward falls out of AD (ppermute's transpose is the reverse rotation).
  Used for eval/forward-only, and differentiable for tests — but AD runs
  all forwards before any backward, so its train memory is O(M) per stage.
- :func:`make_pipeline_value_and_grad_fn` — the executed **1F1B**
  schedule: one scan interleaving forward and backward ticks with an
  O(S) activation ring buffer independent of M (the instruction ISA of
  `schedule.py`, executed). This is what :class:`PipelineEngine` trains
  with.

Model contract: a :class:`~deepspeed_tpu.runtime.pipe.module.PipelineModule`
whose specs decompose as ``prologue + body + epilogue``:

- **body** — the longest homogeneous run of identical LayerSpecs (the
  transformer blocks). Their params are stacked to a leading
  ``[num_stages, layers_per_stage]`` dim sharded ``P('pipe')``: each device
  holds only its stage's layers — the pipeline memory partitioning of
  `pipe/module.py:348`.
- **prologue/epilogue** — leading/trailing heterogeneous specs (embedding,
  final norm, head). They replicate across ``pipe`` and run only on the
  first/last stage (``lax.cond``); tied specs share one param copy and their
  gradients sum across the stages that use them — the tied-weight
  replication + allreduce of `pipe/module.py:405-474`, done by AD.

Layer protocol: built layer objects expose ``init(rng, x) -> params`` and
``apply(params, x, rng=None) -> y``. Flax modules are adapted automatically.
"""

import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops.fp8 import fp8_scope
from deepspeed_tpu.parallel.collectives import (barrier_after,
                                                log_collective_site,
                                                manual_axes, overlap_scope)
from jax import shard_map
from jax.lax import axis_size
from deepspeed_tpu.runtime.pipe.module import LayerSpec, TiedLayerSpec


# ---------------------------------------------------------------------------
# layer adaptation
# ---------------------------------------------------------------------------
class FlaxLayerAdapter:
    """Wrap a flax ``nn.Module`` into the (init, apply) layer protocol."""

    def __init__(self, module):
        self.module = module

    def init(self, rng, x):
        variables = self.module.init({"params": rng, "dropout": rng}, x)
        return variables["params"]

    def apply(self, params, x, rng=None):
        rngs = {"dropout": rng} if rng is not None else {}
        return self.module.apply({"params": params}, x, rngs=rngs)


def adapt_layer(obj):
    """Normalize a built layer object to the (init, apply) protocol."""
    if hasattr(obj, "init") and hasattr(obj, "apply"):
        return obj
    try:
        import flax.linen as nn
        if isinstance(obj, nn.Module):
            return FlaxLayerAdapter(obj)
    except ImportError:
        pass
    raise TypeError(
        f"pipeline layer {obj!r} must expose init(rng, x) and "
        f"apply(params, x, rng=None), or be a flax Module")


def _spec_signature(spec: LayerSpec):
    """Two specs with the same signature build structurally-identical layers
    (stackable into the homogeneous body)."""
    return (spec.typename, spec.module_args,
            tuple(sorted(spec.module_kwargs.items())),
            isinstance(spec, TiedLayerSpec))


def split_specs(specs: List[LayerSpec]):
    """(prologue, body, epilogue): body = the longest run of
    signature-identical non-tied specs."""
    best_lo, best_hi = 0, 0
    i = 0
    while i < len(specs):
        if isinstance(specs[i], TiedLayerSpec):
            i += 1
            continue
        j = i
        sig = _spec_signature(specs[i])
        while j < len(specs) and _spec_signature(specs[j]) == sig:
            j += 1
        if j - i > best_hi - best_lo:
            best_lo, best_hi = i, j
        i = j
    return specs[:best_lo], specs[best_lo:best_hi], specs[best_hi:]


# ---------------------------------------------------------------------------
# parts: built layers + params + specs
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class PipelineParts:
    """Everything the compiled pipeline needs, derived from a PipelineModule."""
    num_stages: int
    layers_per_stage: int
    prologue_specs: List[LayerSpec]
    epilogue_specs: List[LayerSpec]
    prologue_layers: List[Any]          # adapted layer objects
    body_layer: Any                     # one adapted layer (homogeneous)
    epilogue_layers: List[Any]
    params: Dict[str, Any]              # {prologue, body, epilogue, tied}
    param_specs: Dict[str, Any]         # PartitionSpec pytree, same structure
    loss_fn: Callable                   # loss_fn(output, micro_batch)
    auto_axes: tuple = ()               # GSPMD-mode mesh axes (module's)

    def prologue_apply(self, params, micro, rng=None):
        """tokens/micro-batch → first activation (first stage only)."""
        x = micro
        for idx, (spec, layer) in enumerate(
                zip(self.prologue_specs, self.prologue_layers)):
            p = self._layer_params(params, "prologue", idx, spec)
            x = self._apply_one(spec, layer, p, x, rng)
        return x

    def epilogue_apply(self, params, x, rng=None):
        """last activation → model output (last stage only)."""
        for idx, (spec, layer) in enumerate(
                zip(self.epilogue_specs, self.epilogue_layers)):
            p = self._layer_params(params, "epilogue", idx, spec)
            x = self._apply_one(spec, layer, p, x, rng)
        return x

    def body_apply(self, layer_params, x, rng=None):
        return self.body_layer.apply(layer_params, x, rng)

    def _layer_params(self, params, section, idx, spec):
        if isinstance(spec, TiedLayerSpec):
            return params["tied"][spec.key]
        return params[section][f"layer_{idx}"]

    def _apply_one(self, spec, layer, p, x, rng):
        if isinstance(spec, TiedLayerSpec) and spec.forward_fn is not None:
            return spec.forward_fn(p, x)
        return layer.apply(p, x, rng)



def _leaf_names(path):
    return [str(getattr(q, "key", getattr(q, "idx", q))) for q in path]


def _is_expert_leaf(path, a, local=False):
    """Expert-banked body leaves (named ``expert_*`` with a bank dim, e.g.
    `moe/expert_pipe.py:ExpertParallelFFNLayer`) shard their bank dim over
    the ``expert`` mesh axis instead of replicating. The same predicate
    gates the spec AND the gradient tail reduction — they must agree, or a
    replicated leaf would skip its expert pmean (rank-divergent grads
    under a replicated out-spec).

    ``local=True`` when ``a`` is a device-local stage tree (the stacked
    ``[S]`` stage dim stripped, so the bank dim sits one axis lower) —
    getting this wrong silently cross-mixes shard gradients for low-rank
    leaves like biases."""
    min_ndim = 2 if local else 3
    return (any(n.startswith("expert_") for n in _leaf_names(path))
            and a.ndim >= min_ndim)


def _is_mp_leaf(path, a, local=False):
    """Tensor-parallel body leaves (named ``mp_*``, shard dim first, e.g.
    `parallel/pipe_tp.py:TPBlockLayer`) split that dim over the ``model``
    mesh axis — the Megatron column/row partition inside the pipeline.
    Same spec/tail-reduction coupling (and the same ``local`` caveat) as
    :func:`_is_expert_leaf`."""
    min_ndim = 2 if local else 3
    return (any(n.startswith("mp_") for n in _leaf_names(path))
            and a.ndim >= min_ndim)


def body_param_specs(body_params, auto_axes=()):
    """Per-leaf PartitionSpecs for the stacked body [S, L/S, ...]: stage
    dim over ``pipe``; expert banks additionally put their bank dim (the
    first post-stack dim) over ``expert``.

    ``auto_axes``: mesh axes left in GSPMD (auto) mode by a
    partial-manual ``shard_map`` — their mentions are dropped (shard_map
    in/out specs may only name manual axes; the auto-axis sharding lives
    at the jit level and inside via sharding constraints)."""

    def spec(path, a):
        # No trailing Nones after the sharded dim: the compiled step
        # round-trips these shardings with the trailing Nones normalized
        # away, and a spec that differs only there is a NEW jit cache key
        # — every step after the first would recompile once.
        if _is_expert_leaf(path, a):
            s = P("pipe", None, "expert")
        elif _is_mp_leaf(path, a):
            s = P("pipe", None, "model")
        else:
            s = P("pipe", *([None] * (a.ndim - 1)))
        if auto_axes:
            s = P(*(None if ax in auto_axes else ax for ax in s))
        return s

    return jax.tree_util.tree_map_with_path(spec, body_params)


def build_pipeline_parts(module, num_stages: int, rng,
                         example_micro) -> PipelineParts:
    """Build layers, initialize params, and stack the body.

    ``example_micro``: a microbatch-shaped pytree used for shape inference
    (row count is irrelevant — only trailing dims matter).
    """
    pro_specs, body_specs, epi_specs = split_specs(module.specs)
    if not body_specs:
        raise ValueError("PipelineModule needs a homogeneous run of layer "
                         "specs to pipeline (the transformer blocks)")
    if len(body_specs) % num_stages != 0:
        raise ValueError(
            f"{len(body_specs)} pipelined layers do not divide evenly over "
            f"{num_stages} stages; adjust n_layer or the pipe axis")

    params = {"prologue": {}, "body": None, "epilogue": {}, "tied": {}}
    tied_layers: Dict[str, Any] = {}

    def next_rng(i):
        if module.seed_layers:
            return jax.random.PRNGKey(module.base_seed + i)
        return jax.random.fold_in(rng, i)

    layer_idx = 0
    x = example_micro

    def build_one(spec, section, idx, x):
        nonlocal layer_idx
        layer = adapt_layer(spec.build())
        if isinstance(spec, TiedLayerSpec):
            if spec.key not in params["tied"]:
                params["tied"][spec.key] = layer.init(next_rng(layer_idx), x)
                tied_layers[spec.key] = layer
            p = params["tied"][spec.key]
        else:
            p = layer.init(next_rng(layer_idx), x)
            params[section][f"layer_{idx}"] = p
        layer_idx += 1
        if isinstance(spec, TiedLayerSpec) and spec.forward_fn is not None:
            return layer, spec.forward_fn(p, x)
        return layer, layer.apply(p, x, None)

    prologue_layers = []
    for idx, spec in enumerate(pro_specs):
        layer, x = build_one(spec, "prologue", idx, x)
        prologue_layers.append(layer)

    body_layer = None
    body_params = []
    for spec in body_specs:
        layer = adapt_layer(spec.build())
        if body_layer is None:
            body_layer = layer
        p = layer.init(next_rng(layer_idx), x)
        layer_idx += 1
        x = layer.apply(p, x, None)
        body_params.append(p)

    epilogue_layers = []
    for idx, spec in enumerate(epi_specs):
        layer, x = build_one(spec, "epilogue", idx, x)
        epilogue_layers.append(layer)

    # Stack body params: [L, ...] → [S, L/S, ...], leading dim over 'pipe'.
    lps = len(body_specs) // num_stages
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *body_params)
    params["body"] = jax.tree_util.tree_map(
        lambda a: a.reshape((num_stages, lps) + a.shape[1:]), stacked)

    def spec_of(section):
        return jax.tree_util.tree_map(lambda _: P(), params[section])

    # Body PLACEMENT specs: the name-based contract (mp_*/expert_*), or —
    # when the layer carries GSPMD partition metadata (the flax adapter,
    # `parallel/pipe_auto.py`) AND the module opted into auto axes — the
    # layer's own per-leaf specs with the [stage, layers/stage] stacking
    # dims prepended. Placement specs may name auto axes; the shard_map
    # in/out specs (built per call in `_call_pipeline`) are what must
    # stay manual-only. Without auto_axes the adapter metadata is
    # deliberately IGNORED for placement: sharding body params over an
    # axis the all-manual shard_map treats as replicated would at best
    # resharde every step and at worst hit the CPU runtime's collective
    # rendezvous deadlock the engine gate documents.
    auto_axes = tuple(getattr(module, "auto_axes", ()) or ())
    body_place_specs = body_param_specs(params["body"])
    spec_fn = getattr(body_layer, "param_partition_specs", None)
    if spec_fn is not None and auto_axes:
        layer_specs = spec_fn(body_params[0])
        body_place_specs = jax.tree_util.tree_map(
            lambda sp: P("pipe", None, *tuple(sp)), layer_specs,
            is_leaf=lambda x: isinstance(x, P))

    param_specs = {
        "prologue": spec_of("prologue"),
        "epilogue": spec_of("epilogue"),
        "tied": spec_of("tied"),
        "body": body_place_specs,
    }

    loss_fn = module.loss_fn
    if loss_fn is None:
        raise ValueError("PipelineModule.loss_fn required for training")

    return PipelineParts(num_stages=num_stages,
                         layers_per_stage=lps,
                         prologue_specs=pro_specs,
                         epilogue_specs=epi_specs,
                         prologue_layers=prologue_layers,
                         body_layer=body_layer,
                         epilogue_layers=epilogue_layers,
                         params=params,
                         param_specs=param_specs,
                         loss_fn=loss_fn,
                         auto_axes=auto_axes)


def sequential_loss_fn(parts: PipelineParts, params, micro_batches, rng=None):
    """Non-pipelined reference execution of the same parts (test oracle):
    mean loss over the leading microbatch dim."""
    body = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), params["body"])
    n_layers = parts.num_stages * parts.layers_per_stage
    num_total, den_total = 0.0, 0.0
    weighted = None
    M = jax.tree_util.tree_leaves(micro_batches)[0].shape[0]
    for m in range(M):
        micro = jax.tree_util.tree_map(lambda a: a[m], micro_batches)
        x = parts.prologue_apply(params, micro,
                                 None if rng is None
                                 else jax.random.fold_in(rng, m))
        for li in range(n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[li], body)
            x = parts.body_apply(lp, x, None)
        out = parts.epilogue_apply(params, x, None)
        res = parts.loss_fn(out, micro)
        weighted = isinstance(res, tuple)
        if weighted:
            num_total = num_total + res[0]
            den_total = den_total + res[1]
        else:
            num_total = num_total + res
    if weighted:
        return num_total / jnp.maximum(den_total, 1.0)
    return num_total / M


# ---------------------------------------------------------------------------
# the compiled pipeline loss
# ---------------------------------------------------------------------------
def make_pipeline_loss_fn(parts: PipelineParts, mesh, num_micro: int,
                          remat: bool = True, auto_axes=None,
                          overlap=None, fp8=None):
    """Build ``loss_fn(params, batch, rng)`` executing the GPipe rotation.

    ``batch``: pytree of ``[rows, ...]`` arrays, rows divisible by
    ``num_micro``; rows are data-sharded, microbatches run through the
    ``pipe`` axis wavefront. Differentiable end-to-end: ``jax.grad`` of this
    function performs the full backward pipeline (cooldown included).

    ``auto_axes``: GSPMD-mode mesh axes (see ``_call_pipeline``);
    defaults to the module's, recorded on ``parts``.
    ``overlap``: optional ``parallel.collectives.OverlapPlan`` switching
    manual-mode layers to the latency-hiding chunked collectives.
    ``fp8``: optional ``ops.fp8.Fp8Plan`` routing the TP blocks' local
    matmuls through current-scaling fp8 qdq (`ops/fp8.py`).
    """
    auto_axes = _resolve_auto_axes(parts, mesh, auto_axes)
    S = parts.num_stages
    M = num_micro
    T = M + S - 1
    axis_tail = tuple(a for a in mesh.axis_names
                      if a not in ("pipe", "data") and a not in auto_axes)

    def device_fn(body_local, rest, batch_local, rng, use_rng):
        # body_local arrives as [1, L/S, ...] — this stage's shard.
        body_local = jax.tree_util.tree_map(lambda a: a[0], body_local)
        s = lax.axis_index("pipe")

        def micro_at(m):
            return jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, m, 0, keepdims=False),
                batch_local)

        def mb_rng(m, section):
            # distinct dropout stream per (microbatch, stage, section)
            if not use_rng:
                return None
            key = jax.random.fold_in(jax.random.fold_in(rng, m), s)
            return jax.random.fold_in(key, section)

        def stage_fwd(x, key):
            if not use_rng:
                def layer(x, lp):
                    return parts.body_apply(lp, x, None), None
                x, _ = lax.scan(layer, x, body_local)
                return x

            def layer(carry, lp):
                x, k = carry
                k, sub = jax.random.split(k)
                return (parts.body_apply(lp, x, sub), k), None
            (x, _), _ = lax.scan(layer, (x, key), body_local)
            return x

        # activation template (shape-only trace; no FLOPs at runtime)
        act = jax.eval_shape(
            lambda p, mb: parts.prologue_apply(p, mb, None), rest,
            micro_at(0))
        zeros = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), act)
        # loss_fn may return a scalar (per-microbatch mean; averaged over
        # microbatches/shards) or (loss_sum, weight) for the exact global
        # weighted mean (e.g. token CE with uneven ignore-index masks).
        loss_probe = jax.eval_shape(
            lambda p, xx, mb: parts.loss_fn(
                parts.epilogue_apply(p, xx, None), mb),
            rest, act, micro_at(0))
        weighted = isinstance(loss_probe, tuple)
        if not weighted and mesh.shape.get("seq", 1) > 1:
            raise ValueError(
                "pipeline on a mesh with seq > 1 requires the weighted "
                "(loss_sum, weight) loss form: a scalar mean loss cannot "
                "express seq-sharded token counts, so losses and grads "
                "would be silently mis-scaled by the seq degree")

        def mb_loss_pair(x, m_oc):
            res = parts.loss_fn(
                parts.epilogue_apply(rest, x, mb_rng(m_oc, 2)),
                micro_at(m_oc))
            if weighted:
                num, den = res
                return num.astype(jnp.float32), den.astype(jnp.float32)
            return res.astype(jnp.float32), jnp.asarray(1.0, jnp.float32)

        def tick(carry, t):
            x_recv, num_acc, den_acc = carry
            m_in = jnp.clip(t - s, 0, M - 1)
            x_in = lax.cond(
                s == 0,
                lambda: parts.prologue_apply(rest, micro_at(m_in),
                                             mb_rng(m_in, 0)),
                lambda: x_recv)
            x = stage_fwd(x_in, mb_rng(m_in, 1))
            m_out = t - (S - 1)
            m_oc = jnp.clip(m_out, 0, M - 1)
            num, den = lax.cond(
                s == S - 1,
                lambda: mb_loss_pair(x, m_oc),
                lambda: (jnp.asarray(0.0, jnp.float32),
                         jnp.asarray(0.0, jnp.float32)))
            valid = (m_out >= 0) & (m_out < M)
            num_acc = num_acc + jnp.where(valid, num, 0.0)
            den_acc = den_acc + jnp.where(valid, den, 0.0)
            x_next = lax.ppermute(
                x, "pipe", [(i, (i + 1) % S) for i in range(S)])
            return (x_next, num_acc, den_acc), None

        tick_fn = jax.checkpoint(tick) if remat else tick
        zero_f = jnp.asarray(0.0, jnp.float32)
        (_, num_sum, den_sum), _ = lax.scan(
            tick_fn, (zeros, zero_f, zero_f), jnp.arange(T))

        # Only the last stage accumulated loss; share it everywhere so the
        # result is replicated, matching out_specs=P().
        if weighted:
            # exact global weighted mean: sum losses / sum weights. The
            # ``seq`` axis joins the psum — sequence-parallel layers hold
            # per-token-shard partial sums; with replicated compute the
            # n-fold num and den cancel (same note as the 1F1B path).
            seq_tail = tuple(a for a in axis_tail if a == "seq")
            loss_axes = ("pipe", "data") + seq_tail
            num = lax.psum(num_sum, loss_axes)
            den = lax.psum(den_sum, loss_axes)
            loss = num / jnp.maximum(den, 1.0)
            rest_tail = tuple(a for a in axis_tail if a != "seq")
        else:
            # mean of per-(microbatch, shard) means
            loss = lax.psum(num_sum, "pipe") / M
            loss = lax.pmean(loss, "data")
            rest_tail = axis_tail
        if rest_tail:
            loss = lax.pmean(loss, rest_tail)
        return loss

    def pipeline_loss(params, batch, rng):
        return _call_pipeline(mesh, M, device_fn, params, batch, rng,
                              out_specs=lambda body_specs, rest_specs: P(),
                              auto_axes=auto_axes, overlap=overlap,
                              fp8=fp8)

    return pipeline_loss


def _resolve_auto_axes(parts, mesh, auto_axes):
    """One source of truth for the GSPMD-mode axes: the module's
    (recorded on ``parts`` by ``build_pipeline_parts``, where the
    placement specs were derived from it). An explicit argument must
    agree — placement and shard_map manualness disagreeing is exactly
    the silent-resharding / rendezvous-deadlock class this prevents."""
    resolved = parts.auto_axes if auto_axes is None else tuple(auto_axes)
    if tuple(resolved) != tuple(parts.auto_axes):
        raise ValueError(
            f"auto_axes {resolved} disagrees with the module's "
            f"{parts.auto_axes} that built these parts (the body placement "
            "specs were derived from the latter)")
    unknown = set(resolved) - set(mesh.axis_names)
    if unknown:
        raise ValueError(
            f"auto_axes {sorted(unknown)} are not mesh axes "
            f"{tuple(mesh.axis_names)} — a typo here would silently "
            "disable tensor parallelism")
    bad = set(resolved) & {"pipe", "data", "seq"}
    if bad:
        raise ValueError(
            f"auto_axes {sorted(bad)} must stay manual: the 1F1B schedule "
            "ppermutes over pipe, batches shard over data, and the "
            "sequence-parallel loss psums over seq")
    return resolved


def _call_pipeline(mesh, M, device_fn, params, batch, rng, extra=(),
                   out_specs=None, auto_axes=(), overlap=None, fp8=None):
    """Shared shard_map wrapper for the pipeline programs: microbatch the
    batch rows, split off the replicated param groups, build the in/out
    specs, and invoke ``device_fn`` over the mesh. ``out_specs`` is a
    callable of (body_specs, rest_specs) so callers returning grads can
    reuse the input layouts.

    ``auto_axes``: mesh axes the shard_map leaves in GSPMD (auto) mode —
    arrays stay global along them inside ``device_fn`` and the user's
    sharding constraints / param shardings drive the partitioning
    (user-composable tensor parallelism: any flax model's GSPMD
    annotations work inside the pipeline; see `parallel/pipe_auto.py`).
    The pipe/data axes must stay manual (ppermute schedule, batch
    sharding)."""
    batch_sharding = NamedSharding(mesh, P(None, "data"))

    def to_micro(a):
        rows = a.shape[0]
        assert rows % M == 0, (
            f"batch rows {rows} not divisible by {M} microbatches")
        return a.reshape((M, rows // M) + a.shape[1:])

    batch_m = jax.tree_util.tree_map(to_micro, batch)
    batch_m = jax.tree_util.tree_map(
        lambda a: lax.with_sharding_constraint(a, batch_sharding),
        batch_m)
    rest = {k: params[k] for k in ("prologue", "epilogue", "tied")}
    use_rng = rng is not None
    key = rng if use_rng else jnp.zeros((2,), jnp.uint32)

    manual = tuple(a for a in mesh.axis_names if a not in auto_axes)
    body_specs = body_param_specs(params["body"], auto_axes)
    rest_specs = jax.tree_util.tree_map(lambda _: P(), rest)
    batch_specs = jax.tree_util.tree_map(
        lambda _: P(None, "data"), batch_m)

    def manual_device_fn(*args, **kwargs):
        # Declare the MANUAL mesh axes while the device body traces:
        # layers with explicit collectives (TP blocks, expert-parallel
        # FFN) switch them on via parallel.collectives.axis_is_manual;
        # auto axes stay GSPMD-driven (axis_is_manual False → manual
        # collectives no-op, constraints rule). ``overlap`` (an
        # OverlapPlan or None) rides the same trace-time channel: layers
        # consult parallel.collectives.overlap_plan to swap monolithic
        # collectives for the chunked latency-hiding form. ``fp8`` (an
        # ops.fp8.Fp8Plan or None) rides the same way: with no state
        # dict the scope selects stateless current scaling — per-site
        # amax threading isn't available through the manual 1F1B
        # program's hand-written backward.
        with manual_axes(manual), overlap_scope(overlap), fp8_scope(fp8):
            return device_fn(*args, **kwargs)

    fn = shard_map(
        partial(manual_device_fn, use_rng=use_rng),
        mesh=mesh,
        in_specs=(body_specs, rest_specs, batch_specs, P()) +
        tuple(P() for _ in extra),
        out_specs=out_specs(body_specs, rest_specs),
        axis_names=set(manual),
        check_vma=False)
    return fn(params["body"], rest, batch_m, key, *extra)


def _tree_ppermute(tree, perm):
    """Stage-transfer ppermute over a pytree with the leaf permutes chained
    (``barrier_after``): two *independent* in-flight collective-permutes
    split the in-process CPU runtime's global rendezvous (half the devices
    arrive at one op_id, half at the other) and deadlock. Chaining costs
    nothing — per-tick latency is bounded by the largest leaf anyway."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    log_collective_site("pipeline.stage_transfer", "pipe", "ppermute",
                        chunks=len(leaves),
                        chained=not _FIXTURE_UNCHAINED_TRANSFER)
    dep, out = None, []
    for leaf in leaves:
        if _FIXTURE_UNCHAINED_TRANSFER:
            dep = None
        leaf = lax.ppermute(barrier_after(leaf, dep), "pipe", perm)
        dep = leaf
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# trace-only regression fixtures: re-introduce the historical deadlocks
# ---------------------------------------------------------------------------
# Two scheduling bugs were fixed in the uniform-tick restructure (see the
# comments inside ``tick`` below):
#   * the stage transfer sat inside stage-divergent control flow, so some
#     devices reached the ppermute rendezvous and others did not;
#   * concurrent in-flight permutes were not chained, splitting the global
#     rendezvous across op_ids.
# These flags revert each bug THROUGH THE PRODUCTION CODE PATH so the
# analysis.jaxpr deadlock checker can be regression-tested against the real
# pipeline jaxpr, at trace time only. Programs traced under the fixture
# must never be executed — they are the deadlock.
_FIXTURE_DIVERGENT_TRANSFER = False
_FIXTURE_UNCHAINED_TRANSFER = False


@contextlib.contextmanager
def pipeline_trace_fixture(divergent_transfer=False, unchained_transfer=False):
    """TRACE-ONLY: rebuild the pre-fix divergent/unchained tick schedule.

    The flags are read while ``tick`` traces, so the ``vag`` fn must be
    built *and traced* (``jax.jit(...).trace`` / ``make_jaxpr``) inside this
    context. Never run the resulting program."""
    global _FIXTURE_DIVERGENT_TRANSFER, _FIXTURE_UNCHAINED_TRANSFER
    prev = (_FIXTURE_DIVERGENT_TRANSFER, _FIXTURE_UNCHAINED_TRANSFER)
    _FIXTURE_DIVERGENT_TRANSFER = divergent_transfer
    _FIXTURE_UNCHAINED_TRANSFER = unchained_transfer
    try:
        yield
    finally:
        _FIXTURE_DIVERGENT_TRANSFER, _FIXTURE_UNCHAINED_TRANSFER = prev


# ---------------------------------------------------------------------------
# executed 1F1B: interleaved forward/backward in ONE compiled scan
# ---------------------------------------------------------------------------
def make_pipeline_value_and_grad_fn(parts: PipelineParts, mesh,
                                    num_micro: int, compute_dtype=None,
                                    data_local=False, auto_axes=None,
                                    overlap=None, fp8=None):
    """Build ``vag(params, batch, rng, scale) -> (loss, grads)`` running a
    hand-scheduled 1F1B pipeline (the reference's ``TrainSchedule``
    interleave, `runtime/pipe/schedule.py:189-241`, executed rather than
    differentiated).

    Why not ``jax.grad`` of the GPipe rotation: AD runs every forward tick
    before any backward tick, so each stage must hold O(M) microbatch
    activations (the blow-up 1F1B exists to prevent — reference buffer
    bound `runtime/pipe/schedule.py:243-247`). Here one ``lax.scan`` over
    ``M + 2S - 2`` ticks interleaves them: at tick ``t`` stage ``s``
    forwards microbatch ``t - s`` and backwards microbatch
    ``t - (2S - 2 - s)`` — the cotangent for microbatch ``m`` reaches stage
    ``s`` exactly ``2(S-1-s)+1`` ticks after its forward, so a ring buffer
    of ``2S - 1`` stage-input activations suffices **independent of M**.
    Stage internals rematerialize in the backward (one ``jax.vjp`` per
    tick), the Megatron-style full-recompute tradeoff.

    Gradient scaling: backward seeds are 1.0 per microbatch loss-sum; the
    final grads are scaled by ``scale / total_weight`` (weighted losses) or
    ``scale / (M * |data|)`` — weights (token counts) don't depend on
    params, so this equals grad of ``scale * mean_loss``.

    ``data_local=True`` (the 1-bit Adam composition): the dense psum over
    ``data`` is SKIPPED — grads come back with a stacked leading ``data``
    axis, scaled so their *mean* over that axis is the true gradient, for
    a compressed collective to average instead (the analog of the
    reference disabling engine allreduce for OnebitAdam,
    onebit_adam.py:372).

    ``auto_axes`` (round 5, user-composable TP): mesh axes left in GSPMD
    mode — no manual collectives reference them (their reductions are
    XLA's job); typically ``("model",)`` so any flax model's
    ``nn.with_partitioning`` / sharding-constraint annotations do Megatron
    TP inside the 1F1B without hand-written collectives. Defaults to the
    module's, recorded on ``parts``.
    """
    auto_axes = _resolve_auto_axes(parts, mesh, auto_axes)
    S = parts.num_stages
    M = num_micro
    T = M + 2 * S - 2
    K = 2 * S - 1
    axis_tail = tuple(a for a in mesh.axis_names
                      if a not in ("pipe", "data") and a not in auto_axes)
    f32 = jnp.float32

    def cast(tree):
        if compute_dtype is None:
            return tree
        return jax.tree_util.tree_map(
            lambda a: a.astype(compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    def device_fn(body_local, rest, batch_local, rng, scale, use_rng):
        body_local = jax.tree_util.tree_map(lambda a: a[0], body_local)
        s = lax.axis_index("pipe")

        def micro_at(m):
            return jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, m, 0, keepdims=False),
                batch_local)

        def mb_rng(m, section):
            if not use_rng:
                return None
            key = jax.random.fold_in(jax.random.fold_in(rng, m), s)
            return jax.random.fold_in(key, section)

        def stage_fwd(body, x, key):
            if not use_rng:
                def layer(x, lp):
                    return parts.body_apply(cast(lp), x, None), None
                x, _ = lax.scan(layer, x, body)
                return x

            def layer(carry, lp):
                x, k = carry
                k, sub = jax.random.split(k)
                return (parts.body_apply(cast(lp), x, sub), k), None
            (x, _), _ = lax.scan(layer, (x, key if key is not None
                                         else jnp.zeros((2,), jnp.uint32)),
                                 body)
            return x

        def prologue(r, m):
            return parts.prologue_apply(cast(r), micro_at(m), mb_rng(m, 0))

        act = jax.eval_shape(lambda r: prologue(r, 0), rest)
        zeros_act = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), act)
        loss_probe = jax.eval_shape(
            lambda r, xx: parts.loss_fn(
                parts.epilogue_apply(cast(r), xx, None), micro_at(0)),
            rest, act)
        weighted = isinstance(loss_probe, tuple)
        if not weighted and mesh.shape.get("seq", 1) > 1:
            raise ValueError(
                "pipeline on a mesh with seq > 1 requires the weighted "
                "(loss_sum, weight) loss form: a scalar mean loss cannot "
                "express seq-sharded token counts, so losses and grads "
                "would be silently mis-scaled by the seq degree")

        zeros_body_g = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, f32), body_local)
        zeros_rest_g = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, f32), rest)

        def as_pair(res):
            if weighted:
                num, den = res
                return num.astype(f32), den.astype(f32)
            return res.astype(f32), jnp.asarray(1.0, f32)

        def loss_head_pair(y_b, m):
            """vjp of epilogue → loss at the stage OUTPUT (last stage
            only). Contains no model-axis collectives, so it may sit
            inside the stage-divergent cond; the stage vjp itself (which
            does) runs uniformly in the tick body. Seeded with the loss
            scale so fp16 cotangents ride above the underflow floor
            through the whole backward (the reference scales the loss
            before backprop; scaling only at the end in fp32 would make
            dynamic loss scaling a numeric no-op)."""
            def h(r, yy):
                out = parts.epilogue_apply(cast(r), yy, mb_rng(m, 2))
                return as_pair(parts.loss_fn(out, micro_at(m)))
            (num, den), hvjp = jax.vjp(h, rest, y_b)
            gr, gy = hvjp((scale.astype(f32), jnp.asarray(0.0, f32)))
            return gy, gr, num, den

        def prologue_vjp(gx, m):
            _, vjp = jax.vjp(lambda r: prologue(r, m), rest)
            (gr,) = vjp(gx)
            return gr

        def tick(carry, t):
            x_recv, g_recv, buf, gb_acc, gr_acc, num_acc, den_acc = carry

            # ---- forward half: microbatch mf = t - s -----------------
            mf = t - s
            valid_f = (mf >= 0) & (mf < M)
            mf_c = jnp.clip(mf, 0, M - 1)
            x_in = lax.cond(
                valid_f,
                lambda: lax.cond(s == 0,
                                 lambda: prologue(rest, mf_c),
                                 lambda: x_recv),
                lambda: zeros_act)
            slot_f = mf_c % K
            buf = lax.cond(
                valid_f,
                lambda: jax.tree_util.tree_map(
                    lambda b, xi: lax.dynamic_update_index_in_dim(
                        b, xi, slot_f, 0), buf, x_in),
                lambda: buf)
            # stage_fwd runs UNCONDITIONALLY: TP layers put model-axis
            # collectives inside it, and a collective inside stage-
            # divergent control flow is invalid SPMD — the in-process CPU
            # runtime's global collective-permute rendezvous deadlocks
            # when one stage enters the branch and another doesn't (the
            # seed got away with `s < S - 1` here only because all-reduce
            # rendezvous is per replica group). Bubble ticks and the last
            # stage compute on zeros and the result is discarded.
            y = stage_fwd(body_local, x_in, mb_rng(mf_c, 1))
            fwd_perm = [(i, (i + 1) % S) for i in range(S)]
            if _FIXTURE_DIVERGENT_TRANSFER:
                # pre-fix schedule: the transfer only fires on "useful"
                # ticks — valid_f depends on s (= axis_index("pipe")), so
                # stages disagree about entering the branch and the
                # ppermute's global rendezvous deadlocks. Kept compilable
                # but never executed; exists for the deadlock-rule tests.
                x_next = lax.cond(
                    valid_f,
                    lambda: _tree_ppermute(y, fwd_perm),
                    lambda: y)
            else:
                x_next = _tree_ppermute(y, fwd_perm)

            # ---- backward half: microbatch mb = t - (2S-2-s) ---------
            mb_ = t - (2 * S - 2 - s)
            valid_b = (mb_ >= 0) & (mb_ < M)
            mb_c = jnp.clip(mb_, 0, M - 1)
            x_b = jax.tree_util.tree_map(
                lambda b: lax.dynamic_index_in_dim(b, mb_c % K, 0,
                                                   keepdims=False), buf)
            # The two halves are data-independent, so the backward half's
            # collectives (TP chunk rings, g_next) would race x_next on
            # the in-process CPU runtime's global rendezvous. Order the
            # whole backward half after the forward stage transfer by
            # barriering its inputs — the tick's collectives then form
            # one chain: fwd TP → x_next → bwd TP → g_next.
            if _FIXTURE_UNCHAINED_TRANSFER:
                # pre-fix schedule: backward half issues with no dataflow
                # edge on x_next, so its g_next ppermute races the
                # forward transfer on the global rendezvous. Trace-only.
                g_in = g_recv
            else:
                (x_b, g_in), _ = lax.optimization_barrier(
                    ((x_b, g_recv), x_next))

            # The stage vjp — the piece holding model-axis collectives —
            # runs UNCONDITIONALLY and uniformly across stages (same SPMD
            # constraint as stage_fwd above; the seed's per-stage
            # last_vjp/mid_vjp branches compile to DIFFERENT permute
            # channels, splitting the rendezvous). Only the collective-
            # free cotangent seed diverges: the last stage seeds from
            # epilogue∘loss at its own output, the rest from the received
            # cotangent. Invalid (bubble) ticks run on buffer garbage and
            # are masked out of the accumulators below.
            y_b, stage_vjp = jax.vjp(
                lambda b, xx: stage_fwd(b, xx, mb_rng(mb_c, 1)),
                body_local, x_b)
            gy, gr, num, den = lax.cond(
                s == S - 1,
                lambda: loss_head_pair(y_b, mb_c),
                lambda: (g_in, zeros_rest_g, jnp.asarray(0.0, f32),
                         jnp.asarray(0.0, f32)))
            gb, gx = stage_vjp(gy)
            gr = lax.cond(
                s == 0,
                lambda: jax.tree_util.tree_map(
                    jnp.add, gr, prologue_vjp(gx, mb_c)),
                lambda: gr)

            def mask(tree):
                return jax.tree_util.tree_map(
                    lambda a: jnp.where(valid_b, a, jnp.zeros_like(a)),
                    tree)

            gb_acc = jax.tree_util.tree_map(jnp.add, gb_acc, mask(gb))
            gr_acc = jax.tree_util.tree_map(jnp.add, gr_acc, mask(gr))
            num_acc = num_acc + jnp.where(valid_b, num, 0.0)
            den_acc = den_acc + jnp.where(valid_b, den, 0.0)
            g_next = _tree_ppermute(
                mask(gx), [(i, (i - 1) % S) for i in range(S)])
            return (x_next, g_next, buf, gb_acc, gr_acc, num_acc,
                    den_acc), None

        buf0 = jax.tree_util.tree_map(
            lambda a: jnp.zeros((K,) + a.shape, a.dtype), zeros_act)
        zero_f = jnp.asarray(0.0, f32)
        carry0 = (zeros_act, zeros_act, buf0, zeros_body_g, zeros_rest_g,
                  zero_f, zero_f)
        (_, _, _, gb_acc, gr_acc, num_sum, den_sum), _ = lax.scan(
            tick, carry0, jnp.arange(T))

        # ---- reductions + scaling --------------------------------------
        # (the loss scale is already in the accumulated grads via the vjp
        # seed; here only the mean-normalization divides through, in fp32)
        #
        # The ``seq`` axis is COMPUTE-partitioned (sequence-parallel
        # layers shard the token dim; weights stay replicated), so in the
        # weighted form its num/den/grads are partial sums → psum, with
        # the global den normalizing. This is exact in BOTH worlds: with
        # replicated compute every seq rank holds identical num/den/g, so
        # the n-fold psum cancels against the n-fold den in gscale.
        seq_tail = tuple(a for a in axis_tail if a == "seq")
        if weighted:
            loss_axes = ("pipe", "data") + seq_tail
            D = lax.psum(den_sum, loss_axes)
            D = jnp.maximum(D, 1.0)
            loss = lax.psum(num_sum, loss_axes) / D
            gscale = 1.0 / D
        else:
            # scalar-mean losses cannot express seq-sharded token counts;
            # sequence-parallel modules must return (loss_sum, weight)
            n_data = axis_size("data")
            loss = lax.pmean(lax.psum(num_sum, "pipe") / M, "data")
            gscale = 1.0 / (M * n_data)
        # body grads stay pipe-sharded; rest grads sum across the stages
        # that touched them (the tied-weight allreduce, module.py:405-474)
        if data_local:
            # Scale so the MEAN over data ranks equals the true gradient:
            # mean_r(n_data * g_r * gscale) = sum_r g_r * gscale.
            n_data = axis_size("data")
            gb_acc = jax.tree_util.tree_map(
                lambda a: a * (gscale * n_data), gb_acc)
            gr_acc = jax.tree_util.tree_map(
                lambda a: lax.psum(a, "pipe") * (gscale * n_data), gr_acc)
        else:
            gb_acc = jax.tree_util.tree_map(
                lambda a: lax.psum(a, "data") * gscale, gb_acc)
            gr_acc = jax.tree_util.tree_map(
                lambda a: lax.psum(lax.psum(a, "pipe"), "data") * gscale,
                gr_acc)
        if weighted and seq_tail:
            # partial-sum semantics (see note above)
            gb_acc = jax.tree_util.tree_map(
                lambda a: lax.psum(a, seq_tail), gb_acc)
            gr_acc = jax.tree_util.tree_map(
                lambda a: lax.psum(a, seq_tail), gr_acc)
        other_tail = tuple(a for a in axis_tail
                           if not (weighted and a == "seq"))
        if other_tail:
            loss = lax.pmean(loss, other_tail)
            # Replicated leaves: identical per-rank grads (expert-partial
            # cotangents are already psum'd in-layer by psum_grad), so
            # pmean is exact. Expert-SHARDED leaves hold genuinely
            # different shards — never mix them across ``expert``.
            def tail_mean(path, a):
                # NB: gb_acc leaves here are stage-LOCAL (no [S] dim).
                axes = tuple(ax for ax in other_tail
                             if not ((ax == "expert" and
                                      _is_expert_leaf(path, a, local=True))
                                     or (ax == "model" and
                                         _is_mp_leaf(path, a, local=True))))
                return lax.pmean(a, axes) if axes else a
            gb_acc = jax.tree_util.tree_map_with_path(tail_mean, gb_acc)
            gr_acc = jax.tree_util.tree_map(
                lambda a: lax.pmean(a, other_tail), gr_acc)
        # restore the leading stage dim the shard_map out_spec strips
        # (+ a stacked data dim in data_local mode)
        gb_acc = jax.tree_util.tree_map(lambda a: a[None], gb_acc)
        if data_local:
            gb_acc = jax.tree_util.tree_map(lambda a: a[None], gb_acc)
            gr_acc = jax.tree_util.tree_map(lambda a: a[None], gr_acc)
        return loss, gb_acc, gr_acc

    def _out_specs(body_specs, rest_specs):
        if not data_local:
            return (P(), body_specs, rest_specs)
        stack = lambda spec: P("data", *tuple(spec))
        return (P(),
                jax.tree_util.tree_map(stack, body_specs),
                jax.tree_util.tree_map(stack, rest_specs))

    def pipeline_value_and_grad(params, batch, rng, scale):
        loss, gb, gr = _call_pipeline(
            mesh, M, device_fn, params, batch, rng,
            extra=(jnp.asarray(scale, jnp.float32),),
            out_specs=_out_specs, auto_axes=auto_axes, overlap=overlap,
            fp8=fp8)
        grads = {"prologue": gr["prologue"], "body": gb,
                 "epilogue": gr["epilogue"], "tied": gr["tied"]}
        return loss, grads

    return pipeline_value_and_grad

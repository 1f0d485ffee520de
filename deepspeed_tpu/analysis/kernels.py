"""Static analysis below the ``pallas_call`` boundary.

The audit subsystem verifies compiled XLA programs (collectives,
donation, peak memory, deadlocks) but its HLO/jaxpr rules stop at the
``pallas_call`` primitive — exactly where the performance-critical
serving and attention code lives (`ops/pallas/flash_attention.py`,
`ops/pallas/flash_decode.py`, `ops/pallas/fused_adam.py`). This module
walks a traced step (or serving program), extracts every
``pallas_call`` equation from the jaxpr, and checks four per-kernel
properties the Mosaic compiler will not check for us:

1. **VMEM footprint** — the per-grid-step working set: every
   BlockSpec's block shape x dtype width for inputs and outputs
   (doubled for Pallas's pipelined double buffering) plus the declared
   scratch shapes, against the per-platform VMEM budget in
   `analysis/cost.py`'s constants table (:data:`cost.PLATFORMS`,
   ``vmem_bytes``). A block configuration that cannot fit is a
   compile-time failure on real hardware that interpret-mode CI would
   never see.

2. **Tile-alignment lint** — block trailing dims vs the TPU native
   tile for the operand dtype (8x128 f32, 16x128 bf16, 32x128
   int8/fp8). A block whose lane (last) dim is not a multiple of 128,
   or whose sublane (second-minor) dim is not a multiple of the
   dtype's sublane count, wastes register tiles on every touch.
   Geometry-forced shapes are exempt: a block dim that covers the full
   array dim was never a choice, and singleton dims are indexed, not
   tiled.

3. **DMA-elision proofs** — each operand's index map is evaluated
   CONCRETELY over the full grid (index maps are pure functions of the
   grid indices and the scalar-prefetch operands, which the analyzer
   captures as live values by interpreting the traced jaxpr). Pallas
   skips the copy when consecutive grid steps map to the same block,
   so counting distinct-vs-total physical blocks per operand *proves*
   that an index map clamped to the row's occupancy actually dedupes
   dead blocks — and prices the kernel's real HBM traffic for the cost
   model.

   A kernel may instead leave an operand where it is
   (``memory_space=pl.ANY``) and fetch from it by manual DMA inside
   the step — the paged decode kernel walks each row's live blocks of
   the pool that way. Such an operand has no pipelined block and no
   index map: it costs no VMEM beyond the scratch slots the kernel
   declares, and its traffic is what the kernel's *declared walk*
   (:data:`MANUAL_WALKS`, by kernel name) says for the captured
   scalar-prefetch values: blocks fetched against the blocks a dense
   grid over the same rectangle would visit. "Elided" then reads "not
   launched".

4. **Grid-write races** — an output block revisited at NON-consecutive
   grid steps is undefined behavior in Pallas's grid semantics (the
   block is flushed when the grid moves away and re-fetched stale).
   Consecutive revisits are the legitimate accumulator idiom (the
   flash kernels' ``(bh, qi, 0)`` output maps) and pass.

`analysis/rules.py` turns these facts into ``kernel_vmem`` /
``kernel_tiling`` / ``kernel_dma`` findings; `analysis/audit.py` runs
them over the serving flavors and the train flash-attention path
(``ds_tpu_audit --kernels``).
"""

import dataclasses
import time
from typing import Optional

import numpy as np

from deepspeed_tpu.analysis.cost import resolve_platform

# TPU native register tile: (sublane, lane) per element width. The lane
# dim is 128 for every dtype; sublanes scale inversely with width.
LANE = 128
SUBLANES = {4: 8, 2: 16, 1: 32}

# Pallas pipelines block copies: while the grid computes on one block
# the next one streams in, so each input/output block is resident twice.
DOUBLE_BUFFER = 2

# Grids bigger than this skip the concrete index-map sweep (the static
# checks still run); every stock kernel's toy audit grid is far below.
DEFAULT_GRID_POINT_CAP = 65536


def sublane_tile(dtype) -> int:
    """Native sublane count for ``dtype`` (8 f32, 16 bf16, 32 int8/fp8)."""
    return SUBLANES.get(np.dtype(dtype).itemsize, 8)


@dataclasses.dataclass
class OperandFacts:
    """One block-mapped operand (input or output) of a pallas_call."""
    name: str
    kind: str                    # "input" | "output"
    block_shape: tuple
    array_shape: tuple
    dtype: str
    block_bytes: int
    total_fetches: int           # grid points (one block touch each)
    distinct_blocks: int         # unique block indices over the grid
    dma_fetches: int             # after consecutive-step elision
    elided_fraction: float       # 1 - dma_fetches / total_fetches
    index_map_evaluated: bool
    manual_dma: bool = False     # left in HBM, fetched by the kernel

    def to_dict(self):
        return dataclasses.asdict(self)


@dataclasses.dataclass
class KernelFacts:
    """Everything the kernel rules check about one pallas_call."""
    name: str
    grid: tuple
    operands: list               # [OperandFacts]
    scratch_bytes: int
    block_bytes_per_step: int    # single-buffered in+out working set
    vmem_bytes: int              # double-buffered blocks + scratch
    dense_bytes: int             # every grid step pays its block DMA
    dma_bytes: int               # after consecutive-step elision
    races: list                  # [{operand, block, steps}]
    tiling: list                 # [{operand, axis, block_dim, ...}]
    notes: list
    # a flash-attention kernel's walk over its (q tile, kv tile)
    # rectangle (:data:`TILE_WALKS`): the tile of every grid step
    # ``[n_points, 2]``, the (block_q, block_k) a tile spans, and the
    # rectangle's tiles over all rows and lane groups
    tile_steps: Optional[np.ndarray] = None
    tile_blocks: tuple = ()
    tile_rectangle: int = 0

    @property
    def elided_fraction(self):
        if not self.dense_bytes:
            return 0.0
        return 1.0 - self.dma_bytes / self.dense_bytes

    def to_dict(self):
        return {
            "name": self.name,
            "grid": list(self.grid),
            "scratch_bytes": self.scratch_bytes,
            "block_bytes_per_step": self.block_bytes_per_step,
            "vmem_bytes": self.vmem_bytes,
            "dense_bytes": self.dense_bytes,
            "dma_bytes": self.dma_bytes,
            "elided_dma_fraction": round(self.elided_fraction, 6),
            "races": list(self.races),
            "tiling": list(self.tiling),
            "notes": list(self.notes),
            "operands": {op.name: op.to_dict() for op in self.operands},
        }


@dataclasses.dataclass
class KernelAnalysis:
    """All kernels of one traced program + the platform budget."""
    kernels: list                # [KernelFacts]
    platform: str
    vmem_budget_bytes: int
    wall_s: float
    notes: list

    @property
    def dma_bytes(self):
        return sum(k.dma_bytes for k in self.kernels)

    @property
    def dense_bytes(self):
        return sum(k.dense_bytes for k in self.kernels)

    def to_dict(self):
        return {
            "platform": self.platform,
            "vmem_budget_bytes": self.vmem_budget_bytes,
            "wall_s": round(self.wall_s, 3),
            "notes": list(self.notes),
            "dma_bytes": self.dma_bytes,
            "dense_bytes": self.dense_bytes,
            "kernels": {k.name: k.to_dict() for k in self.kernels},
        }

    def kernel_cost_facts(self):
        """Per-kernel traffic facts in the shape
        `cost.estimate_step_cost(kernel_facts=...)` prices."""
        return [{"name": k.name, "dma_bytes": k.dma_bytes,
                 "dense_bytes": k.dense_bytes} for k in self.kernels]


# ---------------------------------------------------------------------------
# pallas_call extraction: concrete jaxpr interpretation
# ---------------------------------------------------------------------------

# Call-like primitives worth recursing through when (and only when) a
# pallas_call hides inside; everything else executes via plain bind.
_CALL_JAXPR_KEYS = {
    "jit": "jaxpr",
    "closed_call": "call_jaxpr",
    "core_call": "call_jaxpr",
    "custom_jvp_call": "call_jaxpr",
    "custom_vjp_call": "call_jaxpr",
    "custom_vjp_call_jaxpr": "fun_jaxpr",
    "remat2": "jaxpr",
    "checkpoint": "jaxpr",
}


def _as_closed(obj):
    from jax.extend import core as jcore

    if isinstance(obj, jcore.ClosedJaxpr):
        return obj
    return jcore.ClosedJaxpr(obj, ())


def _param_jaxprs(params):
    from jax.extend import core as jcore

    out = []
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if isinstance(item, jcore.ClosedJaxpr):
                out.append(item.jaxpr)
            elif isinstance(item, jcore.Jaxpr):
                out.append(item)
    return out


_HAS_PALLAS_CACHE = {}


def _jaxpr_has_pallas(jaxpr):
    key = id(jaxpr)
    hit = _HAS_PALLAS_CACHE.get(key)
    if hit is not None:
        return hit
    _HAS_PALLAS_CACHE[key] = False      # cycle guard
    found = False
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found = True
            break
        if any(_jaxpr_has_pallas(j) for j in _param_jaxprs(eqn.params)):
            found = True
            break
    _HAS_PALLAS_CACHE[key] = found
    return found


def _eqn_has_pallas(eqn):
    if eqn.primitive.name == "pallas_call":
        return True
    return any(_jaxpr_has_pallas(j) for j in _param_jaxprs(eqn.params))


def _bind(eqn, invals):
    subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
    return eqn.primitive.bind(*subfuns, *invals, **bind_params)


def _interp_jaxpr(jaxpr, consts, args, hits):
    """Forward-evaluate ``jaxpr`` with concrete ``args``, recording
    ``(eqn, concrete_invals)`` for every pallas_call reached (first
    occurrence per equation — scan iterations share one). Sub-jaxprs
    are only interpreted when a pallas_call hides inside; everything
    else runs as one compiled bind."""
    from jax.extend import core as jcore

    env = {}

    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    for var, val in zip(jaxpr.constvars, consts):
        env[var] = val
    for var, val in zip(jaxpr.invars, args):
        env[var] = val

    for eqn in jaxpr.eqns:
        invals = [read(v) for v in eqn.invars]
        name = eqn.primitive.name
        outvals = None
        if name == "pallas_call":
            if not any(rec[0] is eqn for rec in hits):
                hits.append((eqn, invals))
            outvals = _bind(eqn, invals)
        elif _eqn_has_pallas(eqn):
            try:
                if name == "scan":
                    outvals = _interp_scan(eqn, invals, hits)
                elif name == "while":
                    outvals = _interp_while(eqn, invals, hits)
                elif name == "cond":
                    branches = eqn.params["branches"]
                    br = branches[int(np.asarray(invals[0]))]
                    outvals = _interp_jaxpr(br.jaxpr, br.consts,
                                            invals[1:], hits)
                elif name in _CALL_JAXPR_KEYS:
                    closed = _as_closed(eqn.params[_CALL_JAXPR_KEYS[name]])
                    outvals = _interp_jaxpr(closed.jaxpr, closed.consts,
                                            invals, hits)
            except Exception:
                outvals = None      # fall through to plain bind
        if outvals is None:
            outvals = _bind(eqn, invals)
        if not eqn.primitive.multiple_results:
            outvals = [outvals]
        for var, val in zip(eqn.outvars, outvals):
            env[var] = val
    return [read(v) for v in jaxpr.outvars]


def _interp_scan(eqn, invals, hits):
    import jax.numpy as jnp

    p = eqn.params
    closed = p["jaxpr"]
    nc, ncar = int(p["num_consts"]), int(p["num_carry"])
    length = int(p["length"])
    consts = invals[:nc]
    carry = list(invals[nc:nc + ncar])
    xs = invals[nc + ncar:]
    order = range(length - 1, -1, -1) if p.get("reverse") else range(length)
    ys_by_i = {}
    for i in order:
        xi = [x[i] for x in xs]
        outs = _interp_jaxpr(closed.jaxpr, closed.consts,
                             [*consts, *carry, *xi], hits)
        carry = list(outs[:ncar])
        ys_by_i[i] = outs[ncar:]
    n_ys = len(next(iter(ys_by_i.values()))) if ys_by_i else 0
    ys = [jnp.stack([ys_by_i[i][j] for i in range(length)])
          for j in range(n_ys)]
    return carry + ys


def _interp_while(eqn, invals, hits, max_iters=100000):
    p = eqn.params
    cond, body = p["cond_jaxpr"], p["body_jaxpr"]
    cn, bn = int(p["cond_nconsts"]), int(p["body_nconsts"])
    cond_consts = invals[:cn]
    body_consts = invals[cn:cn + bn]
    carry = list(invals[cn + bn:])
    for _ in range(max_iters):
        pred = _interp_jaxpr(cond.jaxpr, cond.consts,
                             [*cond_consts, *carry], hits)[0]
        if not bool(np.asarray(pred)):
            return carry
        carry = list(_interp_jaxpr(body.jaxpr, body.consts,
                                   [*body_consts, *carry], hits))
    raise RuntimeError("while loop exceeded the interpreter's iteration "
                       "cap")


def _walk_static(jaxpr, hits, seen):
    """Structural pallas_call sweep (no concrete values) — the fallback
    when the concrete pass is unavailable or fails."""
    if id(jaxpr) in seen:
        return
    seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            if not any(rec[0] is eqn for rec in hits):
                hits.append((eqn, None))
            continue
        for sub in _param_jaxprs(eqn.params):
            _walk_static(sub, hits, seen)


def extract_pallas_calls(fn, args=None):
    """``[(eqn, concrete_invals | None)]`` for every pallas_call in
    ``fn`` traced at ``args``' avals.

    With concrete ``args`` the traced jaxpr is interpreted forward so
    each equation's scalar-prefetch operands are captured as live
    values (what the index-map evaluation needs); tracing alone covers
    programs whose index maps are pure grid functions. Returns the
    extraction plus a note string ("" when the concrete pass ran)."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten(args if args is not None
                                               else ())

    def flat_fn(*leaves):
        return fn(*jax.tree_util.tree_unflatten(treedef, leaves))

    closed = jax.make_jaxpr(flat_fn)(*flat)
    hits = []
    if args is not None:
        try:
            _interp_jaxpr(closed.jaxpr, closed.consts, list(flat), hits)
            return hits, ""
        except Exception as exc:
            hits = []
            note = (f"concrete pass failed ({type(exc).__name__}: "
                    f"{exc}); index maps with scalar operands not "
                    f"evaluated")
            _walk_static(closed.jaxpr, hits, set())
            return hits, note
    _walk_static(closed.jaxpr, hits, set())
    return hits, ""


# ---------------------------------------------------------------------------
# per-kernel facts
# ---------------------------------------------------------------------------

def _block_dims(block_shape):
    """Block shape as plain ints: ``Blocked(n)``/``Element(n)`` dims
    (and bare ints) give ``n``, Pallas's squeezed dim gives 1."""
    return tuple(int(getattr(d, "block_size", d))
                 if isinstance(getattr(d, "block_size", d),
                               (int, np.integer)) else 1
                 for d in block_shape)


def _block_bytes(block_shape, dtype):
    n = 1
    for d in _block_dims(block_shape):
        n *= d
    return n * np.dtype(dtype).itemsize


def _scratch_avals(eqn):
    """The kernel's declared scratch refs, in order: the kernel jaxpr's
    trailing invars."""
    n = int(getattr(eqn.params["grid_mapping"], "num_scratch_operands", 0))
    invars = eqn.params["jaxpr"].invars
    return [v.aval for v in invars[len(invars) - n:]] if n else []


def _scratch_bytes(eqn):
    """Declared VMEM scratch bytes (a semaphore holds none, and a few
    scalars in SMEM are not VMEM)."""
    from jax.experimental.pallas import tpu as pltpu
    total = 0
    for aval in _scratch_avals(eqn):
        if getattr(aval, "memory_space", None) == pltpu.SMEM:
            continue
        try:
            item = np.dtype(getattr(aval, "dtype", np.float32)).itemsize
        except TypeError:           # a semaphore's dtype is no data type
            continue
        total += int(np.prod(getattr(aval, "shape", ()), dtype=np.int64)) \
            * item
    return total


def _tiling_lint(name, block, array):
    """Misaligned / sublane-wasting block dims (see module docstring
    for the exemptions)."""
    out = []
    bdims = _block_dims(block.block_shape)
    adims = tuple(int(d) for d in block.array_shape)
    if len(bdims) < 2:
        return out
    sub = sublane_tile(block.dtype)
    lane_b, lane_a = bdims[-1], adims[-1]
    if lane_b % LANE and lane_b != lane_a:
        out.append({"operand": name, "axis": "lane",
                    "block_dim": lane_b, "tile": LANE,
                    "array_dim": lane_a, "dtype": block.dtype})
    sub_b, sub_a = bdims[-2], adims[-2]
    if sub_b > 1 and sub_b % sub and sub_b != sub_a:
        out.append({"operand": name, "axis": "sublane",
                    "block_dim": sub_b, "tile": sub,
                    "array_dim": sub_a, "dtype": block.dtype})
    return out


@dataclasses.dataclass
class _Block:
    """One BlockMapping, flattened to plain data."""
    block_shape: tuple
    array_shape: tuple
    dtype: str
    index_map: object            # ClosedJaxpr | None
    in_hbm: bool = False         # ``memory_space=pl.ANY``: not pipelined


def _block_of(bm):
    from jax.experimental import pallas as pl
    aval = bm.array_aval
    space = getattr(getattr(bm, "transformed_block_aval", None),
                    "memory_space", None)
    return _Block(block_shape=tuple(bm.block_shape),
                  array_shape=tuple(int(d) for d in aval.shape),
                  dtype=str(np.dtype(aval.dtype)),
                  index_map=getattr(bm, "index_map_jaxpr", None),
                  in_hbm=space == pl.ANY)


def _paged_decode_walk(scalar_vals, blocks, scratch):
    """`ops/pallas/flash_decode.py:flash_decode_paged`'s declared walk:
    ``{operand index: (block shape, dense fetches, fetched)}`` for the
    pool operands it leaves in HBM: the pool's leaves as inputs and,
    aliased to them, as outputs. Slot ``j`` of the scratch is the
    ``(2, *block)`` double buffer of the ``j``-th leaf; a dense grid
    would visit every row's ``pages_per_row * page / block_k`` blocks,
    the kernel fetches the live rows' live ones
    (:func:`~deepspeed_tpu.ops.pallas.flash_decode.paged_grid_blocks`,
    which is its loop bound), priced on the inputs, and writes back the
    one block a live row's position falls in (the loop it replaced
    wrote a page's slab for every row of the batch: the dense form),
    priced on the outputs."""
    from deepspeed_tpu.ops.pallas.flash_decode import (TRASH_PAGE,
                                                       paged_grid_blocks)
    positions, tables = scalar_vals[:2]
    pools = [i for i, b in enumerate(blocks) if b.in_hbm]
    leaves = len(pools) // 2
    block_k = scratch[0][-1]
    page = blocks[pools[0]].array_shape[-1]
    rows = tables.shape[0]
    dense = rows * tables.shape[1] * (page // block_k)
    _, launched = paged_grid_blocks(positions, tables, block_k)
    written = int(np.count_nonzero(tables[:, 0] != TRASH_PAGE))
    walk = {i: (scratch[j][1:], dense, launched)
            for j, i in enumerate(pools[:leaves])}
    walk.update({i: (scratch[j][1:], rows * (page // block_k), written)
                 for j, i in enumerate(pools[leaves:])})
    return walk


# kernels that fetch by manual DMA, by pallas_call name: what they
# fetch for given scalar-prefetch values
MANUAL_WALKS = {"ds_flash_decode_paged": _paged_decode_walk}

# kernels whose grid walks a (q tile, kv tile) rectangle, by pallas_call
# name (`ops/pallas/flash_attention.py`): the operands (q, k), both
# `[rows, seq, lanes]` blocks, whose index maps say which tile a grid
# step takes
TILE_WALKS = {"ds_flash_fwd": (0, 1), "ds_flash_dq": (0, 1),
              "ds_flash_dkv": (0, 1)}


def _tile_walk_facts(name, blocks, visited):
    """``(tile_steps, tile_blocks, tile_rectangle)`` of a kernel in
    :data:`TILE_WALKS`, from its q and k operands' evaluated index
    maps; Nones where either was not evaluated."""
    q, k = TILE_WALKS[name]
    if q not in visited or k not in visited:
        return None, (), 0
    steps = np.stack([visited[q][:, -2], visited[k][:, -2]], axis=1)
    spans = tuple(_block_dims(blocks[i].block_shape)[-2] for i in (q, k))
    n_q, n_k = (blocks[i].array_shape[-2] // span
                for i, span in zip((q, k), spans))
    lanes = {(int(a), int(b)) for a, b in zip(visited[q][:, 0],
                                              visited[q][:, -1])}
    return steps, spans, len(lanes) * n_q * n_k


def _eval_index_map(index_map, grid, scalar_vals, rank):
    """Block index tuples over the full grid, in Pallas's iteration
    order (row-major, last grid dim fastest): an int array
    ``[n_points, rank]``. Scalar-prefetch refs in the map are
    discharged to plain array reads fed with the captured values."""
    import jax
    import jax.numpy as jnp
    from jax.extend import core as jcore
    # no public spelling exists for state discharge (jax 0.9.0)
    from jax._src.state import discharge as state_discharge

    discharged, dconsts = state_discharge.discharge_state(
        index_map.jaxpr, index_map.consts)
    f = jcore.jaxpr_as_fun(jcore.ClosedJaxpr(discharged, dconsts))
    n = int(np.prod(grid))
    idx = np.unravel_index(np.arange(n), grid)   # C order = last fastest

    def one(*gi):
        outs = f(*gi, *scalar_vals)
        return tuple(outs[:rank])

    cols = jax.vmap(one)(*[jnp.asarray(ix, jnp.int32) for ix in idx])
    return np.stack([np.asarray(c) for c in cols], axis=1)


def _fetch_stats(blocks):
    """(distinct, dma_fetches) over row-major grid order. A fetch is
    elided when the block equals the immediately preceding step's."""
    distinct = len({tuple(b) for b in blocks})
    dma = 1
    for i in range(1, len(blocks)):
        if tuple(blocks[i]) != tuple(blocks[i - 1]):
            dma += 1
    return distinct, dma


def _race_scan(blocks):
    """Non-consecutive output-block revisits: ``[{block, steps}]``."""
    last_seen = {}
    flagged = {}
    for i, b in enumerate(map(tuple, blocks)):
        prev = last_seen.get(b)
        if prev is not None and prev != i - 1:
            rec = flagged.setdefault(b, {"block": list(b), "steps": []})
            if prev not in rec["steps"]:
                rec["steps"].append(prev)
            rec["steps"].append(i)
        last_seen[b] = i
    return list(flagged.values())


def kernel_facts(eqn, invals=None, grid_point_cap=DEFAULT_GRID_POINT_CAP):
    """:class:`KernelFacts` for one captured pallas_call equation."""
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    n_scalars = int(getattr(gm, "num_index_operands", 0))
    n_in = int(gm.num_inputs)
    n_out = int(gm.num_outputs)
    name = eqn.params.get("name") or getattr(
        getattr(eqn.params["jaxpr"], "debug_info", None),
        "func_name", None) or "pallas_kernel"
    scalar_vals = None
    if invals is not None:
        scalar_vals = [np.asarray(v) for v in invals[:n_scalars]]
    elif n_scalars == 0:
        scalar_vals = []

    notes = []
    n_points = int(np.prod(grid)) if grid else 1
    sweep = n_points <= grid_point_cap
    if not sweep:
        notes.append(f"grid has {n_points} points (> cap "
                     f"{grid_point_cap}); index maps not evaluated")

    operands, tiling, races = [], [], []
    block_bytes_total = dense_total = dma_total = 0
    blocks = [_block_of(bm) for bm in gm.block_mappings]
    walk = {}
    visited = {}                 # operand index -> block of every step
    if any(b.in_hbm for b in blocks):
        declared = MANUAL_WALKS.get(name)
        if declared is None:
            notes.append(f"operands left in HBM but no declared walk "
                         f"for kernel {name!r}: their traffic is not "
                         f"priced")
        elif scalar_vals is None:
            notes.append("manual-DMA walk reads scalar-prefetch operands "
                         "but no concrete values were captured")
        else:
            walk = declared(scalar_vals, blocks, [
                tuple(int(d) for d in getattr(a, "shape", ()))
                for a in _scratch_avals(eqn)])
    for i, block in enumerate(blocks):
        kind = "input" if i < n_in else "output"
        opname = f"in{i}" if i < n_in else f"out{i - n_in}"
        if block.in_hbm:
            # nothing of it is pipelined into VMEM (the kernel's slots
            # are scratch); its traffic is the declared walk's
            shape, dense, fetched = walk.get(
                i, (block.array_shape[1:], 0, 0))
            bbytes = _block_bytes(shape, block.dtype)
            cut = _Block(tuple(shape), block.array_shape[1:], block.dtype,
                         None)
            tiling.extend(_tiling_lint(opname, cut, cut))
            dense_total += dense * bbytes
            dma_total += fetched * bbytes
            operands.append(OperandFacts(
                name=opname, kind=kind, block_shape=tuple(shape),
                array_shape=block.array_shape, dtype=block.dtype,
                block_bytes=bbytes, total_fetches=dense,
                distinct_blocks=fetched, dma_fetches=fetched,
                elided_fraction=round(1.0 - fetched / dense, 6)
                if dense else 0.0,
                index_map_evaluated=i in walk, manual_dma=True))
            continue
        bbytes = _block_bytes(block.block_shape, block.dtype)
        block_bytes_total += bbytes
        tiling.extend(_tiling_lint(opname, block, block))
        distinct = dma = n_points
        evaluated = False
        if sweep and block.index_map is not None and scalar_vals is not None:
            try:
                visited[i] = _eval_index_map(
                    block.index_map, grid, scalar_vals,
                    len(block.block_shape))
                distinct, dma = _fetch_stats(visited[i])
                evaluated = True
                if kind == "output":
                    for rec in _race_scan(visited[i]):
                        rec["operand"] = opname
                        races.append(rec)
            except Exception as exc:
                notes.append(f"{opname}: index map evaluation failed "
                             f"({type(exc).__name__}: {exc})")
        elif block.index_map is not None and scalar_vals is None:
            notes.append(f"{opname}: index map reads scalar-prefetch "
                         f"operands but no concrete values were "
                         f"captured")
        dense_total += n_points * bbytes
        dma_total += dma * bbytes
        operands.append(OperandFacts(
            name=opname, kind=kind,
            block_shape=_block_dims(block.block_shape),
            array_shape=block.array_shape, dtype=block.dtype,
            block_bytes=bbytes, total_fetches=n_points,
            distinct_blocks=distinct, dma_fetches=dma,
            elided_fraction=round(1.0 - dma / n_points, 6)
            if n_points else 0.0,
            index_map_evaluated=evaluated))

    scratch = _scratch_bytes(eqn)
    steps, spans, rectangle = (_tile_walk_facts(name, blocks, visited)
                               if name in TILE_WALKS else (None, (), 0))
    return KernelFacts(
        name=name, grid=grid, operands=operands, scratch_bytes=scratch,
        block_bytes_per_step=block_bytes_total,
        vmem_bytes=DOUBLE_BUFFER * block_bytes_total + scratch,
        dense_bytes=dense_total, dma_bytes=dma_total,
        races=races, tiling=tiling, notes=notes,
        tile_steps=steps, tile_blocks=spans, tile_rectangle=rectangle)


def _tiling_lint_block(bdims, adims, dtype):
    """Lint arbitrary (block, array, dtype) dims — test seam."""
    blk = _Block(block_shape=bdims, array_shape=adims,
                 dtype=str(np.dtype(dtype)), index_map=None)
    return _tiling_lint("block", blk, blk)


# keep _tiling_lint's signature simple for kernel_facts: it takes the
# operand name and the same _Block twice (block + array live together)
def analyze_kernels(fn, args=None, *, platform="tpu_v5e",
                    grid_point_cap=DEFAULT_GRID_POINT_CAP):
    """Extract and analyze every pallas_call in ``fn`` at ``args``.

    ``fn`` may be jitted or plain; ``args`` concrete arrays (their
    values feed the scalar-prefetch index maps — pass the live call
    args for a DMA-elision proof) or None for a purely structural
    sweep. ``platform`` picks the VMEM budget row from
    `cost.PLATFORMS`. Returns a :class:`KernelAnalysis`.
    """
    t0 = time.perf_counter()
    p = resolve_platform(platform)
    hits, note = extract_pallas_calls(fn, args)
    notes = [note] if note else []
    kernels = []
    seen_names = {}
    for eqn, invals in hits:
        facts = kernel_facts(eqn, invals, grid_point_cap=grid_point_cap)
        n = seen_names.get(facts.name, 0)
        seen_names[facts.name] = n + 1
        if n:
            facts.name = f"{facts.name}#{n}"
        kernels.append(facts)
    return KernelAnalysis(
        kernels=kernels, platform=p.name,
        vmem_budget_bytes=p.vmem_bytes,
        wall_s=time.perf_counter() - t0, notes=notes)


# ---------------------------------------------------------------------------
# elision expectations (the audit's decode proof)
# ---------------------------------------------------------------------------

def paged_dead_block_fraction(positions, page_tables, page_size, block_k):
    """What the decode kernel must not fetch: the fraction of the
    ``rows x pages_per_row x page_size / block_k`` rectangle (in blocks of all
    heads) that holds nothing — blocks past a live row's position and
    every block of a row without a request (first table entry the trash
    page). The paged kernel launches none of them."""
    from deepspeed_tpu.ops.pallas.flash_decode import paged_grid_blocks
    tables = np.asarray(page_tables)
    dense = tables.shape[0] * tables.shape[1] * (
        int(page_size) // int(block_k))
    if not dense:
        return 0.0
    live, _ = paged_grid_blocks(positions, tables, block_k)
    return 1.0 - live / dense


def causal_dead_tile_fraction(kernel):
    """Of the (q tile, kv tile) pairs a causal flash-attention kernel's
    grid visits, the fraction the mask kills whole (every key after
    every query), read from the grid and the index maps
    (:class:`KernelFacts` ``tile_steps``). A grid over the whole
    rectangle fetches K and V for each and skips only the arithmetic:
    0.25 at 2 x 2 tiles, 0.4375 at 8 x 8. A kernel that walks the live
    tiles from a table visits none. ``None`` for a kernel that walks no
    tiles or whose maps were not evaluated."""
    if kernel.tile_steps is None or not len(kernel.tile_steps):
        return None
    qi, ki = kernel.tile_steps[:, 0], kernel.tile_steps[:, 1]
    block_q, block_k = kernel.tile_blocks
    return float(np.mean(ki * block_k > qi * block_q + block_q - 1))


def causal_rectangle_dead_fraction(n_q, n_k, block_q, block_k):
    """What a causal kernel must not visit: the wholly masked tiles'
    share of the ``n_q x n_k`` rectangle."""
    qi, ki = np.meshgrid(np.arange(n_q), np.arange(n_k), indexing="ij")
    return float(np.mean(ki * block_k > qi * block_q + block_q - 1))

"""Jaxpr-level static analysis: collective safety before XLA ever runs.

The HLO side of the audit (`analysis/hlo.py`) reads facts off the
*compiled* program; this module reads the **traced** program — the
closed jaxpr of a train step — where control flow (``cond``/``while``/
``scan``), mesh-axis data dependence, and collective ordering are still
first-class structure instead of partitioned channel ids. Three passes,
all pure functions over a :class:`jax.extend.core.ClosedJaxpr`:

- :func:`check_divergent_collectives` — the PR 5 pipeline deadlock as a
  rule. Values derived from ``lax.axis_index`` are *device-varying*
  (tainted) over that mesh axis; a ``lax.cond`` whose predicate carries
  taint executes its branches divergently across devices. A
  ``ppermute`` inside such a branch deadlocks the in-process runtime
  outright (collective-permute rendezvous is GLOBAL — every device must
  arrive at the *same* op) and is invalid SPMD everywhere; a grouped
  collective (``psum``/``all_gather``/…) is flagged only when its own
  axis is among the divergent ones (devices of one rendezvous group
  taking different branches), which is why the seed's stage-divergent
  ``lax.cond`` survived while its collectives were per-``data``-group
  all-reduces and died the moment TP reductions chunked into permute
  rings. A ``while`` whose *trip count* is device-varying divergently
  executes everything inside it, so any collective in its body is
  flagged.
- :func:`check_unordered_permutes` — the ``barrier_after`` invariant,
  checked instead of assumed: every pair of ``ppermute``s that can be
  in flight concurrently must be ordered by a dataflow edge (the
  overlap library chains each emitted permute through
  ``parallel.collectives.barrier_after``). Two *independent* in-flight
  permutes split the in-process runtime's global rendezvous — half the
  devices arrive at one op, half at the other — and deadlock.
- :func:`propagate_partition_specs` — a lightweight sharding-flow
  interpreter: seed the jaxpr inputs with their PartitionSpecs and push
  them through shape-preserving ops, ``transpose``/``broadcast``/
  ``dot_general``, and control flow. Operands meeting with
  *conflicting* placements on the same dimension force a compiler-
  inserted reshard (all-gather + reslice) that no declared site
  accounts for — recorded as events the ``resharding`` rule sizes and
  reports.

Everything here runs at trace time: no compile, no execution — which is
the point, since the programs being checked for deadlocks must never be
run to find out.
"""

import dataclasses

import numpy as np

from jax.core import DropVar
from jax.extend import core as jcore

# Collective primitives by rendezvous discipline (jaxpr names).
# ``ppermute`` lowers to ``collective-permute`` whose rendezvous is
# global across the mesh — every device must reach the same op.
GLOBAL_RENDEZVOUS = ("ppermute",)
# Grouped collectives rendezvous per replica group along their own axes:
# divergence only breaks them when it splits a group.
GROUPED_COLLECTIVES = ("psum", "pmax", "pmin", "pmean", "all_gather",
                       "all_to_all", "reduce_scatter", "psum_scatter",
                       "pbroadcast", "pgather")
COLLECTIVE_PRIMITIVES = GLOBAL_RENDEZVOUS + GROUPED_COLLECTIVES

# Grouped collectives whose output is *uniform* along their axes (a
# reduction or gather makes every member hold the same value) — they
# erase device-variance taint. ``all_to_all``/``ppermute`` redistribute
# instead and keep (or introduce) variance.
_TAINT_ERASING = ("psum", "pmax", "pmin", "pmean", "all_gather",
                  "pbroadcast")


def _collective_axes(eqn):
    """Mesh axes a collective eqn rendezvouses over, as a tuple."""
    axes = eqn.params.get("axes",
                          eqn.params.get("axis_name",
                                         eqn.params.get("axis_index_groups")
                                         and ()))
    if axes is None:
        axes = ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if a is not None)


def _aval_bytes(aval):
    shape = tuple(getattr(aval, "shape", ()) or ())
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    itemsize = np.dtype(dtype).itemsize
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    return n * itemsize


def _as_jaxprs(value):
    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            if isinstance(v, jcore.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jcore.Jaxpr):
                yield v


def subjaxpr_bindings(eqn):
    """``(jaxpr, binders, label)`` per sub-jaxpr of ``eqn``.

    ``binders`` aligns the inner jaxpr's invars with the eqn's invars
    (outer atoms, or None where no outer atom corresponds — e.g. branch
    binders past the operand list). Control-flow primitives get exact
    maps; anything else maps positionally when the arity matches and
    conservatively (all-None) when it doesn't.
    """
    p = eqn.primitive.name
    if p == "cond":
        ops = list(eqn.invars[1:])
        for i, br in enumerate(eqn.params["branches"]):
            yield br.jaxpr, ops, f"cond branch {i}"
        return
    if p == "while":
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        carry = list(eqn.invars[cn + bn:])
        yield (eqn.params["cond_jaxpr"].jaxpr,
               list(eqn.invars[:cn]) + carry, "while cond")
        yield (eqn.params["body_jaxpr"].jaxpr,
               list(eqn.invars[cn:cn + bn]) + carry, "while body")
        return
    if p == "scan":
        # invars = consts + carry + xs; the body binds them positionally
        # (xs as per-iteration slices — same taint/ordering semantics).
        yield eqn.params["jaxpr"].jaxpr, list(eqn.invars), "scan body"
        return
    for key, value in sorted(eqn.params.items()):
        for jx in _as_jaxprs(value):
            if len(jx.invars) == len(eqn.invars):
                binders = list(eqn.invars)
            else:
                binders = [None] * len(jx.invars)
            yield jx, binders, p


def _scan_length(eqn):
    if eqn.primitive.name == "scan":
        return int(eqn.params.get("length", 1))
    return 1


# ---------------------------------------------------------------------------
# collective site collection
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CollectiveSite:
    """One collective eqn in the traced program."""
    primitive: str
    axes: tuple
    path: tuple          # context stack, e.g. ("shard_map", "scan body")
    out_bytes: int       # device-local payload of one execution
    multiplier: int      # static execution count (product of scan trips)


def collect_collectives(closed_jaxpr):
    """Every collective eqn with its context path and static execution
    multiplier (``scan`` lengths compound; ``while`` counts as 1 — its
    trip count is the HLO side's problem)."""
    sites = []

    def walk(jaxpr, path, mult):
        for eqn in jaxpr.eqns:
            p = eqn.primitive.name
            if p in COLLECTIVE_PRIMITIVES:
                sites.append(CollectiveSite(
                    primitive=p,
                    axes=_collective_axes(eqn),
                    path=path,
                    out_bytes=sum(_aval_bytes(v.aval)
                                  for v in eqn.outvars),
                    multiplier=mult))
            sub_mult = mult * _scan_length(eqn)
            for jx, _, label in subjaxpr_bindings(eqn):
                walk(jx, path + (label,), sub_mult)

    walk(closed_jaxpr.jaxpr, (), 1)
    return sites


# ---------------------------------------------------------------------------
# pass 1: divergent collectives (the PR 5 stage-divergent cond, as a rule)
# ---------------------------------------------------------------------------

def check_divergent_collectives(closed_jaxpr):
    """Deadlock findings for collectives under device-varying control
    flow. Returns ``[{kind, message, path, primitive, axes,
    divergent_axes}]``; empty means the program is collective-uniform.
    """
    findings = []

    def warn(kind, eqn, path, div_axes, msg):
        findings.append({
            "kind": kind,
            "primitive": eqn.primitive.name,
            "axes": tuple(_collective_axes(eqn)),
            "divergent_axes": tuple(sorted(div_axes)),
            "path": path,
            "message": msg,
        })

    def walk(jaxpr, in_taints, path, div_axes, loop_div):
        """Returns per-outvar taints. ``div_axes``: axes the current
        control-flow context diverges over; ``loop_div``: inside a while
        whose trip count is device-varying."""
        env = {}

        def read(atom):
            if isinstance(atom, jcore.Literal):
                return frozenset()
            return env.get(atom, frozenset())

        def write(var, taint):
            if not isinstance(var, DropVar):
                env[var] = taint

        for var, t in zip(jaxpr.invars, in_taints):
            write(var, t)
        for var in jaxpr.constvars:
            write(var, frozenset())

        for eqn in jaxpr.eqns:
            p = eqn.primitive.name
            in_t = [read(a) for a in eqn.invars]
            joined = frozenset().union(*in_t) if in_t else frozenset()

            if p in COLLECTIVE_PRIMITIVES:
                axes = _collective_axes(eqn)
                if loop_div:
                    warn("deadlock", eqn, path, div_axes,
                         f"{p} over {axes} inside a while loop whose "
                         f"trip count varies across devices of mesh "
                         f"axis(es) {tuple(sorted(div_axes))} — devices "
                         f"exit the loop at different iterations and "
                         f"miss the rendezvous")
                elif div_axes and p in GLOBAL_RENDEZVOUS:
                    warn("deadlock", eqn, path, div_axes,
                         f"{p} over {axes} executes inside control flow "
                         f"divergent over mesh axis(es) "
                         f"{tuple(sorted(div_axes))} — collective-"
                         f"permute rendezvous is global, so devices "
                         f"taking the other branch never arrive (the "
                         f"PR 5 stage-divergent pipeline deadlock)")
                elif div_axes and set(axes) & div_axes:
                    hit = tuple(sorted(set(axes) & div_axes))
                    warn("deadlock", eqn, path, div_axes,
                         f"{p} over {axes} executes inside control flow "
                         f"divergent over its own axis(es) {hit} — "
                         f"members of one rendezvous group take "
                         f"different branches")

            if p == "axis_index":
                ax = eqn.params.get("axis_name")
                ax = ax if isinstance(ax, (tuple, list)) else (ax,)
                out_taint = joined | frozenset(a for a in ax
                                               if a is not None)
            elif p in _TAINT_ERASING:
                out_taint = joined - frozenset(_collective_axes(eqn))
            elif p == "all_to_all":
                out_taint = joined | frozenset(_collective_axes(eqn))
            else:
                out_taint = joined

            if p == "cond":
                pred_t = read(eqn.invars[0])
                sub_div = div_axes | pred_t
                out_ts = None
                for jx, binders, label in subjaxpr_bindings(eqn):
                    bt = [read(b) if b is not None else frozenset()
                          for b in binders]
                    branch_out = walk(jx, bt, path + (label,),
                                      sub_div if pred_t else div_axes,
                                      loop_div)
                    if out_ts is None:
                        out_ts = list(branch_out)
                    else:
                        out_ts = [a | b for a, b in zip(out_ts,
                                                        branch_out)]
                for var, t in zip(eqn.outvars, out_ts or []):
                    write(var, t | pred_t)
                continue

            if p == "while":
                cn = eqn.params["cond_nconsts"]
                bn = eqn.params["body_nconsts"]
                cond_jx = eqn.params["cond_jaxpr"].jaxpr
                body_jx = eqn.params["body_jaxpr"].jaxpr
                cconst_t = in_t[:cn]
                bconst_t = in_t[cn:cn + bn]
                carry_t = list(in_t[cn + bn:])
                # Taint-fixpoint over the carry (taints only grow).
                for _ in range(len(carry_t) + 2):
                    body_out = walk(body_jx, bconst_t + carry_t,
                                    path + ("while body",), div_axes,
                                    loop_div)
                    new_carry = [a | b for a, b in zip(carry_t,
                                                       body_out)]
                    if new_carry == carry_t:
                        break
                    carry_t = new_carry
                (cond_t,) = walk(cond_jx, cconst_t + carry_t,
                                 path + ("while cond",), div_axes,
                                 loop_div)
                if cond_t:
                    # Device-varying trip count: re-walk the body in
                    # loop-divergent mode so every collective inside is
                    # flagged.
                    walk(body_jx, bconst_t + carry_t,
                         path + ("while body",), div_axes | cond_t,
                         True)
                for var, t in zip(eqn.outvars, carry_t):
                    write(var, t | cond_t)
                continue

            if p == "scan":
                nc = eqn.params["num_consts"]
                ncar = eqn.params["num_carry"]
                jx = eqn.params["jaxpr"].jaxpr
                const_t = in_t[:nc]
                carry_t = list(in_t[nc:nc + ncar])
                xs_t = in_t[nc + ncar:]
                for _ in range(len(carry_t) + 2):
                    body_out = walk(jx, const_t + carry_t + xs_t,
                                    path + ("scan body",), div_axes,
                                    loop_div)
                    new_carry = [a | b for a, b in
                                 zip(carry_t, body_out[:ncar])]
                    if new_carry == carry_t:
                        break
                    carry_t = new_carry
                out_ts = carry_t + list(body_out[ncar:])
                for var, t in zip(eqn.outvars, out_ts):
                    write(var, t)
                continue

            handled_sub = False
            for jx, binders, label in subjaxpr_bindings(eqn):
                handled_sub = True
                bt = [read(b) if b is not None else joined
                      for b in binders]
                sub_out = walk(jx, bt, path + (label,), div_axes,
                               loop_div)
                if len(sub_out) == len(eqn.outvars):
                    for var, t in zip(eqn.outvars, sub_out):
                        write(var, t)
                else:
                    sub_joined = (frozenset().union(*sub_out)
                                  if sub_out else frozenset())
                    for var in eqn.outvars:
                        write(var, joined | sub_joined)
            if handled_sub:
                continue

            for var in eqn.outvars:
                write(var, out_taint)

        return [read(v) for v in jaxpr.outvars]

    jaxpr = closed_jaxpr.jaxpr
    walk(jaxpr, [frozenset()] * len(jaxpr.invars), (), frozenset(), False)
    return findings


# ---------------------------------------------------------------------------
# pass 2: unordered concurrent collective-permutes (barrier_after, checked)
# ---------------------------------------------------------------------------

def check_unordered_permutes(closed_jaxpr, max_findings=16):
    """Pairs of ``ppermute``s with no dataflow ordering between them.

    Within each (sub-)jaxpr body, every eqn that (transitively) emits a
    ``ppermute`` must be an ancestor or descendant of every other —
    i.e. the emitted permutes form one dependency chain, the invariant
    ``parallel.collectives.barrier_after`` exists to maintain. Branch
    bodies of one ``cond`` are checked independently (they never
    co-execute). Returns ``[{kind, message, path, eqns}]``.
    """
    findings = []
    emits_cache = {}

    def emits_permute(jaxpr):
        key = id(jaxpr)
        if key not in emits_cache:
            emits_cache[key] = False  # cycle-safe default
            found = False
            for eqn in jaxpr.eqns:
                if eqn.primitive.name in GLOBAL_RENDEZVOUS:
                    found = True
                    break
                for jx, _, _ in subjaxpr_bindings(eqn):
                    if emits_permute(jx):
                        found = True
                        break
                if found:
                    break
            emits_cache[key] = found
        return emits_cache[key]

    def walk(jaxpr, path):
        producer = {}
        anc = []
        permute_eqns = []   # [(idx, label)]
        for i, eqn in enumerate(jaxpr.eqns):
            mask = 0
            for a in eqn.invars:
                j = producer.get(a) if not isinstance(a, jcore.Literal) \
                    else None
                if j is not None:
                    mask |= anc[j] | (1 << j)
            anc.append(mask)
            emits = eqn.primitive.name in GLOBAL_RENDEZVOUS
            for jx, _, label in subjaxpr_bindings(eqn):
                walk(jx, path + (label,))
                emits = emits or emits_permute(jx)
            if emits:
                for j, j_label in permute_eqns:
                    if not (mask >> j) & 1 and len(findings) < \
                            max_findings:
                        findings.append({
                            "kind": "unordered_permutes",
                            "path": path,
                            "eqns": (j_label,
                                     str(eqn.primitive.name)),
                            "message":
                                f"two collective-permute-emitting ops "
                                f"({j_label!s} and "
                                f"{eqn.primitive.name}) share no "
                                f"dataflow edge at {'/'.join(path) or 'top level'} — both can be "
                                f"in flight at once, splitting the "
                                f"global rendezvous (chain them with "
                                f"parallel.collectives.barrier_after)",
                        })
                permute_eqns.append((i, eqn.primitive.name))
            for v in eqn.outvars:
                if not isinstance(v, DropVar):
                    producer[v] = i

    walk(closed_jaxpr.jaxpr, ())
    return findings


# ---------------------------------------------------------------------------
# pass 3: PartitionSpec flow (sharding lint)
# ---------------------------------------------------------------------------

UNKNOWN = object()     # spec lattice top: propagation lost track


def _norm_entry(e):
    if e is None:
        return None
    if isinstance(e, (tuple, list)):
        return tuple(e)
    return (e,)


def spec_tuple(spec, rank):
    """A PartitionSpec (or tuple) normalized to exactly ``rank`` per-dim
    entries (None = replicated; tuple of axis names = sharded)."""
    entries = [_norm_entry(e) for e in tuple(spec or ())]
    entries = entries[:rank]
    entries += [None] * (rank - len(entries))
    return tuple(entries)


def _join_specs(specs, avals):
    """Join same-shaped operand specs; returns (spec | UNKNOWN, conflict
    dim | None). Replicated joins with anything (a further slice, no
    comm). A conflict — a reshard the compiler must insert — is either
    two different non-None placements on one dim, or the same mesh axis
    claimed by different dims of different operands."""
    known = [(s, a) for s, a in zip(specs, avals)
             if s is not UNKNOWN and getattr(a, "shape", None) is not None]
    if not known:
        return UNKNOWN, None
    rank = max(len(s) for s, _ in known)
    out = [None] * rank
    axis_dim = {}        # mesh axis name -> dim it shards in the join
    conflict = None
    for s, _ in known:
        for d, e in enumerate(s):
            if e is None:
                continue
            if out[d] is None:
                out[d] = e
            elif out[d] != e:
                conflict = d
            for axis in e:
                if axis_dim.setdefault(axis, d) != d:
                    conflict = d
    return tuple(out), conflict


@dataclasses.dataclass
class ReshardEvent:
    """A point where propagation saw placements forcibly change."""
    kind: str            # "conflict"
    primitive: str
    path: tuple
    dim: int
    bytes: int           # size of the largest operand involved
    specs: tuple         # the operand spec tuples that collided


def propagate_partition_specs(closed_jaxpr, in_specs):
    """Push per-dim PartitionSpec entries through the jaxpr.

    ``in_specs``: one PartitionSpec (or per-dim tuple, or None for
    replicated) per jaxpr invar. Returns ``(out_specs, events)`` where
    ``out_specs`` has an entry (tuple | UNKNOWN) per outvar and
    ``events`` lists :class:`ReshardEvent`s — operands meeting with
    conflicting placements, i.e. compiler-inserted reshards no declared
    site accounts for.

    Deliberately partial: shape-preserving ops, ``transpose``,
    ``broadcast_in_dim``, ``squeeze``/``expand_dims``, ``dot_general``,
    ``convert_element_type`` and control flow propagate; anything else
    (including everything inside ``shard_map``, whose body is manual)
    degrades to UNKNOWN instead of guessing.
    """
    events = []

    def walk(jaxpr, specs_in, path):
        env = {}

        def read(atom):
            if isinstance(atom, jcore.Literal):
                return spec_tuple(None, np.ndim(atom.val))
            return env.get(atom, UNKNOWN)

        def write(var, spec):
            if not isinstance(var, DropVar):
                env[var] = spec

        for var, s in zip(jaxpr.invars, specs_in):
            rank = len(getattr(var.aval, "shape", ()) or ())
            write(var, UNKNOWN if s is UNKNOWN
                  else spec_tuple(s, rank))
        for var in jaxpr.constvars:
            rank = len(getattr(var.aval, "shape", ()) or ())
            write(var, spec_tuple(None, rank))

        for eqn in jaxpr.eqns:
            p = eqn.primitive.name
            in_s = [read(a) for a in eqn.invars]
            avals = [getattr(a, "aval", None) for a in eqn.invars]
            out_rank = [len(getattr(v.aval, "shape", ()) or ())
                        for v in eqn.outvars]

            if p == "sharding_constraint":
                sh = eqn.params.get("sharding")
                spec = getattr(sh, "spec", None)
                write(eqn.outvars[0],
                      spec_tuple(spec, out_rank[0]) if spec is not None
                      else UNKNOWN)
                continue

            if p == "transpose":
                s = in_s[0]
                if s is UNKNOWN:
                    write(eqn.outvars[0], UNKNOWN)
                else:
                    perm = eqn.params["permutation"]
                    write(eqn.outvars[0], tuple(s[d] for d in perm))
                continue

            if p == "broadcast_in_dim":
                s = in_s[0]
                out = [None] * out_rank[0]
                if s is not UNKNOWN:
                    for src, dst in enumerate(
                            eqn.params["broadcast_dimensions"]):
                        out[dst] = s[src]
                    write(eqn.outvars[0], tuple(out))
                else:
                    write(eqn.outvars[0], UNKNOWN)
                continue

            if p in ("squeeze", "expand_dims"):
                s = in_s[0]
                if s is UNKNOWN:
                    write(eqn.outvars[0], UNKNOWN)
                    continue
                in_shape = tuple(avals[0].shape)
                if p == "squeeze":
                    dims = set(eqn.params["dimensions"])
                    write(eqn.outvars[0],
                          tuple(e for d, e in enumerate(s)
                                if d not in dims))
                else:
                    out = list(s)
                    for d in sorted(eqn.params["dimensions"]):
                        out.insert(d, None)
                    write(eqn.outvars[0], tuple(out))
                del in_shape
                continue

            if p == "dot_general":
                ls, rs = in_s[0], in_s[1]
                if ls is UNKNOWN or rs is UNKNOWN:
                    write(eqn.outvars[0], UNKNOWN)
                    continue
                ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
                lfree = [d for d in range(len(ls))
                         if d not in lc and d not in lb]
                rfree = [d for d in range(len(rs))
                         if d not in rc and d not in rb]
                out = tuple([ls[d] for d in lb]
                            + [ls[d] for d in lfree]
                            + [rs[d] for d in rfree])
                write(eqn.outvars[0], out)
                continue

            if p == "cond":
                out_specs = None
                for jx, binders, label in subjaxpr_bindings(eqn):
                    bs = [read(b) if b is not None else UNKNOWN
                          for b in binders]
                    branch_out = walk(jx, bs, path + (label,))
                    if out_specs is None:
                        out_specs = list(branch_out)
                    else:
                        out_specs = [
                            a if (a is not UNKNOWN and a == b) else
                            UNKNOWN
                            for a, b in zip(out_specs, branch_out)]
                for var, s in zip(eqn.outvars, out_specs or []):
                    write(var, s)
                continue

            if p == "scan":
                nc = eqn.params["num_consts"]
                ncar = eqn.params["num_carry"]
                jx = eqn.params["jaxpr"].jaxpr
                # xs lose their leading (scanned) dim inside the body.
                xs_specs = [UNKNOWN if s is UNKNOWN else tuple(s[1:])
                            for s in in_s[nc + ncar:]]
                body_in = in_s[:nc + ncar] + xs_specs
                body_out = walk(jx, body_in, path + ("scan body",))
                carry_out = [
                    a if (a is not UNKNOWN and a == b) else UNKNOWN
                    for a, b in zip(body_out[:ncar],
                                    in_s[nc:nc + ncar])]
                ys = [UNKNOWN if s is UNKNOWN else (None,) + tuple(s)
                      for s in body_out[ncar:]]
                for var, s in zip(eqn.outvars, carry_out + ys):
                    write(var, s)
                continue

            if p == "jit":
                for jx, binders, label in subjaxpr_bindings(eqn):
                    sub_out = walk(jx, in_s, path + (label,))
                    if len(sub_out) == len(eqn.outvars):
                        for var, s in zip(eqn.outvars, sub_out):
                            write(var, s)
                    else:
                        for var in eqn.outvars:
                            write(var, UNKNOWN)
                continue

            has_sub = False
            for jx, _, label in subjaxpr_bindings(eqn):
                has_sub = True
                # Opaque call (shard_map bodies are manual; custom_vjp
                # wraps its own trace): still recurse so nested passes
                # COULD see it, but specs inside are not meaningful —
                # degrade outputs to UNKNOWN.
                walk(jx, [UNKNOWN] * len(jx.invars), path + (label,))
            if has_sub:
                for var in eqn.outvars:
                    write(var, UNKNOWN)
                continue

            # Structural elementwise rule: all non-scalar operands share
            # the output shape → join their specs (conflicts = forced
            # reshard), scalars ride along.
            if len(eqn.outvars) == 1 and out_rank[0] > 0:
                peers = [(s, a) for s, a in zip(in_s, avals)
                         if a is not None
                         and tuple(getattr(a, "shape", ()) or ()) ==
                         tuple(eqn.outvars[0].aval.shape)]
                if peers and all(s is not UNKNOWN for s, _ in peers):
                    joined, conflict = _join_specs(
                        [s for s, _ in peers], [a for _, a in peers])
                    if conflict is not None:
                        events.append(ReshardEvent(
                            kind="conflict", primitive=p, path=path,
                            dim=conflict,
                            bytes=max(_aval_bytes(a) for _, a in peers),
                            specs=tuple(s for s, _ in peers)))
                        joined = UNKNOWN
                    write(eqn.outvars[0], joined)
                    continue
            for var in eqn.outvars:
                write(var, UNKNOWN)

        return [read(v) for v in jaxpr.outvars]

    jaxpr = closed_jaxpr.jaxpr
    n = len(jaxpr.invars)
    seeds = list(in_specs) + [UNKNOWN] * (n - len(in_specs))
    out = walk(jaxpr, seeds[:n], ())
    return out, events


# ---------------------------------------------------------------------------
# tracing front door
# ---------------------------------------------------------------------------

def trace_jaxpr(fn, args, fresh=True):
    """ClosedJaxpr of a (jitted or plain) step function at ``args``'
    avals — a retrace, never a compile.

    ``fresh=True`` (default) traces the *unwrapped* callable
    (``fn.__wrapped__`` for a jitted fn) so the Python body actually
    re-runs: trace-time instrumentation — the
    ``parallel.collectives`` site log, the pipeline trace fixtures —
    only fires on a genuine retrace, and a jitted ``fn.trace`` is
    served from the jit cache after the step has compiled.
    ``fresh=False`` takes the cache-sharing path (cheapest when only
    the jaxpr itself is needed)."""
    import jax

    if fresh:
        # Unwrap the jit boundary, then trace through a THROWAWAY lambda:
        # the pjit trace cache is keyed on the underlying function object,
        # so make_jaxpr of the long-lived step fn is a cache hit that
        # skips its Python body entirely. A fresh closure per call forces
        # the body to actually re-run.
        inner = getattr(fn, "__wrapped__", None)
        target = inner if callable(inner) else fn
        return jax.make_jaxpr(lambda *a: target(*a))(*args)
    trace = getattr(fn, "trace", None)
    if callable(trace):
        return trace(*args).jaxpr
    return jax.make_jaxpr(fn)(*args)


def input_specs_of(args):
    """Per-flat-leaf PartitionSpecs of concrete call arguments: committed
    ``jax.Array``s report their NamedSharding spec; anything else
    (numpy, scalars) is treated as replicated."""
    import jax

    specs = []
    for leaf in jax.tree_util.tree_leaves(args):
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        rank = np.ndim(leaf)
        specs.append(spec_tuple(spec, rank))
    return specs

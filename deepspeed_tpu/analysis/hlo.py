"""HLO parsing core for the compiled-program audit subsystem.

Under XLA every collective, buffer alias, and dtype decision in a train
step is a *compile-time* artifact: an HLO op with a static shape, an
``input_output_alias`` entry in the module header, a ``while`` loop with
a known trip count. This module reads those facts off ``compile()``'s
``as_text()`` dump so the audit rules (`analysis/rules.py`) can check
them against what the engine *declared* it wanted.

Accounting is **trip-count-aware**: HLO programs are split into their
computations, the call graph (``while`` body/condition, ``calls=``,
``to_apply=``, conditional branches) is walked from ENTRY, and each
computation gets an execution multiplier — a collective inside a
``lax.scan``-lowered ``while`` with ``known_trip_count n=K`` counts K
times, not once (counted ONCE, the executed-1F1B pipeline's per-tick
``collective-permute`` volume was unpinnable). Text without any
computation headers (hand-written snippets in tests) falls back to flat
counting, and ``trip_aware=False`` counts every op once.
"""

import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    # fp8 families (quantized-comm futures): 1 byte each.
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# e.g. "f32[8,128]{1,0}" or "u8[16]" or "f32[]" or "f8e4m3fn[256]"
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

# `%name = <shape-or-tuple> <op>(` — ops may be async "-start" forms;
# "-done" forms return the same buffer and are skipped to avoid double
# counting.
_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
                "collective-permute", "collective-broadcast")
# The shape is everything between "=" and the op name — matched
# non-greedily so nested variadic tuples like ((f32[8], f32[4]),
# (f32[8], f32[4])) capture whole (a "[^)]*" shape class truncates them
# at the first close-paren and silently undercounts).
_OP_RE = re.compile(
    r"=\s+(?P<shape>.+?)\s+"
    r"(?P<op>" + "|".join(_COLLECTIVES) + r")(?P<suffix>-start|-done)?\(")


def _element_bytes(shape_text, skip_scalars=False):
    """(dtype, bytes) of each array element appearing in a (tuple) shape.
    ``skip_scalars`` drops zero-rank elements (async-start context/scratch
    scalars like ``u32[]``, which are bookkeeping, not payload)."""
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue  # token/opaque types carry no payload
        if skip_scalars and not dims:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        sizes.append((dtype, n * _DTYPE_BYTES[dtype]))
    return sizes


def _shape_bytes(shape_text):
    return sum(b for _, b in _element_bytes(shape_text))


# ---------------------------------------------------------------------------
# computation splitting and the execution-multiplier call-graph walk
# ---------------------------------------------------------------------------

# Computation headers sit at column 0 and look like
#   `%region_0.13_spmd (param.1: (s32[], f32[4])) -> (s32[], f32[4]) {`
# or `ENTRY %main.48_spmd (param.2: f32[6,4]) -> f32[4] {`
# while op lines are indented — the parse keys off that.
_HEADER_NAME_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)")

_COND_REF_RE = re.compile(r"condition=%?([\w.\-]+)")
_BODY_REF_RE = re.compile(r"body=%?([\w.\-]+)")
_CALLS_REF_RE = re.compile(r"calls=%?([\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%?([\w.\-]+)")
_TRUE_REF_RE = re.compile(r"true_computation=%?([\w.\-]+)")
_FALSE_REF_RE = re.compile(r"false_computation=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
# `backend_config={"known_trip_count":{"n":"6"}}` on the while op line.
_TRIP_RE = re.compile(r"known_trip_count[^0-9]*(\d+)")
# Fallback: the scan-lowered condition is `i < constant(K)` with the
# induction variable starting at 0 and stepping by 1.
_COND_CONST_RE = re.compile(r"=\s+s(?:32|64)\[\]\s+constant\((\d+)\)")
_COND_LT_RE = re.compile(r"compare\(.*direction=LT")


def split_computations(hlo_text):
    """``(computations, entry_name)``: computation name -> body text.

    Returns ``({}, None)`` for text with no computation headers (e.g.
    hand-written op snippets), which callers treat as one flat program.
    """
    comps = {}
    entry = None
    buf = None
    for line in hlo_text.splitlines():
        if not line:
            continue
        if line.startswith("HloModule"):
            continue
        if line[0] not in " \t}" and "{" in line and "->" in line \
                and "(" in line:
            m = _HEADER_NAME_RE.match(line)
            if m:
                buf = []
                comps[m.group(2)] = buf
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            buf = None
            continue
        if buf is not None:
            buf.append(line)
    return comps, entry


def _while_trip_count(line, comps):
    """Static trip count of a ``while`` op line, or None if unknown."""
    m = _TRIP_RE.search(line)
    if m:
        return int(m.group(1))
    cond = _COND_REF_RE.search(line)
    if cond and cond.group(1) in comps:
        body = "\n".join(comps[cond.group(1)])
        consts = _COND_CONST_RE.findall(body)
        if len(consts) == 1 and _COND_LT_RE.search(body):
            return int(consts[0])
    return None


def _computation_edges(name, lines, comps):
    """Call-graph edges out of one computation:
    ``[(child, factor, is_while_body)]``."""
    edges = []
    for line in lines:
        if " while(" in line:
            trip = _while_trip_count(line, comps)
            bm = _BODY_REF_RE.search(line)
            cm = _COND_REF_RE.search(line)
            if bm:
                edges.append((bm.group(1), trip if trip else 1, trip))
            if cm:
                # the condition runs trip+1 times; collectives inside
                # conditions are pathological but account them anyway
                edges.append((cm.group(1), trip + 1 if trip else 1, None))
            continue
        for rx in (_CALLS_REF_RE, _TO_APPLY_RE, _TRUE_REF_RE,
                   _FALSE_REF_RE):
            m = rx.search(line)
            if m:
                edges.append((m.group(1), 1, None))
        m = _BRANCHES_RE.search(line)
        if m:
            for ref in m.group(1).split(","):
                ref = ref.strip().lstrip("%")
                if ref:
                    edges.append((ref, 1, None))
    return edges


def while_loops(hlo_text):
    """Every ``while`` op in the program: ``[{body, condition,
    trip_count, has_collectives, parent}]``. ``trip_count`` is None when
    neither the ``known_trip_count`` backend config nor the canonical
    `i < K` condition shape is present — volume through that loop cannot
    be statically accounted."""
    comps, _ = split_computations(hlo_text)
    loops = []
    for name, lines in comps.items():
        for line in lines:
            if " while(" not in line:
                continue
            bm = _BODY_REF_RE.search(line)
            cm = _COND_REF_RE.search(line)
            body = bm.group(1) if bm else None
            body_text = "\n".join(comps.get(body, []))
            loops.append({
                "parent": name,
                "body": body,
                "condition": cm.group(1) if cm else None,
                "trip_count": _while_trip_count(line, comps),
                "has_collectives": bool(_OP_RE.search(body_text)),
            })
    return loops


def computation_multipliers(hlo_text):
    """Execution count of every computation, walked from ENTRY.

    A ``while`` body's multiplier is its parent's times the static trip
    count (1 when the trip count is unknown — the old flat behavior,
    surfaced separately by ``while_loops`` so rules can flag it).
    Computations reachable through several call sites accumulate the sum
    of their path multipliers. Returns ``{}`` when the text has no
    parsable computations.
    """
    comps, entry = split_computations(hlo_text)
    if not comps or entry is None:
        return {}
    edges = {name: _computation_edges(name, lines, comps)
             for name, lines in comps.items()}
    mult = {name: 0 for name in comps}
    mult[entry] = 1

    def walk(name, m):
        for child, factor, _ in edges.get(name, ()):
            if child not in mult:
                continue
            mult[child] += m * factor
            walk(child, m * factor)

    walk(entry, 1)
    return mult


# ---------------------------------------------------------------------------
# collective accounting
# ---------------------------------------------------------------------------

def collective_ops(hlo_text, trip_aware=True):
    """Every collective op with its execution weight:
    ``[{op, computation, multiplier, dtype_bytes: {dtype: bytes}}]``.

    ``dtype_bytes`` is ONE execution's output payload; multiply by
    ``multiplier`` for per-step volume (``collective_bytes`` does).
    """
    if trip_aware:
        mult = computation_multipliers(hlo_text)
    else:
        mult = {}
    if mult:
        comps, _ = split_computations(hlo_text)
        segments = [(name, "\n".join(lines), mult.get(name, 0))
                    for name, lines in comps.items()]
    else:
        segments = [(None, hlo_text, 1)]
    ops = []
    for comp_name, text, m in segments:
        for match in _OP_RE.finditer(text):
            if match.group("suffix") == "-done":
                continue
            shape = match.group("shape")
            # async-start outputs are (operands..., results..., scratch...):
            # count only the result half. Halving the whole tuple's bytes
            # is exact only for symmetric collectives (all-reduce);
            # all-gather-start / reduce-scatter-start pair shard-sized
            # operands with differently-sized results. Scratch entries are
            # zero-rank scalars (collective-permute-start appends two
            # u32[] contexts) — drop them FIRST, then the remaining
            # flattened list is (operands..., results...) with matching
            # counts, variadic included, and the second half is the
            # results.
            if match.group("suffix") == "-start" and shape.startswith("("):
                elems = _element_bytes(shape, skip_scalars=True)
                elems = elems[len(elems) // 2:]
            else:
                elems = _element_bytes(shape)
            per = {}
            for dtype, b in elems:
                per[dtype] = per.get(dtype, 0) + b
            ops.append({"op": match.group("op"), "computation": comp_name,
                        "multiplier": m, "dtype_bytes": per})
    return ops


def collective_counts(hlo_text, trip_aware=True):
    """Execution counts of every collective op: ``{op_name: count}``.

    The per-step number of times each collective RUNS — ops inside a
    ``while``/``scan`` body count once per trip (``trip_aware=True``,
    the default). The byte-free companion of :func:`collective_bytes`,
    for pins on op *mix* (e.g. the overlap rule: a chunked collective
    matmul must show ``collective-permute`` executions where the
    monolithic form had ``all-reduce``)."""
    counts = {}
    for op in collective_ops(hlo_text, trip_aware=trip_aware):
        counts[op["op"]] = counts.get(op["op"], 0) + op["multiplier"]
    return counts


def collective_bytes(hlo_text, by_dtype=False, trip_aware=True):
    """Sum output bytes of every collective op in an HLO dump.

    Returns ``{op_name: bytes, ..., "total": bytes}``. Async pairs are
    counted once (the ``-start``, result element only — its output tuple
    also aliases the operand); sync tuple outputs sum their array
    elements. With ``trip_aware=True`` (the default) an op inside a
    ``while``/``scan`` body is weighted by the loop's static trip count —
    ``trip_aware=False`` restores the old one-count-per-op behavior.
    For ``all-reduce``/``all-to-all`` the output size equals the input
    size, so "output bytes" is the per-device payload in both directions
    of a symmetric exchange — a consistent basis for *ratios* between two
    programs, which is what the tests pin.

    With ``by_dtype=True`` every per-op entry is a ``{dtype: bytes}``
    dict instead ("total" stays a plain sum) — how the quantized-allreduce
    proof separates the int8 gradient exchange from same-op fp32 traffic
    (scale vectors, the ZeRO-1 param-refresh gather) sharing the program.
    """
    counts = {}
    for op in collective_ops(hlo_text, trip_aware=trip_aware):
        per_op = counts.setdefault(op["op"], {})
        for dtype, b in op["dtype_bytes"].items():
            per_op[dtype] = per_op.get(dtype, 0) + b * op["multiplier"]
    if by_dtype:
        out = {op: dict(d) for op, d in counts.items()}
        out["total"] = sum(b for d in counts.values() for b in d.values())
        return out
    flat = {op: sum(d.values()) for op, d in counts.items()}
    flat["total"] = sum(flat.values())
    return flat


# Result shapes of fp8 family, e.g. "%q = f8e4m3fn[64,256] convert(...)".
# Tuple results open with "(", so the optional paren is matched too.
_FP8_RESULT_RE = re.compile(r"=\s*\(?\s*(f8[a-z0-9]+)\[")


def fp8_value_counts(hlo_text, trip_aware=True):
    """Execution counts of ops producing fp8-typed values: ``{dtype: n}``.

    The fp8 qdq pair (`ops/fp8.py`) lowers each quantize to a
    ``convert`` whose RESULT shape is an fp8 dtype — on CPU the converts
    stay explicit next to the f32 dot, on TPU XLA fuses them into the
    native fp8 GEMM, but either way the lowered text carries the
    fp8-typed values. Forward operands show as ``f8e4m3fn``, backward
    cotangents as ``f8e5m2`` — the fp8 audit rule pins both. With
    ``trip_aware=True`` ops inside while/scan bodies count once per
    trip (same accounting as :func:`collective_counts`)."""
    mult = computation_multipliers(hlo_text) if trip_aware else {}
    if mult:
        comps, _ = split_computations(hlo_text)
        segments = [("\n".join(lines), mult.get(name, 0))
                    for name, lines in comps.items()]
    else:
        segments = [(hlo_text, 1)]
    out = {}
    for text, m in segments:
        for hit in _FP8_RESULT_RE.finditer(text):
            dt = hit.group(1)
            out[dt] = out.get(dt, 0) + m
    return out


# Per-device ring-algorithm send bytes as a multiple of the op's OUTPUT
# bytes (N = ring size): all-reduce sends 2·(N-1)/N · M; all-gather sends
# (N-1)/N · M (output M, shard M/N moved N-1 times); reduce-scatter
# output is the M/N shard but each device sends M·(N-1)/N = (N-1)·out;
# all-to-all and collective-permute move (N-1)/N and 1× their payload.
_RING_SEND_FACTORS = {
    "all-reduce": lambda n: 2 * (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "reduce-scatter": lambda n: float(n - 1),
    "all-to-all": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
    "collective-broadcast": lambda n: 1.0,
}
# Every parsed collective must have a send factor — fail at import, not
# at some caller's KeyError, when _COLLECTIVES grows.
assert set(_RING_SEND_FACTORS) == set(_COLLECTIVES)


def ring_send_bytes(hlo_text, n_devices, by_dtype=False, trip_aware=True):
    """Per-device bytes each device *sends* under ring algorithms.

    Converts ``collective_bytes``'s output-bytes basis into the send-volume
    basis the ZeRO paper's communication claims use (2M for an all-reduce
    of M bytes, M for all-gather / reduce-scatter) so ratios between
    compiled programs can be compared against published numbers directly.
    Approximation: every collective is assumed to span ``n_devices`` (true
    for the single-axis ZeRO tests this backs; subgroup collectives would
    need per-op replica-group parsing).

    ``by_dtype=True`` keys each op's sends by element dtype, mirroring
    ``collective_bytes(by_dtype=True)``; ``trip_aware`` as there.
    """
    out = collective_bytes(hlo_text, by_dtype=True, trip_aware=trip_aware)
    sends = {}
    for op, d in out.items():
        if op == "total":
            continue
        factor = _RING_SEND_FACTORS[op](n_devices)
        sends[op] = {dt: int(b * factor) for dt, b in d.items()}
    if by_dtype:
        sends["total"] = sum(b for d in sends.values() for b in d.values())
        return sends
    flat = {op: sum(d.values()) for op, d in sends.items()}
    flat["total"] = sum(flat.values())
    return flat


# ---------------------------------------------------------------------------
# static peak-memory estimation (buffer liveness over the schedule)
# ---------------------------------------------------------------------------

# Ops that define views or bookkeeping, not fresh device buffers.
_ZERO_COST_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
                  "after-all", "add-dependency", "partition-id",
                  "replica-id", "opt-barrier"}

_PEAK_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<shape>.+?)\s+"
    r"(?P<op>[\w\-]+)\(")
_PEAK_USE_RE = re.compile(r"%([\w.\-]+)")


def _top_level_tuple_bytes(shape_text):
    """Byte size of each top-level element of a (possibly tuple) shape."""
    s = shape_text.strip()
    if not s.startswith("("):
        return [_shape_bytes(s)]
    parts, depth, start = [], 0, 1
    for i, ch in enumerate(s):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                parts.append(s[start:i])
                break
        elif ch == "," and depth == 1:
            parts.append(s[start:i])
            start = i + 1
    return [_shape_bytes(p) for p in parts]


def estimate_peak_memory(hlo_text):
    """Static peak device memory of a scheduled HLO module, by buffer
    liveness.

    jax's ``compile().as_text()`` dumps the *scheduled* module
    (``is_scheduled=true``), so line order within a computation is
    execution order. Each op line defines a buffer of its output shape's
    size, alive from its definition to its last textual use; views and
    bookkeeping (``parameter``/``get-tuple-element``/``tuple``/
    ``bitcast``/async ``-done``) define nothing. Called computations
    contribute their own internal peak at the call line — a ``while``
    body's footprint lands on the ``while`` op (a loop's peak does not
    scale with its trip count, unlike its collective *volume*, which is
    why the two walks are separate), ``conditional`` branches contribute
    the max across branches (one executes), and ``fusion`` bodies
    contribute nothing (fused ops never materialize). Donation-aware:
    root outputs aliased to entry parameters via ``input_output_alias``
    reuse the argument's buffer and allocate nothing new.

    Returns a dict::

        peak_bytes            argument + liveness peak (per device —
                              SPMD entry shapes are already local)
        temp_peak_bytes       liveness peak alone (intermediates +
                              un-aliased outputs)
        parameter_bytes       entry argument footprint
        output_bytes          entry root footprint
        donated_output_bytes  root bytes aliased onto donated arguments
        per_computation       {computation: internal peak}

    Against XLA's own buffer assignment (``compiled.memory_analysis()``)
    this is an *upper bound*: buffer assignment additionally reuses
    dead buffers' allocations for same-sized successors, which pure
    liveness does not model. The bench row reports both sides.
    """
    comps, entry = split_computations(hlo_text)
    if not comps or entry is None:
        comps = {"<flat>": [l for l in hlo_text.splitlines() if l.strip()]}
        entry = "<flat>"
        aliases = []
    else:
        aliases = input_output_aliases(hlo_text)

    peak_memo = {}

    def callee_contribution(op, line):
        if op == "fusion":
            return 0
        subs = []
        if op == "while":
            for rx in (_BODY_REF_RE, _COND_REF_RE):
                m = rx.search(line)
                if m and m.group(1) in comps:
                    subs.append(peak_of(m.group(1)))
            return max(subs, default=0)
        if op == "conditional":
            m = _BRANCHES_RE.search(line)
            if m:
                for ref in m.group(1).split(","):
                    ref = ref.strip().lstrip("%")
                    if ref in comps:
                        subs.append(peak_of(ref))
            for rx in (_TRUE_REF_RE, _FALSE_REF_RE):
                m = rx.search(line)
                if m and m.group(1) in comps:
                    subs.append(peak_of(m.group(1)))
            return max(subs, default=0)
        for rx in (_CALLS_REF_RE, _TO_APPLY_RE):
            m = rx.search(line)
            if m and m.group(1) in comps:
                subs.append(peak_of(m.group(1)))
        return max(subs, default=0)

    def line_alloc(op, shape):
        if op in _ZERO_COST_OPS or op.endswith("-done"):
            return 0
        if op == "while":
            return 0   # the carry aliases the while's operand buffer
        if op.endswith("-start") and shape.strip().startswith("("):
            # async tuple = (operands..., results..., scratch scalars):
            # operands alias existing buffers; only results are new.
            elems = _element_bytes(shape, skip_scalars=True)
            return sum(b for _, b in elems[len(elems) // 2:])
        return _shape_bytes(shape)

    def walk(name, donated_root=0, donated_defs=()):
        """(liveness peak, parameter bytes, root bytes) of one
        computation. ``donated_defs``: def names whose buffers are
        written in place into donated arguments (allocate nothing)."""
        lines = comps[name]
        parsed = []        # (def name, alloc, callee peak)
        param_bytes = 0
        root_bytes = 0
        for line in lines:
            m = _PEAK_DEF_RE.match(line)
            if m is None:
                parsed.append(None)
                continue
            op = m.group("op")
            shape = m.group("shape")
            is_root = line.lstrip().startswith("ROOT")
            alloc = line_alloc(op, shape)
            if op == "parameter":
                param_bytes += _shape_bytes(shape)
            if m.group("name") in donated_defs:
                alloc = 0
            if is_root:
                root_bytes = _shape_bytes(shape)
                alloc = max(0, alloc - donated_root)
            parsed.append((m.group("name"), alloc,
                           callee_contribution(op, line)))
        defined = {p[0]: i for i, p in enumerate(parsed)
                   if p is not None}
        last_use = dict(defined)
        for i, line in enumerate(lines):
            for use in _PEAK_USE_RE.findall(line):
                if use in last_use and i > last_use[use]:
                    last_use[use] = i
        free_at = {}
        for dname, i in defined.items():
            free_at.setdefault(last_use[dname], []).append(
                parsed[i][1])
        live = peak = 0
        for i, p in enumerate(parsed):
            if p is None:
                continue
            live += p[1]
            peak = max(peak, live + p[2])
            for b in free_at.get(i, ()):
                live -= b
        return peak, param_bytes, root_bytes

    def peak_of(name):
        if name not in peak_memo:
            peak_memo[name] = 0       # cycle guard
            peak_memo[name] = walk(name)[0]
        return peak_memo[name]

    # Donated output bytes: per-aliased-entry sizes of the root tuple.
    # When ROOT is a `tuple` view the aliased buffers are the tuple's
    # operand defs — written in place into the donated argument, so
    # those defs allocate nothing; otherwise subtract off the root def.
    root_shape = None
    root_op = None
    root_operands = []
    for line in comps[entry]:
        if line.lstrip().startswith("ROOT"):
            m = _PEAK_DEF_RE.match(line)
            if m:
                root_shape = m.group("shape")
                root_op = m.group("op")
                root_operands = _PEAK_USE_RE.findall(
                    line[m.end():])
    donated = 0
    donated_defs = set()
    if root_shape is not None and aliases:
        elems = _top_level_tuple_bytes(root_shape)
        for e in aliases:
            oi = e["output_index"]
            if not oi:
                donated += sum(elems)
            elif oi[0] < len(elems):
                donated += elems[oi[0]]
                if root_op == "tuple" and oi[0] < len(root_operands):
                    donated_defs.add(root_operands[oi[0]])
    entry_peak, param_bytes, root_bytes = walk(
        entry, donated_root=0 if root_op == "tuple" else donated,
        donated_defs=donated_defs)
    per_comp = {entry: entry_peak}
    per_comp.update(peak_memo)
    return {
        "peak_bytes": param_bytes + entry_peak,
        "temp_peak_bytes": entry_peak,
        "parameter_bytes": param_bytes,
        "output_bytes": root_bytes,
        "donated_output_bytes": donated,
        "per_computation": per_comp,
    }


# ---------------------------------------------------------------------------
# input/output aliasing (donation) and host transfers
# ---------------------------------------------------------------------------

_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{[\d,\s]*\}(?:,\s*([\w-]+))?\)")


def input_output_aliases(hlo_text):
    """Parse the module header's ``input_output_alias`` map.

    Returns ``[{output_index: tuple, param_number: int, kind: str}]`` —
    the executable's actual buffer donations, to diff against what the
    engine *declared* via ``donate_argnums``.
    """
    key = "input_output_alias="
    i = hlo_text.find(key)
    if i < 0:
        return []
    s = hlo_text[i + len(key):]
    depth = 0
    end = 0
    for j, ch in enumerate(s):
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                end = j
                break
    inner = s[1:end]
    return [
        {"output_index": tuple(int(x) for x in oi.split(",") if x.strip()),
         "param_number": int(pn),
         "kind": kind or "may-alias"}
        for oi, pn, kind in _ALIAS_ENTRY_RE.findall(inner)
    ]


def aliased_param_numbers(hlo_text):
    """Entry-parameter numbers the executable aliases into its outputs."""
    return {e["param_number"] for e in input_output_aliases(hlo_text)}


def _dims_superset(dims, want):
    """True iff multiset ``dims`` contains multiset ``want``."""
    from collections import Counter
    have = Counter(dims)
    return all(have[d] >= n for d, n in Counter(want).items())


def payload_shaped_dots(hlo_text, payload_dims):
    """Dot ops touching a cache-payload-shaped array.

    A dot line counts when any shape on it (output or operand) has a
    dim MULTISET containing ``payload_dims`` — for the decode program
    that is exactly a dense attention contraction over the full
    ``[max_batch, max_seq, n_head, head_dim]`` KV buffer (the einsum's
    batched layout permutes those dims, hence multiset, and no other
    decode dot carries all four sizes at once). The flash-decode audit
    pins this list empty: the Pallas kernel's dots only ever see
    ``block_k``-sized cache slices.
    """
    out = []
    want = tuple(int(d) for d in payload_dims)
    for line in hlo_text.splitlines():
        if " dot(" not in line:
            continue
        for _, dims in _SHAPE_RE.findall(line):
            if not dims:
                continue
            ds = [int(x) for x in dims.split(",")]
            if len(ds) >= len(want) and _dims_superset(ds, want):
                out.append(line.strip())
                break
    return out


def seq_sized_value_bytes(hlo_text, seq):
    """Total bytes of value DEFINITIONS carrying a ``seq``-sized dim,
    entry parameters excluded — a compile-time proxy for how much
    cache-length-proportional data a decode step materializes. The
    dense path defines attention-score rows, softmax temporaries and
    (quantized) dequant copies all shaped ``[..., max_seq, ...]``; the
    flash kernel's working set is ``block_k``-sized, so only the
    written-back cache itself survives at this size. Parameters are
    excluded because both paths take the identical cache buffers as
    inputs — the A/B signal is in what the program CREATES.
    """
    total = 0
    seq = int(seq)
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "parameter(" in ls or "=" not in ls:
            continue
        shape_text = ls.split("=", 1)[1]
        # shape(s) sit between '=' and the op name; stop at the first
        # opcode paren to avoid re-counting operand shapes.
        op_at = shape_text.find("(")
        if op_at >= 0:
            shape_text = shape_text[:op_at]
        for dtype, dims in _SHAPE_RE.findall(shape_text):
            if dtype not in _DTYPE_BYTES or not dims:
                continue
            ds = [int(x) for x in dims.split(",")]
            if seq not in ds:
                continue
            n = 1
            for d in ds:
                n *= d
            total += n * _DTYPE_BYTES[dtype]
    return total


def payload_shaped_values(hlo_text, dtype, payload_dims):
    """Count value DEFINITIONS of ``dtype`` whose dims contain
    ``payload_dims`` (multiset). With a quantized KV cache these are
    full-precision cache-sized intermediates — the dense path's
    dequantized copies; the per-head scale planes lack ``head_dim`` so
    they never match. Zero under flash decode: dequantization happens
    in-register on ``block_k`` slices."""
    n = 0
    want = tuple(int(d) for d in payload_dims)
    defre = re.compile(r"=\s+" + re.escape(dtype) + r"\[([\d,]+)\]")
    for line in hlo_text.splitlines():
        m = defre.search(line)
        if not m:
            continue
        ds = [int(x) for x in m.group(1).split(",")]
        if len(ds) >= len(want) and _dims_superset(ds, want):
            n += 1
    return n


def payload_shaped_copies(hlo_text, payload_dims):
    """``copy`` ops whose result has exactly the shape of one layer's K
    or V cache buffer: each is a relayout or a duplicate of the whole
    buffer, paid on every call of the program whatever share of the
    cache is live. A decode program that updates its donated cache in
    place, in the layout its attention kernel reads, has none."""
    dims = ",".join(str(int(d)) for d in payload_dims)
    return re.findall(r"= \w+\[%s\]\S* copy\([^\n]*" % dims, hlo_text)


def parameter_copies(hlo_text, n_params):
    """``copy`` ops of the entry computation that copy one of the
    program's first ``n_params`` parameters (a jitted function's
    flattened arguments in order, so its weights where they come
    first), directly or through bitcasts: ``[(parameter number, the
    copy's result type)]``. Each is a re-layout of an argument from the
    layout it was handed in to the one its consumer reads, paid on every
    call; a program whose weights lie in the format its compiler asks
    for has none."""
    comps, entry = split_computations(hlo_text)
    ops = {}        # name -> (opcode, result type, first operand)
    for line in comps.get(entry, ()):
        m = _PEAK_DEF_RE.match(line)
        if m:
            # a parameter's number; any other op's first operand by name
            operand = re.match(r"\d+|[^%]*%([\w.\-]+)", line[m.end():])
            ops[m.group("name")] = (
                m.group("op"), m.group("shape"),
                (operand.group(1) or operand.group(0)) if operand else "")
    found = []
    for opcode, result, source in ops.values():
        if opcode != "copy":
            continue
        while ops.get(source, ("",))[0] == "bitcast":
            source = ops[source][2]
        origin = ops.get(source)
        if origin and origin[0] == "parameter" and \
                int(origin[2]) < n_params:
            found.append((int(origin[2]), result))
    return sorted(found)


# Custom-call targets that round-trip through the Python host (jax
# pure_callback / io_callback / debug.callback lower to these).
_HOST_CALLBACK_TARGETS = (
    "xla_python_cpu_callback",
    "xla_python_gpu_callback",
    "xla_ffi_python_cpu_callback",
    "xla_ffi_python_gpu_callback",
    "xla_ffi_partitioned_python_cpu_callback",
)
_INOUTFEED_RE = re.compile(r"=\s+.+?\s+(infeed|outfeed)(-done)?\(")


def host_transfer_ops(hlo_text):
    """Ops that move data between device and host inside the program:
    ``[{kind, line}]`` with kind in {"infeed", "outfeed",
    "host-transfer", "host-callback"}. A compiled train step should have
    none — each one forces a device/host sync in the middle of the step.
    """
    hits = []
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = _INOUTFEED_RE.search(ls)
        if m and not m.group(2):
            hits.append({"kind": m.group(1), "line": ls})
            continue
        if "is_host_transfer=true" in ls:
            hits.append({"kind": "host-transfer", "line": ls})
            continue
        if "custom-call" in ls:
            for target in _HOST_CALLBACK_TARGETS:
                if f'custom_call_target="{target}"' in ls:
                    hits.append({"kind": "host-callback", "line": ls})
                    break
    return hits

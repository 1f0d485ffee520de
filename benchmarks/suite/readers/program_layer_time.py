"""Device time of ONE compiled program by the model's layers: the
program's own instruction-to-scope map
(``deepspeed_tpu.telemetry.programs.op_names``: every instruction, no
marker, in every cell) over the trace's self times.

An op belongs to ``program`` (``prefill`` | ``decode``) if it ran while
a harness span of that name was open on the host, by
``program_scope_time``'s rule (``engine.prefill`` and ``engine.decode``
return only once their device work is done); a run that holds one
program (``train_step``) needs no span and takes every op. An
instruction belongs to the **innermost** scope of the vocabulary
(``telemetry.scopes``) in its ``op_name``, and a listed scope takes its
children. A fusion carries its root's ``op_name``: it is its root's.
What the compiler adds itself names no origin (a weight's slices
fetched ahead, ``slice-done``; a relayout ``copy`` in front of a
matmul): the program's map lays it to what it **feeds**
(``programs.FEEDS``), it counts under that scope, and the log says how
much of a scope's time came that way.

Arguments: ``scopes`` (names: ms of the ops under one of them),
``ops`` (a regular expression on the opcode as the trace spells it,
``copy``, ``copy-done``, ``fusion``, ``custom-call:tpu_custom_call``;
with ``scopes`` both must hold), ``unscoped`` (true: 100 less the
share, in %, of the program's device time under no vocabulary scope),
``per`` as in ``program_scope_time`` (``window``, ``step``,
``span:<name>``).

Once a run and program it logs, in ms a call of the program: the time
by innermost scope, largest first, with the part of it that was fed;
the ten largest ops under no scope and the ten largest the compiler
added, with their whole ``op_name``; and the time of every ``copy*``,
``*-done`` and ``transpose`` op by scope. ``None`` without a trace,
without the registry (an older program), for a program the run never
ran, or where no op lies under ``scopes`` (``ops`` alone reads 0.0
where the program ran and no such op did).
"""

import bisect
import re
import time

from benchmarks.suite import xplane

MOVES = re.compile(r"^(copy|transpose)|-done$")
NO_SCOPE = "(no scope)"


class Table:
    """``rows``: ``[(instruction, opcode, op_name, scope or None,
    seconds, fed)]`` of the program's ops in the trace, seconds
    averaged over chips, ``fed`` where the ``op_name`` is that of what
    the op feeds; ``calls``: how often the program ran in the
    segment."""

    def __init__(self, rows, calls):
        self.rows, self.calls = rows, calls
        self.seconds = sum(r[4] for r in rows)


def events_of(trace, program):
    """``{chip: events}`` of the ops that ran while a harness span named
    ``program`` was open, and the number of such spans; every op, and
    ``None``, where the trace holds no such span."""
    spans = sorted((s, e) for n, s, e in trace.spans if n == program)
    if not spans:
        return dict(trace.devices), None
    starts = [s for s, _ in spans]

    def inside(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < spans[i][1]

    return {chip: [ev for ev in events if inside(0.5 * (ev[1] + ev[2]))]
            for chip, events in trace.devices.items()}, len(spans)


def table(ctx, result, program):
    """The program's `Table`, built once a trace (and kept on it: a
    dozen metrics read one table)."""
    kept = result.trace.__dict__.setdefault("_program_layer_tables", {})
    if program not in kept:
        kept[program] = _build(ctx, result, program)
    return kept[program]


def _build(ctx, result, program):
    try:
        from deepspeed_tpu.telemetry import programs, scopes
    except ImportError:
        return None
    trace = result.trace
    events, calls = events_of(trace, program)
    if calls is None:
        if len(programs.registered()) != 1:
            return None     # several programs and no span to tell them by
        calls = result.facts.get("profiled_steps") or 1
    t0 = time.perf_counter()
    known = programs.op_names(program)
    if known is None:
        return None
    ctx.log(f"program_layer_time: the {program} program's "
            f"{len(known)} instructions lowered and read in "
            f"{time.perf_counter() - t0:.2f} s")
    acc = {}
    for evs in events.values():
        for name, seconds in xplane.self_times(evs):
            acc[name] = acc.get(name, 0.0) + seconds / len(events)
    rows = []
    for name, seconds in acc.items():
        instruction, _, opcode = name.partition(" ")
        origin = known.get(instruction)
        fed = bool(origin) and origin.startswith(programs.FEEDS)
        rows.append((instruction, opcode, origin,
                     scopes.innermost(origin), seconds, fed))
    out = Table(rows, calls)
    if out.seconds:
        _log(ctx, program, out)
    return out


def _log(ctx, program, t):
    ms = 1e3 / t.calls

    def by_scope(rows):
        acc, fed = {}, {}
        for r in rows:
            key = r[3] or NO_SCOPE
            acc[key] = acc.get(key, 0.0) + r[4]
            fed[key] = fed.get(key, 0.0) + (r[4] if r[5] else 0.0)
        return ", ".join(
            f"{k} {v * ms:.3f}" +
            (f" ({fed[k] * ms:.3f} fed)" if fed[k] else "")
            for k, v in sorted(acc.items(), key=lambda kv: -kv[1]))

    fed = sum(r[4] for r in t.rows if r[5])
    ctx.log(f"{program} by layer, ms a call over {t.calls} calls "
            f"({t.seconds * ms:.3f} in all, {fed * ms:.3f} of it ops the "
            f"compiler added, laid to what they feed): " + by_scope(t.rows))
    bare = sorted((r for r in t.rows if r[3] is None),
                  key=lambda r: -r[4])[:10]
    ctx.log(f"{program}, the largest ops under no scope, ms a call: " +
            "; ".join(f"{r[0]} {r[1]} {r[4] * ms:.3f} [" + (
                r[2] or ("not in the program's text" if r[2] is None
                         else "no op_name")) + "]" for r in bare))
    most = sorted((r for r in t.rows if r[5]), key=lambda r: -r[4])[:10]
    ctx.log(f"{program}, the largest ops the compiler added, ms a call: " +
            "; ".join(f"{r[0]} {r[1]} {r[4] * ms:.3f} [{r[2]}]"
                      for r in most))
    moves = {}
    for r in t.rows:
        if MOVES.search(r[1]):
            kind = xplane.category(f"{r[0]} {r[1]}")
            moves.setdefault(kind, []).append(r)
    ctx.log(f"{program}, copies, -done and transposes by scope, ms a "
            f"call: " + "; ".join(
                f"{kind}: {by_scope(rows)}" for kind, rows in sorted(
                    moves.items(),
                    key=lambda kv: -sum(r[4] for r in kv[1]))))


def read(ctx, result, program, scopes=None, ops=None, unscoped=False,
         per="window"):
    if result.trace is None:
        return None
    t = table(ctx, result, program)
    if t is None or not t.seconds:
        return None
    if unscoped:
        bare = sum(r[4] for r in t.rows if r[3] is None)
        return 100.0 - 100.0 * bare / t.seconds
    from deepspeed_tpu.telemetry.scopes import chain
    rx = re.compile(ops) if ops else None
    seconds = sum(
        r[4] for r in t.rows
        if (scopes is None or set(scopes) & set(chain(r[2])))
        and (rx is None or rx.search(r[1])))
    if per == "step":
        count = result.facts.get("profiled_steps")
    elif per.startswith("span:"):
        count = sum(1 for n, _, _ in result.trace.spans if n == per[5:])
    else:
        count = 1
    if not count or (not seconds and scopes is not None):
        return None         # no such scope in the program: nothing to read
    return 1e3 * seconds / count

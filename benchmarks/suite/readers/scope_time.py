"""Device time, in ms, of the ops that belong to named phases of the
program. A trace names an op by its HLO instruction (``fusion.12``),
which says nothing of where it came from; the compiled program's text
does (``metadata={op_name=".../ds_moe_dispatch/gather"}``, from
``jax.named_scope``). A driver that has that text hands over
``result.facts["op_scopes"]`` (instruction -> ``op_name``,
``scopes_of``); an op counts if its ``op_name`` holds one of
``scopes``, or if its name in the trace matches ``pattern`` (a kernel
XLA brings itself carries no scope). ``per`` as in ``op_time``. ``None``
without a trace, without the map (a program that names no phases), or
where nothing matches."""

import re

from benchmarks.suite import xplane

INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def scopes_of(hlo_text, marker):
    """``{instruction: op_name}`` for the instructions of a compiled
    program's text whose ``op_name`` contains ``marker``."""
    return {m.group(1): m.group(2) for m in INSTRUCTION.finditer(hlo_text)
            if marker in m.group(2)}


def read(ctx, result, scopes, per="step", pattern=None):
    trace, known = result.trace, result.facts.get("op_scopes")
    if trace is None or not known:
        return None
    rx = re.compile(pattern) if pattern else None
    per_chip = []
    for events in trace.devices.values():
        total = 0.0
        for name, seconds in xplane.self_times(events):
            where = known.get(name.partition(" ")[0], "")
            if any(s in where for s in scopes) or (rx and rx.search(name)):
                total += seconds
        per_chip.append(total)
    seconds = sum(per_chip) / len(per_chip)
    count = result.facts.get("profiled_steps") if per == "step" else 1
    if not seconds or not count:
        return None
    return 1e3 * seconds / count

"""The process's compiled programs by name, for whoever asks which
scope an instruction of a profiler trace belongs to.

An engine registers each program it jits (`InferenceEngine`:
``prefill`` and ``decode``; `DeepSpeedEngine`: ``train_step``, at its
first batch) as a closure that gives the program's function, what it
donates and the arguments it is called with **as shapes**: what is
registered holds no device array and does not keep the engine alive,
and it outlives the engine (a benchmark's readers ask after the driver
that built the engine has returned). Registering costs a dict entry
and a tree of shapes: nothing is traced, lowered or compiled until
:func:`op_names` is called, and nothing on a hot path calls it.

A trace names a device op by its HLO instruction (``fusion.12``), which
says nothing of where it came from; the compiled program's text does
(``metadata={op_name=".../ds_mlp/dot_general"}``). :func:`op_names`
traces, lowers and compiles the program again (the persistent compile
cache answers once the program has run) and returns ``{instruction:
op_name}`` for every instruction. `telemetry/scopes.py` lays an
``op_name`` to the vocabulary.

**Another tree's names.** The persistent cache's key leaves locations
out, so a cache directory that two trees share serves this tree's
program the executable the other tree compiled, under the other tree's
names (the instructions are the same: a scope changes no instruction).
:func:`compiled_text` sees it (a scope the lowered program names is
nowhere in the compiled text) and compiles once more under a key that
holds the locations; that entry answers from then on.

The newest engine to register a name owns it.
"""

import re

import jax

from deepspeed_tpu.telemetry import scopes

_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = ', re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAME = re.compile(r"%([\w.\-]+)")
_OPCODE = re.compile(r"[\])}] ([a-z][\w\-]*)\(")
# opcodes that move data and compute nothing
MOVERS = {"copy", "copy-start", "copy-done", "bitcast", "transpose", "slice",
          "reshape", "async-start", "async-done", "get-tuple-element"}
# in front of an op_name that is not the instruction's own but that of
# what it feeds (:func:`op_names`)
FEEDS = "(feeds) "
_METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"

# name -> lowering() -> (function, donate_argnums, arguments) or None
_programs = {}


def register(name, lowering):
    """``lowering()`` gives the program's Python function, the
    arguments it donates and :func:`shapes` of what it is called with,
    or ``None`` where the program can no longer be lowered; only
    :func:`compiled_text` calls it. It must hold no device array."""
    _programs[name] = lowering


def shapes(tree):
    """``tree`` with every array as its ``jax.ShapeDtypeStruct`` (its
    format, the sharding and the layout its bytes lie in, kept where
    the array was committed to one: a jit takes a committed argument's
    layout, so the twin lowers the program that runs)."""
    def shape(x):
        if not isinstance(x, jax.Array):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.format if x.committed else None)
    return jax.tree_util.tree_map(shape, tree)


def registered():
    return sorted(_programs)


def _lowered(fn, donate_argnums, args):
    """``fn`` traced anew under its own name: a function jax has not
    seen, so neither its trace nor its executable comes out of the
    process's own caches."""
    def again(*a):
        return fn(*a)
    again.__name__ = again.__qualname__ = getattr(fn, "__name__", "program")
    return jax.jit(again, donate_argnums=donate_argnums).lower(*args)


def _scopes_named(text):
    return {t for t in scopes.TOKEN.findall(text) if t in scopes.SCOPES}


def compiled_text(name):
    """The compiled program's HLO text; ``None`` for a name nobody
    registered or a program that can no longer be lowered."""
    lowering = _programs.get(name)
    found = lowering() if lowering is not None else None
    if found is None:
        return None
    lowered = _lowered(*found)
    text = lowered.compile().as_text()
    if _scopes_named(lowered.as_text(debug_info=True)) <= _scopes_named(text):
        return text
    # the cache has served another tree's executable (the module's
    # docstring): once more, under a key that holds the locations
    before = getattr(jax.config, _METADATA_IN_KEY)
    jax.config.update(_METADATA_IN_KEY, True)
    try:
        return _lowered(*found).compile().as_text()
    finally:
        jax.config.update(_METADATA_IN_KEY, before)


def op_names(name):
    """``{instruction: op_name}`` of every instruction of the compiled
    program ``name``; ``None`` as :func:`compiled_text`.

    What the compiler adds itself names no origin: a weight's slices
    fetched ahead into fast memory (``slice-start`` / ``slice-done``), a
    fusion of its own making. And a relayout ``copy`` of a weight in
    front of a matmul names the weight (``params['layers_1']['attn']
    ['q_proj']``), which is under no scope. Such an instruction (no
    ``op_name``; or one of `MOVERS` whose ``op_name`` lies under no
    vocabulary scope) is laid to what it feeds: it gets ``FEEDS`` and
    the ``op_name`` of the first instruction among its users (through
    users that have none under a scope either) that lies under one, so
    a prefetched or re-laid weight belongs to the matmul that reads it.
    Where that finds nothing (the program's result, a parameter nobody
    scoped reads) the instruction keeps what it had."""
    text = compiled_text(name)
    if text is None:
        return None
    own, users, moves = {}, {}, set()
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        origin = _OP_NAME.search(line)
        own[m.group(1)] = origin.group(1) if origin else ""
        opcode = _OPCODE.search(line, m.end())
        if opcode and opcode.group(1) in MOVERS:
            moves.add(m.group(1))
        for operand in _NAME.findall(line, m.end()):
            users.setdefault(operand, []).append(m.group(1))

    def fed(instruction):
        """The first scoped ``op_name`` downstream, breadth first, a few
        instructions deep."""
        front, seen = [instruction], {instruction}
        for _ in range(8):
            front = [u for i in front for u in users.get(i, ())
                     if u in own and u not in seen and not seen.add(u)]
            for u in front:
                if scopes.innermost(own[u]):
                    return FEEDS + own[u]
        return None

    return {i: origin if scopes.innermost(origin) or
            (origin and i not in moves) else fed(i) or origin
            for i, origin in own.items()}

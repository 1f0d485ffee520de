"""The process's one ``jax.monitoring`` listener: persistent-cache
hit/miss counters, and the compile ledger on the span ring.

**Counters.** jax's compiler records
``/jax/compilation_cache/cache_hits`` / ``cache_misses`` whenever the
persistent cache (``compilation_cache_dir`` in the engine config) serves
or misses a lookup; :func:`counts` exposes the running tallies so the
engine's ``compile`` telemetry event (and the tuner's rerun report) can
show that a warmed cache produced near-zero recompilation.

**Ledger.** jax times every trace of a jitted function to a jaxpr, every
lowering of one to MLIR and every backend compile (a read of the
persistent cache included) and reports each with the function's name.
Each becomes one *kept* record of the span ring (`telemetry/spans.py`)
on ``spans.clock``: ``<path of the span open in that thread>/jax/trace``,
``.../jax/lower``, ``.../jax/backend_compile`` (bare ``jax/...`` where
none is open), attrs ``fun``, ``step`` where an open span carries one,
and on ``backend_compile`` ``cache``: ``"hit"``, ``"miss"``, or
``"off"`` where no persistent cache was asked. jax reports the span on
``time.time``; the record closes at the listener's call and opens that
span's length before it, so it lies inside the span it fell in and a
reader that nests records by time sees it as that span's child. A
compile inside a serving window is then a record
``serve/step/admit/prefill/jax/backend_compile {fun, step, cache}``.
:func:`last_compile` is the newest ``backend_compile``, for the
``analysis`` block's ``recompile`` event.

:func:`install` registers the listeners, and the collector's callback
with them (``spans.install_collector``); ``import deepspeed_tpu`` calls
it, so that the weights' and a reference's jits are on the ledger too.
Installing twice is safe. Between events the listeners cost nothing.

:func:`configure` is the one place the program chooses a cache
directory. ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside:
where it is set, jax already reads it and no code path sets another.
"""

import os

from deepspeed_tpu.telemetry import spans

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
# jax's timed phases of a jit's first call -> the ledger's leaf
LEDGER = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/lower",
    "/jax/core/compile/backend_compile_duration": "jax/backend_compile",
}
BACKEND_COMPILE = "jax/backend_compile"

_counts = {"hits": 0, "misses": 0}
_installed = False
# what the persistent cache said of the compile now running ("hit" /
# "miss"; None: it was not asked), and the newest backend_compile
_state = {"cache": None, "last": None}


def _listener(event, **kwargs):
    if event == _HIT_EVENT:
        _counts["hits"] += 1
        _state["cache"] = "hit"
    elif event == _MISS_EVENT:
        _counts["misses"] += 1
        _state["cache"] = "miss"


def _time_span_listener(event, start_time, end_time, **kwargs):
    leaf = LEDGER.get(event)
    if leaf is None:
        return
    t1, seconds = spans.clock(), end_time - start_time
    attrs = {"fun": kwargs.get("fun_name")}
    if leaf == BACKEND_COMPILE:
        attrs["cache"] = _state["cache"] or "off"
        _state["cache"] = None
    spans.keep_under_open_span(leaf, t1 - seconds, t1, attrs)
    if leaf == BACKEND_COMPILE:
        _state["last"] = dict(attrs, seconds=seconds)


def install():
    """Register the monitoring listeners and the collector's callback
    (idempotent). Returns True when the listeners are active, False when
    jax.monitoring is unavailable."""
    global _installed
    if _installed:
        return True
    try:
        from jax import monitoring
        monitoring.register_event_listener(_listener)
        monitoring.register_event_time_span_listener(_time_span_listener)
    except Exception:
        return False
    spans.install_collector()
    _installed = True
    return True


def configure(default_dir):
    """Turn the persistent compilation cache on and count its traffic.

    The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set
    (jax reads it itself — nothing is overridden), else ``default_dir``.
    Returns the directory in use. Safe after the process's first
    compile: jax latches "no cache" there, so the cache is reset (the
    public ``reset_cache``) and the next compile re-reads the setting.
    """
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as jax_cc)
    placed = os.environ.get(ENV_VAR)
    if not placed:
        jax.config.update("jax_compilation_cache_dir", str(default_dir))
    jax_cc.reset_cache()
    install()
    return placed or str(default_dir)


def counts():
    """``{"hits": int, "misses": int}`` accumulated since install()."""
    return dict(_counts)


def last_compile():
    """``{"fun", "seconds", "cache"[, "step"]}`` of the newest backend
    compile on the ledger; ``None`` before the first."""
    return _state["last"]


def reset():
    """Zero the counters (test helper)."""
    _counts["hits"] = 0
    _counts["misses"] = 0

"""Persistent-compilation-cache hit/miss counters.

jax's compiler records ``/jax/compilation_cache/cache_hits`` /
``cache_misses`` monitoring events whenever the persistent cache
(``compilation_cache_dir`` in the engine config) serves or misses a
lookup. This module installs one process-wide listener and exposes the
running counts so the engine's ``compile`` telemetry event (and the
tuner's rerun report) can show that a warmed cache produced near-zero
recompilation.

The listener is a no-op until :func:`install` is called — the engine
calls it exactly when it applies ``compilation_cache_dir`` — and
installing twice is safe.

:func:`configure` is the one place the program chooses a cache
directory. ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside:
where it is set, jax already reads it and no code path sets another.
"""

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_counts = {"hits": 0, "misses": 0}
_installed = False


def _listener(event, **kwargs):
    if event == _HIT_EVENT:
        _counts["hits"] += 1
    elif event == _MISS_EVENT:
        _counts["misses"] += 1


def install():
    """Register the monitoring listener (idempotent). Returns True when
    the listener is active, False when jax.monitoring is unavailable."""
    global _installed
    if _installed:
        return True
    try:
        from jax import monitoring
        monitoring.register_event_listener(_listener)
    except Exception:
        return False
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


def reset():
    """Zero the counters (test helper)."""
    _counts["hits"] = 0
    _counts["misses"] = 0

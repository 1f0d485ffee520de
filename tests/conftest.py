"""Test harness: run everything on a virtual 8-device CPU mesh, and
compile cheaply and once a run.

The reference's distributed tests fork NCCL process trees and need real GPUs
(`tests/unit/common.py:14-100`); here XLA fakes 8 host devices so every
sharding/collective path is exercised on CPU (SURVEY.md §4's improvement
note). Must set the env vars before jax is imported anywhere.

**The compiler.** Most of a unit test's time is XLA's CPU compiler
(`ROADMAP.md`, Design 1). The modules of ``tests/unit`` therefore
compile with ``jax_disable_most_optimizations`` and through jax's
persistent compilation cache, in a directory that is new for each run,
that the run's xdist workers share, and that is removed when the run
ends: nothing crosses from one run to the next. A test that needs the
compiler's normal pipeline says so itself with
``pytest.mark.full_compile``, on the module (``pytestmark``) or on the
test: one that reads a compiled program (its text, its cost or memory
analysis, its input formats, an executable for a described TPU), or
one whose tolerance was set under that pipeline's rounding. It then
compiles as every module outside ``tests/unit`` does, with the cache
off: ``tests/model`` runs a hundred steps a case, so its time is the
programs' and not the compiler's, and its curves are compared as the
optimizing compiler rounds them (PR 59 measured both);
``tests/benchmark_suite`` is the benchmark's. Neither setting is in
jax's in-memory key of a compiled program, so the in-memory caches are
dropped where the setting changes.
"""

import os
import shutil
import tempfile

# The env vars cover a jax that is first imported below; the
# jax.config.update calls cover one that something imported earlier (its
# config defaults are read at import). XLA_FLAGS is read lazily at first
# backend init, so the device-count flag works from here either way.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_platform_name", "cpu")

import pytest  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHEAP_DIR = os.path.join(_HERE, "unit", "")
# the run's compilation cache: made by the process that owns the run
# (xdist's controller, or the only process) and handed to its workers
_run = {"cache_dir": None, "cheap": None}


def pytest_configure(config):
    workerinput = getattr(config, "workerinput", None)
    if workerinput is None:
        _run["cache_dir"] = tempfile.mkdtemp(prefix="ds_tpu_tests_jax_cache_")
    else:
        _run["cache_dir"] = workerinput["ds_tpu_jax_cache_dir"]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    """xdist's controller, before a worker starts."""
    node.workerinput["ds_tpu_jax_cache_dir"] = _run["cache_dir"]


def pytest_unconfigure(config):
    if _run["cache_dir"] and not hasattr(config, "workerinput"):
        shutil.rmtree(_run["cache_dir"], ignore_errors=True)
        _run["cache_dir"] = None


def _set_pipeline(cheap):
    """The process's compiler settings for what runs next. A program
    compiled under the other setting is not handed out again: the
    persistent cache's key holds the compiler's options, the in-memory
    caches do not and are dropped."""
    cache_dir = _run["cache_dir"] if cheap else None
    if _run["cheap"] == cheap and \
            jax.config.jax_compilation_cache_dir == cache_dir:
        return
    from jax.experimental.compilation_cache import compilation_cache as cc
    if _run["cheap"] is not None and _run["cheap"] != cheap:
        jax.clear_caches()
    _run["cheap"] = cheap
    jax.config.update("jax_disable_most_optimizations", cheap)
    # a child process (a CLI under test) compiles as its parent does
    os.environ["JAX_DISABLE_MOST_OPTIMIZATIONS"] = "1" if cheap else "0"
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    cc.reset_cache()


def _cheap(node):
    return str(node.path).startswith(_CHEAP_DIR) and \
        node.get_closest_marker("full_compile") is None


@pytest.fixture(scope="module", autouse=True)
def _module_compiles(request):
    """Before the module's own fixtures compile anything."""
    _set_pipeline(_cheap(request.node))


@pytest.fixture(autouse=True)
def _test_compiles(request, _module_compiles):
    """A marked test of an unmarked module, and the module's setting
    again after a test that chose a cache directory of its own."""
    _set_pipeline(_cheap(request.node))


@pytest.fixture
def fault_registry():
    """Armed-fault registry handle that is guaranteed clean before AND
    after the test — injected faults must never leak across tests."""
    from deepspeed_tpu.runtime.resilience import fault_injection
    fault_injection.clear_faults()
    yield fault_injection
    fault_injection.clear_faults()

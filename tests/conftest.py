"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference's distributed tests fork NCCL process trees and need real GPUs
(`tests/unit/common.py:14-100`); here XLA fakes 8 host devices so every
sharding/collective path is exercised on CPU (SURVEY.md §4's improvement
note). Must set the env vars before jax is imported anywhere.
"""

import os

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


@pytest.fixture
def fault_registry():
    """Armed-fault registry handle that is guaranteed clean before AND
    after the test — injected faults must never leak across tests."""
    from deepspeed_tpu.runtime.resilience import fault_injection
    fault_injection.clear_faults()
    yield fault_injection
    fault_injection.clear_faults()

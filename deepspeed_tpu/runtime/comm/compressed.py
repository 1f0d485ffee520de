"""Error-feedback 1-bit compressed allreduce, TPU-native.

Capability parity with the reference's ``Compressed_Allreduce``
(`runtime/fp16/onebit_adam.py:104-228`) and its MPI/cupy data plane
(`runtime/custom_collectives.py:23-153`), re-designed as an XLA collective:

- sign/scale compression and bit-packing run on-device (VPU elementwise +
  an 8-wide dot against powers of two replacing ``cupy.packbits``);
- the 2-phase "gather to chunk-server, server-reduce, allgather" MPI
  topology becomes one ``all_to_all`` + one ``all_gather`` over a named
  mesh axis inside ``shard_map`` — each rank is the server for its 1/world
  chunk, exactly like the reference's rank-owned chunks;
- worker and server error-feedback residuals are carried by the caller as
  explicit state (the reference stashes them on the optimizer,
  onebit_adam.py:305-308).

Wire volume per device is ~n/4 bytes (packed signs both ways + scalars) vs
8n bytes for an fp32 ring allreduce — the reference's headline "up to 5x
less communication" (README.md:19,40).

All functions here are pure and must be called inside ``shard_map`` with
``axis_name`` bound (tests drive them over the 8-device CPU mesh).
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.parallel.collectives import (
    gather_from_chunk_servers, scatter_to_chunk_servers)
from jax.lax import axis_size

__all__ = ["pack_signs", "unpack_signs", "compressed_allreduce",
           "error_feedback_sizes"]

_POW2 = tuple(1 << i for i in range(8))


def pack_signs(signs):
    """Pack a [..., n] bool array (True = +1) into [..., n//8] uint8.

    ``n`` must be a multiple of 8. The analog of ``cupy.packbits``
    (`custom_collectives.py:33`), expressed as a reshape + small dot so XLA
    lowers it to vectorized integer ops.
    """
    *lead, n = signs.shape
    assert n % 8 == 0, f"pack_signs needs n % 8 == 0, got {n}"
    bits = signs.reshape(*lead, n // 8, 8).astype(jnp.uint8)
    weights = jnp.asarray(_POW2, jnp.uint8)
    return (bits * weights).sum(axis=-1).astype(jnp.uint8)


def unpack_signs(packed, dtype=jnp.float32):
    """Inverse of :func:`pack_signs`: [..., m] uint8 → [..., 8*m] ±1."""
    *lead, m = packed.shape
    weights = jnp.asarray(_POW2, jnp.uint8)
    bits = (packed[..., None] & weights) > 0
    pm1 = jnp.where(bits, jnp.asarray(1, jnp.int8), jnp.asarray(-1, jnp.int8))
    return pm1.reshape(*lead, m * 8).astype(dtype)


def _compress(x, n_valid):
    """sign+scale compression: returns (packed_signs, scale, residual).

    ``scale = ||x||_2 / sqrt(n_valid)`` (reference onebit_adam.py:122-139);
    the residual is the error-feedback term ``x - scale * sign(x)`` with
    any padding region zeroed so dead elements never accumulate error.
    """
    n = x.shape[-1]
    valid = (jnp.arange(n) < n_valid)
    x = jnp.where(valid, x, 0.0)
    scale = jnp.linalg.norm(x) / jnp.sqrt(jnp.asarray(n_valid, x.dtype))
    signs = x >= 0
    sgn = jnp.where(signs, 1.0, -1.0).astype(x.dtype)
    residual = jnp.where(valid, x - scale * sgn, 0.0)
    return pack_signs(signs), scale, residual


def error_feedback_sizes(n, world):
    """(padded_n, chunk) for an n-element buffer over a world-size axis.

    Padding aligns to ``8 * world`` so every per-rank chunk packs to whole
    bytes (the reference pads to ``world`` divisibility the same way,
    onebit_adam.py:117-121, plus cupy's byte alignment).
    """
    align = 8 * world
    padded = ((n + align - 1) // align) * align
    return padded, padded // world


def compressed_allreduce(x, worker_error, server_error, axis_name,
                         n_valid=None):
    """1-bit error-feedback averaging allreduce of ``x`` over ``axis_name``.

    Must run inside ``shard_map``. Per rank:
      ``x``            [padded_n]  local vector to average (padding zeroed)
      ``worker_error`` [padded_n]  this rank's compression residual
      ``server_error`` [chunk]     residual for the chunk this rank serves

    Returns ``(avg, new_worker_error, new_server_error)`` where ``avg`` is
    the doubly-compressed average — identical on every rank, like the
    reference's final allgather (onebit_adam.py:200-228).
    """
    world = axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    padded_n = x.shape[-1]
    chunk = padded_n // world
    assert chunk * world == padded_n and chunk % 8 == 0, (
        f"buffer of {padded_n} not aligned for world {world}; "
        f"use error_feedback_sizes()")
    if n_valid is None:
        n_valid = padded_n

    # Phase 1 — worker compression (reference 122-139).
    corrected = x + worker_error
    packed, scale, new_worker_error = _compress(corrected, n_valid)

    # Phase 2 — exchange: rank r receives every rank's packed chunk r
    # (the reference's igather to chunk servers, custom_collectives.py:23;
    # the same chunk-server scatter the int8 path in `comm/quantized.py`
    # rides, factored into `parallel/collectives.py`).
    packed = packed.reshape(world, chunk // 8)
    recv = scatter_to_chunk_servers(packed, axis_name)       # [world, chunk/8]
    scales = gather_from_chunk_servers(scale, axis_name)     # [world]

    # Phase 3 — server reduce + second compression (reference 160-199).
    decoded = unpack_signs(recv) * scales[:, None]           # [world, chunk]
    chunk_avg = decoded.mean(axis=0) + server_error
    # Validity mask for this rank's chunk within the original n_valid.
    chunk_valid = jnp.clip(n_valid - rank * chunk, 0, chunk)
    n_csafe = jnp.maximum(chunk_valid, 1)
    valid = jnp.arange(chunk) < chunk_valid
    chunk_avg = jnp.where(valid, chunk_avg, 0.0)
    s_scale = jnp.linalg.norm(chunk_avg) / jnp.sqrt(
        n_csafe.astype(chunk_avg.dtype))
    s_signs = chunk_avg >= 0
    s_sgn = jnp.where(s_signs, 1.0, -1.0).astype(chunk_avg.dtype)
    new_server_error = jnp.where(valid, chunk_avg - s_scale * s_sgn, 0.0)

    # Phase 4 — allgather the served chunks (reference 200-228).
    all_packed, all_scales = gather_from_chunk_servers(
        (pack_signs(s_signs), s_scale), axis_name)           # [world, ...]
    avg = (unpack_signs(all_packed) *
           all_scales[:, None]).reshape(padded_n)
    avg = jnp.where(jnp.arange(padded_n) < n_valid, avg, 0.0)
    return avg, new_worker_error, new_server_error

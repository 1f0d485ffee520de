"""Unit tests for the HLO communication accounting itself.

``collective_bytes``/``ring_send_bytes`` back the pinned byte-ratio
claims (1-bit Adam 16x, ZeRO stage volumes); these tests pin the parser
and the ring conversion factors on hand-written HLO snippets so a
regex or factor regression cannot silently skew every downstream ratio.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.analysis.hlo import collective_bytes, ring_send_bytes

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

SYNTH = """
HloModule synth
  %x = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={}
  %ag = bf16[2048]{0} all-gather(%y), dimensions={0}
  %rs = f32[128]{0} reduce-scatter(%ar), dimensions={0}
  %aa = u8[256]{0} all-to-all(%z), dimensions={0}
  %done = f32[1024]{0} all-reduce-done(%started)
"""


def test_collective_bytes_synthetic():
    cb = collective_bytes(SYNTH)
    assert cb["all-reduce"] == 4096          # done-form not double counted
    assert cb["all-gather"] == 4096          # bf16[2048]
    assert cb["reduce-scatter"] == 512
    assert cb["all-to-all"] == 256
    assert cb["total"] == 4096 + 4096 + 512 + 256


def test_ring_send_factors_synthetic():
    n = 8
    rs = ring_send_bytes(SYNTH, n)
    assert rs["all-reduce"] == int(4096 * 2 * 7 / 8)
    assert rs["all-gather"] == int(4096 * 7 / 8)
    assert rs["reduce-scatter"] == 512 * 7       # (n-1) x shard-sized out
    assert rs["all-to-all"] == int(256 * 7 / 8)


def test_async_start_counts_result_half():
    hlo = ("%s = (f32[64]{0}, f32[512]{0}, u32[], u32[]) "
           "all-gather-start(%p), dimensions={0}")
    cb = collective_bytes(hlo)
    # Operand f32[64] and scratch scalars skipped; result f32[512] counted.
    assert cb["all-gather"] == 2048


def test_matches_real_compiled_allreduce():
    # Byte-magnitude check on a real compiled program: summing a
    # [n, 131072] f32 array over its sharded axis needs a cross-shard
    # reduction whose full payload is the 131072-float (512 KB) result —
    # a parser that drops the dims product (counting ~1 element/shape)
    # fails this by three orders of magnitude.
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(len(devs)), ("data",))

    x = jax.device_put(
        np.zeros((len(devs), 131072), np.float32),
        NamedSharding(mesh, PartitionSpec("data", None)))

    def f(x):
        y = jnp.sum(x, axis=0)   # reduce across the sharded axis
        return jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, PartitionSpec()))

    txt = jax.jit(f).lower(x).compile().as_text()
    cb = collective_bytes(txt)
    expected = 131072 * 4
    # all-reduce, or reduce-scatter+all-gather — either way the summed
    # payload is within 2x of the 512 KB result size.
    assert expected * 0.9 <= cb["total"] <= expected * 2.2, cb

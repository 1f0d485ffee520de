"""ZeRO-3 16-bit param gathers: numerics + wire-dtype proof.

Stage 3 casts each fp32 param shard to the compute dtype BEFORE the
per-use all-gather (`zero/sharding.py:make_param_caster`), halving param
traffic vs XLA's default gather-then-cast — the analog of the reference
gathering updated fp16 (not fp32 master) params (`zero/stage1.py:692`).
Exactness: cast is elementwise, so cast∘gather == gather∘cast bitwise;
the backward is pinned by custom_vjp to cast the cotangent to fp32
before any reduction, so gradient numerics are untouched.

The wire-dtype claim is asserted on the SPMD-partitioner pass dump
(`xla_dump_hlo_pass_re`): that stage is backend-independent — the final
CPU HLO re-widens the gather to f32 because CPU emulates bf16 math in
f32 and its simplifier hoists the convert, which a native-bf16 backend
has no reason to do.
"""

import glob
import re

import pytest

from tests.unit.zero_fixtures import (
    HIDDEN, build_engine, lowered_train_step, make_batch)


def test_stage3_losses_match_stage0_exactly():
    # Cast-then-gather must be bitwise-neutral: stage-3 training equals
    # the unsharded baseline step for step.
    b = make_batch()
    e0, e3 = build_engine(0), build_engine(3)
    for _ in range(5):
        l0 = float(e0.train_batch(b))
        l3 = float(e3.train_batch(b))
        assert l0 == pytest.approx(l3, rel=1e-6), (l0, l3)


@pytest.mark.full_compile
def test_stage3_param_gathers_are_bf16_at_partitioner_level(tmp_path):
    # The fixture clears jax's caches between its warm-up step and the
    # dump compile, so XLA really compiles with these options (a
    # same-HLO executable cached earlier in the process otherwise
    # short-circuits the compile and no dump appears — observed once
    # under full-suite cache pressure; green in isolation).
    lowered_train_step(3, compiler_options={
        "xla_dump_to": str(tmp_path), "xla_dump_hlo_pass_re": "spmd"})

    dumps = sorted(glob.glob(str(tmp_path / "*spmd-partition*")))
    assert dumps, "no spmd-partitioner dump produced"
    txt = open(dumps[-1]).read()
    gathers = [ln for ln in txt.splitlines() if "all-gather(" in ln]
    # Param-sized gathers: one kernel shard is [HIDDEN/8, HIDDEN] ->
    # gathered [HIDDEN, HIDDEN]. Every such gather must be bf16.
    shape = re.compile(r"=\s+(\w+)\[(\d+),(\d+)\]")
    param_gathers = []
    for ln in gathers:
        m = shape.search(ln)
        if m and int(m.group(2)) == HIDDEN and int(m.group(3)) == HIDDEN:
            param_gathers.append(m.group(1))
    assert param_gathers, f"no param-sized all-gathers found:\n{gathers[:5]}"
    assert all(d == "bf16" for d in param_gathers), param_gathers

"""``flops.py`` against the program's own count; the plain reference
against the program's model."""

import json
import os
import sys

import numpy as np
import pytest

from benchmarks.suite import flops
from benchmarks.suite.reference import gpt2_ref

from . import tiny

ROOT = os.path.dirname(os.path.dirname(tiny.SUITE))


def config_files():
    d = os.path.join(tiny.SUITE, "configs")
    return sorted(f for f in os.listdir(d) if f.endswith(".json"))


@pytest.mark.parametrize("name", config_files())
@pytest.mark.parametrize("seq", [1024, 512])
def test_train_flops_equal_the_programs_count(name, seq):
    sys.path.insert(0, ROOT)
    import bench
    from deepspeed_tpu.models.gpt2 import GPT2Config

    with open(os.path.join(tiny.SUITE, "configs", name)) as f:
        cfg = json.load(f)
    program = GPT2Config(n_embd=cfg["n_embd"], n_layer=cfg["n_layer"],
                         n_head=cfg["n_head"],
                         vocab_size=cfg["vocab_size"])
    assert flops.train_flops_per_token(cfg, seq) == \
        bench.model_flops_per_token(program, seq)


def test_known_sizes():
    with open(os.path.join(tiny.SUITE, "configs", "gpt2-medium.json")) as f:
        medium = json.load(f)
    assert flops.train_flops_per_token(medium, 1024) == 2424637440
    assert flops.param_count(medium) == 354823168


def test_kernel_work_and_roofline_share():
    import types

    from benchmarks.suite import xplane
    from benchmarks.suite.readers import roofline

    with open(os.path.join(tiny.SUITE, "configs", "gpt2-medium.json")) as f:
        medium = json.load(f)
    ctx = types.SimpleNamespace(
        config=medium, devices=[0], peaks=flops.peaks_for("TPU v5 lite"),
        workload={"traffic": {"rows": 8, "seq": 1024}})
    res = types.SimpleNamespace(facts={"profiled_steps": 2}, trace=None)
    ops, moved = flops.flash_attention_train_step(ctx, res)
    # 7 * B*H*T^2*D a layer: 8 x 16 x 1024^2 x 64 x 7 x 24
    assert ops == 7 * 8 * 1024 ** 2 * 1024 * 24
    assert moved == 16 * 8 * 1024 * 1024 * 2 * 24
    assert roofline.read(ctx, res, pattern="k", per="step",
                         work="flash_attention_train_step") is None
    # a kernel that takes twice its least time reads 50 %
    least = max(ops / 197e12, moved / 819e9)
    res.trace = xplane.Trace(devices={0: [
        ("attn.1 custom-call:tpu_custom_call", 0.0, 2 * least),
        ("fusion.1 fusion", 2 * least, 3 * least),
        ("attn.2 custom-call:tpu_custom_call", 3 * least, 5 * least)]},
        spans=[])
    assert roofline.read(ctx, res, pattern="tpu_custom_call$", per="step",
                         work="flash_attention_train_step") == \
        pytest.approx(50.0)
    res.facts = {}
    assert flops.flash_decode_step(ctx, res) is None


def test_unknown_device_kind_is_an_error():
    assert flops.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks_for("cpu")


def test_reference_agrees_with_the_program_on_gpt2_tiny():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import (
        GPT2LMHead, gpt2_tiny, make_gpt2_loss_fn)

    model = GPT2LMHead(gpt2_tiny(dtype=jnp.float32))
    ids = np.random.default_rng(0).integers(0, 256, (2, 48)).astype(
        np.int32)
    params = model.init({"params": jax.random.PRNGKey(3)},
                        jnp.asarray(ids))["params"]
    want = model.apply({"params": params}, jnp.asarray(ids))
    got = gpt2_ref.logits(params, jnp.asarray(ids), n_head=4, eps=1e-6)
    # float32 on both sides: only the order of sums differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)
    loss = make_gpt2_loss_fn(model)(params, {"input_ids": jnp.asarray(ids)})
    assert float(gpt2_ref.loss(params, jnp.asarray(ids), 4, 1e-6)) == \
        pytest.approx(float(loss), rel=1e-6)
    # and it is a reference for *this* epsilon: the published one differs
    other = gpt2_ref.logits(params, jnp.asarray(ids), n_head=4, eps=1e-2)
    assert float(jnp.abs(other - want).max()) > 1e-3

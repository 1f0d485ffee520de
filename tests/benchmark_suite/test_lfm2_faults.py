"""Every named fault of `benchmarks/suite/tools/fault_readings_lfm2.py`
over its check's limit at toy size on the CPU, the sound layers under
theirs, and weights at 3 bits of mantissa over the mixer's: the checks
are `drivers/serve_lfm2.py`'s own, on `tiny_lfm2.py`'s widths. No number
from here is a device metric."""

import jax
import numpy as np
import pytest

from benchmarks.suite.drivers import serve_lfm2
from benchmarks.suite.reference import lfm2_moe_ref as ref
from benchmarks.suite.tools import fault_readings_lfm2 as faults

from . import tiny_lfm2


@pytest.fixture(scope="module")
def toy():
    from deepspeed_tpu.models.lfm2_moe import (Lfm2MoeLM,
                                               init_lfm2_moe_params)
    cfg = tiny_lfm2.CONFIG
    mc = serve_lfm2.model_config(cfg)
    params = init_lfm2_moe_params(Lfm2MoeLM(mc), jax.random.PRNGKey(5))
    return cfg, mc, params, tiny_lfm2.workload()["correctness"]


MIXER = sorted(faults.mixer_faults(tiny_lfm2.CONFIG))
PROGRAM = ["the last tenant's window carried into a prompt",
           "the padded tail entering the window"]
ATTENTION = sorted(faults.attention_faults(tiny_lfm2.CONFIG))
EXPERTS = sorted(faults.expert_faults(tiny_lfm2.CONFIG, 2))
WHOLE = ["an untied head", "one dense layer for two"]


@pytest.mark.parametrize("fault", MIXER + PROGRAM)
def test_a_faulty_mixer_reads_over_the_limit(toy, fault):
    cfg, mc, params, corr = toy
    if fault in PROGRAM:
        with faults.program_faults(mc, 3, 32)[fault]():
            got = serve_lfm2.check_mixer(mc, cfg, params, 3, 32,
                                         corr["mixer_rtol"])
    else:
        got = serve_lfm2.check_mixer(
            mc, cfg, params, 3, 32, corr["mixer_rtol"],
            reference=faults.mixer_faults(cfg)[fault])
    assert not got["ok"], (fault, got)
    assert got["dead_rows_window_moved"] == 0.0


@pytest.mark.parametrize("fault", ATTENTION)
def test_a_faulty_attention_reads_over_the_limit(toy, fault):
    cfg, mc, params, corr = toy
    got = serve_lfm2.check_attention(
        mc, cfg, params, 3, 32, 4, "flash", corr["attention_rtol"],
        corr["attention_decode_rtol"],
        reference=faults.attention_faults(cfg)[fault])
    assert not got["ok"], (fault, got)


@pytest.mark.parametrize("fault", EXPERTS)
def test_a_faulty_expert_layer_reads_over_the_limit(toy, fault):
    cfg, mc, params, corr = toy
    got = serve_lfm2.check_experts(mc, cfg, params, 3, 32, 6,
                                   corr["expert_rtol"],
                                   **faults.expert_faults(cfg, 2)[fault])
    assert not got["ok"], (fault, got)


@pytest.mark.parametrize("fault", WHOLE)
def test_a_faulty_model_reads_over_the_logits_limit(toy, fault):
    """The whole forward: the sound reference's logit row against the
    faulty one's stands over ``deep_rtol`` (what `check_slot` reads of
    the engine's own row)."""
    cfg, mc, params, corr = toy
    toks = np.random.default_rng(0).integers(0, 256, 40)
    want = np.asarray(ref.forward(params, toks, cfg, rows=[39])[0])
    got = np.asarray(faults.whole_faults(mc, 3)[fault](
        params, toks, cfg, rows=[39])[0])
    assert serve_lfm2._off(got, want) > corr["deep_rtol"], fault


def test_sound_layers_read_under_their_limits(toy):
    cfg, mc, params, corr = toy
    assert serve_lfm2.check_mixer(mc, cfg, params, 3, 32,
                                  corr["mixer_rtol"])["ok"]
    assert serve_lfm2.check_attention(
        mc, cfg, params, 3, 32, 4, "flash", corr["attention_rtol"],
        corr["attention_decode_rtol"])["ok"]
    assert serve_lfm2.check_experts(mc, cfg, params, 3, 32, 6,
                                    corr["expert_rtol"])["ok"]
    low = faults.low(params)
    got = serve_lfm2.check_mixer(mc, cfg, low, 3, 32, corr["mixer_rtol"],
                                 sound=params)
    assert not got["ok"], got

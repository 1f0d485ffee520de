"""Every named fault of `benchmarks/suite/tools/fault_readings_ling.py`
over its check's limit at toy size on the CPU, the sound layers under
theirs, and weights at 3 bits of mantissa over the mixer's: the checks
are `drivers/serve_ling.py`'s own, on `tiny_ling.py`'s widths. No number
from here is a device metric."""

import jax
import pytest

from benchmarks.suite.drivers import serve_ling
from benchmarks.suite.tools import fault_readings_ling as faults

from . import tiny_ling


@pytest.fixture(scope="module")
def toy():
    from deepspeed_tpu.models.ling_hybrid import (LingHybridLM,
                                                  init_ling_hybrid_params)
    cfg = tiny_ling.CONFIG
    mc = serve_ling.model_config(cfg)
    params = init_ling_hybrid_params(LingHybridLM(mc),
                                     jax.random.PRNGKey(5))
    return cfg, mc, params, tiny_ling.workload()["correctness"]


MIXER = sorted(faults.mixer_faults(tiny_ling.CONFIG))
ATTENTION = sorted(faults.attention_faults(tiny_ling.CONFIG))
EXPERTS = sorted(faults.expert_faults(tiny_ling.CONFIG, 4))


@pytest.mark.parametrize("fault", MIXER)
def test_a_faulty_mixer_reads_over_the_limit(toy, fault):
    cfg, mc, params, corr = toy
    reference = faults.mixer_faults(cfg)[fault]
    got = serve_ling.check_mixer(mc, cfg, params, 3, 32, corr["mixer_rtol"],
                                 reference=reference)
    if "bfloat16" in fault:     # a rounding: seen, if under the limit here
        assert max(got["prefill"], got["decode"]) > 1e-4, got
    else:
        assert not got["ok"], (fault, got)


@pytest.mark.parametrize("fault", ATTENTION)
def test_a_faulty_attention_reads_over_the_limit(toy, fault):
    cfg, mc, params, corr = toy
    reference = faults.attention_faults(cfg)[fault]
    got = serve_ling.check_attention(
        mc, cfg, params, 3, 32, 4, "flash", corr["attention_rtol"],
        corr["attention_decode_rtol"], reference=reference)
    assert not got["ok"], (fault, got)


@pytest.mark.parametrize("fault", EXPERTS)
def test_a_faulty_expert_layer_reads_over_the_limit(toy, fault):
    cfg, mc, params, corr = toy
    got = serve_ling.check_experts(mc, cfg, params, 3, 32, 6,
                                   corr["expert_rtol"],
                                   **faults.expert_faults(cfg, 4)[fault])
    assert not got["ok"], (fault, got)


def test_sound_layers_read_under_their_limits(toy):
    cfg, mc, params, corr = toy
    assert serve_ling.check_mixer(mc, cfg, params, 3, 32,
                                  corr["mixer_rtol"])["ok"]
    assert serve_ling.check_attention(
        mc, cfg, params, 3, 32, 4, "flash", corr["attention_rtol"],
        corr["attention_decode_rtol"])["ok"]
    assert serve_ling.check_experts(mc, cfg, params, 3, 32, 6,
                                    corr["expert_rtol"])["ok"]
    low = faults.low(params)
    got = serve_ling.check_mixer(mc, cfg, low, 3, 32, corr["mixer_rtol"],
                                 sound=params)
    assert not got["ok"], got

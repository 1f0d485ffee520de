"""What PR 35 added to the benchmark, at toy size on the CPU: the work
function of a prompt's prefill attention, the share of its roofline on
a hand-made trace (the kernel's custom call lies inside the scope and
is found there), the share of the walk's blocks that went through the
kernel from the program's own counters, and what each reads where the
program has no such scope or counter (nothing, and no raise). No number
from here is a device metric."""

import jax
import pytest

from benchmarks.suite import flops_mla_prefill, harness, xplane
from benchmarks.suite.drivers import serve_mla

from . import test_manifest, tiny_mla
from .test_mla_rehearsal import config_file, metric

ROOFLINE = "mla_prefill_attn_roofline.serve"
BLOCKS = "mla_prefill_kernel_blocks_pct.serve"
KERNEL = "ds_flash_prefill_latent.5 custom-call:tpu_custom_call"


def test_the_two_metrics_are_in_the_manifest():
    by_name = {m["name"]: m for m in test_manifest.MANIFEST["per_layer"]}
    assert list(by_name)[-2:] == [ROOFLINE, BLOCKS]    # appended
    for name, source in ((ROOFLINE, "device_trace"),
                         (BLOCKS, "program_counter")):
        assert by_name[name] == {
            "name": name, "unit": "%", "better": "higher", "source": source,
            "layer": "kernels", "moves": "ttft_p90_ms",
            "workloads": [tiny_mla.CELL]}


def hand_made(calls, scopes):
    """Two prompts' prefills of 5 ms under the scope each: 2 ms of the
    kernel, 3 ms of a fusion (the expansion), beside a fusion of
    another scope."""
    trace = xplane.Trace(
        devices={0: [(KERNEL, 0.0, 2e-3), ("fusion.2 fusion", 2e-3, 5e-3),
                     ("fusion.9 fusion", 5e-3, 9e-3),
                     (KERNEL, 20e-3, 22e-3),
                     ("fusion.2 fusion", 22e-3, 25e-3)]},
        spans=[("prefill", -1e-3, 10e-3), ("prefill", 19e-3, 26e-3),
               ("decode", 30e-3, 31e-3)])
    facts = {"program_scopes": scopes, "prefill_chunks_profiled": calls,
             "prefill_chunk": 1024, "kv_bytes_per_element": 2}
    return harness.Result(correct=True, attempted=1, failed=0, setup_s=1.0,
                          end_to_end={}, facts=facts, detail={},
                          trace=trace)


SCOPES = {"prefill": {
    "ds_flash_prefill_latent.5":
        "jit(p)/ds_mla_prefill_attn/while/body/jit(_block_call)/"
        "ds_flash_prefill_latent/pallas_call",
    "fusion.2": "jit(p)/ds_mla_prefill_attn/while/body/dot_general",
    "fusion.9": "jit(p)/ds_mla_project/dot_general"}, "decode": {}}


def test_work_of_a_prompts_prefill_attention():
    """Six calls of 1024 at the published widths: 21 block visits and
    18 blocks' worth of admitted pairs a layer, 7 layers: 7.94 TFLOP,
    40.3 ms at the MXU's peak; bound by operations."""
    ctx = tiny_mla.context(jax.devices()[:1], 1.0, True,
                           config=config_file())
    ops, moved = flops_mla_prefill.mla_prefill_attention_prompt(
        ctx, hand_made(6.0, SCOPES))
    expand = 21 * 2 * 1024 * 512 * 16384
    pairs = 18 * 1024 * 1024 * 64 * 2 * (192 + 128)
    assert ops == 7 * (expand + pairs)
    assert 40.2e-3 < ops / 197e12 < 40.4e-3
    assert moved == 7 * 2 * (21 * 1024 * 576 + 6 * 1024 * (
        64 * 192 + 576 + 64 * 128))
    assert moved / 819e9 < 0.1 * ops / 197e12
    # a prompt of one call: its own block, half of it admitted
    ops, _ = flops_mla_prefill.mla_prefill_attention_prompt(
        ctx, hand_made(1.0, SCOPES))
    assert ops == 7 * (2 * 1024 * 512 * 16384 +
                       0.5 * 1024 * 1024 * 64 * 2 * 320)


def test_roofline_share_reads_the_whole_scope_the_kernel_in_it():
    ctx = tiny_mla.context(jax.devices()[:1], 1.0, True,
                           config=config_file())
    res = hand_made(6.0, SCOPES)
    # kernel and expansion both, a prefill span: (2 + 3) ms
    assert metric(ctx, res, "mla_prefill_attn_ms.serve") == \
        pytest.approx(5.0)
    ops, _ = flops_mla_prefill.mla_prefill_attention_prompt(ctx, res)
    assert metric(ctx, res, ROOFLINE) == pytest.approx(
        100 * (ops / 197e12) / 5e-3)
    # the parent's program: the scope is there, plain XLA under it
    parent = hand_made(6.0, {"prefill": {
        "fusion.2": SCOPES["prefill"]["fusion.2"]}, "decode": {}})
    assert metric(ctx, parent, ROOFLINE) == pytest.approx(
        100 * (ops / 197e12) / 3e-3)


@pytest.mark.parametrize("missing", ["scopes", "calls", "trace"])
def test_nothing_to_read_reads_nothing(missing):
    ctx = tiny_mla.context(jax.devices()[:1], 1.0, True,
                           config=config_file())
    res = hand_made(None if missing == "calls" else 6.0,
                    None if missing == "scopes" else SCOPES)
    if missing == "trace":
        res.trace = None
    assert metric(ctx, res, ROOFLINE) is None
    assert metric(ctx, res, BLOCKS) is None     # no ring of this run


@pytest.mark.parametrize("impl,share", [("flash", 100.0), ("dense", 0.0)])
def test_kernel_blocks_share_from_the_programs_counters(impl, share):
    """The cell's driver at toy size: every block of every prompt's
    walk goes through the kernel under ``"flash"``, none under
    ``"dense"``; a program whose spans carry no such counters (the
    parent's) reads nothing."""
    ctx = tiny_mla.context(jax.devices()[:1], seconds=2.0, trace=False)
    ctx.workload["inference"]["attention_impl"] = impl
    res = serve_mla.run(ctx)
    assert res.correct, res.detail["checks"]
    assert metric(ctx, res, BLOCKS) == share
    assert metric(ctx, res, ROOFLINE) is None   # untraced
    if impl == "flash":
        from deepspeed_tpu.telemetry import spans
        strip = ("attn_blocks", "attn_blocks_kernel")
        for r in spans.recent(0.0):
            if r[3]:
                for key in strip:
                    r[3].pop(key, None)
        assert metric(ctx, res, BLOCKS) is None

"""The OLMoE cell's driver end to end at toy size on the CPU, its work
functions against XLA's own count, and its readers on a hand-made
trace. No number from here is a device metric."""

import copy
import re
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.suite import flops_olmoe, harness, xplane
from benchmarks.suite.drivers import train_olmoe
from benchmarks.suite.readers import (device_idle, fact, kernel_time, mfu,
                                      roofline_in, scope_time, series_stat)

from . import test_manifest, tiny

CELL = "train-olmoe-1b-7b-seq4096"
# the configuration file's keys at toy widths: one layer, as the cell
CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 32,
    "num_hidden_layers": 16, "num_attention_heads": 4, "num_experts": 8,
    "num_experts_per_tok": 2, "max_position_embeddings": 128,
    "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "n_embd": 64, "n_layer": 1, "n_head": 4, "n_positions": 128,
    "assumed": {"router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001,
                "initializer_range": 0.02},
    "train": {"compute_dtype": "bfloat16", "param_dtype": "float32",
              "use_flash_attention": True},
}


def context(seconds, trace, seed=2 ** 31 + 77):
    wl = copy.deepcopy(tiny.workload(CELL))
    wl["traffic"].update(rows=2, seq=128)
    wl["trace"].update(reserve_s=0.3, blocking_steps=2, profiled_steps=2)
    return harness.Context(
        cell={"name": "tiny", "chips": 1}, workload=wl, config=CONFIG,
        seed=seed, seconds=seconds, trace=trace,
        t_process=time.perf_counter(), devices=jax.devices()[:1],
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        log=lambda msg: print(msg, file=sys.stderr),
        compiles=harness.CompileCounter())


def test_cell_is_in_the_manifest_with_its_metrics():
    assert CELL in test_manifest.CELLS
    assert tiny.workload(CELL)["driver"] == "train_olmoe"
    listed = set(test_manifest.listed("per_layer", CELL))
    assert {"moe_ms.train", "moe_expert_matmul_ms.train",
            "moe_permute_ms.train", "moe_expert_matmul_roofline.train",
            "moe_load_max_over_mean.train", "train_mfu_pct",
            "flash_fwd_ms.train"} <= listed
    # their patterns match any tpu_custom_call, and this step holds the
    # grouped matmuls too
    assert not {"flash_attn_ms.train", "flash_attn_roofline.train"} & listed


@pytest.mark.parametrize("trace", [False, True])
def test_train_olmoe_driver(trace):
    ctx = context(seconds=1.0, trace=trace)
    res = train_olmoe.run(ctx)
    checks = res.detail["checks"]
    assert res.correct, checks
    assert res.failed == 0 and res.attempted > 3
    assert checks["compiles_in_window"] == 0
    assert checks["train_step_jit_entries"] == [1, 1]
    assert checks["dropped_tokens"] == 0.0
    assert set(checks["reference"]) == {"loss", "ce", "lb", "z", "logits",
                                        "expert_choice", "router",
                                        "experts", "ok"}
    assert res.end_to_end["train_tokens_per_s_per_chip"] > 0
    assert mfu.read(ctx, res) > 0
    assert res.facts["flops_per_token"] == \
        flops_olmoe.train_flops_per_token(CONFIG, 128)
    # 512 pairs a step over 8 experts: the fullest holds at least the mean
    load = fact.read(ctx, res, key="moe_load_max_over_mean")
    assert 1.0 <= load <= 8.0
    assert res.detail["last_step_counters"]["moe_lb_loss"] > 0
    assert res.trace is None        # a CPU trace has no device plane
    assert device_idle.read(ctx, res) is None
    for name in ("moe_ms.train", "moe_permute_ms.train",
                 "moe_expert_matmul_ms.train",
                 "moe_expert_matmul_roofline.train"):
        spec = test_manifest.load(tiny.SUITE, "metrics", name + ".json")
        reader = {"scope_time": scope_time, "kernel_time": kernel_time,
                  "roofline_in": roofline_in}[spec["reader"]]
        assert reader.read(ctx, res, **spec["args"]) is None
    step = series_stat.read(ctx, res, series="train_step", stat="median",
                            scale=1000)
    scopes = res.facts["op_scopes"]
    if trace:
        assert step > 0
        # the compiled step's text names the four phases
        found = {s for s in ("ds_moe_route", "ds_moe_dispatch",
                             "ds_moe_experts", "ds_moe_combine")
                 if any(s in where for where in scopes.values())}
        assert len(found) == 4, sorted(set(scopes.values()))[:20]
    else:
        assert step is None and scopes is None


def test_flops_olmoe_against_xla_cost_analysis():
    """The forward pass's matmuls written out densely (every token
    through its first ``num_experts_per_tok`` experts: the count does
    not depend on which) and counted by XLA, against a third of the
    training count."""
    cfg = dict(CONFIG, n_layer=2)
    c, i, e, k = 64, 32, 8, 2
    heads, t, v = 4, 128, 256
    f32 = jnp.float32

    def forward(x, wq, wk, wv, wo, router, w_gate, w_up, w_down, head):
        for _ in range(cfg["n_layer"]):
            q, kk, vv = ((x @ w).reshape(t, heads, c // heads)
                         for w in (wq, wk, wv))
            att = jax.nn.softmax(jnp.einsum("thd,shd->hts", q, kk), -1)
            x = x + jnp.einsum("hts,shd->thd", att, vv).reshape(t, c) @ wo
            p = jax.nn.softmax(x @ router, -1)
            for j in range(k):
                h = jax.nn.silu(x @ w_gate[j]) * (x @ w_up[j])
                x = x + p[:, j:j + 1] * (h @ w_down[j])
        return x @ head

    shapes = [(t, c), (c, c), (c, c), (c, c), (c, c), (c, e), (e, c, i),
              (e, c, i), (e, i, c), (c, v)]
    compiled = jax.jit(forward).lower(
        *[jax.ShapeDtypeStruct(s, f32) for s in shapes]).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    counted = cost["flops"] / t
    analytic = flops_olmoe.train_flops_per_token(cfg, t) / 3.0
    # XLA also counts the softmaxes, the activations and the adds
    assert analytic <= counted <= 1.08 * analytic, (analytic, counted)
    assert flops_olmoe.active_matmul_params(cfg) == \
        2 * (4 * c * c + k * 3 * c * i + c * e) + v * c


def test_flops_olmoe_at_the_published_widths():
    cfg = test_manifest.load(test_manifest.ROOT, "benchmarks", "suite",
                             "configs", "olmoe-1b-7b.json")
    per_token = flops_olmoe.train_flops_per_token(cfg, 4096)
    assert abs(per_token - 1.122e9) < 1e6
    assert abs(8192 * per_token - 9.19e12) < 1e10
    assert abs(flops_olmoe.param_count(cfg) - 625.6e6) < 1e5
    assert abs(16 * flops_olmoe.param_count(cfg) - 10.01e9) < 1e7
    ctx = context(seconds=1.0, trace=False)
    ctx.config = cfg
    ctx.workload["traffic"].update(rows=2, seq=4096)
    ops, moved = flops_olmoe.expert_matmuls_train_step(ctx, None)
    assert abs(ops - 2.474e12) < 1e9
    assert ops / 197e12 > moved / 819e9       # bound by operations


HLO = """
  %fusion.7 = bf16[8,4]{1,0} fusion(%p0), kind=kLoop, calls=%fc.1, metadata={op_name="jit(step)/jvp(OlmoeLM)/layers_0/experts/ds_moe_dispatch/gather" stack_frame_id=4}
  ROOT %sort.1 = s32[64]{0} sort(%p1), dimensions={0}, metadata={op_name="jit(step)/jvp(OlmoeLM)/layers_0/experts/ds_moe_route/jit(argsort)/sort"}
  %tgmm.3 = bf16[8,4]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(OlmoeLM))/layers_0/experts/ds_moe_experts/jit(tgmm)/pallas_call"}
  %fusion.9 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fc.2, metadata={op_name="jit(step)/jvp(OlmoeLM)/final_norm/mul"}
"""


def hand_made(profiled_steps=2):
    ms = 1e-3
    events = [("fusion.7 fusion", 0.0, 2 * ms),
              ("sort.1 sort", 2 * ms, 3 * ms),
              ("tgmm.3 custom-call:tpu_custom_call", 3 * ms, 7 * ms),
              ("fusion.9 fusion", 7 * ms, 17 * ms)]
    res = harness.Result(
        correct=True, attempted=0, failed=0, setup_s=1.0, end_to_end={},
        facts={"profiled_steps": profiled_steps,
               "op_scopes": scope_time.scopes_of(HLO, "ds_moe_")},
        detail={}, trace=xplane.Trace(devices={0: events}, spans=[]))
    return res


def test_scopes_of_reads_instruction_and_scope():
    scopes = scope_time.scopes_of(HLO, "ds_moe_")
    assert set(scopes) == {"fusion.7", "sort.1", "tgmm.3"}
    assert "ds_moe_route" in scopes["sort.1"]


def test_new_readers_on_a_hand_made_trace():
    ctx = context(seconds=1.0, trace=True)
    res = hand_made()
    every = ["ds_moe_route", "ds_moe_dispatch", "ds_moe_experts",
             "ds_moe_combine"]
    assert scope_time.read(ctx, res, scopes=every) == pytest.approx(3.5)
    assert scope_time.read(ctx, res, scopes=["ds_moe_route",
                                             "ds_moe_dispatch",
                                             "ds_moe_combine"]) == \
        pytest.approx(1.5)
    assert scope_time.read(ctx, res, scopes=["ds_moe_route"],
                           pattern="^fusion.9 ") == pytest.approx(5.5)
    assert scope_time.read(ctx, res, scopes=["nothing_of_that_name"]) is None
    spec = test_manifest.load(tiny.SUITE, "metrics",
                              "moe_expert_matmul_ms.train.json")
    assert kernel_time.read(ctx, res, **spec["args"]) == pytest.approx(2.0)
    # toy traffic: 2 x 128 tokens x 2 experts = 512 rows of 64 x 32
    spec = test_manifest.load(tiny.SUITE, "metrics",
                              "moe_expert_matmul_roofline.train.json")
    ops, moved = flops_olmoe.expert_matmuls_train_step(ctx, res)
    assert ops == 18 * 512 * 64 * 32
    least = max(ops / 197e12, moved / 819e9)
    assert roofline_in.read(ctx, res, **spec["args"]) == \
        pytest.approx(100 * least / 2e-3)
    # a program that names no phases, or no trace: nothing to read
    res.facts["op_scopes"] = None
    assert scope_time.read(ctx, res, scopes=every) is None
    res.trace = None
    assert roofline_in.read(ctx, res, **spec["args"]) is None
    assert fact.read(ctx, res, key="moe_load_max_over_mean") is None


def test_stall_watch_logs_a_stall_with_stacks_and_its_end(tmp_path):
    """`tools/stall_watch.py`: steps, then none for longer than
    `stall_s`, then steps again: the log holds the stall with every
    thread's stack and its end, and the once-a-second sample."""
    from benchmarks.suite.tools import stall_watch

    class Stepper:
        def step(self):
            return "stepped"

    path = tmp_path / "stall.log"
    with open(path, "w", buffering=1) as out:
        dog = stall_watch.Watchdog(out, stall_s=0.2, after_steps=3,
                                   poll_s=0.02)
        stall_watch.beat_on_return(Stepper, "step", dog)
        stepper = Stepper()
        dog.start()
        try:
            for _ in range(5):
                assert stepper.step() == "stepped"
            time.sleep(0.5)                 # the stall
            deadline = time.perf_counter() + 5
            while "stall over" not in path.read_text() and \
                    time.perf_counter() < deadline:
                stepper.step()
                time.sleep(0.02)
        finally:
            dog.stop()
    assert not dog._thread.is_alive()
    assert dog.steps >= 6
    log = path.read_text()
    assert "STALL: no step returned for 0." in log
    assert "test_stall_watch_logs_a_stall" in log     # the stacks
    assert log.index("stall over") > log.index("STALL")
    assert re.search(r"steps \d+ cpu \d", log)
    cpu = stall_watch.thread_cpu()
    assert all(seconds >= 0 for _, seconds in cpu.values())
    assert "meminfo" in stall_watch.machine()

"""``readers/program_layer_time.py`` and the metrics of PR 53: the
reader on a hand-made trace and map, the manifest's new entries by
membership, and every new metric a number at toy size in the chat and
hybrid cells (a CPU trace holds no device plane, so the toy run's trace
is laid out here from what the run left: the ring's own spans and the
compiled programs' own instructions)."""

import importlib
import json
import os
import re

import jax
import pytest

from benchmarks.suite import harness, program_ring, xplane
from benchmarks.suite.readers import program_layer_time
from deepspeed_tpu.telemetry import programs

from . import test_manifest, tiny, tiny_hybrid

S7 = [c for c in test_manifest.CELLS if c.startswith("serve-")]
TRAIN = [c for c in test_manifest.CELLS if c.startswith("train-")]
CHAT, HYBRID = "serve-gpt2-medium-chat", "serve-granite-4.0-h-micro-rag"


def cells(*words):
    return [c for c in S7 if any(w in c for w in words)]


FIVE = cells("gpt2", "granite", "kimi", "mimo", "laguna")
# name -> (reader, unit, better, source, layer, moves, cells)
NEW = {
    "decode_scoped_pct.serve": ("program_layer_time", "%", "higher",
                                "device_trace", "device", "itl_p95_ms", S7),
    "prefill_scoped_pct.serve": ("program_layer_time", "%", "higher",
                                 "device_trace", "device", "ttft_p90_ms",
                                 S7),
    "train_scoped_pct.train": ("program_layer_time", "%", "higher",
                               "device_trace", "device",
                               "train_tokens_per_s_per_chip", TRAIN),
    "attn_proj_ms.serve": ("program_layer_time", "ms", "lower",
                           "device_trace", "kernels", "itl_p95_ms", S7),
    "dense_mlp_ms.serve": ("program_layer_time", "ms", "lower",
                           "device_trace", "serving loop", "itl_p95_ms",
                           FIVE),
    "head_ms.serve": ("program_layer_time", "ms", "lower", "device_trace",
                      "serving loop", "itl_p95_ms", S7),
    "attn_proj_prefill_ms.serve": ("program_layer_time", "ms", "lower",
                                   "device_trace", "kernels",
                                   "ttft_p90_ms", S7),
    "dense_mlp_prefill_ms.serve": ("program_layer_time", "ms", "lower",
                                   "device_trace", "serving loop",
                                   "ttft_p90_ms", FIVE),
    "kv_write_prefill_ms.serve": ("program_layer_time", "ms", "lower",
                                  "device_trace", "KV cache",
                                  "ttft_p90_ms", S7),
    "attn_plain_prefill_ms.serve": (
        "program_layer_time", "ms", "lower", "device_trace", "kernels",
        "ttft_p90_ms", cells("gpt2", "granite", "nemotron", "qwen3")),
    "copy_decode_ms.serve": ("program_layer_time", "ms", "lower",
                             "device_trace", "KV cache", "itl_p95_ms", S7),
    "copy_prefill_ms.serve": ("program_layer_time", "ms", "lower",
                              "device_trace", "KV cache", "ttft_p90_ms",
                              S7),
    "idle_decode_upload_ms.serve": ("idle_under_span", "ms", "lower",
                                    "device_trace", "device", "itl_p95_ms",
                                    S7),
    "idle_decode_dispatch_ms.serve": ("idle_under_span", "ms", "lower",
                                      "device_trace", "device",
                                      "itl_p95_ms", S7),
    "idle_decode_wait_ms.serve": ("idle_under_span", "ms", "lower",
                                  "device_trace", "device", "itl_p95_ms",
                                  S7),
    "idle_prefill_ms.serve": ("idle_under_span", "ms", "lower",
                              "device_trace", "device", "ttft_p90_ms", S7),
    "moe_dispatch_rows_useful_pct.serve": (
        "span_counter_ratio", "%", "higher", "program_counter", "experts",
        "itl_p95_ms", cells("kimi", "nemotron", "qwen3", "mimo", "laguna")),
}


def spec_of(name):
    return test_manifest.load(tiny.SUITE, "metrics", name + ".json")


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_in_the_manifest(name):
    """By membership: where an entry stands in its list is nobody's
    business (a later PR appends behind it)."""
    reader, unit, better, source, layer, moves, where = NEW[name]
    entries = [m for m in test_manifest.MANIFEST["per_layer"]
               if m["name"] == name]
    assert len(entries) == 1
    assert entries[0] == {"name": name, "unit": unit, "better": better,
                          "source": source, "layer": layer, "moves": moves,
                          "workloads": where}
    assert len(where) in (3, 4, 5, 7)
    spec = spec_of(name)
    assert spec["reader"] == reader
    mod = importlib.import_module("benchmarks.suite.readers." + reader)
    assert callable(mod.read)
    # each cell listed reports the end-to-end metric the entry moves
    for cell in where:
        assert moves in test_manifest.listed("end_to_end", cell)


def test_the_new_entries_were_appended_and_nothing_else_moved():
    names = [m["name"] for m in test_manifest.MANIFEST["per_layer"]]
    assert len(set(names)) == len(names)
    assert set(NEW) <= set(names)
    first_new = min(names.index(n) for n in NEW)
    assert first_new >= 97      # what the manifest held before them


# ---------------------------------------------------------------------------
# the reader on a hand-made trace and map
# ---------------------------------------------------------------------------

MAPS = {
    "prefill": {
        "fusion.1": "jit(p)/LM/ds_attn_qkv/dot_general",
        "fusion.2": "jit(p)/LM/ds_attn_prefill_plain/ds_kv_write/dus",
        "copy.3": "jit(p)/LM/ds_attn_prefill_plain/ds_kv_write/reshape",
        "copy.4": "",
        "fusion.5": "jit(p)/LM/layers_0/add"},
    "decode": {
        "fusion.1": "jit(d)/LM/ds_experts/jit(e)/ds_moe_experts/dot",
        "fusion.2": "jit(d)/LM/ds_experts/add",
        "copy.3": programs.FEEDS + "jit(d)/LM/ds_mlp/dot_general",
        "slice-done.6": "jit(d)/LM/ds_head/slice",
        "attn.7": "jit(d)/LM/ds_attn_decode_plain/ds_flash_decode_paged/k"},
}


def hand_made(monkeypatch, maps=MAPS, logs=None):
    """One prefill span (ops 4 + 2 + 1 + 1 + 2 ms) and two decode spans
    (1 + 2 + 1 ms, then 1 + 0.5 + 0.5 ms); the instructions share names
    across the programs."""
    ms = 1e-3
    trace = xplane.Trace(
        devices={0: [("fusion.1 fusion", 0, 4 * ms),
                     ("fusion.2 fusion", 4 * ms, 6 * ms),
                     ("copy.3 copy", 6 * ms, 7 * ms),
                     ("copy.4 copy", 7 * ms, 8 * ms),
                     ("fusion.5 fusion", 8 * ms, 10 * ms),
                     ("fusion.1 fusion", 20 * ms, 21 * ms),
                     ("fusion.2 fusion", 21 * ms, 23 * ms),
                     ("copy.3 copy", 23 * ms, 24 * ms),
                     ("fusion.1 fusion", 30 * ms, 31 * ms),
                     ("slice-done.6 slice-done", 31 * ms, 31.5 * ms),
                     ("attn.7 custom-call:tpu_custom_call", 31.5 * ms,
                      32 * ms)]},
        spans=[("prefill", -1 * ms, 11 * ms), ("decode", 19 * ms, 25 * ms),
               ("decode", 29 * ms, 33 * ms)])
    monkeypatch.setattr(programs, "op_names", lambda name: maps.get(name))
    monkeypatch.setattr(programs, "registered", lambda: sorted(maps))
    ctx = tiny.context(tiny.serve_workload(CHAT), jax.devices()[:1], 1.0,
                       True)
    if logs is not None:
        ctx.log = logs.append
    res = harness.Result(correct=True, attempted=1, failed=0, setup_s=1.0,
                         end_to_end={}, facts={}, detail={}, trace=trace)
    return ctx, res


def test_innermost_scope_children_and_the_two_programs(monkeypatch):
    logs = []
    ctx, res = hand_made(monkeypatch, logs=logs)
    read = program_layer_time.read
    # the same instruction is another layer in the other program
    assert read(ctx, res, program="prefill", scopes=["ds_attn_qkv"],
                per="span:prefill") == pytest.approx(4.0)
    assert read(ctx, res, program="decode", scopes=["ds_moe_experts"],
                per="span:decode") == pytest.approx(1.0)
    # a listed scope takes its children: ds_experts holds ds_moe_experts
    assert read(ctx, res, program="decode", scopes=["ds_experts"],
                per="span:decode") == pytest.approx(2.0)
    assert read(ctx, res, program="prefill",
                scopes=["ds_attn_prefill_plain"],
                per="span:prefill") == pytest.approx(3.0)
    assert read(ctx, res, program="prefill", scopes=["ds_kv_write"],
                per="span:prefill") == pytest.approx(3.0)
    # per: the window whole
    assert read(ctx, res, program="decode", scopes=["ds_head"]) == \
        pytest.approx(0.5)
    # a scope the program does not hold: nothing to read
    assert read(ctx, res, program="decode", scopes=["ds_kv_write"],
                per="span:decode") is None
    # once a run and program: the map's cost, by layer, bare, moves
    assert sum("by layer" in ln for ln in logs) == 2
    decode = [ln for ln in logs if ln.startswith("decode")]
    assert len(decode) == 4
    assert "ds_experts 1.000" in decode[0] and "ds_moe_experts 1.000" in \
        decode[0] and "over 2 calls" in decode[0]
    # what the compiler added counts under what it feeds, and is told
    assert "ds_mlp 0.500 (0.500 fed)" in decode[0]
    assert "copy.3 copy 0.500 [(feeds) jit(d)/LM/ds_mlp/" in decode[2]
    assert "slice-done slice-done: ds_head 0.250" in decode[3]
    assert "copy copy: ds_mlp 0.500 (0.500 fed)" in decode[3]
    prefill = [ln for ln in logs if ln.startswith("prefill")]
    assert "copy.4 copy 1.000 [no op_name]" in prefill[1]
    assert "fusion.5 fusion 2.000 [jit(p)/LM/layers_0/add]" in prefill[1]


def test_unscoped_share_and_ops(monkeypatch):
    ctx, res = hand_made(monkeypatch)
    read = program_layer_time.read
    # prefill: 3 of 10 ms under no scope (a bare copy, an unscoped add)
    assert read(ctx, res, program="prefill", unscoped=True) == \
        pytest.approx(70.0)
    assert read(ctx, res, program="decode", unscoped=True) == \
        pytest.approx(100.0)
    assert read(ctx, res, program="prefill", ops="^(copy|transpose)",
                per="span:prefill") == pytest.approx(2.0)
    assert read(ctx, res, program="decode", ops="^(copy|transpose)",
                per="span:decode") == pytest.approx(0.5)
    assert read(ctx, res, program="decode", ops="-done$",
                per="span:decode") == pytest.approx(0.25)
    # both must hold; ops alone reads 0.0 where no such op ran
    assert read(ctx, res, program="prefill", ops="^copy",
                scopes=["ds_kv_write"], per="span:prefill") == \
        pytest.approx(1.0)
    assert read(ctx, res, program="decode", ops="^sort",
                per="span:decode") == 0.0


def test_none_without_a_trace_a_registry_or_the_program(monkeypatch):
    ctx, res = hand_made(monkeypatch)
    read = program_layer_time.read
    # a program the run never ran (no such span, several registered)
    assert read(ctx, res, program="train_step", unscoped=True) is None
    # a program nobody registered (an older tree's engine)
    ctx, res = hand_made(monkeypatch, maps={})
    assert read(ctx, res, program="decode", unscoped=True) is None
    res.trace = None
    assert read(ctx, res, program="decode", unscoped=True) is None


def test_one_program_needs_no_span(monkeypatch):
    maps = {"train_step": {
        "fusion.1": "jit(t)/jvp(LM)/ds_mlp/dot",
        "fusion.2": "jit(t)/transpose(jvp(LM))/ds_mlp/dot",
        "fusion.5": "jit(t)/ds_opt_update/mul"}}
    ctx, res = hand_made(monkeypatch, maps=maps)
    res.trace.spans = []
    res.facts["profiled_steps"] = 2
    read = program_layer_time.read
    # fusion.1 4 + 1 + 1, fusion.2 2 + 2 = 10 ms under ds_mlp, 2 under
    # ds_opt_update, and 4 of ops the program's text does not hold
    assert read(ctx, res, program="train_step", scopes=["ds_mlp"],
                per="step") == pytest.approx(5.0)
    assert read(ctx, res, program="train_step", unscoped=True) == \
        pytest.approx(75.0)


# ---------------------------------------------------------------------------
# every new metric a number at toy size, chat and hybrid
# ---------------------------------------------------------------------------

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \S+ ([a-z\-]+)\(",
                         re.M)


def trace_of(ctx, res, offset=123.0):
    """A trace in the shape of the chip's from what the toy run left:
    each of the profiled segment's program spans (``serve/step/decode``,
    ``serve/step/admit/prefill``) becomes a harness span 5 us wider, and
    its middle half holds that program's own instructions in the
    compiled text's order, on a clock ``offset`` away from the ring's."""
    v = program_ring.view(ctx, res)
    ops = {name: [f"{i} {op}" for i, op in INSTRUCTION.findall(
        programs.compiled_text(name))] for name in ("prefill", "decode")}
    paths = {program_ring.DECODE: "decode",
             program_ring.STEP + "/admit/prefill": "prefill"}
    events, marks = [], []
    for path, t0, t1, _ in v.ring:
        if path not in paths or not v.seg0 <= t1 < v.w1:
            continue
        name = paths[path]
        marks.append((name, t0 - 5e-6 + offset, t1 + 5e-6 + offset))
        at, op_s = t0 + 0.25 * (t1 - t0) + offset, \
            0.5 * (t1 - t0) / len(ops[name])
        events += [(op, at + k * op_s, at + (k + 1) * op_s)
                   for k, op in enumerate(ops[name])]
    return xplane.Trace(devices={0: events}, spans=marks)


def run_toy(cell):
    wl = tiny.workload(cell)
    driver = importlib.import_module(
        "benchmarks.suite.drivers." + wl["driver"])
    if cell == CHAT:
        ctx = tiny.context(tiny.serve_workload(cell), jax.devices()[:1],
                           seconds=2.0, trace=True)
    else:
        ctx = tiny_hybrid.context(jax.devices()[:1], seconds=2.0,
                                  trace=True)
    logs = []
    ctx.log = logs.append
    res = driver.run(ctx)
    assert res.trace is None        # the CPU's trace has no device plane
    res.trace = trace_of(ctx, res)
    return ctx, res, logs


@pytest.mark.parametrize("cell", [CHAT, HYBRID])
def test_every_new_metric_of_a_cell_is_a_number_at_toy_size(cell):
    ctx, res, logs = run_toy(cell)
    assert res.correct, res.detail["checks"]
    mine = [n for n in test_manifest.listed("per_layer", cell) if n in NEW]
    assert len(mine) == 15
    got = {}
    for name in mine:
        spec = spec_of(name)
        reader = importlib.import_module(
            "benchmarks.suite.readers." + spec["reader"])
        got[name] = reader.read(ctx, res, **spec["args"])
    assert all(isinstance(v, float) for v in got.values()), got
    assert got["decode_scoped_pct.serve"] >= 50
    assert got["prefill_scoped_pct.serve"] >= 50
    for name in ("attn_proj_ms.serve", "dense_mlp_ms.serve",
                 "head_ms.serve", "attn_proj_prefill_ms.serve",
                 "dense_mlp_prefill_ms.serve", "kv_write_prefill_ms.serve",
                 "attn_plain_prefill_ms.serve"):
        assert got[name] > 0, (name, got)
    idle = [got[n] for n in mine if n.startswith("idle_")]
    assert len(idle) == 4 and all(v >= 0 for v in idle) and sum(idle) > 0
    # the programs were lowered for the readers once each, after the run
    assert sum("instructions lowered and read" in ln for ln in logs) == 2
    assert sum("by layer, ms a call" in ln for ln in logs) == 2
    # and the older tree's cells report them not: the parent has no
    # registry, and the reader says None instead of raising
    import builtins
    real = builtins.__import__

    def no_registry(name, *a, **k):
        if name == "deepspeed_tpu.telemetry" and a and a[2] and \
                "programs" in a[2]:
            raise ImportError(name)
        return real(name, *a, **k)

    res.trace.__dict__.pop("_program_layer_tables")
    builtins.__import__ = no_registry
    try:
        assert program_layer_time.read(
            ctx, res, program="decode", unscoped=True) is None
    finally:
        builtins.__import__ = real

"""`ds_tpu_serve` CLI end-to-end (`deepspeed_tpu/inference/serve.py`).

In-process ``main(argv)`` calls (no subprocess — the CLI compiles a
tiny model, and one interpreter amortizes jax startup): a synthetic
open-loop stream with the compile-contract gate and telemetry JSONL
that feeds ``ds_tpu_metrics summary`` serve mode, a request-file +
config-file run, the --expect-compiles failure path, and the argparse
usage errors."""

import json

import pytest

from deepspeed_tpu.inference.serve import main
from deepspeed_tpu.telemetry.cli import read_events, summarize


class TestUsageErrors:
    def test_stream_required(self):
        with pytest.raises(SystemExit) as e:
            main([])
        assert e.value.code == 2

    def test_streams_mutually_exclusive(self, tmp_path):
        reqs = tmp_path / "r.jsonl"
        reqs.write_text('{"prompt": [1]}\n')
        with pytest.raises(SystemExit) as e:
            main(["--requests", str(reqs), "--synthetic", "2"])
        assert e.value.code == 2


def test_synthetic_stream_end_to_end(tmp_path, capsys):
    """One serve: all requests complete, exactly 2 compiles, and the
    telemetry log summarizes in serve mode."""
    log = tmp_path / "serve.jsonl"
    rc = main(["--synthetic", "5", "--max-new", "4",
               "--expect-compiles", "2", "--jsonl", str(log), "--json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out)
    assert result["ok"] is True
    assert result["requests"] == 5
    assert len(result["completions"]) == 5
    assert result["compile_counts"] == {"prefill": 1, "decode": 1}
    assert all(c["tokens"] for c in result["completions"])
    assert {c["bucket"] for c in result["completions"]} <= {16, 32}

    events = read_events(str(log))
    s = summarize(events)
    assert s["mode"] == "serve"
    assert s["steps"] == len(
        [e for e in events if e.get("event") == "decode_step"])
    assert s["tokens"] >= 5                   # >= one token per request
    assert s["latency_s"]["p50"] is not None
    assert 0.0 < s["batch_occupancy"]["mean"] <= 1.0
    assert s["mfu"] is None                   # serve summaries skip MFU


def test_result_and_log_carry_the_schedulers_request_times(tmp_path,
                                                           capsys):
    """An operator's use of the stamps: the result JSON has time to
    first token and latency per completion, and the log one
    ``request_done`` event each, which the summary reads."""
    log = tmp_path / "serve.jsonl"
    rc = main(["--synthetic", "4", "--max-new", "4", "--jsonl", str(log),
               "--json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out)
    for c in result["completions"]:
        assert 0 < c["ttft_s"] <= c["latency_s"]
    events = read_events(str(log))
    done = {e["rid"]: e for e in events
            if e.get("event") == "request_done"}
    assert set(done) == {c["rid"] for c in result["completions"]}
    for c in result["completions"]:
        e = done[c["rid"]]
        assert e["ttft_s"] == pytest.approx(c["ttft_s"])
        assert e["latency_s"] == pytest.approx(c["latency_s"])
        assert e["tokens"] == len(c["tokens"])
    rq = summarize(events)["requests"]
    assert rq["count"] == 4 and rq["ttft_s"]["n"] == 4
    assert rq["ttft_s"]["p50"] >= rq["hold_s"]["p50"] > 0


def test_requests_file_with_config(tmp_path, capsys):
    cfg = tmp_path / "ds_config.json"
    cfg.write_text(json.dumps({
        "train_batch_size": 1,
        "train_micro_batch_size_per_gpu": 1,
        "inference": {"max_batch": 2, "seq_buckets": [16, 32],
                      "prefill_chunk": 4, "max_new_tokens": 4}}))
    reqs = tmp_path / "stream.jsonl"
    reqs.write_text("\n".join([
        json.dumps({"rid": "a", "prompt": [1, 2, 3],
                    "max_new_tokens": 3}),
        json.dumps({"prompt": list(range(20))}),      # bucket 32, defaults
        json.dumps({"rid": "late", "prompt": [4, 5],
                    "arrival_step": 3, "max_new_tokens": 2}),
    ]) + "\n")
    rc = main(["--config", str(cfg), "--requests", str(reqs)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "3/3 requests completed" in out
    assert "prefill=1 decode=1" in out
    assert "a: prompt 3 tokens -> 3 generated" in out


def test_expect_compiles_violation_exits_nonzero(capsys):
    rc = main(["--synthetic", "2", "--max-new", "2",
               "--expect-compiles", "1"])
    assert rc == 1
    captured = capsys.readouterr()
    assert "FAIL" in captured.err
    assert "compile count 2 != expected 1" in captured.err


def test_flash_attention_and_sampling_flags(capsys):
    """Flash decode + quantized cache + hot sampling still hold the
    2-compile contract, and the knobs land in the result dict."""
    rc = main(["--synthetic", "4", "--max-new", "3",
               "--attention", "flash", "--block-k", "8",
               "--kv-cache-dtype", "int8",
               "--temperature", "0.8", "--top-k", "16",
               "--top-p", "0.9", "--seed", "3",
               "--expect-compiles", "2", "--json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out)
    assert result["ok"] is True
    assert len(result["completions"]) == 4
    assert result["compile_counts"] == {"prefill": 1, "decode": 1}
    assert result["attention"] == {"impl": "flash", "block_k": 8}
    assert result["sampling"] == {"temperature": 0.8, "top_k": 16,
                                  "top_p": 0.9, "seed": 3}


def test_sampling_config_keys_and_seed_precedence(tmp_path, capsys):
    """attention/sampling knobs flow through --config, and a
    non-default --seed overrides the config's sampling_seed."""
    cfg = tmp_path / "ds_config.json"
    cfg.write_text(json.dumps({
        "train_batch_size": 1,
        "train_micro_batch_size_per_gpu": 1,
        "inference": {"max_batch": 2, "seq_buckets": [16, 32],
                      "prefill_chunk": 4, "max_new_tokens": 3,
                      "attention_impl": "flash",
                      "attention_block_k": 8,
                      "temperature": 0.5, "top_k": 8,
                      "sampling_seed": 99}}))
    rc = main(["--config", str(cfg), "--synthetic", "3", "--json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out)
    assert result["attention"]["impl"] == "flash"
    assert result["sampling"]["temperature"] == 0.5
    assert result["sampling"]["seed"] == 99      # config wins at --seed 0
    rc = main(["--config", str(cfg), "--synthetic", "3", "--seed", "7",
               "--json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out)
    assert result["sampling"]["seed"] == 7       # explicit --seed wins


def test_greedy_serve_is_sampling_invariant(tmp_path, capsys):
    """temperature 0 (the default) never consumes the PRNG key: serves
    whose configs differ ONLY in sampling_seed emit identical token
    streams (--seed stays 0 so the synthetic prompts are shared)."""
    streams = []
    for sampling_seed in (1, 2):
        cfg = tmp_path / f"cfg{sampling_seed}.json"
        cfg.write_text(json.dumps({
            "train_batch_size": 1,
            "train_micro_batch_size_per_gpu": 1,
            "inference": {"max_batch": 2, "seq_buckets": [16, 32],
                          "prefill_chunk": 4,
                          "attention_impl": "flash",
                          "attention_block_k": 8,
                          "sampling_seed": sampling_seed}}))
        rc = main(["--config", str(cfg), "--synthetic", "3",
                   "--max-new", "4", "--json"])
        assert rc == 0
        result = json.loads(capsys.readouterr().out)
        assert result["sampling"]["seed"] == sampling_seed
        streams.append([c["tokens"] for c in result["completions"]])
    assert streams[0] == streams[1]


# ---------------------------------------------------------------------------
# the pool: prefix sharing, sessions, and the switch that went
# ---------------------------------------------------------------------------

class TestPagedUsageErrors:
    @pytest.mark.parametrize("value", ["ring", "paged"])
    def test_kv_layout_flag_is_gone(self, value, capsys):
        """`--kv-layout` chose a layout until PR 28: now argparse does
        not know it, whatever it is set to."""
        with pytest.raises(SystemExit) as e:
            main(["--synthetic", "2", "--kv-layout", value])
        assert e.value.code == 2
        assert "--kv-layout" in capsys.readouterr().err

    def test_config_kv_layout_ring_is_refused(self, tmp_path):
        cfg = tmp_path / "ds_config.json"
        cfg.write_text(json.dumps({
            "train_batch_size": 1,
            "train_micro_batch_size_per_gpu": 1,
            "inference": {"kv_layout": "ring"}}))
        with pytest.raises(ValueError, match="only KV layout since PR 28"):
            main(["--config", str(cfg), "--synthetic", "2"])


def test_paged_prefix_sharing_end_to_end(tmp_path, capsys):
    """The CI paged smoke, in-process: a shared system prompt makes the
    radix cache hit, the hits gate and the 2-compile gate both hold,
    and the telemetry log summarizes with the paging block."""
    log = tmp_path / "paged.jsonl"
    rc = main(["--synthetic", "6", "--max-new", "4",
               "--arrival-every", "1",
               "--shared-prefix", "12",
               "--expect-compiles", "2", "--expect-prefix-hits", "1",
               "--jsonl", str(log), "--json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out)
    assert result["ok"] is True
    assert result["compile_counts"] == {"prefill": 1, "decode": 1}
    pg = result["paging"]
    assert pg["prefix_hits"] >= 1
    assert pg["pages_free"] + pg["pages_resident"] == pg["n_pages"] - 1
    assert any(c["prefix_hit"] for c in result["completions"])
    # prefix hits translate into skipped prefill chunks, never fewer
    # generated tokens
    assert sum(c["prefill_chunks_skipped"]
               for c in result["completions"]) >= 1
    assert all(c["tokens"] for c in result["completions"])

    s = summarize(read_events(str(log)))
    assert s["mode"] == "serve"
    assert s["paging"]["prefix"]["hits"] >= 1
    assert s["paging"]["pages"]["total"] == pg["n_pages"]
    assert s["paging"]["cache_bytes_total"] > 0


def test_expect_prefix_hits_violation_exits_nonzero(capsys):
    # no shared prefix -> no hits -> the gate must trip
    rc = main(["--synthetic", "2", "--max-new", "2",
               "--no-prefix-cache",
               "--expect-prefix-hits", "1"])
    assert rc == 1
    captured = capsys.readouterr()
    assert "FAIL" in captured.err
    assert "prefix hits" in captured.err


def test_paged_config_file_with_sessions(tmp_path, capsys):
    """The page knobs flow through --config (`kv_layout: "paged"` is
    still accepted there, and changes nothing), and session_id rides
    the request JSONL into parked sessions."""
    cfg = tmp_path / "ds_config.json"
    cfg.write_text(json.dumps({
        "train_batch_size": 1,
        "train_micro_batch_size_per_gpu": 1,
        "inference": {"max_batch": 2, "seq_buckets": [16, 32],
                      "prefill_chunk": 4, "max_new_tokens": 3,
                      "kv_layout": "paged", "page_size": 8}}))
    reqs = tmp_path / "stream.jsonl"
    reqs.write_text("\n".join([
        json.dumps({"rid": "a", "prompt": [1, 2, 3, 4, 5],
                    "max_new_tokens": 3, "session_id": "chat-1"}),
        json.dumps({"rid": "b", "prompt": [9, 8, 7],
                    "max_new_tokens": 2}),
    ]) + "\n")
    rc = main(["--config", str(cfg), "--requests", str(reqs), "--json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out)
    assert result["ok"] is True
    assert result["paging"]["page_size"] == 8
    # "a" carried a session_id: its pages parked instead of freeing
    parked = result["paging"]["sessions_parked_device"] + \
        result["paging"]["sessions_parked_host"]
    assert parked == 1


class TestSpeculativeUsageErrors:
    def test_min_accepted_requires_speculative(self):
        with pytest.raises(SystemExit) as e:
            main(["--synthetic", "2", "--expect-min-accepted", "1.0"])
        assert e.value.code == 2

    def test_speculative_is_single_replica(self):
        with pytest.raises(SystemExit) as e:
            main(["--synthetic", "2", "--speculative", "--replicas", "2"])
        assert e.value.code == 2

    def test_checkpoint_is_single_replica(self, tmp_path):
        with pytest.raises(SystemExit) as e:
            main(["--synthetic", "2", "--checkpoint", str(tmp_path),
                  "--replicas", "2"])
        assert e.value.code == 2

    def test_spec_k_positive(self):
        with pytest.raises(SystemExit) as e:
            main(["--synthetic", "2", "--speculative", "--spec-k", "0"])
        assert e.value.code == 2


def test_speculative_serve_end_to_end(tmp_path, capsys):
    """The CI smoke in miniature: 3 compiled programs (decode never
    entered), the speculative facts block lands in the result, and the
    mean-accepted gate passes with the calibrated block scale."""
    log = tmp_path / "spec.jsonl"
    rc = main(["--synthetic", "4", "--max-new", "4",
               "--speculative", "--spec-k", "3", "--draft-layers", "1",
               "--block-scale", "0.1",
               "--expect-compiles", "3", "--expect-min-accepted", "1.0",
               "--jsonl", str(log), "--json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out)
    assert result["ok"] is True
    assert len(result["completions"]) == 4
    assert result["compile_counts"] == \
        {"prefill": 1, "decode": 0, "draft": 1, "verify": 1}
    sp = result["speculative"]
    assert sp["k"] == 3 and sp["draft_layers"] == 1
    assert sp["mean_accepted"] >= 1.0
    assert 0.0 <= sp["draft_efficiency"] <= 1.0

    s = summarize(read_events(str(log)))
    assert s["speculative"]["accepted_tokens"] >= 4
    assert s["speculative"]["mean_accepted"] >= 1.0


def test_speculative_text_output_and_gate_failure(capsys):
    """Human-readable compiles line names all four programs; an
    unreachable acceptance gate exits 1 with the why."""
    rc = main(["--synthetic", "2", "--max-new", "3",
               "--speculative", "--spec-k", "2", "--draft-layers", "1",
               "--expect-min-accepted", "3.5"])
    assert rc == 1
    captured = capsys.readouterr()
    assert "draft=1 verify=1" in captured.out
    assert "speculative:" in captured.out
    assert "FAIL" in captured.err
    assert "mean accepted" in captured.err


def _save_tiny_checkpoint(tmp_path, scan_layers=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny
    from deepspeed_tpu.runtime.resilience.checkpoint import (
        CheckpointManager)

    cfg = gpt2_tiny(n_embd=32, dtype=jnp.float32,
                    scan_layers=scan_layers)
    model = GPT2LMHead(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    host = jax.tree_util.tree_map(np.asarray, params)
    meta = {"global_steps": 7,
            "topology": {"mesh_shape": {"data": 1, "model": 1},
                         "param_layout":
                             "stacked" if scan_layers else "per_layer"}}
    mgr = CheckpointManager(save_dir=str(tmp_path),
                            io_retry_base_s=0.001)
    mgr.save(str(tmp_path), "step7", {"params": host}, meta)
    return str(tmp_path)


def test_checkpoint_serve_end_to_end(tmp_path, capsys):
    """Training→serving handoff: a per-layer checkpoint serves
    unrolled with the plain 2-program contract and the checkpoint
    block reports the inferred geometry."""
    ckpt_dir = _save_tiny_checkpoint(tmp_path / "ckpt")
    rc = main(["--checkpoint", ckpt_dir, "--n-head", "4",
               "--synthetic", "3", "--max-new", "3",
               "--expect-compiles", "2", "--json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out)
    assert result["ok"] is True
    assert len(result["completions"]) == 3
    ck = result["checkpoint"]
    assert ck["tag"] == "step7"
    assert ck["n_layer"] == 2 and ck["n_embd"] == 32
    assert ck["param_layout"] == "per_layer"


def test_checkpoint_layout_conversion_with_speculative(tmp_path,
                                                       capsys):
    """A per-layer training checkpoint served as scan_layers (the
    stack round trip) AND speculatively: 3 programs, outputs complete."""
    ckpt_dir = _save_tiny_checkpoint(tmp_path / "ckpt")
    rc = main(["--checkpoint", ckpt_dir, "--n-head", "4",
               "--scan-layers",
               "--speculative", "--spec-k", "2", "--draft-layers", "1",
               "--synthetic", "3", "--max-new", "3",
               "--expect-compiles", "3", "--json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out)
    assert result["ok"] is True
    assert result["compile_counts"]["decode"] == 0
    assert result["checkpoint"]["param_layout"] == "per_layer"


def _save_hybrid_checkpoint(tmp_path, seed):
    import jax
    import numpy as np

    from deepspeed_tpu.models import granite_hybrid as gh
    from deepspeed_tpu.runtime.resilience.checkpoint import (
        CheckpointManager)

    model = gh.GraniteHybridLM(gh.granite_hybrid_tiny())
    params = gh.init_granite_hybrid_params(model,
                                           jax.random.PRNGKey(seed))
    host = jax.tree_util.tree_map(np.asarray, params)
    mgr = CheckpointManager(save_dir=str(tmp_path),
                            io_retry_base_s=0.001)
    mgr.save(str(tmp_path), "step3", {"params": host},
             {"global_steps": 3})
    return str(tmp_path)


_HYBRID_ARGS = ["--model", "granite-hybrid-tiny", "--synthetic", "3",
                "--max-new", "3", "--expect-compiles", "2", "--json",
                "--seed", "5"]


def test_hybrid_preset_serves_from_a_seed_and_from_its_checkpoint(
        tmp_path, capsys):
    """``--model`` picks the preset; the same weights, from ``--seed``
    or from a checkpoint saved in bfloat16, give the same completions
    through the same two programs, in the dtype they were saved in."""
    assert main(_HYBRID_ARGS) == 0
    seeded = json.loads(capsys.readouterr().out)
    assert seeded["ok"] is True and len(seeded["completions"]) == 3
    ckpt_dir = _save_hybrid_checkpoint(tmp_path / "ckpt", seed=5)
    assert main(_HYBRID_ARGS + ["--checkpoint", ckpt_dir]) == 0
    loaded = json.loads(capsys.readouterr().out)
    assert loaded["ok"] is True
    assert ([c["tokens"] for c in loaded["completions"]]
            == [c["tokens"] for c in seeded["completions"]])
    ck = loaded["checkpoint"]
    assert ck["tag"] == "step3" and ck["n_layer"] == 6
    assert ck["n_embd"] == 64 and ck["vocab_size"] == 256


@pytest.mark.parametrize("model, message", [
    ("gpt2-tiny", "name its preset with --model"),
    ("granite-4.0-h-micro", "is not granite-4.0-h-micro's"),
])
def test_hybrid_checkpoint_under_the_wrong_preset_exits(
        tmp_path, model, message):
    ckpt_dir = _save_hybrid_checkpoint(tmp_path / "ckpt", seed=0)
    with pytest.raises(SystemExit) as e:
        main(["--checkpoint", ckpt_dir, "--model", model,
              "--synthetic", "2"])
    assert message in str(e.value)


def test_checkpoint_missing_dir_exits(tmp_path):
    with pytest.raises(SystemExit) as e:
        main(["--checkpoint", str(tmp_path / "nope"),
              "--synthetic", "2"])
    assert "no valid checkpoint" in str(e.value)

"""Autotuner search driver (`deepspeed_tpu/analysis/tune.py`).

The acceptance contract: on a toy GPT-2 base config the tuner returns a
tuned config whose cost-model score STRICTLY beats the untuned default,
with every candidate compiled through the audit path and zero rule
findings on the winner. Rejections are typed, never silent, and the
expected-run JSONL it emits is consumable by ``ds_tpu_metrics``
summarize/diff.

The in-process search here is restricted to one dimension (two engine
compiles) so it fits the tier-1 budget; the full default sweep runs in
``BENCH_MODEL=tune``.
"""

import json
import math

import pytest

from deepspeed_tpu.analysis.tune import (
    REJECT_BUILD_ERROR,
    REJECT_PEAK_MEMORY,
    SERVING_DIMENSION_NAMES,
    Choice,
    deep_merge,
    default_dimensions,
    evaluate_candidate,
    evaluate_serving_candidate,
    expected_events,
    serving_dimensions,
    tune,
    write_expected_log,
)

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

BASE = {
    "train_batch_size": 8,
    "train_micro_batch_size_per_gpu": 1,
    "gradient_accumulation_steps": 1,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    "steps_per_print": 10 ** 9,
    "bf16": {"enabled": True},
    "zero_optimization": {"stage": 3, "gather_chunks": 2},
}

# One-dimension search: deeper gather chunking earns a larger overlap
# credit on the same wire bytes, so this candidate must strictly win.
DIMS = [("zero", [Choice(
    "zero3_gather4",
    {"zero_optimization": {"stage": 3, "gather_chunks": 4}})])]


@pytest.fixture(scope="module")
def tuned():
    return tune(dict(BASE), dimensions=DIMS, platform="tpu_v5e")


# ---------------------------------------------------------------------------
# pure helpers
# ---------------------------------------------------------------------------

def test_deep_merge_is_recursive_and_non_mutating():
    base = {"a": {"x": 1, "y": 2}, "b": 3}
    out = deep_merge(base, {"a": {"y": 9, "z": 8}, "c": 7})
    assert out == {"a": {"x": 1, "y": 9, "z": 8}, "b": 3, "c": 7}
    assert base == {"a": {"x": 1, "y": 2}, "b": 3}


def test_default_dimensions_cover_the_issue_space():
    dims = dict(default_dimensions(BASE, world_size=8))
    assert {"zero", "fp8", "overlap", "batch", "remat", "scan"} <= \
        set(dims)
    zero_labels = {c.label for c in dims["zero"]}
    assert {"zero1", "zero2", "zero3_gather2",
            "zero3_gather4"} == zero_labels
    # batch choices keep micro x accum x world == the global batch
    for c in dims["batch"]:
        cfg = c.config
        assert (cfg["train_micro_batch_size_per_gpu"]
                * cfg["gradient_accumulation_steps"] * 8
                == cfg["train_batch_size"])
    # model-side knobs carry no engine-config overrides
    assert all(not c.config for c in dims["remat"] + dims["scan"])


# ---------------------------------------------------------------------------
# the search (module-scoped: two engine compiles total)
# ---------------------------------------------------------------------------

def test_tuned_config_strictly_beats_untuned_default(tuned):
    assert tuned.improved
    assert tuned.best.score < tuned.base.score
    assert tuned.best.label == "zero3_gather4"
    assert tuned.tuned_config["zero_optimization"]["gather_chunks"] == 4
    # untouched base keys survive the merge
    assert tuned.tuned_config["bf16"] == {"enabled": True}


def test_every_candidate_went_through_the_audit(tuned):
    # zero rule findings on the winner is the acceptance bar
    assert tuned.best.reject_reason is None
    assert tuned.best.findings == 0
    for cand in tuned.candidates:
        assert cand.reject_reason is None
        assert cand.cost is not None and cand.cost.ok


def test_result_serializes(tuned):
    d = tuned.to_dict()
    blob = json.loads(json.dumps(d))
    assert blob["improved"] is True
    assert blob["best"]["score"] < blob["base"]["score"]
    assert blob["candidates_total"] == 2


def test_expected_log_is_metrics_compatible(tuned, tmp_path):
    path = tmp_path / "expected.jsonl"
    n = write_expected_log(str(path), tuned, steps=4)
    assert n == 2 + 4   # run_start + compile + steps
    from deepspeed_tpu.telemetry.cli import summarize
    events = [json.loads(line) for line in path.read_text().splitlines()]
    assert all(e["schema"] == "ds-tpu-telemetry/1" for e in events)
    summary = summarize(events)
    assert summary["steps"] == 4
    assert summary["step_s"]["mean"] == pytest.approx(
        tuned.best.cost.step_seconds)
    # predicted events carry the winner's static facts
    comp = next(e for e in events if e["event"] == "compile")
    assert comp["static_peak_bytes"] == tuned.best.cost.peak_bytes
    assert comp["expected_step_s"] == tuned.best.cost.step_seconds


def test_expected_events_empty_when_nothing_scored(tuned):
    import copy
    broken = copy.deepcopy(tuned)
    broken.best.cost = None
    assert expected_events(broken) == []


# ---------------------------------------------------------------------------
# typed rejections
# ---------------------------------------------------------------------------

def test_build_error_is_typed_rejection():
    bad = deep_merge(BASE, {"zero_optimization": {"stage": 9}})
    res = evaluate_candidate(bad, {}, label="bad")
    assert res.reject_reason == REJECT_BUILD_ERROR
    assert res.reject_detail
    assert math.isinf(res.score)
    assert res.to_dict()["score"] is None


@pytest.mark.slow
def test_peak_budget_rejection_is_typed():
    res = evaluate_candidate(
        dict(BASE), {}, peak_budget_bytes=1, label="tiny-budget")
    assert res.reject_reason == REJECT_PEAK_MEMORY
    assert "budget" in res.reject_detail
    assert math.isinf(res.score)


# ---------------------------------------------------------------------------
# --serving: paged-KV serving knobs
# ---------------------------------------------------------------------------

def test_serving_dimensions_respect_engine_geometry():
    dims = dict(serving_dimensions(
        {"inference": {"prefill_chunk": 4, "seq_buckets": [16, 32]}}))
    assert set(dims) == set(SERVING_DIMENSION_NAMES)
    # page sizes are prefill-chunk multiples capped at the largest
    # bucket; park sweeps the host evacuation threshold
    assert [c.label for c in dims["page"]] == ["page4", "page8", "page16"]
    assert [c.label for c in dims["park"]] == ["park0", "park25", "park50"]
    big_chunk = dict(serving_dimensions(
        {"inference": {"prefill_chunk": 16, "seq_buckets": [16]}}))
    assert [c.label for c in big_chunk["page"]] == ["page16"]


def test_serving_contract_breaker_is_typed_rejection():
    """page_size 12 can't divide max_seq 32: the engine refuses to
    build and the tuner reports the typed rejection instead of scoring
    (or silently skipping) the point."""
    res = evaluate_serving_candidate(
        {"inference": {"page_size": 12}}, label="page12",
        dimension="page")
    assert res.reject_reason == REJECT_BUILD_ERROR
    assert "page_size" in res.reject_detail
    assert math.isinf(res.score)


@pytest.mark.slow
def test_serving_candidate_scores_through_the_paged_audit():
    res = evaluate_serving_candidate(
        {"inference": {"page_size": 8}}, label="page8",
        dimension="page")
    assert res.reject_reason is None
    assert res.findings == 0
    assert res.tokens > 0                     # max_batch tokens / step
    assert math.isfinite(res.score)
    assert res.cost.step_seconds > 0

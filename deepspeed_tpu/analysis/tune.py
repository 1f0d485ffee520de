"""`ds_tpu_tune`: config autotuner over the audit's exact-aval lowering.

DeepCompile's thesis (arxiv 2504.09983) is that the profile->transform
loop should be automatic. This module closes that loop for the discrete
config space the repo already exposes: every candidate is compiled
through the SAME lowering path the audit uses (`audit_engine` — exact
avals, full rule catalog), scored with the roofline cost model
(`analysis/cost.py`), and unsafe candidates are *rejected with a typed
reason*, never scored:

- ``candidate_build_error`` — the engine refused the config or the
  compile threw,
- ``audit_rule_findings`` — error-severity rule findings (donation
  regressions, dtype leaks, host transfers, ...),
- ``peak_memory_over_budget`` — the cost model's static-peak gate
  (`cost.REJECT_PEAK_MEMORY`).

Search strategy is greedy coordinate descent over named dimensions
(:func:`default_dimensions`): sweep one dimension at a time, keep the
best point so far, move on. That bounds compiles to the SUM of the
dimension sizes instead of their product — every compile is wall-clock
the tuner itself pays (the reason ``scan_layers`` exists), so the
default space stays ~15 candidates. A candidate only replaces the
incumbent when its score is STRICTLY lower, so ties keep the user's
base config.

Dimensions over the engine config: ZeRO stage {1,2,3} x
``gather_chunks``, fp8 wire+matmul on/off, ``tensor_parallel.overlap``
chunks/bidirectional, micro x accum via `solve_elastic_batch`. Two
model-side dimensions (remat policy, ``scan_layers``) apply to the toy
GPT-2 the CLI builds — they ride the report's ``model`` section rather
than the engine config JSON.

``--serving`` swaps the space and the evaluator: the dimensions become
the paged KV cache's knobs (``inference.page_size`` multiples of the
prefill chunk, ``inference.host_park_threshold``), and each candidate
compiles through `audit_decode`'s allocator-churn stream instead of a
train step — so a page size that breaks the 2-compile contract, lowers
a host transfer, or fails the engine's divisibility checks is rejected
with a typed reason, and the survivors are scored on the decode
program's roofline cost.

Outputs: the tuned config JSON (``--output``) and an expected-vs-
measured telemetry log (``--expected-log``) — synthetic ``compile`` +
``step`` events in the `ds-tpu-telemetry/1` schema carrying the
winner's predicted step seconds, so ``ds_tpu_metrics diff expected.jsonl
measured.jsonl`` quantifies the model's error once the TPU run exists.
"""

import argparse
import copy
import dataclasses
import json
import math
import os
import sys
import time
from typing import Optional

from deepspeed_tpu.analysis.cost import (PLATFORMS, REJECT_PEAK_MEMORY,
                                         estimate_step_cost,
                                         resolve_platform)

__all__ = ["REJECT_BUILD_ERROR", "REJECT_RULE_FINDINGS",
           "REJECT_PEAK_MEMORY", "Choice", "CandidateResult",
           "TuneResult", "deep_merge", "default_dimensions",
           "serving_dimensions", "build_toy_gpt2_engine",
           "evaluate_candidate", "evaluate_serving_candidate", "tune",
           "expected_events", "write_expected_log", "main"]

# Typed rejection reasons (cost.py owns REJECT_PEAK_MEMORY).
REJECT_BUILD_ERROR = "candidate_build_error"
REJECT_RULE_FINDINGS = "audit_rule_findings"

DIMENSION_NAMES = ("zero", "fp8", "overlap", "batch", "remat", "scan")
SERVING_DIMENSION_NAMES = ("page", "chunk", "batch", "park", "block")


def deep_merge(base, overrides):
    """Recursive dict merge returning a new dict (overrides win)."""
    out = copy.deepcopy(base)
    for key, val in overrides.items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = deep_merge(out[key], val)
        else:
            out[key] = copy.deepcopy(val)
    return out


@dataclasses.dataclass(frozen=True)
class Choice:
    """One point along a dimension: engine-config overrides plus
    model-side overrides (toy GPT-2 constructor kwargs)."""
    label: str
    config: dict = dataclasses.field(default_factory=dict)
    model: dict = dataclasses.field(default_factory=dict)


def default_dimensions(base_config, world_size=1):
    """The stock search space: ``[(dimension_name, [Choice, ...])]``.

    Every dimension includes the "leave it alone" point implicitly (the
    incumbent is always a candidate), so choices here are pure
    overrides of the current best config.
    """
    from deepspeed_tpu.runtime.elastic.batch import solve_elastic_batch

    zero = [
        Choice("zero1", {"zero_optimization": {"stage": 1}}),
        Choice("zero2", {"zero_optimization": {"stage": 2}}),
        Choice("zero3_gather2",
               {"zero_optimization": {"stage": 3, "gather_chunks": 2}}),
        Choice("zero3_gather4",
               {"zero_optimization": {"stage": 3, "gather_chunks": 4}}),
    ]
    fp8 = [
        Choice("fp8_wire_matmul",
               {"fp8": {"enabled": True,
                        "wire": {"enabled": True,
                                 "dtype": "f8e4m3fn"}}}),
    ]
    overlap = [
        Choice("overlap_off",
               {"tensor_parallel": {"overlap": {"enabled": False}}}),
        Choice("overlap_chunks2",
               {"tensor_parallel": {"overlap": {"enabled": True,
                                                "chunks": 2}}}),
        Choice("overlap_chunks4",
               {"tensor_parallel": {"overlap": {"enabled": True,
                                                "chunks": 4}}}),
        Choice("overlap_chunks4_bidir",
               {"tensor_parallel": {"overlap": {"enabled": True,
                                                "chunks": 4,
                                                "bidirectional": True}}}),
    ]
    batch = []
    target = int(base_config.get("train_batch_size", 8))
    seen = set()
    for accum in (1, 2, 4):
        try:
            plan = solve_elastic_batch(target, world_size,
                                       prefer_accum=accum)
        except Exception:
            continue
        key = (plan.micro_batch, plan.grad_accum)
        if key in seen or not plan.exact:
            continue
        seen.add(key)
        batch.append(Choice(
            f"micro{plan.micro_batch}_accum{plan.grad_accum}",
            {"train_batch_size": plan.global_batch,
             "train_micro_batch_size_per_gpu": plan.micro_batch,
             "gradient_accumulation_steps": plan.grad_accum}))
    remat = [
        Choice("remat_off", model={"remat": False}),
        Choice("remat_dots", model={"remat": True,
                                    "remat_policy": "dots"}),
        Choice("remat_full", model={"remat": True,
                                    "remat_policy": "full"}),
    ]
    scan = [
        Choice("scan_layers", model={"scan_layers": True}),
    ]
    dims = [("zero", zero), ("fp8", fp8), ("overlap", overlap),
            ("batch", batch), ("remat", remat), ("scan", scan)]
    return [(name, choices) for name, choices in dims if choices]


def serving_dimensions(base_config):
    """The ``--serving`` search space: paged-KV knobs over the config's
    ``inference`` block.

    ``page`` sweeps page_size as multiples of the prefill chunk (only
    multiples can keep prefill chunk-aligned; a size that doesn't also
    divide the largest seq bucket is still offered — the engine rejects
    it at build and the tuner reports the typed rejection instead of
    silently skipping the point). ``park`` sweeps the host-RAM
    evacuation threshold: 0 never parks to host, higher values trade
    host-copy wall for device pages under session churn. ``block``
    sweeps the flash-decode ``attention_block_k`` (the engine clamps it
    to the page size and rejects non-divisors at build, so an
    incompatible pairing comes back as a typed rejection): smaller
    blocks elide dead-cache DMAs at finer granularity — visible to the
    score only because `evaluate_serving_candidate` prices kernel HBM
    traffic from the analyzer's elision-aware DMA bytes.

    ``chunk`` sweeps ``prefill_chunk`` — the disaggregated prefill
    tier's unit of work AND the page-size alignment quantum, so a
    chunk that no longer divides the candidate's page size (or exceeds
    a bucket) is engine-rejected and surfaces as a typed
    ``candidate_build_error``, never a silent skip. ``batch`` sweeps
    decode ``max_batch``: more concurrent rows amortize weight
    streaming per token but multiply the KV pool pressure; with
    disaggregated tiers (ISSUE 20) these two dimensions are exactly
    the per-tier sizing knobs (``prefill_chunk`` for the prefill tier,
    ``max_batch`` for the decode tier).
    """
    inf = base_config.get("inference") or {}
    pc = int(inf.get("prefill_chunk", 4))
    buckets = inf.get("seq_buckets") or (16, 32)
    max_seq = max(int(b) for b in buckets)
    page = [Choice(f"page{pc * mult}",
                   {"inference": {"page_size": pc * mult}})
            for mult in (1, 2, 4) if pc * mult <= max_seq]
    chunk = [Choice(f"chunk{c}",
                    {"inference": {"prefill_chunk": c}})
             for c in (2, 4, 8) if c <= max_seq]
    batch = [Choice(f"batch{b}",
                    {"inference": {"max_batch": b}})
             for b in (1, 2, 4)]
    park = [Choice(f"park{int(t * 100)}",
                   {"inference": {"host_park_threshold": t}})
            for t in (0.0, 0.25, 0.5)]
    block = [Choice(f"blk{bk}",
                    {"inference": {"attention_block_k": bk}})
             for bk in (2, 4, 8) if bk <= max_seq]
    dims = [("page", page), ("chunk", chunk), ("batch", batch),
            ("park", park), ("block", block)]
    return [(name, choices) for name, choices in dims if choices]


# ---------------------------------------------------------------------------
# candidate evaluation (build -> audit -> cost)
# ---------------------------------------------------------------------------

def build_toy_gpt2_engine(config, model_overrides=None):
    """``(engine, batch)`` for one candidate: toy GPT-2 supplies the
    model/loss (the ``ds_tpu_audit --config`` convention); the tuner's
    model-side knobs (``remat``/``remat_policy``/``scan_layers``) are
    `GPT2Config` kwargs."""
    import jax
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (GPT2LMHead, gpt2_tiny,
                                           init_gpt2_params,
                                           make_gpt2_loss_fn)

    model = GPT2LMHead(gpt2_tiny(**(model_overrides or {})))
    params = init_gpt2_params(model, jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=copy.deepcopy(config),
        loss_fn=make_gpt2_loss_fn(model), params=params)
    rows = int(config.get("train_batch_size", 8))
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 255, (rows, 32)).astype(np.int32)}
    return engine, batch


def _batch_tokens(batch):
    for leaf in batch.values():
        size = getattr(leaf, "size", None)
        if size:
            return int(size)
    return 0


@dataclasses.dataclass
class CandidateResult:
    label: str
    dimension: str
    config: dict
    model: dict
    reject_reason: Optional[str] = None
    reject_detail: str = ""
    flavor: str = ""
    findings: int = 0
    tokens: int = 0
    cost: object = None              # cost.StepCost when scored
    collective_bytes_by_dtype: dict = dataclasses.field(
        default_factory=dict)
    audit_wall_s: float = 0.0

    @property
    def score(self):
        if self.reject_reason or self.cost is None:
            return math.inf
        return self.cost.score

    def to_dict(self):
        return {
            "label": self.label,
            "dimension": self.dimension,
            "config": self.config,
            "model": self.model,
            "ok": self.reject_reason is None,
            "reject_reason": self.reject_reason,
            "reject_detail": self.reject_detail,
            "flavor": self.flavor,
            "findings": self.findings,
            "score": None if math.isinf(self.score) else self.score,
            "cost": self.cost.to_dict() if self.cost is not None else None,
            "audit_wall_s": self.audit_wall_s,
        }


def evaluate_candidate(config, model_overrides, *, build=None,
                       platform="tpu_v5e", peak_budget_bytes=None,
                       rules=None, label="candidate", dimension="base"):
    """Compile one candidate through the audit path and score it.

    Never raises for a bad candidate: build/compile failures and
    error-severity rule findings come back as typed rejections so the
    search can report *why* a point dropped out.
    """
    import jax
    from deepspeed_tpu.analysis.audit import audit_engine
    from deepspeed_tpu.analysis.rules import SEV_ERROR

    build = build or build_toy_gpt2_engine
    res = CandidateResult(label=label, dimension=dimension,
                          config=config, model=dict(model_overrides or {}))
    t0 = time.perf_counter()
    try:
        engine, batch = build(config, model_overrides)
        report = audit_engine(engine, batch, rules=rules)
    except Exception as exc:
        res.reject_reason = REJECT_BUILD_ERROR
        res.reject_detail = f"{type(exc).__name__}: {exc}"
        res.audit_wall_s = round(time.perf_counter() - t0, 3)
        return res
    res.audit_wall_s = round(time.perf_counter() - t0, 3)
    res.flavor = report.flavor
    res.findings = len(report.findings)
    res.tokens = _batch_tokens(batch)
    res.collective_bytes_by_dtype = \
        report.stats.get("collective_bytes_by_dtype") or {}
    errors = [f for f in report.findings if f.severity == SEV_ERROR]
    if errors:
        res.reject_reason = REJECT_RULE_FINDINGS
        res.reject_detail = "; ".join(
            f"{f.rule}: {f.message}" for f in errors[:4])
        return res
    sites = (report.stats.get("jaxpr") or {}).get("collective_sites") or []
    n_devices = getattr(engine.mesh, "size", None) or jax.device_count()
    cost = estimate_step_cost(
        report.hlo_text, n_devices=n_devices, platform=platform,
        collective_sites=sites, peak_budget_bytes=peak_budget_bytes)
    res.cost = cost
    if cost.reject_reason:
        res.reject_reason = cost.reject_reason
        res.reject_detail = (
            f"static peak {cost.peak_bytes} B > budget "
            f"{cost.peak_budget_bytes} B")
    return res


def evaluate_serving_candidate(config, model_overrides=None, *,
                               build=None, platform="tpu_v5e",
                               peak_budget_bytes=None, rules=None,
                               label="candidate", dimension="base"):
    """Compile one serving candidate through ``audit_decode``'s
    allocator-churn stream and score its decode program.

    The candidate's ``inference`` block configures the engine; the
    full rule catalog runs over the post-churn decode HLO,
    so a page_size that breaks the 2-compile contract or lowers a host
    transfer comes back as a typed rejection, never a score. The audit
    runs with ``kernels=True``, so the score includes the decode
    kernel's HBM time priced from the analyzer's elision-aware DMA
    bytes — which is what lets the ``block`` dimension rank
    ``attention_block_k`` on real traffic (dense operand sizes are
    identical across block sizes). Drop-in for
    :func:`evaluate_candidate` in the greedy driver
    (``model_overrides``/``build`` are accepted and ignored).
    """
    from deepspeed_tpu.analysis.audit import audit_decode
    from deepspeed_tpu.analysis.rules import SEV_ERROR

    inf = dict(config.get("inference") or {})
    res = CandidateResult(label=label, dimension=dimension,
                          config=config, model={})
    t0 = time.perf_counter()
    try:
        report = audit_decode(config_overrides=inf, rules=rules,
                              kernels=True)
    except Exception as exc:
        res.reject_reason = REJECT_BUILD_ERROR
        res.reject_detail = f"{type(exc).__name__}: {exc}"
        res.audit_wall_s = round(time.perf_counter() - t0, 3)
        return res
    res.audit_wall_s = round(time.perf_counter() - t0, 3)
    res.flavor = report.flavor
    res.findings = len(report.findings)
    # one decode step produces max_batch tokens — the serving analog of
    # the train step's batch tokens for the cost model's per-token view
    res.tokens = int((report.stats.get("cache") or {}).get(
        "max_batch", 0))
    res.collective_bytes_by_dtype = \
        report.stats.get("collective_bytes_by_dtype") or {}
    errors = [f for f in report.findings if f.severity == SEV_ERROR]
    if errors:
        res.reject_reason = REJECT_RULE_FINDINGS
        res.reject_detail = "; ".join(
            f"{f.rule}: {f.message}" for f in errors[:4])
        return res
    kstats = report.stats.get("kernels") or {}
    kernel_facts = [
        {"name": name, "dma_bytes": kd.get("dma_bytes", 0),
         "dense_bytes": kd.get("dense_bytes", 0)}
        for name, kd in (kstats.get("kernels") or {}).items()]
    cost = estimate_step_cost(
        report.hlo_text, n_devices=1, platform=platform,
        peak_budget_bytes=peak_budget_bytes,
        kernel_facts=kernel_facts)
    res.cost = cost
    if cost.reject_reason:
        res.reject_reason = cost.reject_reason
        res.reject_detail = (
            f"static peak {cost.peak_bytes} B > budget "
            f"{cost.peak_budget_bytes} B")
    return res


# ---------------------------------------------------------------------------
# the greedy search driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TuneResult:
    platform: str
    base: CandidateResult
    best: CandidateResult
    candidates: list
    skipped: int = 0

    @property
    def improved(self):
        """True when the winner STRICTLY beats the untuned base."""
        return self.best.score < self.base.score

    @property
    def tuned_config(self):
        return self.best.config

    @property
    def model_overrides(self):
        return self.best.model

    def to_dict(self):
        return {
            "platform": self.platform,
            "improved": self.improved,
            "base": self.base.to_dict(),
            "best": self.best.to_dict(),
            "tuned_config": self.tuned_config,
            "model_overrides": self.model_overrides,
            "candidates": [c.to_dict() for c in self.candidates],
            "candidates_total": len(self.candidates),
            "skipped": self.skipped,
        }


def tune(base_config, *, build=None, dimensions=None, platform="tpu_v5e",
         peak_budget_bytes=None, rules=None, max_candidates=0, log=None,
         evaluate=None):
    """Greedy coordinate-descent search (see module docstring).

    ``dimensions`` defaults to :func:`default_dimensions`;
    ``max_candidates`` (0 = unbounded) caps compiles after the base.
    ``evaluate`` swaps the candidate evaluator (default
    :func:`evaluate_candidate`; ``--serving`` passes
    :func:`evaluate_serving_candidate`). Returns a :class:`TuneResult`;
    the base config itself is always the first candidate, so
    ``result.improved`` compares against it.
    """
    import jax

    platform = resolve_platform(platform)
    say = log or (lambda msg: None)
    evaluate = evaluate or evaluate_candidate
    if dimensions is None:
        dimensions = default_dimensions(base_config, jax.device_count())

    say(f"tune: base config on platform {platform.name}")
    base = evaluate(
        base_config, {}, build=build, platform=platform,
        peak_budget_bytes=peak_budget_bytes, rules=rules,
        label="base", dimension="base")
    results = [base]
    best = base
    seen = {json.dumps([base_config, {}], sort_keys=True)}
    skipped = 0
    for dim_name, choices in dimensions:
        for choice in choices:
            cand_cfg = deep_merge(best.config, choice.config)
            cand_model = {**best.model, **choice.model}
            key = json.dumps([cand_cfg, cand_model], sort_keys=True)
            if key in seen:
                continue
            if max_candidates and len(results) > max_candidates:
                skipped += 1
                continue
            seen.add(key)
            res = evaluate(
                cand_cfg, cand_model, build=build, platform=platform,
                peak_budget_bytes=peak_budget_bytes, rules=rules,
                label=choice.label, dimension=dim_name)
            results.append(res)
            if res.reject_reason:
                say(f"tune: [{dim_name}] {choice.label} rejected "
                    f"({res.reject_reason})")
            else:
                say(f"tune: [{dim_name}] {choice.label} score "
                    f"{res.score * 1e6:.2f}us")
            if res.score < best.score:
                best = res
                say(f"tune: [{dim_name}] {choice.label} is the new best")
    if skipped:
        say(f"tune: --max-candidates dropped {skipped} candidate(s) "
            "unevaluated")
    return TuneResult(platform=platform.name, base=base, best=best,
                      candidates=results, skipped=skipped)


# ---------------------------------------------------------------------------
# expected-vs-measured report (ds_tpu_metrics diff-compatible)
# ---------------------------------------------------------------------------

def expected_events(result, steps=8):
    """Synthetic telemetry events predicting the winner's run: one
    ``compile`` event with the static facts + ``steps`` identical
    ``step`` events at the predicted wall. Schema `ds-tpu-telemetry/1`,
    so ``ds_tpu_metrics diff expected.jsonl measured.jsonl`` reports
    prediction error directly. (Phase names here are the cost model's
    compute/interconnect split, not the runtime's span names — the
    step-time rows are the comparable ones.)"""
    from deepspeed_tpu.telemetry.events import SCHEMA_VERSION

    best = result.best
    cost = best.cost
    if cost is None:
        return []
    now = time.time()
    tokens = best.tokens
    fpt = (cost.flops / tokens) if tokens else 0
    events = [{
        "schema": SCHEMA_VERSION, "event": "run_start", "t": now,
        "source": "ds_tpu_tune", "flavor": best.flavor,
        "platform": result.platform,
    }, {
        "schema": SCHEMA_VERSION, "event": "compile", "t": now,
        "source": "ds_tpu_tune", "flavor": best.flavor,
        "flops_per_token": fpt,
        "batch_tokens": tokens,
        "collective_bytes_by_dtype": best.collective_bytes_by_dtype,
        "static_peak_bytes": cost.peak_bytes,
        "expected_step_s": cost.step_seconds,
        "kernel_dma_bytes": cost.kernel_dma_bytes,
        "kernel_dense_bytes": cost.kernel_dense_bytes,
    }]
    for i in range(steps):
        events.append({
            "schema": SCHEMA_VERSION, "event": "step", "t": now,
            "source": "ds_tpu_tune", "flavor": best.flavor,
            "step": i, "wall_s": cost.step_seconds, "tokens": tokens,
            "phases": {
                "compute": cost.compute_seconds,
                "interconnect": cost.exposed_interconnect_seconds,
            },
        })
    return events


def write_expected_log(path, result, steps=8):
    events = expected_events(result, steps=steps)
    with open(path, "w") as f:
        for evt in events:
            f.write(json.dumps(evt, sort_keys=True) + "\n")
    return len(events)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _format_text(result):
    lines = [f"tune: platform {result.platform}, "
             f"{len(result.candidates)} candidate(s) compiled through "
             "the audit path"]
    head = (f"{'candidate':28s}{'dim':10s}{'score_us':>10s}"
            f"{'wire_MB':>9s}{'peak_MB':>9s}  status")
    lines.append(head)
    for res in result.candidates:
        if res.cost is not None:
            score = "inf" if math.isinf(res.score) \
                else f"{res.score * 1e6:.2f}"
            wire = f"{res.cost.wire_bytes / (1 << 20):.2f}"
            peak = f"{res.cost.peak_bytes / (1 << 20):.2f}"
        else:
            score = wire = peak = "-"
        status = res.reject_reason or (
            "best" if res is result.best else "ok")
        lines.append(f"{res.label:28s}{res.dimension:10s}{score:>10s}"
                     f"{wire:>9s}{peak:>9s}  {status}")
    if result.improved:
        gain = (1.0 - result.best.score / result.base.score) * 100.0
        lines.append(
            f"winner: {result.best.label} — score "
            f"{result.best.score * 1e6:.2f}us, "
            f"{gain:.1f}% below the base config "
            f"({result.base.score * 1e6:.2f}us), "
            f"{result.best.findings} rule finding(s)")
    else:
        lines.append("winner: base config (no candidate strictly "
                     "improved the cost-model score)")
    if result.best.model:
        lines.append(f"model overrides (apply to the model ctor): "
                     f"{json.dumps(result.best.model, sort_keys=True)}")
    return "\n".join(lines)


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="ds_tpu_tune",
        description="Search overlap/fp8/ZeRO/batch/remat/scan config "
                    "space: compile each candidate through the audit's "
                    "exact-aval lowering (rule findings reject it), "
                    "score with the roofline cost model, emit the tuned "
                    "config.")
    parser.add_argument("--config", required=True,
                        help="base DeepSpeed-style JSON config (the "
                             "untuned default being beaten)")
    parser.add_argument("--platform", default=None,
                        help="cost-model constants table to use "
                             f"(known: {sorted(PLATFORMS)}; default: "
                             "the config's analysis.platform, else "
                             "tpu_v5e)")
    parser.add_argument("--serving", action="store_true",
                        help="tune the paged serving engine instead of "
                             "the train step: sweep inference.page_size "
                             "and host_park_threshold, each candidate "
                             "compiled through the ds_tpu_audit decode "
                             "churn stream (contract-breaking configs "
                             "are rejected, not scored)")
    parser.add_argument("--dimensions", default=None,
                        help="comma-separated subset of the search "
                             f"dimensions (default: all of "
                             f"{list(DIMENSION_NAMES)}; with --serving: "
                             f"{list(SERVING_DIMENSION_NAMES)})")
    parser.add_argument("--peak-budget-mb", type=float, default=None,
                        help="reject candidates whose static peak "
                             "exceeds this budget (default: "
                             "analysis.peak_memory_budget_mb from the "
                             "config, if set)")
    parser.add_argument("--max-candidates", type=int, default=0,
                        help="cap on candidate compiles after the base "
                             "(0 = unbounded)")
    parser.add_argument("--output", default=None, metavar="FILE",
                        help="write the tuned config JSON here")
    parser.add_argument("--expected-log", default=None, metavar="FILE",
                        help="write the ds_tpu_metrics-compatible "
                             "expected-run JSONL here")
    parser.add_argument("--metrics-steps", type=int, default=8,
                        help="synthetic step events in --expected-log "
                             "(default 8)")
    parser.add_argument("--compilation-cache-dir", default=None,
                        metavar="DIR",
                        help="persistent XLA compile cache for every "
                             "candidate (reruns become cache hits)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="emit the full JSON report instead of text")
    args = parser.parse_args(argv)

    # Candidate compiles read compile-time artifacts; default to the CPU
    # backend with an 8-device virtual mesh (the ds_tpu_audit setup) so
    # tuning runs anywhere. Must happen before jax import.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if "cpu" in os.environ.get("JAX_PLATFORMS", "") \
            and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()

    try:
        with open(args.config) as f:
            base_config = json.load(f)
    except (OSError, ValueError) as exc:
        parser.error(f"cannot read --config: {exc}")
    if not isinstance(base_config, dict):
        parser.error("--config must hold a JSON object")

    platform_name = args.platform or \
        (base_config.get("analysis") or {}).get("platform") or "tpu_v5e"
    try:
        platform = resolve_platform(platform_name)
    except ValueError as exc:
        parser.error(str(exc))

    import jax

    if args.compilation_cache_dir:
        base_config = deep_merge(
            base_config,
            {"compilation_cache_dir": args.compilation_cache_dir})
        # toy candidates compile in well under the persistence
        # threshold; cache them anyway so tuner reruns are hits
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)

    known_names = SERVING_DIMENSION_NAMES if args.serving \
        else DIMENSION_NAMES
    if args.serving:
        dimensions = serving_dimensions(base_config)
    else:
        dimensions = None
    if args.dimensions:
        wanted = [d.strip() for d in args.dimensions.split(",")
                  if d.strip()]
        unknown = sorted(set(wanted) - set(known_names))
        if unknown:
            parser.error(f"unknown dimension(s) {unknown}; known: "
                         f"{list(known_names)}")
        stock = dict(serving_dimensions(base_config)) if args.serving \
            else dict(default_dimensions(base_config,
                                         jax.device_count()))
        dimensions = [(name, stock[name]) for name in wanted
                      if name in stock]

    peak_budget_bytes = None
    if args.peak_budget_mb:
        peak_budget_bytes = int(args.peak_budget_mb * (1 << 20))
    else:
        analysis_cfg = base_config.get("analysis") or {}
        budget_mb = analysis_cfg.get("peak_memory_budget_mb") or 0
        if budget_mb:
            peak_budget_bytes = int(float(budget_mb) * (1 << 20))

    result = tune(base_config, dimensions=dimensions, platform=platform,
                  peak_budget_bytes=peak_budget_bytes,
                  max_candidates=args.max_candidates,
                  log=lambda msg: print(msg, file=sys.stderr),
                  evaluate=evaluate_serving_candidate if args.serving
                  else None)

    if args.output:
        with open(args.output, "w") as f:
            json.dump(result.tuned_config, f, indent=2, sort_keys=True)
            f.write("\n")
    if args.expected_log:
        write_expected_log(args.expected_log, result,
                           steps=args.metrics_steps)

    if args.as_json:
        from deepspeed_tpu.telemetry.events import SCHEMA_VERSION
        print(json.dumps({"schema": SCHEMA_VERSION,
                          **result.to_dict()},
                         indent=2, sort_keys=True))
    else:
        print(_format_text(result))
    # 0: a scoreable winner exists (tuned or base); 1: nothing scored.
    return 0 if not math.isinf(result.best.score) else 1


if __name__ == "__main__":
    sys.exit(main())

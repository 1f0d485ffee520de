"""Audit orchestrator: lower a compiled step, run the rule catalog.

Three entry points, layered:

- ``audit_hlo(hlo_text, **ctx)`` — rules over HLO text you already have.
- ``audit_engine(engine, batch)`` — lower a live engine's compiled train
  step (any flavor: dense, ZeRO-1/2/3, offload, quantized, onebit,
  pipeline), build the :class:`StepContext` from the engine's own
  config, and run the catalog plus the recompile detector.
- ``audit_flavors(...)`` — build toy engines for the stock step flavors
  and audit each; backs ``bin/ds_tpu_audit`` and the zero-findings pins
  in ``tests/unit/test_audit_rules.py``.

``donated_jit`` is the declaration side of the donation audit: the
engine's step factories jit through it so the *declared*
``donate_argnums`` ride on the compiled callable
(``_ds_donate_argnums``) where the audit can diff them against the
executable's actual ``input_output_alias`` map.
"""

import dataclasses
import json
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.parallel.collectives import record_collective_sites

from deepspeed_tpu.analysis.hlo import (
    aliased_param_numbers,
    collective_bytes,
    estimate_peak_memory,
    ring_send_bytes,
    while_loops,
)
from deepspeed_tpu.analysis.jaxpr import (
    check_divergent_collectives,
    check_unordered_permutes,
    input_specs_of,
    propagate_partition_specs,
    trace_jaxpr,
)
from deepspeed_tpu.analysis.rules import (
    SEV_ERROR,
    Finding,
    StepContext,
    run_rules,
)

# The engine's seven stock compiled-step flavors, auditable end-to-end.
STEP_FLAVORS = ("dense", "zero1", "zero2", "zero3", "offload", "quantized",
                "pipeline")
# Extra toy flavors the CLI accepts but the default sweep (and the
# un-slow flavor test matrix) skips — heavier compiles exercising
# specific subsystems. `pipeline_tp` runs pipe x model x data with
# tensor_parallel.overlap on, driving the overlap rule end-to-end;
# `fp8` runs GPT-2-tiny with fp8 delayed-scaling matmuls + the
# quantized ZeRO-3 gather wire, driving the fp8 rule end-to-end;
# `decode` runs the serving engine (`inference/`) through a scripted
# continuous-batching stream across two seq buckets and audits the
# compiled decode program: zero in-loop recompiles, cache-dtype
# hygiene, and donation of the KV page pool.
# `speculative` drives the self-speculative serving engine
# (`inference/speculative.py`) through the same churn stream
# and audits the pinned three-program contract (prefill /
# draft / verify, plain decode at zero entries), the draft-truncation
# flop ratio, accept-loop invariants, and host-transfer hygiene of the
# draft and verify programs.
# `disagg` builds one prefill-tier and one decode-tier engine
# (heterogeneous max_batch), streams requests through the synchronous
# disaggregation coordinator, and audits the ONE-program-per-tier
# compile pins, the cross-tier handoff geometry, and host-transfer
# hygiene of the decode tier's steady-state program.
EXTRA_FLAVORS = ("pipeline_tp", "fp8", "decode", "speculative",
                 "disagg")


class AuditError(RuntimeError):
    """Raised by the engine when ``analysis.fail_on_findings`` is set and
    the compile-time audit found error-severity findings."""

    def __init__(self, report):
        super().__init__(report.to_text())
        self.report = report


def donated_jit(fn, donate_argnums=()):
    """``jax.jit`` that records its declared donations on the wrapper.

    The stamp (``_ds_donate_argnums``) makes the engine's donation
    *intent* machine-readable so the donation audit can diff it against
    the compiled executable's actual input/output aliasing — a plain
    ``jax.jit`` call site that silently drops ``donate_argnums`` loses
    the stamp too, which the audit reports as un-donated state."""
    jitted = jax.jit(fn, donate_argnums=tuple(donate_argnums))
    try:
        jitted._ds_donate_argnums = tuple(donate_argnums)
    except Exception:  # pragma: no cover - jit wrappers accept attrs today
        pass
    return jitted


@dataclass
class AuditReport:
    flavor: str
    findings: list
    stats: dict = field(default_factory=dict)
    # compiled HLO text of the audited step — kept off to_dict()/to_json()
    # (it can be megabytes); the autotuner's cost model reads it.
    hlo_text: str = field(default="", repr=False)

    @property
    def ok(self):
        """No error-severity findings (warnings don't fail a run)."""
        return all(f.severity != SEV_ERROR for f in self.findings)

    def to_dict(self):
        return {"flavor": self.flavor, "ok": self.ok,
                "findings": [f.to_dict() for f in self.findings],
                "stats": self.stats}

    def to_json(self, indent=2):
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    def to_text(self):
        lines = [f"[{self.flavor}] "
                 + ("OK — no findings" if not self.findings else
                    f"{len(self.findings)} finding(s)")]
        cb = self.stats.get("collective_bytes") or {}
        if cb:
            vols = ", ".join(f"{op} {b / 1e6:.2f}MB"
                             for op, b in sorted(cb.items())
                             if op != "total")
            lines.append(f"  collectives/step (trip-aware): "
                         f"{vols or 'none'}; total {cb.get('total', 0) / 1e6:.2f}MB")
        if "donated_expected" in self.stats:
            lines.append(
                f"  donation: {self.stats.get('donated_aliased', 0)}"
                f"/{self.stats['donated_expected']} donated buffers aliased")
        if "while_loops" in self.stats:
            n = self.stats["while_loops"]
            unknown = self.stats.get("unknown_trip_counts", 0)
            lines.append(f"  loops: {n} while loop(s), "
                         + ("all trip counts known" if not unknown
                            else f"{unknown} with UNKNOWN trip count"))
        if "compile_cache_size" in self.stats:
            lines.append(f"  recompiles: cache size "
                         f"{self.stats['compile_cache_size']} after "
                         f"{self.stats.get('steps_run', 0)} step(s)")
        ks = self.stats.get("kernels") or {}
        for kname, kd in (ks.get("kernels") or {}).items():
            lines.append(
                f"  kernel {kname}: grid {tuple(kd['grid'])}, "
                f"VMEM {kd['vmem_bytes'] / 1024:.1f}KB / "
                f"{ks.get('vmem_budget_bytes', 0) / (1 << 20):.0f}MB, "
                f"elided DMA {kd['elided_dma_fraction']:.1%}")
        if ks.get("expected_elision") is not None:
            lines.append(f"  elision contract: >= "
                         f"{ks['expected_elision']:.1%} proven")
        for f in self.findings:
            lines.append(f"  - [{f.severity}] {f.rule}: {f.message}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# lowering and context extraction
# ---------------------------------------------------------------------------

def _lower_step(fn, args):
    """Lower+compile a jitted step; map declared donations through arg
    flattening and unused-arg pruning onto HLO entry-parameter numbers.

    Returns ``(hlo_text, expected_donated_params, donated_param_info)``.
    ``args_info`` carries per-flat-leaf donation flags; the executable's
    ``_kept_var_idx`` says which flat leaves survived pruning (HLO
    parameter i is the i-th kept leaf). Pruned leaves never reach the
    executable so they are no HBM concern and drop out of the
    expectation.
    """
    lowered = fn.lower(*args)
    compiled = lowered.compile()
    hlo_text = compiled.as_text()
    info_leaves = jax.tree_util.tree_leaves(
        lowered.args_info, is_leaf=lambda x: hasattr(x, "donated"))
    kept = getattr(getattr(compiled, "_executable", None),
                   "_kept_var_idx", None)
    kept = sorted(kept) if kept is not None else range(len(info_leaves))
    expected, pinfo = set(), {}
    for hlo_param, flat_idx in enumerate(kept):
        if flat_idx >= len(info_leaves):
            continue
        leaf = info_leaves[flat_idx]
        if not getattr(leaf, "donated", False):
            continue
        expected.add(hlo_param)
        shape = tuple(getattr(leaf, "shape", ()) or ())
        dtype = getattr(leaf, "dtype", None)
        itemsize = np.dtype(dtype).itemsize if dtype is not None else 1
        nbytes = int(np.prod(shape, dtype=np.int64)) * itemsize \
            if shape else itemsize
        pinfo[hlo_param] = {"shape": list(shape), "dtype": str(dtype),
                            "bytes": nbytes}
    return hlo_text, expected, pinfo


def _engine_flavor(engine):
    cfg = engine._config
    if getattr(engine.loss_fn, "direct_value_and_grad", None) is not None:
        return "pipeline"
    if engine._offload:
        return "offload"
    if cfg.comm_quantization.enabled:
        return "quantized"
    if engine.optimizer_name == "onebitadam" or \
            (engine.optimizer_name or "").lower() == "onebitadam":
        return "onebit"
    if engine.sparse_gradients_enabled():
        return "sparse"
    fp8 = getattr(cfg, "fp8", None)
    if fp8 is not None and (fp8.enabled or fp8.wire_enabled):
        return "fp8"
    stage = engine.zero_optimization_stage()
    return f"zero{stage}" if stage else "dense"


def _engine_fn_args(engine, placed, rng, lr):
    """The compiled step callable and the exact lowering argument list —
    mirrors ``train_batch``'s call so ``lower()`` hits the jit cache."""
    step = engine._compiled_train_step
    fn = getattr(step, "inner", step)
    if engine._offload:
        args = [engine.params, engine.device_state, placed, rng, lr]
    else:
        args = [engine.params, engine.opt_state, engine.device_state,
                placed, rng, lr]
        if getattr(step, "fp8", False):
            # fp8 amax-state threading; discovery is idempotent, so an
            # audit that lowers before the first step call allocates it.
            args.append(engine._ensure_fp8_state(placed, rng))
        elif hasattr(step, "inner"):   # error-feedback residual threading
            args.append(engine._qcomm_residuals)
    if engine._fault_arg:
        args.append(jnp.asarray(1.0))
    return fn, tuple(args)


def _jaxpr_facts(fn, args):
    """Trace-time facts for the rule catalog: the three jaxpr passes
    over the step's closed jaxpr (a retrace, never a compile). Returns
    ``{divergent, unordered, reshard_events}`` — or all-None on a trace
    failure, which downgrades the trace-time rules to not-run rather
    than failing the whole audit."""
    try:
        with record_collective_sites() as sites:
            closed = trace_jaxpr(fn, args)
        divergent = check_divergent_collectives(closed)
        unordered = check_unordered_permutes(closed)
        _, events = propagate_partition_specs(closed,
                                              input_specs_of(args))
    except Exception as exc:  # pragma: no cover - defensive
        return {"divergent": None, "unordered": None,
                "reshard_events": None, "collective_sites": None,
                "trace_error": str(exc)}
    return {
        "divergent": divergent,
        "unordered": unordered,
        "reshard_events": [
            {"kind": e.kind, "primitive": e.primitive,
             "path": list(e.path), "dim": e.dim, "bytes": e.bytes,
             "specs": [list(s) for s in e.specs]}
            for e in events],
        "collective_sites": [dataclasses.asdict(s) for s in sites],
    }


def _replicated_state_leaves(engine):
    """Large optimizer-state leaves placed fully replicated — under
    ZeRO >= 1 these mean the partition spec never attached (the
    resharding rule sizes and reports them)."""
    if engine._offload or engine.opt_state is None:
        return []
    leaves = []
    flat, _ = jax.tree_util.tree_flatten_with_path(engine.opt_state)
    for path, leaf in flat:
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        if spec is None or any(e is not None for e in tuple(spec)):
            continue
        nbytes = int(getattr(leaf, "nbytes", 0) or 0)
        leaves.append({"path": jax.tree_util.keystr(path),
                       "bytes": nbytes,
                       "shape": list(getattr(leaf, "shape", ()))})
    return leaves


def _engine_context(engine, hlo_text, expected, pinfo, jaxpr_facts=None):
    cfg = engine._config
    dtype = engine.compute_dtype
    compute = ("bf16" if dtype == jnp.bfloat16 else
               "f16" if dtype == jnp.float16 else "f32")
    param_bytes = sum(
        int(np.prod(l.shape, dtype=np.int64)) * 4
        for l in jax.tree_util.tree_leaves(engine.params))
    flavor = _engine_flavor(engine)
    skip = set()
    if flavor in ("onebit", "sparse"):
        # Both replace the gradient exchange with their own compressed /
        # CSR wire formats — the generic ZeRO/dtype budgets don't model
        # them (their exact ratios are pinned by dedicated tests).
        skip |= {"zero_budget", "dtype_hygiene"}
    step = engine._compiled_train_step
    declared = getattr(getattr(step, "inner", step),
                       "_ds_donate_argnums", None)
    tp = getattr(cfg, "tensor_parallel", None)
    facts = jaxpr_facts or {}
    analysis_cfg = getattr(cfg, "analysis", None)
    budget_mb = float(getattr(analysis_cfg, "peak_memory_budget_mb", 0)
                      or 0)
    plan = getattr(engine, "_zero3_plan", None)
    return StepContext(
        hlo_text=hlo_text,
        flavor=flavor,
        n_devices=int(engine.mesh.shape.get("data", 1)),
        compute_dtype=compute,
        platform=jax.devices()[0].platform,
        zero_stage=engine.zero_optimization_stage(),
        comm_quantized=cfg.comm_quantization.enabled,
        offload=engine._offload,
        pipeline=(flavor == "pipeline"),
        param_bytes=param_bytes,
        expected_donated_params=expected,
        donated_param_info=pinfo,
        declared_donate_argnums=declared,
        overlap_enabled=bool(tp is not None and tp.overlap_enabled),
        overlap_chunks=int(tp.overlap_chunks) if tp is not None else 1,
        fp8_enabled=bool(cfg.fp8.enabled),
        fp8_wire_dtype=cfg.fp8.active_wire_dtype(),
        jaxpr_divergent=facts.get("divergent"),
        jaxpr_unordered=facts.get("unordered"),
        reshard_events=facts.get("reshard_events"),
        collective_sites=facts.get("collective_sites"),
        zero3_gather_leaves=int(plan.gather_leaves) if plan else 0,
        zero3_gather_chunks=int(plan.gather_chunks) if plan else 1,
        zero3_max_gather_bytes=int(plan.max_gather_bytes) if plan else 0,
        replicated_leaves=_replicated_state_leaves(engine),
        peak_memory=estimate_peak_memory(hlo_text),
        peak_budget_bytes=int(budget_mb * (1 << 20)),
        skip_rules=skip)


def compiled_cache_size(engine):
    """Entries in the compiled train step's jit cache (None if the jit
    wrapper doesn't expose it). 1 after any number of same-shape steps —
    growth means something recompiles every call."""
    step = engine._compiled_train_step
    if step is None:
        return None
    fn = getattr(step, "inner", step)
    cache_size = getattr(fn, "_cache_size", None)
    try:
        return int(cache_size()) if callable(cache_size) else None
    except Exception:
        return None


def check_recompile(engine, baseline=1):
    """Recompile detector: Finding when the step's jit cache outgrew the
    expected single entry (shape-unstable batches, dtype drift, a python
    value captured as a tracer-changing constant, ...)."""
    n = compiled_cache_size(engine)
    if n is None or n <= baseline:
        return []
    return [Finding(
        "recompile", SEV_ERROR,
        f"compiled train step has {n} cache entries (expected "
        f"{baseline}) — the step recompiled during the run",
        {"cache_size": n, "expected": baseline})]


# ---------------------------------------------------------------------------
# audit entry points
# ---------------------------------------------------------------------------

def audit_hlo(hlo_text, rules=None, **ctx_kwargs):
    """Run the rule catalog over raw HLO text (no engine needed).

    The trace-time rules (`deadlock`, the spec-flow half of
    `resharding`) need a jaxpr and stay not-run here; `peak_memory`
    works from the text alone."""
    ctx = StepContext(hlo_text=hlo_text, **ctx_kwargs)
    if ctx.peak_memory is None:
        ctx.peak_memory = estimate_peak_memory(hlo_text)
    report = AuditReport(flavor=ctx.flavor, findings=run_rules(ctx, rules))
    report.stats = _hlo_stats(hlo_text, ctx)
    return report


def _hlo_stats(hlo_text, ctx):
    loops = while_loops(hlo_text)
    stats = {
        "collective_bytes": collective_bytes(hlo_text),
        "collective_bytes_by_dtype": collective_bytes(hlo_text,
                                                      by_dtype=True),
        "collective_bytes_flat": collective_bytes(hlo_text,
                                                  trip_aware=False),
        "ring_send_bytes": ring_send_bytes(hlo_text,
                                           max(ctx.n_devices, 2)),
        "while_loops": len(loops),
        "unknown_trip_counts": sum(1 for l in loops
                                   if l["trip_count"] is None),
        "trip_counts": [l["trip_count"] for l in loops],
        "param_bytes": ctx.param_bytes,
    }
    if ctx.expected_donated_params is not None:
        aliased = aliased_param_numbers(hlo_text)
        stats["donated_expected"] = len(ctx.expected_donated_params)
        stats["donated_aliased"] = len(
            ctx.expected_donated_params & aliased)
    if ctx.peak_memory:
        stats["peak_memory"] = {
            k: ctx.peak_memory.get(k, 0)
            for k in ("peak_bytes", "temp_peak_bytes",
                      "parameter_bytes", "output_bytes",
                      "donated_output_bytes")}
    if ctx.jaxpr_divergent is not None:
        stats["jaxpr"] = {
            "divergent_collectives": len(ctx.jaxpr_divergent),
            "unordered_permutes": len(ctx.jaxpr_unordered or ()),
            "reshard_conflicts": len(ctx.reshard_events or ()),
        }
        if ctx.collective_sites is not None:
            stats["jaxpr"]["collective_sites"] = [
                dict(s) for s in ctx.collective_sites]
    return stats


def audit_compiled_step(engine, placed, rng, lr, rules=None):
    """In-engine compile-time audit: lower the just-compiled step with
    the live call's exact avals (so the engine's own step call right
    after is a jit-cache hit) and run the rule catalog. Backs the
    opt-in ``analysis`` config block (`runtime/engine.py`)."""
    fn, args = _engine_fn_args(engine, placed, rng, lr)
    hlo_text, expected, pinfo = _lower_step(fn, args)
    ctx = _engine_context(engine, hlo_text, expected, pinfo,
                          jaxpr_facts=_jaxpr_facts(fn, args))
    report = AuditReport(flavor=ctx.flavor, findings=run_rules(ctx, rules))
    report.stats = _hlo_stats(hlo_text, ctx)
    report.hlo_text = hlo_text
    return report


def audit_engine(engine, batch, rules=None, steps=0):
    """Audit a live engine's compiled train step.

    Runs one ``train_batch`` if the step isn't compiled yet (lazy
    compile), plus ``steps`` more for the recompile detector, then
    lowers the step with the exact argument avals ``train_batch`` uses
    (a jit-cache hit, not a second compile) and runs the rule catalog.
    """
    t0 = time.perf_counter()
    steps_run = 0
    if engine._compiled_train_step is None:
        engine.train_batch(batch)
        steps_run += 1
    for _ in range(steps):
        engine.train_batch(batch)
        steps_run += 1
    placed = engine._shard_batch(batch)
    rng = jax.random.PRNGKey(0)
    lr = jnp.asarray(1e-3, jnp.float32)
    fn, args = _engine_fn_args(engine, placed, rng, lr)
    hlo_text, expected, pinfo = _lower_step(fn, args)
    ctx = _engine_context(engine, hlo_text, expected, pinfo,
                          jaxpr_facts=_jaxpr_facts(fn, args))
    findings = run_rules(ctx, rules)
    if (rules is None or "recompile" in rules) \
            and "recompile" not in ctx.skip_rules:
        findings.extend(check_recompile(engine))
    report = AuditReport(flavor=ctx.flavor, findings=findings)
    report.stats = _hlo_stats(hlo_text, ctx)
    report.hlo_text = hlo_text
    report.stats["compile_cache_size"] = compiled_cache_size(engine)
    report.stats["steps_run"] = steps_run
    report.stats["audit_wall_s"] = round(time.perf_counter() - t0, 3)
    return report


# ---------------------------------------------------------------------------
# stock flavor builders (toy engines; used by the CLI, tests, and bench)
# ---------------------------------------------------------------------------

_TOY_HIDDEN = 256
_TOY_LAYERS = 4


def _toy_params_and_loss(hidden=_TOY_HIDDEN, nlayers=_TOY_LAYERS):
    keys = jax.random.split(jax.random.PRNGKey(0), nlayers)
    params = {
        f"linear_{i}": {
            "kernel": jax.random.normal(
                k, (hidden, hidden), jnp.float32) * 0.02,
            "bias": jnp.zeros((hidden,), jnp.float32),
        }
        for i, k in enumerate(keys)
    }

    def loss_fn(params, batch, rng=None):
        x = batch["x"]
        for i in range(nlayers):
            layer = params[f"linear_{i}"]
            x = x @ layer["kernel"] + layer["bias"]
            if i < nlayers - 1:
                x = jax.nn.relu(x)
        return jnp.mean(jnp.square(x - batch["y"]))

    return params, loss_fn


def _toy_batch(rows=16, hidden=_TOY_HIDDEN):
    rng = np.random.default_rng(0)
    return {"x": rng.normal(size=(rows, hidden)).astype(np.float32),
            "y": rng.normal(size=(rows, hidden)).astype(np.float32)}


def _dense_family_config(flavor):
    cfg = {"train_batch_size": 16,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "steps_per_print": 10 ** 9}
    if flavor == "dense":
        cfg["bf16"] = {"enabled": True}
    elif flavor in ("zero1", "zero2"):
        cfg["bf16"] = {"enabled": True}
        cfg["zero_optimization"] = {"stage": int(flavor[-1])}
    elif flavor == "zero3":
        # Explicit gather-on-use path with ring chunking so the audit
        # exercises the stage-3 overlap/budget rules end-to-end.
        cfg["bf16"] = {"enabled": True}
        cfg["zero_optimization"] = {"stage": 3, "gather_chunks": 2}
    elif flavor == "offload":
        cfg["bf16"] = {"enabled": True}
        cfg["zero_optimization"] = {"stage": 2, "cpu_offload": True}
    elif flavor == "quantized":
        # fp32 compute keeps the dense baseline's wire dtype — the
        # quantized audit checks the int8 replacement, not bf16 hygiene.
        cfg["comm_quantization"] = {"enabled": True, "chunk_size": 512,
                                    "bucket_mb": 4}
    else:
        raise ValueError(f"unknown dense-family flavor {flavor!r}")
    return cfg


def build_flavor_engine(flavor, config_overrides=None):
    """``(engine, batch)`` for one stock step flavor, toy-sized so all
    seven compile inside a CPU test budget."""
    import deepspeed_tpu

    if flavor == "pipeline":
        from deepspeed_tpu.models.gpt2 import gpt2_tiny
        from deepspeed_tpu.models.gpt2_pipe import gpt2_pipeline_module
        from deepspeed_tpu.parallel.mesh import build_mesh
        rows, seq = 8, 16
        mesh = build_mesh({"pipe": 2, "data": 4},
                          devices=jax.devices()[:8])
        module = gpt2_pipeline_module(gpt2_tiny(), seq_len=seq)
        cfg = {"train_batch_size": rows,
               "gradient_accumulation_steps": 2,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "steps_per_print": 10 ** 9}
        cfg.update(config_overrides or {})
        engine, _, _, _ = deepspeed_tpu.initialize(
            config=cfg, model=module, mesh=mesh)
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(
            0, 255, (rows, seq)).astype(np.int32)}
        return engine, batch

    if flavor == "pipeline_tp":
        # pipe x model x data with tensor_parallel.overlap on: the 1F1B
        # step whose row-parallel combines lower to chunked ppermute
        # rings — the flavor the overlap rule audits end-to-end.
        from deepspeed_tpu.parallel.mesh import build_mesh
        from deepspeed_tpu.parallel.pipe_tp import tp_pipeline_module
        rows, seq = 8, 16
        mesh = build_mesh({"pipe": 2, "model": 2, "data": 2},
                          devices=jax.devices()[:8])
        module = tp_pipeline_module(vocab=64, d_model=16, n_head=4,
                                    seq_len=seq, n_blocks=2, num_stages=2)
        cfg = {"train_batch_size": rows,
               "gradient_accumulation_steps": 2,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "steps_per_print": 10 ** 9,
               "tensor_parallel": {"overlap": {"enabled": True,
                                               "chunks": 4}}}
        cfg.update(config_overrides or {})
        engine, _, _, _ = deepspeed_tpu.initialize(
            config=cfg, model=module, mesh=mesh)
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(
            0, 64, (rows, seq)).astype(np.int32)}
        return engine, batch

    if flavor == "fp8":
        # fp8 delayed-scaling matmuls on GPT-2-tiny (the model whose
        # Dense layers route through `ops/fp8.py:fp8_dot_general`) plus
        # the quantized ZeRO-3 gather wire — the flavor the fp8 rule
        # audits end-to-end.
        from deepspeed_tpu.models.gpt2 import (
            GPT2LMHead, gpt2_tiny, init_gpt2_params, make_gpt2_loss_fn)
        rows, seq = 8, 16
        model = GPT2LMHead(gpt2_tiny())
        params = init_gpt2_params(model, jax.random.PRNGKey(0))
        cfg = {"train_batch_size": rows,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "steps_per_print": 10 ** 9,
               "bf16": {"enabled": True},
               "zero_optimization": {"stage": 3, "gather_chunks": 2},
               "fp8": {"enabled": True,
                       "wire": {"enabled": True, "dtype": "f8e4m3fn"}}}
        cfg.update(config_overrides or {})
        engine, _, _, _ = deepspeed_tpu.initialize(
            config=cfg, loss_fn=make_gpt2_loss_fn(model), params=params)
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(
            0, 255, (rows, seq)).astype(np.int32)}
        return engine, batch

    cfg = _dense_family_config(flavor)
    cfg.update(config_overrides or {})
    params, loss_fn = _toy_params_and_loss()
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg, loss_fn=loss_fn, params=params)
    return engine, _toy_batch()


# The sub-pallas_call rule subset (`analysis/kernels.py` facts); the
# kernel-only flavors run exactly these.
KERNEL_RULES = ("kernel_vmem", "kernel_tiling", "kernel_dma")


def _kernel_analysis_for(fn, args, engine):
    """Kernel analysis of a serving program at representative occupancy.

    ``decode_lowering_args()`` carries all-zero positions and all-trash
    page tables — correct avals for lowering, but degenerate for a
    DMA-elision proof (no row is live). Replace them with a half-full
    scenario: row ``b`` at position ``(b+1) * max_seq / (2 *
    max_batch)`` and distinct live page-table entries (no cross-row
    physical sharing). Returns ``(KernelAnalysis, expected_elision)``
    where the expectation is the scenario's dead-block fraction
    (`kernels.paged_dead_block_fraction`) — the contract
    `rules.rule_kernel_dma` enforces.
    """
    from deepspeed_tpu.analysis.kernels import (
        analyze_kernels, paged_dead_block_fraction)

    args = list(args)
    B = engine.spec.max_batch
    max_seq = engine.max_seq
    pos = np.array([(b + 1) * max_seq // (2 * B) for b in range(B)],
                   np.int32)
    args[3] = jnp.asarray(pos)                 # positions operand
    ppr = engine.pages_per_row
    pt = (np.arange(B * ppr).reshape(B, ppr)
          % (engine.n_pages - 1)) + 1         # live, distinct, non-trash
    args[4] = jnp.asarray(pt.astype(np.int32))
    ana = analyze_kernels(fn, tuple(args))
    if not ana.kernels:
        return ana, None
    return ana, paged_dead_block_fraction(
        pos, pt, engine.page_size, engine.attention_block_k)


def _serve_churn_stream(sched, vocab_size):
    """The serving audits' scripted stream, run through ``sched``;
    returns every completion. Allocator churn end to end: r0/r1 share a
    >page_size prefix (r1 is a radix hit on r0's interned pages), r2
    parks its pages under a session id, r3's 30-token prompt squeezes
    the pool (pressure ladder: radix eviction, then host evacuation of
    r2's parked pages) and length-evicts, r4 re-hits the shared prefix
    open-loop; then r5 extends s0's history (prompt + every token that
    fed a decode step) so admission pages the parked KV back in and
    restarts prefill mid-prompt."""
    from deepspeed_tpu.inference.scheduler import Request

    rng = np.random.default_rng(0)

    def toks(n):
        return rng.integers(0, vocab_size, n).tolist()

    base = toks(12)
    stream = [
        Request("r0", base + toks(3), max_new_tokens=4),
        Request("r1", base + toks(5), max_new_tokens=5),
        Request("r2", toks(6), max_new_tokens=4, session_id="s0"),
        Request("r3", toks(30), max_new_tokens=10),
        Request("r4", base + toks(2), max_new_tokens=3, arrival_step=3)]
    s0 = {c.rid: c for c in sched.run(stream)}["r2"]
    follow = stream[2].prompt + s0.tokens + toks(2)
    return sched.run([Request("r5", follow, max_new_tokens=3,
                              session_id="s0")])


def _page_facts(engine):
    return {"page_size": engine.page_size, "n_pages": engine.n_pages,
            "pages_per_row": engine.pages_per_row,
            "max_seq": engine.max_seq}


def audit_decode(rules=None, config_overrides=None, kv_cache_dtype=None,
                 attention_impl="flash", kernels=False):
    """Audit the serving engine's compiled decode program.

    Builds a tiny :class:`~deepspeed_tpu.inference.engine.
    InferenceEngine`, drives a scripted continuous-batching stream that
    crosses two seq buckets with admission/eviction (more requests than
    cache rows, mixed prompt lengths and generation budgets), then
    lowers the decode program through its live avals (a jit-cache hit)
    and runs the rule catalog over it — the `decode` rule pins zero
    in-loop recompiles and cache-dtype hygiene, the generic donation
    rule pins that the KV pool actually aliases in place,
    and the `flash_decode` rule pins that the stock flash attention
    path (``attention_impl="flash"``, the default) actually deleted the
    dense full-cache contraction from the lowered program.

    The scripted stream churns the page allocator end to end:
    shared-prefix admissions (radix hits), a pool-pressure request that
    rides the eviction ladder and length-evicts, a
    parked session that the pressure evacuates to host RAM, and a
    follow-up that pages it back in and resumes mid-prompt — then the
    `decode` rule pins that the post-churn program still lowered zero
    host transfers and the jit caches never grew past the 2-compile
    contract.

    ``kernels=True`` additionally runs the sub-``pallas_call`` analyzer
    (`analysis/kernels.py`) over the decode program at a representative
    half-full occupancy and arms the ``kernel_vmem`` /
    ``kernel_tiling`` / ``kernel_dma`` rules — including the
    DMA-elision proof that the clamped index maps turn the scenario's
    dead cache blocks into elided fetches.
    """
    import jax.numpy as jnp
    from deepspeed_tpu.inference.cache import (
        cache_dtype_census, payload_shape as kv_payload_shape)
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny

    t0 = time.perf_counter()
    cfg = gpt2_tiny(n_embd=32, dtype=jnp.float32)
    model = GPT2LMHead(cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    inf_cfg = {"max_batch": 2, "seq_buckets": (16, 32),
               "prefill_chunk": 4, "kv_cache_dtype": kv_cache_dtype,
               "attention_impl": attention_impl, "attention_block_k": 8}
    inf_cfg.update(config_overrides or {})
    engine = InferenceEngine(model, params, config=inf_cfg)
    sched = ContinuousBatchingScheduler(engine)
    completions = _serve_churn_stream(sched, cfg.vocab_size)
    hlo_text, expected, pinfo = _lower_step(engine._decode,
                                            engine.decode_lowering_args())
    kernel_ana = kernel_expected = None
    if kernels:
        kernel_ana, kernel_expected = _kernel_analysis_for(
            engine._decode, engine.decode_lowering_args(), engine)
    census = cache_dtype_census(engine.cache)
    payload_shape = kv_payload_shape(engine.spec)
    ctx = StepContext(
        hlo_text=hlo_text, flavor="decode",
        compute_dtype="f32" if cfg.dtype == jnp.float32 else "bf16",
        expected_donated_params=expected, donated_param_info=pinfo,
        declared_donate_argnums=getattr(
            engine._decode, "_ds_donate_argnums", None),
        decode_compile_counts=engine.compile_counts(),
        decode_kv_cache_dtype=engine.kv_cache_dtype,
        decode_cache_census=census,
        decode_attention_impl=engine.attention_impl,
        decode_cache_payload_shape=payload_shape,
        decode_platform=jax.devices()[0].platform,
        decode_page_facts=_page_facts(engine),
        kernel_analysis=kernel_ana,
        kernel_expected_elision=kernel_expected,
        skip_rules={"recompile"})
    findings = run_rules(ctx, rules)
    findings.extend(engine.recompile_findings())
    report = AuditReport(flavor="decode", findings=findings)
    report.stats = _hlo_stats(hlo_text, ctx)
    report.hlo_text = hlo_text
    report.stats["compile_counts"] = engine.compile_counts()
    report.stats["completions"] = len(completions)
    report.stats["finish_reasons"] = sorted(
        c.finish_reason for c in completions)
    report.stats["cache"] = engine.cache_facts()
    report.stats["attention"] = {"impl": engine.attention_impl,
                                 "block_k": engine.attention_block_k}
    report.stats["paging"] = sched.paging.facts()
    if kernel_ana is not None:
        report.stats["kernels"] = kernel_ana.to_dict()
        report.stats["kernels"]["expected_elision"] = kernel_expected
    report.stats["audit_wall_s"] = round(time.perf_counter() - t0, 3)
    return report


def _xla_flops(fn, args):
    """Compiled-program flop count from XLA cost analysis (0.0 when the
    backend doesn't report one)."""
    try:
        ca = fn.lower(*args).compile().cost_analysis()
    except Exception:
        return 0.0
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return float((ca or {}).get("flops", 0.0) or 0.0)


def audit_speculative(rules=None, config_overrides=None,
                      kv_cache_dtype=None, attention_impl="flash",
                      k=3, draft_layers=1, n_layer=4, kernels=False):
    """Audit the self-speculative serving engine end to end.

    Runs :func:`audit_decode`'s scripted churn stream (radix hits, pool
    pressure, host park + mid-prompt resume) with speculation enabled,
    then audits:

    - the pinned THREE-program contract — prefill, draft, verify each
      exactly one jit-cache entry and the plain decode program at ZERO
      (an entry means the scheduler silently fell back mid-stream);
    - draft truncation — XLA cost-analysis flops of the draft step vs
      the full-depth decode step at the same avals must sit near
      ``draft_layers / n_layer``, not near 1.0;
    - accept-loop invariants (``mean_accepted >= 1.0`` by construction,
      ``draft_efficiency`` within [0, 1]);
    - draft/verify program hygiene — donation of the cache operand,
      zero host transfers, and the flash payload pins on the T=1 draft
      step.
    """
    import jax.numpy as jnp
    from deepspeed_tpu.inference.cache import (
        cache_dtype_census, payload_shape as kv_payload_shape)
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny

    t0 = time.perf_counter()
    cfg = gpt2_tiny(n_embd=32, n_layer=n_layer, dtype=jnp.float32)
    model = GPT2LMHead(cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    inf_cfg = {"max_batch": 2, "seq_buckets": (16, 32),
               "prefill_chunk": 4, "kv_cache_dtype": kv_cache_dtype,
               "attention_impl": attention_impl,
               "attention_block_k": 8,
               "speculative": {"enabled": True, "k": k,
                               "draft_layers": draft_layers}}
    inf_cfg.update(config_overrides or {})
    engine = InferenceEngine(model, params, config=inf_cfg)
    spec = engine.speculative
    sched = ContinuousBatchingScheduler(engine)
    completions = _serve_churn_stream(sched, cfg.vocab_size)
    compile_counts = engine.compile_counts()
    draft_args = spec.draft_lowering_args()
    draft_hlo, expected, pinfo = _lower_step(spec._draft, draft_args)
    kernel_ana = kernel_expected = None
    if kernels:
        kernel_ana, kernel_expected = _kernel_analysis_for(
            spec._draft, draft_args, engine)
    verify_hlo, v_expected, v_pinfo = _lower_step(
        spec._verify, spec.verify_lowering_args())
    draft_flops = _xla_flops(spec._draft, draft_args)
    full_flops = _xla_flops(engine._decode,
                            engine.decode_lowering_args())
    ctx = StepContext(
        hlo_text=draft_hlo, flavor="speculative",
        compute_dtype="f32",
        expected_donated_params=expected, donated_param_info=pinfo,
        declared_donate_argnums=getattr(
            spec._draft, "_ds_donate_argnums", None),
        decode_compile_counts=compile_counts,
        decode_kv_cache_dtype=engine.kv_cache_dtype,
        decode_cache_census=cache_dtype_census(engine.cache),
        decode_attention_impl=engine.attention_impl,
        decode_cache_payload_shape=kv_payload_shape(engine.spec),
        decode_platform=jax.devices()[0].platform,
        decode_page_facts=_page_facts(engine),
        spec_facts=spec.facts(),
        spec_compile_counts=compile_counts,
        spec_draft_hlo=draft_hlo, spec_verify_hlo=verify_hlo,
        spec_draft_flops=draft_flops, spec_full_flops=full_flops,
        kernel_analysis=kernel_ana,
        kernel_expected_elision=kernel_expected,
        skip_rules={"recompile"})
    findings = run_rules(ctx, rules)
    # verify program: full-depth dense by design (the flash kernel
    # is a T=1 specialization), so only the donation pin applies
    v_ctx = StepContext(
        hlo_text=verify_hlo, flavor="speculative",
        compute_dtype="f32",
        expected_donated_params=v_expected,
        donated_param_info=v_pinfo,
        declared_donate_argnums=getattr(
            spec._verify, "_ds_donate_argnums", None),
        skip_rules={"recompile"})
    findings.extend(run_rules(v_ctx, {"donation"}))
    findings.extend(engine.recompile_findings())
    report = AuditReport(flavor="speculative", findings=findings)
    report.stats = _hlo_stats(draft_hlo, StepContext(
        hlo_text=draft_hlo, flavor="speculative"))
    report.stats.update({
        "compile_counts": compile_counts,
        "completions": len(completions),
        "finish_reasons": sorted(c.finish_reason for c in completions),
        "speculative": spec.facts(),
        "draft_flops": draft_flops, "full_flops": full_flops,
        "draft_flops_ratio":
            draft_flops / full_flops if full_flops else None,
        "cache": engine.cache_facts(),
        "paging": sched.paging.facts(),
    })
    if kernel_ana is not None:
        report.stats["kernels"] = kernel_ana.to_dict()
        report.stats["kernels"]["expected_elision"] = kernel_expected
    report.hlo_text = draft_hlo
    report.stats["audit_wall_s"] = round(time.perf_counter() - t0, 3)
    return report


def audit_disagg(rules=None, config_overrides=None):
    """Audit the disaggregated prefill/decode tiers (ISSUE 20).

    Builds one prefill-tier and one decode-tier engine over the SAME
    tiny model params but deliberately heterogeneous ``max_batch``
    (2 vs 3 — tiers size independently; the handoff contract pins only
    the paged geometry), drives a scripted mixed-length stream through
    the synchronous `inference/disagg.py:DisaggCoordinator`, then runs
    the rule catalog over the decode tier's post-stream program:

    - one-program-per-tier pins (``disagg_tier_counts``): after the
      whole stream the prefill tier's jit census must read
      ``{prefill: 1, decode: 0}`` and the decode tier's the inverse —
      the warmup-to-drain contract that makes tier capacity planning
      a pure host-side concern;
    - handoff geometry (``disagg_page_facts``): ``page_size`` /
      ``pages_per_row`` equal across tiers, because the handoff is a
      raw page copy keyed by the page table;
    - zero host-transfer ops in the decode tier's steady-state HLO
      (the handoff itself rides the store OUTSIDE the compiled
      programs) plus the standard paged-decode hygiene: donation of
      the paged pool, cache-dtype census, pool-geometry consistency.
    """
    import jax.numpy as jnp
    from deepspeed_tpu.inference.cache import (
        cache_dtype_census, payload_shape as kv_payload_shape)
    from deepspeed_tpu.inference.disagg import DisaggCoordinator
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import Request
    from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny

    t0 = time.perf_counter()
    cfg = gpt2_tiny(n_embd=32, dtype=jnp.float32)
    model = GPT2LMHead(cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    base = {"seq_buckets": (16, 32), "prefill_chunk": 4,
            "attention_block_k": 8}
    base.update(config_overrides or {})
    pre_engine = InferenceEngine(model, params, config=dict(
        base, max_batch=2, tier="prefill"))
    dec_engine = InferenceEngine(model, params, config=dict(
        base, max_batch=3, tier="decode"))
    coord = DisaggCoordinator([pre_engine], [dec_engine])
    rng = np.random.default_rng(0)
    # mixed-length stream across both buckets: short prompts, a
    # long-bucket prompt, and a mid-length one — every request crosses
    # the handoff (max_new_tokens > 1 keeps them off the
    # finish-at-prefill fast path)
    stream = [
        Request("r0", rng.integers(0, cfg.vocab_size, 3).tolist(),
                max_new_tokens=4),
        Request("r1", rng.integers(0, cfg.vocab_size, 20).tolist(),
                max_new_tokens=6),
        Request("r2", rng.integers(0, cfg.vocab_size, 6).tolist(),
                max_new_tokens=3),
        Request("r3", rng.integers(0, cfg.vocab_size, 12).tolist(),
                max_new_tokens=5),
    ]
    completions = coord.run(stream)
    hlo_text, expected, pinfo = _lower_step(
        dec_engine._decode, dec_engine.decode_lowering_args())
    tier_counts = {"prefill": pre_engine.compile_counts(),
                   "decode": dec_engine.compile_counts()}
    page_facts = {t: {"page_size": e.page_size,
                      "pages_per_row": e.pages_per_row,
                      "n_pages": e.n_pages, "max_seq": e.max_seq}
                  for t, e in (("prefill", pre_engine),
                               ("decode", dec_engine))}
    census = cache_dtype_census(dec_engine.cache)
    payload_shape = kv_payload_shape(dec_engine.spec)
    ctx = StepContext(
        hlo_text=hlo_text, flavor="disagg",
        compute_dtype="f32",
        expected_donated_params=expected, donated_param_info=pinfo,
        declared_donate_argnums=getattr(
            dec_engine._decode, "_ds_donate_argnums", None),
        decode_compile_counts=dec_engine.compile_counts(),
        decode_kv_cache_dtype=dec_engine.kv_cache_dtype,
        decode_cache_census=census,
        decode_attention_impl=dec_engine.attention_impl,
        decode_cache_payload_shape=payload_shape,
        decode_platform=jax.devices()[0].platform,
        decode_page_facts=page_facts["decode"],
        disagg_tier_counts=tier_counts,
        disagg_page_facts=page_facts,
        skip_rules={"recompile"})
    findings = run_rules(ctx, rules)
    findings.extend(pre_engine.recompile_findings())
    findings.extend(dec_engine.recompile_findings())
    report = AuditReport(flavor="disagg", findings=findings)
    report.stats = _hlo_stats(hlo_text, ctx)
    report.hlo_text = hlo_text
    report.stats["tier_compile_counts"] = tier_counts
    report.stats["tier_page_facts"] = page_facts
    report.stats["tiers"] = coord.tier_stats()
    report.stats["completions"] = len(completions)
    report.stats["finish_reasons"] = sorted(
        c["finish_reason"] for c in completions)
    report.stats["cache"] = dec_engine.cache_facts()
    report.stats["audit_wall_s"] = round(time.perf_counter() - t0, 3)
    return report


def audit_flash_train(rules=None, batch=1, seq=256, n_head=2,
                      head_dim=128, block_q=128, block_k=128):
    """Audit the training flash-attention kernels (forward + both
    backward passes) with the sub-``pallas_call`` analyzer.

    Traces ``value_and_grad`` of a causal `ops/pallas/flash_attention.
    flash_attention` sum at a representative geometry, extracts the
    three Pallas kernels (fwd, dQ, dKV) and runs the kernel rule subset
    (:data:`KERNEL_RULES`) — there is no engine and no HLO here, so the
    step-level catalog doesn't apply. Stock blocks must come back
    zero-findings: lane dims 128-aligned, sublane dims 8-aligned for
    f32, VMEM working sets far under budget, and every output map
    constant in the innermost grid dim (the carried-accumulator idiom,
    not a grid-write race).
    """
    from deepspeed_tpu.analysis.kernels import (
        analyze_kernels, causal_dead_tile_fraction,
        causal_rectangle_dead_fraction)
    from deepspeed_tpu.ops.pallas import flash_attention

    t0 = time.perf_counter()
    shape = (batch, seq, n_head, head_dim)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in keys)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block_q,
                               block_k=block_k,
                               implementation="pallas").sum()

    fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
    ana = analyze_kernels(fn, (q, k, v))
    # the elision contract of a causal call: the tiles the mask kills
    # whole are no part of the walk
    expected = causal_rectangle_dead_fraction(
        seq // block_q, seq // block_k, block_q, block_k)
    ctx = StepContext(hlo_text="", flavor="flash_train",
                      kernel_analysis=ana,
                      kernel_expected_elision=expected,
                      skip_rules={"recompile"})
    findings = run_rules(ctx, set(rules) if rules is not None
                         else set(KERNEL_RULES))
    report = AuditReport(flavor="flash_train", findings=findings)
    report.stats = {"kernels": ana.to_dict(),
                    "expected_elision": expected,
                    "dead_tile_fraction": {
                        k.name: causal_dead_tile_fraction(k)
                        for k in ana.kernels},
                    "geometry": {"batch": batch, "seq": seq,
                                 "n_head": n_head, "head_dim": head_dim,
                                 "block_q": block_q, "block_k": block_k},
                    "audit_wall_s": round(time.perf_counter() - t0, 3)}
    return report


def audit_kernel_flavors(rules=None):
    """The ``ds_tpu_audit --kernels`` sweep: every stock Pallas kernel
    path under the sub-``pallas_call`` analyzer.

    Covers the train flash-attention kernels (fwd/dQ/dKV), the decode
    flavor (the paged kernel's row walk, with its DMA-elision proof),
    and the speculative flavor (draft program). Returns ``{name: AuditReport}``;
    stock kernels must come back zero-findings everywhere.
    """
    reports = {
        "flash_train": audit_flash_train(rules=rules),
        "decode_paged": audit_decode(rules=rules, kernels=True),
        "speculative": audit_speculative(rules=rules, kernels=True),
    }
    for name, rep in reports.items():
        rep.flavor = name
    return reports


def audit_flavors(flavors=None, rules=None, steps=0,
                  config_overrides=None):
    """Build + audit toy engines for the stock flavors.

    Returns ``{flavor: AuditReport}`` in the order requested."""
    out = {}
    for flavor in flavors or STEP_FLAVORS:
        if flavor == "decode":
            # the serving flavor audits an InferenceEngine, not a
            # train-step engine — it has its own orchestrator.
            out[flavor] = audit_decode(rules=rules)
            continue
        if flavor == "speculative":
            out[flavor] = audit_speculative(rules=rules)
            continue
        if flavor == "disagg":
            out[flavor] = audit_disagg(rules=rules)
            continue
        engine, batch = build_flavor_engine(
            flavor, config_overrides=config_overrides)
        out[flavor] = audit_engine(engine, batch, rules=rules, steps=steps)
    return out

"""Declarative audit rules over a compiled step's HLO.

Each rule is a pure function ``rule(ctx: StepContext) -> [Finding]`` —
it reads compile-time facts off the HLO text (via `analysis/hlo.py`)
and diffs them against what the engine configuration *promises*:
donated buffers actually alias outputs, bf16/fp16 runs don't leak fp32
onto the wire beyond the fp32-master design allowance, ZeRO stages stay
inside their per-stage byte budgets, nothing round-trips through the
host mid-step, and every collective-carrying loop has a statically
known trip count (else its volume cannot be accounted at all).

Rules return ``[]`` when not applicable (e.g. the dtype-hygiene rule on
a pure-fp32 run) so the orchestrator (`analysis/audit.py`) can run the
whole catalog over any step flavor. The allowances are deliberately
generous versions of the exact pins in ``tests/unit`` — tests pin exact
architecture numbers; rules catch order-of-magnitude regressions on
arbitrary user models.
"""

from dataclasses import dataclass, field, asdict

from deepspeed_tpu.analysis.hlo import (
    aliased_param_numbers,
    collective_bytes,
    collective_counts,
    collective_ops,
    fp8_value_counts,
    host_transfer_ops,
    while_loops,
)

SEV_ERROR = "error"
SEV_WARNING = "warning"
SEV_INFO = "info"
_SEV_RANK = {SEV_INFO: 0, SEV_WARNING: 1, SEV_ERROR: 2}


@dataclass
class Finding:
    rule: str
    severity: str
    message: str
    details: dict = field(default_factory=dict)

    def to_dict(self):
        return asdict(self)


@dataclass
class StepContext:
    """Everything a rule may diff the HLO against.

    ``expected_donated_params`` are HLO entry-parameter numbers (i.e.
    already mapped from ``donate_argnums`` through arg flattening and
    unused-arg pruning by the audit orchestrator); ``param_bytes`` is
    the fp32 master footprint the ZeRO budgets are expressed in.
    """
    hlo_text: str
    flavor: str = "custom"
    n_devices: int = 1
    compute_dtype: str = "f32"       # "bf16" | "f16" | "f32"
    platform: str = None             # backend that compiled hlo_text
    zero_stage: int = 0
    comm_quantized: bool = False
    offload: bool = False
    pipeline: bool = False
    param_bytes: int = 0
    expected_donated_params: set = None
    donated_param_info: dict = field(default_factory=dict)
    declared_donate_argnums: tuple = None
    # Donated buffers smaller than this (scalar step counters, loss-scale
    # flags) are not an HBM concern; XLA may legitimately skip aliasing
    # them.
    min_donation_bytes: int = 64
    # tensor_parallel.overlap: when the config promises the latency-
    # hiding collective matmul, the overlap rule pins that the lowered
    # step actually carries the chunked ppermute rings.
    overlap_enabled: bool = False
    overlap_chunks: int = 1
    # fp8 (`ops/fp8.py` + the quantized collective wire): fp8_enabled
    # promises qdq matmuls (f8e4m3fn forward operands, f8e5m2 backward
    # cotangents in the lowered text); fp8_wire_dtype (a codec name from
    # `runtime/comm/codecs.py`) promises quantized collective payloads —
    # 1-byte wire buffers (the bitcast-packed u8 from `encode_wire`, or
    # raw s8/f8 elements) moving through the gather/ring family.
    fp8_enabled: bool = False
    fp8_wire_dtype: str = None
    # Explicit ZeRO-3 gather-on-use schedule (`zero/stage3.py:Zero3Plan`):
    # how many sharded leaves gather per use, the ring chunking, and the
    # largest single gathered leaf in compute-dtype bytes. gather_leaves
    # == 0 means no explicit schedule was declared (stages < 3, or the
    # legacy spec-sharded stage 3) and the schedule pins don't apply.
    zero3_gather_leaves: int = 0
    zero3_gather_chunks: int = 1
    zero3_max_gather_bytes: int = 0
    # Trace-time facts from the jaxpr front end (`analysis/jaxpr.py`);
    # None means the pass didn't run (HLO-only audits), [] means it ran
    # clean. The orchestrator fills these from the traced step.
    jaxpr_divergent: list = None     # check_divergent_collectives dicts
    jaxpr_unordered: list = None     # check_unordered_permutes dicts
    reshard_events: list = None      # propagate_partition_specs events
    replicated_leaves: list = None   # [{path, bytes, shape}] large fully-
    #                                  replicated state leaves
    collective_sites: list = None    # parallel.collectives SiteRecord
    #                                  dicts captured while tracing —
    #                                  source-level attribution for the
    #                                  permute-chain findings/stats
    # Conflicting-placement reshards below this are noise (tiny norms,
    # scalars); full replication below it is a deliberate choice the
    # ZeRO partitioner itself makes for small leaves.
    min_reshard_bytes: int = 1 << 20
    # Static peak-memory estimate (`analysis/hlo.py:estimate_peak_memory`
    # dict) and an optional explicit budget; 0 derives the per-ZeRO-stage
    # default from param_bytes.
    peak_memory: dict = None
    peak_budget_bytes: int = 0
    # Serving audit (`inference/engine.py`): decode_compile_counts is
    # the engine's {"prefill": n, "decode": n} AFTER a scripted stream
    # exercised admit/evict across >= 2 seq buckets — any program above
    # decode_expected_compiles means a shape leaked into a jit boundary
    # and the serving loop recompiled mid-stream. decode_cache_census
    # ({dtype: payload leaf count} from `cache_dtype_census`) plus the
    # configured decode_kv_cache_dtype pin cache-storage hygiene: one
    # payload dtype, and the codec's dtype when quantization is on.
    decode_compile_counts: dict = None
    decode_expected_compiles: int = 1
    decode_kv_cache_dtype: str = None
    decode_cache_census: dict = None
    # Flash-decode attention (`ops/pallas/flash_decode.py`):
    # decode_attention_impl names the engine's configured decode
    # attention ("dense" | "flash"; None = not a serving audit),
    # decode_cache_payload_shape is one layer's k/v pool shape
    # (n_pages, n_head, head_dim, page_size), and decode_platform is
    # the backend the audited program lowered for — the Pallas
    # custom-call pin only applies to real TPU lowerings (interpret
    # mode inlines the kernel as plain HLO).
    decode_attention_impl: str = None
    decode_cache_payload_shape: tuple = None
    decode_platform: str = None
    # Paged KV cache (`inference/paging.py`): the page tables are
    # fixed-shape int32 DATA inputs — allocator churn, prefix sharing
    # and host-tier parking are host-side bookkeeping that must never
    # lower a host transfer into the steady-state decode program
    # (parking runs OUTSIDE the compiled step, through
    # `engine.gather_pages`). decode_page_facts is the engine's
    # `cache_facts()` geometry (page_size / n_pages / pages_per_row /
    # max_seq) for the internal-consistency pins; None = not a serving
    # audit.
    decode_page_facts: dict = None
    # Speculative decoding (`inference/speculative.py`): spec_facts is
    # the decoder's `facts()` (k / draft_layers / n_layer and the
    # accept counters), spec_compile_counts the engine's full jit-cache
    # census {prefill, decode, draft, verify} after a scripted churn
    # stream — the pinned THREE-program contract, including decode == 0
    # (the plain decode program must never be entered while speculation
    # is on; one entry means the scheduler fell back mid-stream).
    # spec_draft_hlo / spec_verify_hlo are the compiled draft / verify
    # programs for host-transfer and payload pins; spec_draft_flops /
    # spec_full_flops are XLA cost-analysis flop counts for the
    # truncated draft step vs a same-shape full-depth step — their
    # ratio proves the truncation is real (~draft_layers/n_layer, not
    # ~1.0).
    spec_facts: dict = None
    spec_compile_counts: dict = None
    spec_draft_hlo: str = None
    spec_verify_hlo: str = None
    spec_draft_flops: float = 0.0
    spec_full_flops: float = 0.0
    # Disaggregated serving (`inference/disagg.py`):
    # disagg_tier_counts is {tier: compile_counts} after a scripted
    # stream ran through both tiers — the ONE-program-per-tier pin
    # (prefill tier {prefill: 1, decode: 0}, decode tier inverted; any
    # other census means a tier entered the other tier's program and
    # the whole point of the split is gone). disagg_page_facts is
    # {tier: cache_facts()} for the handoff-geometry pin: the KV
    # handoff is a raw page copy keyed by the page table, so
    # page_size/pages_per_row must match across tiers exactly.
    disagg_tier_counts: dict = None
    disagg_page_facts: dict = None
    # Pallas kernel analysis (`analysis/kernels.py`): kernel_analysis is
    # the step's `KernelAnalysis` (None = the sub-pallas_call pass did
    # not run; the kernel_* rules are inert). kernel_expected_elision is
    # the audit's *proof obligation* for the DMA-elision trick: the
    # dead-block fraction the clamped index maps MUST elide, computed
    # from the analysis scenario's positions
    # (`kernels.paged_dead_block_fraction`). None = no elision contract
    # (train kernels have no occupancy clamp to prove).
    kernel_analysis: object = None
    kernel_expected_elision: float = None
    skip_rules: set = field(default_factory=set)


def _fmt_bytes(n):
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024 or unit == "GB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024


def _slack(ctx):
    """Budget slack: 20% of the fp32 master footprint (floor 4KB).

    Generous on purpose — XLA may legitimately reduce a tied/shared
    parameter's gradient contributions separately before adding (e.g. a
    tied embedding pays its grad all-reduce twice), and scalars/norms
    ride along. The violations these rules exist for (a silent fp32
    upcast doubling wire bytes, a missing refresh gather, a whole extra
    param-sized exchange) overshoot 20% by construction."""
    return max(4096, int(0.2 * ctx.param_bytes))


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

def rule_donation(ctx):
    """Declared ``donate_argnums`` must become real input/output aliases.

    The engine donates params/opt-state/device-state into each step so
    XLA updates them in place; a donation that fails to alias (or a
    dropped ``donate_argnums``) silently doubles that buffer's HBM."""
    if ctx.expected_donated_params is None:
        return []
    aliased = aliased_param_numbers(ctx.hlo_text)
    missing = []
    for p in sorted(ctx.expected_donated_params):
        info = ctx.donated_param_info.get(p, {})
        if info.get("bytes", ctx.min_donation_bytes) < ctx.min_donation_bytes:
            continue
        if p not in aliased:
            missing.append({"param": p, **info})
    if not missing:
        return []
    total = sum(m.get("bytes", 0) for m in missing)
    return [Finding(
        "donation", SEV_ERROR,
        f"{len(missing)} donated input buffer(s) totalling "
        f"{_fmt_bytes(total)} are not aliased into the step outputs — "
        f"un-donated params/opt-state live twice in HBM",
        {"missing_count": len(missing), "missing_bytes": total,
         "missing": missing[:16],
         "declared_donate_argnums":
             list(ctx.declared_donate_argnums or ()) or None,
         "aliased_params": len(aliased)})]


def rule_dtype_hygiene(ctx):
    """No fp32 on the wire beyond the fp32-master design allowance.

    In a bf16/fp16 run the *gradient* exchange legitimately rides fp32
    (fp32 master weights; `grad_epilogue` casts grads up before the
    all-reduce) — but every ZeRO stage gathers its parameters at compute
    dtype (cast-then-gather: `zero/sharding.py:make_param_caster` once a
    step at stages 1 and 2, `zero/stage3.py` per use), and under
    comm_quantization the gradient all-reduce must have been replaced by
    the int8 exchange entirely. Anything above those allowances is a
    silent upcast paying 2x wire bytes. Two programs still read one
    parameter-sized fp32 gather at stages 1 and 2 and are allowed it:
    the step kinds that hand replicated fp32 masters to a manual region
    (quantized comm, pipeline, sparse gradients: their update ends with
    the masters' refresh gather), and any step compiled by the CPU
    backend, which emulates bf16 in f32 and re-widens the 16-bit gather
    on the wire (``ctx.platform``; an HLO-only audit knows no backend
    and is held to the strict budget).

    fp8 runs need no extra allowance: the quantized wire packs its
    per-chunk f32 scales INSIDE the bitcast u8 buffers
    (`runtime/comm/codecs.py:encode_wire`), and the delayed-scaling
    amax state moves as tiny f32 max-reductions (a few histories of
    ``amax_history_len`` floats each) that sit well inside the 4KB
    slack floor."""
    low_precision = ctx.compute_dtype in ("bf16", "f16")
    if not low_precision and not ctx.comm_quantized:
        return []
    f32 = {}
    for op in collective_ops(ctx.hlo_text):
        b = op["dtype_bytes"].get("f32", 0) * op["multiplier"]
        if b:
            f32[op["op"]] = f32.get(op["op"], 0) + b
    m_bytes = ctx.param_bytes
    slack = _slack(ctx)
    findings = []

    reduce_f32 = f32.get("all-reduce", 0) + f32.get("reduce-scatter", 0)
    gather_f32 = f32.get("all-gather", 0)
    other_f32 = sum(b for op, b in f32.items()
                    if op not in ("all-reduce", "reduce-scatter",
                                  "all-gather"))

    if ctx.comm_quantized:
        # scales ride all-gather; the gradient all-reduce must be gone.
        if f32.get("all-reduce", 0) > 4096:
            findings.append(Finding(
                "dtype_hygiene", SEV_ERROR,
                f"comm_quantization is on but an fp32 all-reduce of "
                f"{_fmt_bytes(f32['all-reduce'])} remains — the gradient "
                f"sync was not replaced by the int8 exchange",
                {"f32_all_reduce_bytes": f32["all-reduce"]}))
        if not low_precision:
            return findings

    allow_reduce = m_bytes + slack
    allow_other = slack
    if ctx.zero_stage in (1, 2) and (
            ctx.comm_quantized or ctx.pipeline or ctx.flavor == "sparse"
            or ctx.platform == "cpu"):
        # replicated fp32 masters' refresh, or the CPU's widened gather
        allow_gather = m_bytes + slack
    elif ctx.zero_stage >= 3:
        # Stage 3 gathers at compute dtype (cast-then-gather), but the
        # SPMD partitioner may sink the convert and re-widen the 16-bit
        # gather to f32 on the wire (the CPU backend does), and the
        # explicit path's backward re-gather doubles the pass count when
        # XLA doesn't CSE the remat. Budget the widened fwd+bwd envelope;
        # chunked rings are the same gathers as permutes, so they share
        # it through the "other" family.
        allow_gather = 2 * m_bytes + slack
        allow_other = 2 * m_bytes + slack
    else:
        # stage 0 has no param traffic; stages 1 and 2 gather the
        # 16-bit copy, so an fp32 gather there is the upcast.
        allow_gather = slack

    checks = [("all-reduce/reduce-scatter", reduce_f32, allow_reduce),
              ("all-gather", gather_f32, allow_gather),
              ("other collectives", other_f32, allow_other)]
    for name, got, allowed in checks:
        if got > allowed:
            findings.append(Finding(
                "dtype_hygiene", SEV_ERROR,
                f"fp32 {name} traffic of {_fmt_bytes(got)} exceeds the "
                f"{ctx.compute_dtype} run's allowance of "
                f"{_fmt_bytes(allowed)} — a silent upcast is paying 2x "
                f"wire bytes",
                {"family": name, "f32_bytes": got, "allowed_bytes": allowed,
                 "zero_stage": ctx.zero_stage,
                 "compute_dtype": ctx.compute_dtype}))
    return findings


_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2}


def gradient_exchange_bytes(volumes, n_devices):
    """The gradient bytes one step's reduction covers, whatever XLA
    called it (``volumes``: :func:`collective_bytes`, trip-aware).

    An all-reduce's output is the whole gradient. A reduce-scatter's
    output is the 1/N shard a device keeps. And the TPU compiler spells
    a reduce-scatter over N devices as a ring of collective-permutes,
    N-1 hops of one shard each — first met on the v5e (PR 21): the
    ZeRO-2 step of GPT-2 350M on ``data=4`` moves its bf16 gradient as
    590 MB of permutes and holds no op named reduce-scatter."""
    n = max(int(n_devices), 1)
    covered = volumes.get("all-reduce", 0) + \
        n * volumes.get("reduce-scatter", 0)
    if n > 1:
        covered += volumes.get("collective-permute", 0) * n // (n - 1)
    return covered


def rule_zero_budget(ctx):
    """Per-stage ZeRO collective byte ceilings (output-bytes basis).

    Generalizes the pinned proofs of ``test_zero_comm_volume.py`` into
    ceilings any model can be checked against: stage 0 moves one
    gradient exchange and NO param traffic; stages 1/2 add exactly one
    gather of the parameters (their 16-bit copy at the step's head; M
    is the ceiling, which the CPU backend's re-widened gather and the
    replicated-master step kinds' fp32 refresh reach); stage 3's total
    stays within the ZeRO paper's 1.5x-of-DP envelope. M = fp32 param
    bytes."""
    if ctx.param_bytes <= 0 or ctx.comm_quantized or ctx.pipeline:
        return []
    v = collective_bytes(ctx.hlo_text)
    m_bytes = ctx.param_bytes
    slack = _slack(ctx)
    ar = v.get("all-reduce", 0) + v.get("reduce-scatter", 0)
    ag = v.get("all-gather", 0)
    findings = []

    def over(name, got, allowed, extra=None):
        findings.append(Finding(
            "zero_budget", SEV_ERROR,
            f"stage-{ctx.zero_stage} {name} volume {_fmt_bytes(got)} "
            f"exceeds the budget {_fmt_bytes(allowed)} "
            f"(M = {_fmt_bytes(m_bytes)})",
            dict({"got_bytes": got, "allowed_bytes": allowed,
                  "param_bytes": m_bytes, "volumes": v}, **(extra or {}))))

    if ctx.offload or ctx.zero_stage == 0:
        if ar > m_bytes + slack:
            over("gradient exchange (all-reduce)", ar, m_bytes + slack)
        if ag > slack:
            over("all-gather", ag, slack,
                 {"note": "plain DP / offload grad step has no param "
                          "refresh gather"})
    elif ctx.zero_stage in (1, 2):
        # one gradient exchange in any of its spellings. Declared TP
        # overlap rings are permutes too, and not the gradient's: with
        # them on, only the named reductions are attributed.
        if ctx.overlap_enabled:
            v_grad = {k: b for k, b in v.items()
                      if k != "collective-permute"}
        else:
            v_grad = v
        grad = gradient_exchange_bytes(v_grad, ctx.n_devices)
        if grad > m_bytes + slack:
            over("gradient exchange (all-reduce, reduce-scatter or "
                 "permute ring)", grad, m_bytes + slack)
        if ag > m_bytes + slack:
            over("param gather (all-gather)", ag, m_bytes + slack)
        # the exchange may ride at the compute dtype (the v5e's does)
        floor = m_bytes * _DTYPE_BYTES.get(ctx.compute_dtype, 4) // 4
        if ctx.n_devices > 1 and grad < floor - slack:
            findings.append(Finding(
                "zero_budget", SEV_WARNING,
                f"stage-{ctx.zero_stage} gradient exchange covers "
                f"{_fmt_bytes(grad)}, below the {ctx.compute_dtype} "
                f"gradient's {_fmt_bytes(floor)} less "
                f"{_fmt_bytes(slack)} — gradient sync may be missing",
                {"got_bytes": grad, "param_bytes": m_bytes,
                 "floor_bytes": floor, "volumes": v}))
    else:  # stage >= 3
        # Total envelope: forward per-use gathers (one param-sized pass,
        # f32-widened worst case on backends that sink the 16-bit cast
        # through the gather) + the backward re-gather (a second pass
        # when XLA doesn't CSE the remat's recompute back into the
        # forward's) + the fp32 gradient exchange — the ZeRO paper's 3Ψ
        # vs plain DP's 2Ψ, i.e. the 1.5x envelope, measured here at the
        # widened worst case.
        total = v.get("total", 0)
        allowed = int(3.2 * m_bytes) + 2 * slack
        if total > allowed:
            over("total collective", total, allowed)
        if ctx.zero3_gather_leaves > 0:
            # An explicit gather-on-use schedule was declared: pin it.
            # (a) No up-front/monolithic all-gather — no single gather
            # op may move more than the largest declared leaf (the
            # schedule gathers layer-by-layer; one op carrying the whole
            # param tree is exactly the regression it exists to prevent).
            per_leaf = 2 * ctx.zero3_max_gather_bytes + slack
            for op in collective_ops(ctx.hlo_text):
                if op["op"] != "all-gather":
                    continue
                b = sum(op["dtype_bytes"].values())
                if b > per_leaf:
                    findings.append(Finding(
                        "zero_budget", SEV_ERROR,
                        f"stage-{ctx.zero_stage} all-gather of "
                        f"{_fmt_bytes(b)} exceeds the largest declared "
                        f"per-leaf gather allowance {_fmt_bytes(per_leaf)}"
                        f" — an up-front full-param gather defeats the "
                        f"gather-on-use schedule",
                        {"got_bytes": b, "allowed_bytes": per_leaf,
                         "computation": op.get("computation"),
                         "gather_leaves": ctx.zero3_gather_leaves,
                         "max_gather_bytes": ctx.zero3_max_gather_bytes}))
            # (b) Per-layer gather counts: every sharded leaf must
            # gather through its own op (all-gather, or ppermute ring
            # hops when chunked) — fewer gather-family ops than leaves
            # means leaves were coalesced into a bulk gather.
            counts = collective_counts(ctx.hlo_text)
            gather_ops = counts.get("all-gather", 0) + \
                counts.get("collective-permute", 0)
            if gather_ops < ctx.zero3_gather_leaves:
                findings.append(Finding(
                    "zero_budget", SEV_ERROR,
                    f"stage-{ctx.zero_stage} step executes only "
                    f"{gather_ops} gather-family op(s) for "
                    f"{ctx.zero3_gather_leaves} sharded leaves — the "
                    f"per-layer gather schedule did not reach the "
                    f"lowered program",
                    {"gather_ops": gather_ops,
                     "gather_leaves": ctx.zero3_gather_leaves,
                     "counts": counts}))
    return findings


def rule_host_transfer(ctx):
    """No host round-trips inside a compiled step.

    Infeed/outfeed, ``is_host_transfer=true`` sends/recvs, and Python
    host-callback custom-calls each force a device/host sync mid-step —
    the async dispatch pipeline stalls every step."""
    hits = host_transfer_ops(ctx.hlo_text)
    if not hits:
        return []
    kinds = sorted({h["kind"] for h in hits})
    return [Finding(
        "host_transfer", SEV_ERROR,
        f"{len(hits)} host transfer op(s) inside the compiled step "
        f"({', '.join(kinds)}) — each forces a mid-step host sync",
        {"count": len(hits), "kinds": kinds,
         "ops": [h["line"][:200] for h in hits[:8]]})]


def rule_trip_count(ctx):
    """Every collective-carrying loop must have a static trip count.

    Without one the loop's collective volume cannot be accounted (the
    historical flat-count limitation) and none of the byte-budget rules
    can be trusted for this program."""
    unknown = [l for l in while_loops(ctx.hlo_text)
               if l["has_collectives"] and l["trip_count"] is None]
    if not unknown:
        return []
    return [Finding(
        "trip_count", SEV_WARNING,
        f"{len(unknown)} while loop(s) carry collectives but have no "
        f"statically known trip count — their wire volume is "
        f"under-accounted (counted once, not per iteration)",
        {"loops": [{"body": l["body"], "parent": l["parent"]}
                   for l in unknown]})]


def rule_overlap(ctx):
    """The promised latency-hiding collective matmul must be in the HLO.

    With ``tensor_parallel.overlap`` enabled on a pipeline step, the
    rewired manual-TP sites replace their monolithic blocking collectives
    with chunked ``collective-permute`` rings — so the lowered program
    must execute at least ``chunks - 1`` collective-permutes (the 1F1B
    stage transfers alone already permute; the ring chunks add more),
    and the in-loop (per-tick) ``all-reduce`` count must be ZERO: any
    all-reduce executing more than once per step means a rewired site
    regressed to the blocking form. (The legitimate grad/loss psums run
    once, after the tick scan — multiplier 1.)

    Separately (not pipeline-gated): an explicit ZeRO-3 schedule with
    ``gather_chunks > 1`` promises each sharded leaf gathers as
    ``chunks`` ppermute ring stripes (`zero/stage3.py`) — the lowered
    step must carry at least ``leaves x (chunks - 1)`` collective-
    permutes, else the ring rewiring silently fell back to monolithic
    all-gathers."""
    findings = []
    counts = collective_counts(ctx.hlo_text)
    if ctx.overlap_enabled and ctx.pipeline:
        permutes = counts.get("collective-permute", 0)
        need = max(1, ctx.overlap_chunks - 1)
        if permutes < need:
            findings.append(Finding(
                "overlap", SEV_ERROR,
                f"tensor_parallel.overlap promises chunked ppermute rings "
                f"(chunks={ctx.overlap_chunks}) but the step executes only "
                f"{permutes} collective-permute(s) (< {need}) — the overlap "
                f"rewiring did not reach the lowered program",
                {"collective_permutes": permutes, "required": need,
                 "chunks": ctx.overlap_chunks, "counts": counts}))
        if ctx.overlap_chunks > 1:
            in_loop = [op for op in collective_ops(ctx.hlo_text)
                       if op["op"] == "all-reduce" and op["multiplier"] > 1]
            if in_loop:
                total = sum(op["multiplier"] for op in in_loop)
                findings.append(Finding(
                    "overlap", SEV_ERROR,
                    f"{len(in_loop)} all-reduce op(s) execute inside the "
                    f"pipeline tick loop ({total} executions/step) — a "
                    f"rewired row-parallel/combine site regressed to the "
                    f"monolithic blocking collective",
                    {"in_loop_all_reduces": len(in_loop),
                     "executions_per_step": total,
                     "computations": sorted({op["computation"] or ""
                                             for op in in_loop})}))
    if ctx.zero_stage >= 3 and ctx.zero3_gather_leaves > 0 and \
            ctx.zero3_gather_chunks > 1 and ctx.n_devices > 1:
        permutes = counts.get("collective-permute", 0)
        need = max(1, ctx.zero3_gather_leaves
                   * (ctx.zero3_gather_chunks - 1))
        if permutes < need:
            findings.append(Finding(
                "overlap", SEV_ERROR,
                f"zero_optimization.gather_chunks="
                f"{ctx.zero3_gather_chunks} promises ppermute ring "
                f"stripes for {ctx.zero3_gather_leaves} gathered leaves "
                f"but the step executes only {permutes} "
                f"collective-permute(s) (< {need}) — the ring gather "
                f"schedule did not reach the lowered program",
                {"collective_permutes": permutes, "required": need,
                 "gather_chunks": ctx.zero3_gather_chunks,
                 "gather_leaves": ctx.zero3_gather_leaves,
                 "counts": counts}))
    return findings


def rule_deadlock(ctx):
    """No collective may execute divergently, and concurrent permutes
    must be dep-chained.

    Both facts come from the traced jaxpr (`analysis/jaxpr.py`), i.e.
    they are proven before the program ever runs — which matters because
    the failure mode being detected is a hang, not an exception. A
    collective inside control flow that branches on a device-varying
    value (anything derived from ``lax.axis_index``) strands part of its
    rendezvous on the other branch: fatal always for
    ``ppermute``/collective-permute (global rendezvous — the PR 5
    stage-divergent pipeline deadlock), fatal for grouped collectives
    when the divergence splits their own axis. Separately, two
    ``ppermute``s with no dataflow edge between them can be in flight
    simultaneously and split the in-process runtime's rendezvous — the
    invariant ``parallel.collectives.barrier_after`` exists to maintain,
    checked here instead of assumed."""
    findings = []
    for d in ctx.jaxpr_divergent or ():
        findings.append(Finding(
            "deadlock", SEV_ERROR, d["message"],
            {"primitive": d.get("primitive"),
             "axes": list(d.get("axes", ())),
             "divergent_axes": list(d.get("divergent_axes", ())),
             "path": list(d.get("path", ()))}))
    for d in ctx.jaxpr_unordered or ():
        findings.append(Finding(
            "deadlock", SEV_ERROR, d["message"],
            {"kind": "unordered_permutes",
             "path": list(d.get("path", ())),
             "eqns": list(d.get("eqns", ()))}))
    for s in ctx.collective_sites or ():
        # source-level confession: an emitter declared it skipped the
        # dep-chain (parallel.collectives SiteRecord.chained=False).
        if s.get("primitive") == "ppermute" and not s.get("chained", True):
            findings.append(Finding(
                "deadlock", SEV_ERROR,
                f"collective site {s.get('site')!r} emits ppermutes over "
                f"axis {s.get('axis')!r} outside the barrier_after "
                f"dep-chain: concurrent in-flight permutes split the "
                f"global rendezvous",
                {"kind": "unchained_site", "site": dict(s)}))
    return findings


def rule_resharding(ctx):
    """Sharding-flow hygiene: no accidental replication, no unattributed
    reshards.

    From the PartitionSpec propagation over the traced jaxpr: operands
    meeting with *conflicting* placements force a compiler-inserted
    reshard (an all-gather + reslice) that no declared overlap/gather
    site accounts for — flagged per conflict above
    ``min_reshard_bytes``. Separately, a ZeRO run (stage >= 1) whose
    optimizer state contains large fully-replicated leaves is paying
    stage-0 memory while claiming otherwise — the partitioner
    (`zero/sharding.py`) legitimately replicates only small or
    non-divisible leaves, so big replicated ones mean the spec never
    attached. (ZeRO-1/2's param-refresh all-gathers are GSPMD-implicit
    sharding declarations, not jaxpr eqns, so attribution here is
    config-driven: the refresh allowance lives in ``rule_zero_budget``'s
    byte ceilings, while this rule polices placements.)

    An explicit gather-on-use stage-3 run (`zero/stage3.py`) *declares*
    its gather/re-shard traffic through ``SiteRecord``s (sites
    ``zero3_gather`` / ``zero3_reshard``): conflict events no larger
    than the declared per-leaf gather are attributed to that schedule
    and exempted. A stage-3 run whose trace registered NO zero3 sites
    gets no exemption — an unregistered gather still fires here."""
    findings = []
    big = [e for e in ctx.reshard_events or ()
           if e.get("bytes", 0) >= ctx.min_reshard_bytes]
    if big and ctx.zero_stage >= 3 and ctx.zero3_max_gather_bytes > 0:
        zero3_sites = [s for s in ctx.collective_sites or ()
                       if str(s.get("site", "")).startswith("zero3_")]
        if zero3_sites:
            allow = 2 * ctx.zero3_max_gather_bytes + 4096
            big = [e for e in big if e.get("bytes", 0) > allow]
    if big:
        total = sum(e["bytes"] for e in big)
        findings.append(Finding(
            "resharding", SEV_WARNING,
            f"{len(big)} operand join(s) with conflicting "
            f"PartitionSpecs (largest {_fmt_bytes(max(e['bytes'] for e in big))}, "
            f"total {_fmt_bytes(total)}) force compiler-inserted "
            f"reshards not attributable to any declared gather site",
            {"events": big[:8], "total_bytes": total}))
    if ctx.zero_stage >= 1 and ctx.n_devices > 1:
        rep = [l for l in ctx.replicated_leaves or ()
               if l.get("bytes", 0) >= ctx.min_reshard_bytes]
        if rep:
            total = sum(l["bytes"] for l in rep)
            findings.append(Finding(
                "resharding", SEV_ERROR,
                f"stage-{ctx.zero_stage} run holds {len(rep)} large "
                f"fully-replicated optimizer-state leaves "
                f"({_fmt_bytes(total)}) — the ZeRO partition spec never "
                f"attached; every device pays stage-0 memory",
                {"leaves": rep[:8], "total_bytes": total}))
    return findings


def rule_peak_memory(ctx):
    """Static peak device memory must fit the per-ZeRO-stage budget.

    The liveness estimate (`analysis/hlo.py:estimate_peak_memory`) is
    checked against an explicit ``peak_budget_bytes`` when configured,
    else a generous per-stage formula in units of M (fp32 master bytes):
    params (M) + optimizer state (3M, sharded /N under ZeRO >= 1, 0 on
    device under offload) + 3M of gradients/activations headroom. Toy
    flavors sit near 50% of this; the rule exists to catch
    order-of-magnitude regressions (a lost donation doubling state, a
    replicated optimizer) on real models — exact orderings are pinned
    by tests, not here."""
    est = ctx.peak_memory
    if not est or ctx.param_bytes <= 0:
        return []
    m_bytes = ctx.param_bytes
    budget = ctx.peak_budget_bytes
    if not budget:
        n = max(ctx.n_devices, 1)
        if ctx.offload:
            opt_m = 0.0
        elif ctx.zero_stage >= 1:
            opt_m = 3.0 / n
        else:
            opt_m = 3.0
        budget = int(m_bytes * (1.0 + opt_m + 3.0)) + 2 * _slack(ctx)
    peak = est.get("peak_bytes", 0)
    if peak <= budget:
        return []
    return [Finding(
        "peak_memory", SEV_ERROR,
        f"static peak-memory estimate {_fmt_bytes(peak)} exceeds the "
        f"stage-{ctx.zero_stage} budget {_fmt_bytes(budget)} "
        f"(M = {_fmt_bytes(m_bytes)}; args "
        f"{_fmt_bytes(est.get('parameter_bytes', 0))} + liveness peak "
        f"{_fmt_bytes(est.get('temp_peak_bytes', 0))})",
        {"peak_bytes": peak, "budget_bytes": budget,
         "parameter_bytes": est.get("parameter_bytes", 0),
         "temp_peak_bytes": est.get("temp_peak_bytes", 0),
         "donated_output_bytes": est.get("donated_output_bytes", 0),
         "zero_stage": ctx.zero_stage, "param_bytes": m_bytes})]


def rule_fp8(ctx):
    """The promised fp8 compute and quantized wire must be in the HLO.

    ``fp8_enabled`` promises qdq matmuls: the lowered step must carry
    ``f8e4m3fn``-typed values (forward-operand quantizes — on CPU the
    explicit converts next to the f32 dot, on TPU the operands of the
    fused native fp8 GEMM) AND ``f8e5m2``-typed values (the backward
    cotangent quantizes); either missing means the fp8 rewiring was
    silently dropped — paying bf16/fp32 compute while claiming fp8.

    ``fp8_wire_dtype`` promises quantized collective payloads: at least
    one collective must move a 1-byte element type (the bitcast-packed
    ``u8`` wire buffer from `runtime/comm/codecs.py:encode_wire`, or
    raw ``s8``/fp8 elements). Zero 1-byte collective bytes means every
    ring/gather still ships full precision."""
    if not ctx.fp8_enabled and not ctx.fp8_wire_dtype:
        return []
    findings = []
    if ctx.fp8_enabled:
        counts = fp8_value_counts(ctx.hlo_text)
        e4 = sum(n for dt, n in counts.items() if dt.startswith("f8e4m3"))
        e5 = counts.get("f8e5m2", 0)
        if e4 == 0:
            findings.append(Finding(
                "fp8", SEV_ERROR,
                "fp8 is enabled but the lowered step carries no "
                "f8e4m3fn-typed values — no forward operand is "
                "quantized; the fp8 matmul rewiring did not reach the "
                "compiled program",
                {"fp8_value_counts": counts}))
        if e5 == 0:
            findings.append(Finding(
                "fp8", SEV_ERROR,
                "fp8 is enabled but the lowered step carries no "
                "f8e5m2-typed values — backward cotangents are not "
                "quantized (out_qdq missing from the backward)",
                {"fp8_value_counts": counts}))
    if ctx.fp8_wire_dtype:
        cb = collective_bytes(ctx.hlo_text, by_dtype=True)
        wire = 0
        for op, d in cb.items():
            if op == "total":
                continue
            wire += sum(b for dt, b in d.items()
                        if dt in ("u8", "s8") or dt.startswith("f8"))
        if wire == 0:
            findings.append(Finding(
                "fp8", SEV_ERROR,
                f"fp8 wire_dtype={ctx.fp8_wire_dtype!r} promises "
                f"quantized collective payloads but no collective moves "
                f"a 1-byte element type — every gather/ring still ships "
                f"full precision",
                {"wire_dtype": ctx.fp8_wire_dtype,
                 "collective_bytes_by_dtype":
                     {op: dict(d) for op, d in cb.items()
                      if op != "total"}}))
    return findings


def rule_decode(ctx):
    """The serving loop's recompile contract and cache-dtype hygiene.

    The decode engine compiles exactly two programs (chunked prefill +
    decode) and reuses them for the whole serve; admission, eviction
    and seq buckets are host-side bookkeeping that must never reach a
    jit boundary. ``decode_compile_counts`` is the engine's jit-cache
    census after a stream crossed bucket sizes — growth past
    ``decode_expected_compiles`` is the mid-stream recompile the whole
    design exists to prevent (every extra entry stalls live requests
    for a full XLA compile).

    Cache hygiene: the KV cache's payload leaves must store ONE dtype,
    and when ``kv_cache_dtype`` names a codec it must be that codec's
    dtype — a mixed or full-precision census means some layer's cache
    silently skipped quantization and the promised HBM saving is gone.

    The pool (``decode_page_facts``): the page tables are
    fixed-shape device data — steady-state decode must lower ZERO host
    transfer ops (a page gather routed through infeed/outfeed or a host
    callback stalls every step; host-tier parking runs outside the
    compiled programs), and the pool geometry must be internally
    consistent (page 0 is the reserved trash page, so ``n_pages >= 2``;
    ``pages_per_row * page_size`` must cover ``max_seq`` exactly, else
    some row positions have no page-table entry and decode reads the
    trash page as live KV).

    Disaggregated tiers (``disagg_tier_counts``): each tier pins
    exactly ONE compiled program — its own — warmup-to-drain; an entry
    in the other tier's jit cache means the tier boundary leaked (a
    prefill worker decoding, or vice versa). And because the handoff
    is a raw page copy keyed by the page table, both tiers must share
    ``page_size``/``pages_per_row`` exactly (``disagg_page_facts``) —
    a mismatch scatters prefilled KV into the wrong pool offsets.
    """
    if ctx.decode_compile_counts is None and \
            ctx.decode_cache_census is None and \
            ctx.decode_page_facts is None and \
            ctx.disagg_tier_counts is None:
        return []
    findings = []
    if ctx.decode_page_facts is not None:
        hits = host_transfer_ops(ctx.hlo_text) if ctx.hlo_text else []
        if hits:
            kinds = sorted({h["kind"] for h in hits})
            findings.append(Finding(
                "decode", SEV_ERROR,
                f"paged decode program lowers {len(hits)} host transfer "
                f"op(s) ({', '.join(kinds)}) — page-table gathers must "
                f"stay on device; a host round-trip in steady-state "
                f"decode stalls every step",
                {"count": len(hits), "kinds": kinds,
                 "ops": [h["line"][:200] for h in hits[:8]]}))
        pf = ctx.decode_page_facts
        ps = pf.get("page_size", 0)
        n_pg = pf.get("n_pages", 0)
        ppr = pf.get("pages_per_row", 0)
        max_seq = pf.get("max_seq", 0)
        if pf:
            if ps < 1 or n_pg < 2 or ppr < 1:
                findings.append(Finding(
                    "decode", SEV_ERROR,
                    f"paged cache geometry is degenerate (page_size="
                    f"{ps}, n_pages={n_pg}, pages_per_row={ppr}) — the "
                    f"pool needs >= 2 pages (page 0 is the reserved "
                    f"trash page) and a positive page size",
                    {"page_facts": dict(pf)}))
            elif max_seq and ppr * ps != max_seq:
                findings.append(Finding(
                    "decode", SEV_ERROR,
                    f"paged cache geometry mismatch: pages_per_row="
                    f"{ppr} x page_size={ps} = {ppr * ps} does not "
                    f"cover max_seq={max_seq} — positions past the "
                    f"table read the trash page as live KV",
                    {"page_facts": dict(pf)}))
    if ctx.disagg_tier_counts:
        pins = {"prefill": {"prefill": 1, "decode": 0},
                "decode": {"prefill": 0, "decode": 1}}
        for tier, counts in sorted(ctx.disagg_tier_counts.items()):
            want = pins.get(tier)
            if want is None:
                continue
            got = {p: int((counts or {}).get(p) or 0)
                   for p in ("prefill", "decode")}
            if got != want:
                findings.append(Finding(
                    "decode", SEV_ERROR,
                    f"disaggregated {tier} tier holds compile counts "
                    f"{got} (expected {want}) — each tier pins exactly "
                    f"one compiled program, its own, warmup-to-drain; "
                    f"any entry in the other tier's program means the "
                    f"tier boundary leaked",
                    {"tier": tier, "counts": got, "expected": want}))
    dpf = ctx.disagg_page_facts
    if dpf and "prefill" in dpf and "decode" in dpf:
        for key in ("page_size", "pages_per_row"):
            a = (dpf.get("prefill") or {}).get(key)
            b = (dpf.get("decode") or {}).get(key)
            if a != b:
                findings.append(Finding(
                    "decode", SEV_ERROR,
                    f"handoff geometry mismatch: prefill tier {key}="
                    f"{a} vs decode tier {key}={b} — the KV handoff "
                    f"is a raw page copy keyed by the page table, so "
                    f"both tiers must share the paged geometry "
                    f"exactly",
                    {"key": key, "prefill": a, "decode": b}))
    for prog, n in sorted((ctx.decode_compile_counts or {}).items()):
        if n is not None and n > ctx.decode_expected_compiles:
            findings.append(Finding(
                "decode", SEV_ERROR,
                f"serving {prog} program accumulated {n} jit cache "
                f"entries (expected {ctx.decode_expected_compiles}) — "
                f"a shape or dtype leaked into the compiled boundary "
                f"and the decode loop recompiled mid-stream",
                {"program": prog, "cache_size": n,
                 "expected": ctx.decode_expected_compiles}))
    census = ctx.decode_cache_census
    if census:
        if len(census) > 1:
            findings.append(Finding(
                "decode", SEV_ERROR,
                f"KV cache payload leaves store mixed dtypes "
                f"{sorted(census)} — every layer's cache must share one "
                f"storage dtype",
                {"census": dict(census),
                 "kv_cache_dtype": ctx.decode_kv_cache_dtype}))
        from deepspeed_tpu.runtime.comm.codecs import CODECS
        codec = CODECS.get(ctx.decode_kv_cache_dtype)
        if codec is not None:
            import jax.numpy as jnp
            want = str(jnp.dtype(codec.dtype))
            stray = sorted(dt for dt in census if dt != want)
            if stray:
                findings.append(Finding(
                    "decode", SEV_ERROR,
                    f"kv_cache_dtype={ctx.decode_kv_cache_dtype!r} "
                    f"promises {want} cache storage but payload leaves "
                    f"store {stray} — quantization silently skipped; "
                    f"the promised KV HBM saving is not happening",
                    {"census": dict(census), "expected_dtype": want,
                     "kv_cache_dtype": ctx.decode_kv_cache_dtype}))
    return findings


def rule_flash_decode(ctx):
    """Flash decode actually deleted the dense attention work.

    When the engine promises ``attention_impl="flash"`` the compiled
    decode program must show it, not just route through a differently-
    named Python function:

    - on TPU the Pallas kernel lowers to a ``custom-call`` — its
      absence means the kernel silently fell back to something XLA
      made up (interpret mode off-TPU inlines the kernel as plain HLO,
      so that pin is platform-gated);
    - NO dot may touch a full cache-payload-shaped array
      (`analysis/hlo.py:payload_shaped_dots`): one surviving
      contraction of a whole pool leaf means the
      dense softmax is still running and the O(max_seq) HBM traffic
      the kernel exists to delete is still being paid;
    - with a quantized cache, NO f32 value may be cache-payload-shaped
      (`payload_shaped_values`): such a value is the dense path's
      dequantized HBM copy — flash dequantizes in-register per block;
    - over the paged pool, NO ``copy`` may have a pool leaf's shape
      (`payload_shaped_copies`): the pool is donated and written a page
      slab at a time, in the layout the kernel reads — the kernel takes
      the 4-D pool as it is, in ``ANY`` memory, and cuts its ``(H, D,
      block_k)`` blocks from it itself — so such a copy is XLA re-laying
      out the whole reserved pool on every step, live or not (measured
      at three quarters of a step: `PERF.md`, PR 25). Judged where the
      kernel is a kernel: off-TPU interpret mode inlines it as plain
      HLO and carries the ``ANY`` operand through its emulated grid
      loop by copy, which says nothing about the chip
      (`tests/unit/test_tpu_compile.py` holds the compiled program at 0
      on a described v5e).
    """
    if ctx.decode_attention_impl != "flash":
        return []
    findings = []
    if ctx.decode_platform == "tpu" and "custom-call" not in ctx.hlo_text:
        findings.append(Finding(
            "flash_decode", SEV_ERROR,
            "attention_impl='flash' on TPU but the decode program "
            "contains no custom-call — the Pallas flash-decode kernel "
            "never made it into the lowering",
            {"platform": ctx.decode_platform}))
    payload = ctx.decode_cache_payload_shape
    if payload:
        from deepspeed_tpu.analysis.hlo import (payload_shaped_copies,
                                                payload_shaped_dots,
                                                payload_shaped_values)
        copies = payload_shaped_copies(ctx.hlo_text, payload) \
            if ctx.decode_platform in (None, "tpu") else []
        if copies:
            findings.append(Finding(
                "flash_decode", SEV_ERROR,
                f"the paged decode program copies the whole pool: "
                f"{len(copies)} copy op(s) of a pool leaf's shape "
                f"{tuple(payload)} — a write or a kernel operand that "
                f"XLA cannot take in the pool's own layout",
                {"payload_shape": tuple(payload),
                 "pool_shaped_copies": len(copies),
                 "copies": copies[:8]}))
        dots = payload_shaped_dots(ctx.hlo_text, payload)
        if dots:
            findings.append(Finding(
                "flash_decode", SEV_ERROR,
                f"attention_impl='flash' but {len(dots)} dot(s) still "
                f"contract over the full cache payload shape "
                f"{tuple(payload)} — the dense attention softmax "
                f"survived the rewrite",
                {"payload_shape": tuple(payload),
                 "dots": dots[:8]}))
        from deepspeed_tpu.runtime.comm.codecs import CODECS
        if ctx.decode_kv_cache_dtype in CODECS:
            n = payload_shaped_values(ctx.hlo_text, "f32", payload)
            if n:
                findings.append(Finding(
                    "flash_decode", SEV_ERROR,
                    f"quantized KV cache "
                    f"({ctx.decode_kv_cache_dtype!r}) but the decode "
                    f"program materializes {n} f32 cache-payload-"
                    f"shaped value(s) — a full-precision dequantized "
                    f"cache copy is being written to HBM",
                    {"payload_shape": tuple(payload),
                     "f32_payload_values": n,
                     "kv_cache_dtype": ctx.decode_kv_cache_dtype}))
    return findings


def rule_speculative(ctx):
    """Self-speculative decoding's pinned contracts.

    Program-count contract: a speculative serve compiles exactly THREE
    programs — prefill, draft, verify — and the plain decode program
    stays at ZERO jit-cache entries. One decode entry means the
    scheduler silently fell back to token-at-a-time mid-stream (the
    speedup is gone and nobody noticed); draft/verify above 1 means a
    shape (draft window, batch, bucket) leaked into a jit boundary.

    Truncation contract: the draft program must actually run only
    ``draft_layers`` of ``n_layer`` blocks. XLA cost-analysis flops for
    the draft step vs a same-shape full-depth step prove it — the
    ratio must sit near draft_layers/n_layer, not near 1.0 (a ratio
    near 1.0 means the truncation knob never reached the lowering and
    the "draft" pays full-model cost for approximate tokens).

    Accept-loop invariants: every verify round emits the correction /
    bonus token even when all drafts miss, so ``mean_accepted`` (tokens
    emitted per row-round) is >= 1.0 BY CONSTRUCTION — below 1.0 the
    accept machinery is dropping tokens. ``draft_efficiency`` is a
    fraction of drafted tokens and must stay within [0, 1].

    Paged layout: draft and verify are steady-state programs — both
    must lower zero host-transfer ops, same as plain decode. Flash
    draft (T=1) on TPU must carry the Pallas custom-call and must not
    contract over the full cache payload shape (verify always runs
    dense full-depth; its payload dots are expected).
    """
    if ctx.spec_facts is None:
        return []
    findings = []
    facts = ctx.spec_facts
    expected = {"prefill": 1, "decode": 0, "draft": 1, "verify": 1}
    for prog, want in sorted(expected.items()):
        n = (ctx.spec_compile_counts or {}).get(prog)
        if n is None or n == want:
            continue
        if prog == "decode":
            msg = (f"speculative serve entered the plain decode "
                   f"program {n} time(s) — speculation silently fell "
                   f"back to token-at-a-time decoding mid-stream")
        else:
            msg = (f"speculative {prog} program accumulated {n} jit "
                   f"cache entries (expected {want}) — a shape or "
                   f"dtype leaked into the compiled boundary")
        findings.append(Finding(
            "speculative", SEV_ERROR, msg,
            {"program": prog, "cache_size": n, "expected": want,
             "compile_counts": dict(ctx.spec_compile_counts or {})}))
    dl = facts.get("draft_layers", 0)
    nl = facts.get("n_layer", 0)
    if not 0 < dl < nl:
        findings.append(Finding(
            "speculative", SEV_ERROR,
            f"degenerate draft depth draft_layers={dl} of n_layer={nl} "
            f"reached the engine — the builder must disable "
            f"speculation (2-program fallback) instead of drafting at "
            f"full depth",
            {"facts": dict(facts)}))
    if ctx.spec_full_flops and ctx.spec_draft_flops:
        ratio = ctx.spec_draft_flops / ctx.spec_full_flops
        # non-layer work (embeddings, ln_f, lm_head) is shared, so the
        # honest ratio lands between draft_layers/n_layer and 1;
        # flagging past the midpoint catches the failure mode this pin
        # exists for (truncation never lowered -> ratio ~= 1.0)
        bound = (dl / nl + 1.0) / 2.0 if nl else 1.0
        if ratio > bound:
            findings.append(Finding(
                "speculative", SEV_ERROR,
                f"draft step costs {ratio:.2f}x the full-depth step "
                f"(expected ~{dl}/{nl} = {dl / nl if nl else 0:.2f}, "
                f"bound {bound:.2f}) — the layer truncation never "
                f"reached the lowering and the draft pays full-model "
                f"flops",
                {"draft_flops": ctx.spec_draft_flops,
                 "full_flops": ctx.spec_full_flops,
                 "ratio": ratio, "bound": bound}))
    rounds = facts.get("row_rounds", 0)
    mean_acc = facts.get("mean_accepted", 0.0)
    if rounds and mean_acc < 1.0:
        findings.append(Finding(
            "speculative", SEV_ERROR,
            f"mean accepted tokens/round is {mean_acc:.3f} over "
            f"{rounds} row-round(s) — every verify emits at least the "
            f"correction token, so < 1.0 means the accept loop is "
            f"dropping tokens",
            {"facts": dict(facts)}))
    eff = facts.get("draft_efficiency", 0.0)
    if not 0.0 <= eff <= 1.0:
        findings.append(Finding(
            "speculative", SEV_ERROR,
            f"draft_efficiency {eff:.3f} outside [0, 1] — accepted "
            f"draft count exceeds drafted count; the accept gather is "
            f"reading past the draft window",
            {"facts": dict(facts)}))
    for name, hlo in (("draft", ctx.spec_draft_hlo),
                      ("verify", ctx.spec_verify_hlo)):
        hits = host_transfer_ops(hlo) if hlo else []
        if hits:
            kinds = sorted({h["kind"] for h in hits})
            findings.append(Finding(
                "speculative", SEV_ERROR,
                f"paged speculative {name} program lowers "
                f"{len(hits)} host transfer op(s) "
                f"({', '.join(kinds)}) — page-table gathers must "
                f"stay on device in every steady-state program",
                {"program": name, "count": len(hits),
                 "kinds": kinds,
                 "ops": [h["line"][:200] for h in hits[:8]]}))
    if ctx.decode_attention_impl == "flash" and ctx.spec_draft_hlo:
        if ctx.decode_platform == "tpu" and \
                "custom-call" not in ctx.spec_draft_hlo:
            findings.append(Finding(
                "speculative", SEV_ERROR,
                "attention_impl='flash' on TPU but the draft program "
                "contains no custom-call — the T=1 draft step lost the "
                "Pallas flash-decode kernel",
                {"platform": ctx.decode_platform}))
        payload = ctx.decode_cache_payload_shape
        if payload:
            from deepspeed_tpu.analysis.hlo import payload_shaped_dots
            dots = payload_shaped_dots(ctx.spec_draft_hlo, payload)
            if dots:
                findings.append(Finding(
                    "speculative", SEV_ERROR,
                    f"attention_impl='flash' but the draft program "
                    f"still contracts over the full cache payload "
                    f"shape {tuple(payload)} in {len(dots)} dot(s) — "
                    f"dense attention survived in the draft step",
                    {"payload_shape": tuple(payload),
                     "dots": dots[:8]}))
    return findings


def rule_kernel_vmem(ctx):
    """Every pallas_call's per-grid-step working set fits in VMEM.

    The working set is the double-buffered input+output block bytes
    plus declared scratch (`kernels.KernelFacts.vmem_bytes`) against
    the platform budget (`cost.Platform.vmem_bytes`). Interpret-mode CI
    executes any block shape happily; on hardware an over-budget config
    is a Mosaic compile failure — this rule is the only place the
    constraint is checked before a TPU sees the program.
    """
    ana = ctx.kernel_analysis
    if ana is None:
        return []
    findings = []
    budget = ana.vmem_budget_bytes
    for k in ana.kernels:
        if k.vmem_bytes > budget:
            findings.append(Finding(
                "kernel_vmem", SEV_ERROR,
                f"kernel '{k.name}': per-grid-step VMEM working set "
                f"{_fmt_bytes(k.vmem_bytes)} exceeds the "
                f"{ana.platform} budget {_fmt_bytes(budget)} "
                f"(blocks {_fmt_bytes(k.block_bytes_per_step)} "
                f"double-buffered + scratch "
                f"{_fmt_bytes(k.scratch_bytes)})",
                {"kernel": k.name, "vmem_bytes": k.vmem_bytes,
                 "budget_bytes": budget,
                 "block_bytes_per_step": k.block_bytes_per_step,
                 "scratch_bytes": k.scratch_bytes,
                 "grid": list(k.grid)}))
    return findings


def rule_kernel_tiling(ctx):
    """Block trailing dims respect the dtype's native TPU tile.

    Native register tiles are (8, 128) f32, (16, 128) bf16, (32, 128)
    int8/fp8 (`kernels.SUBLANES`). A block whose lane dim is not a
    multiple of 128, or whose sublane dim is not a multiple of the
    dtype's sublane count, pads to full tiles on every load — silently
    wasting VMEM and bandwidth. Geometry-forced dims (block == array
    extent, singleton indexed dims) are exempt; see
    `kernels._tiling_lint`.
    """
    ana = ctx.kernel_analysis
    if ana is None:
        return []
    findings = []
    for k in ana.kernels:
        for t in k.tiling:
            findings.append(Finding(
                "kernel_tiling", SEV_WARNING,
                f"kernel '{k.name}' operand {t['operand']}: "
                f"{t['axis']} block dim {t['block_dim']} is not a "
                f"multiple of the {t['dtype']} native tile "
                f"{t['tile']} (array dim {t['array_dim']}) — every "
                f"touch pads to full tiles",
                {"kernel": k.name, **t}))
    return findings


def rule_kernel_dma(ctx):
    """Grid-write safety and the DMA-elision proof.

    An output block revisited at NON-consecutive grid steps is a race
    under Pallas's grid semantics: the block is flushed when the grid
    moves away, so the revisit reads back stale data (consecutive
    revisits are the legitimate carried-accumulator idiom and pass).

    When the audit declares an elision contract
    (``kernel_expected_elision``, the dead-block fraction implied by
    the analysis scenario's positions), the byte-weighted INPUT elided
    fraction proved by the index-map sweep must reach it — the static
    proof that dead cache blocks cost no DMA, instead of asserting it
    in prose. A kernel with clamped index maps elides them; the paged
    decode kernel launches no dead block at all: its pool operands stay
    in HBM and their fetches are its declared walk
    (`kernels.MANUAL_WALKS`), held to the same contract.
    """
    ana = ctx.kernel_analysis
    if ana is None:
        return []
    findings = []
    for k in ana.kernels:
        for race in k.races:
            findings.append(Finding(
                "kernel_dma", SEV_ERROR,
                f"kernel '{k.name}' operand {race['operand']}: output "
                f"block {tuple(race['block'])} is written at "
                f"non-consecutive grid steps {race['steps'][:6]} — "
                f"the block is flushed between visits and the revisit "
                f"reads stale data",
                {"kernel": k.name, **race}))
    if ctx.kernel_expected_elision is not None:
        in_dma = in_dense = 0
        unevaluated = []
        for k in ana.kernels:
            inputs = [op for op in k.operands if op.kind == "input"]
            if k.tile_steps is not None:
                # a kernel that walks (q tile, kv tile) pairs is judged
                # on the tiles it visits against the rectangle's: what
                # the causal mask kills should not be stepped over, let
                # alone fetched
                in_dma += len(k.tile_steps) * k.block_bytes_per_step
                in_dense += k.tile_rectangle * k.block_bytes_per_step
                continue
            # a kernel that walks the cache by manual DMA is judged on
            # what it walks: its pipelined inputs are one row block a
            # grid step, with nothing to elide
            walked = [op for op in inputs if op.manual_dma]
            for op in walked or inputs:
                in_dma += op.dma_fetches * op.block_bytes
                in_dense += op.total_fetches * op.block_bytes
                if not op.index_map_evaluated:
                    unevaluated.append(f"{k.name}/{op.name}")
        proved = 1.0 - in_dma / in_dense if in_dense else 0.0
        expected = float(ctx.kernel_expected_elision)
        if unevaluated:
            findings.append(Finding(
                "kernel_dma", SEV_WARNING,
                f"elision contract declared but "
                f"{len(unevaluated)} operand index map(s) could not "
                f"be evaluated ({', '.join(unevaluated[:4])}) — the "
                f"DMA-elision proof is incomplete",
                {"unevaluated": unevaluated}))
        elif proved + 1e-6 < expected:
            findings.append(Finding(
                "kernel_dma", SEV_WARNING,
                f"index maps elide only {proved:.1%} of input block "
                f"DMAs; the scenario's occupancy requires "
                f"{expected:.1%} — dead cache blocks are being "
                f"fetched (unclamped index map, or a walk past the "
                f"rows' positions?)",
                {"proved_elision": round(proved, 6),
                 "expected_elision": round(expected, 6),
                 "input_dma_bytes": in_dma,
                 "input_dense_bytes": in_dense}))
    return findings


# Rule catalog: id -> rule. `recompile` is listed for config validation
# but runs in the orchestrator (it needs live step objects, not HLO).
RULES = {
    "donation": rule_donation,
    "dtype_hygiene": rule_dtype_hygiene,
    "zero_budget": rule_zero_budget,
    "host_transfer": rule_host_transfer,
    "trip_count": rule_trip_count,
    "overlap": rule_overlap,
    "deadlock": rule_deadlock,
    "resharding": rule_resharding,
    "peak_memory": rule_peak_memory,
    "fp8": rule_fp8,
    "decode": rule_decode,
    "flash_decode": rule_flash_decode,
    "speculative": rule_speculative,
    "kernel_vmem": rule_kernel_vmem,
    "kernel_tiling": rule_kernel_tiling,
    "kernel_dma": rule_kernel_dma,
}
RULE_IDS = tuple(RULES) + ("recompile",)


def run_rules(ctx, rules=None):
    """Run the catalog (or the named subset) over one step's context."""
    findings = []
    for rule_id, rule in RULES.items():
        if rules is not None and rule_id not in rules:
            continue
        if rule_id in ctx.skip_rules:
            continue
        findings.extend(rule(ctx))
    findings.sort(key=lambda f: -_SEV_RANK.get(f.severity, 0))
    return findings

"""Driver of the serving cells of a Nemotron-H model held as a share
(`deepspeed_tpu/models/nemotron_h.py`: blocks that are a Mamba-2 mixer,
an attention or an expert layer alone; experts that work in a latent):
``InferenceEngine`` + ``ContinuousBatchingScheduler`` built as
``inference/serve.py:main`` builds them, under ``drivers/serve.py``'s
open loop (its ``warm_up``, ``serve_loop``, ``Tracker`` and
``install_spans``, imported, so a token is stamped here as it is
there), on ``drivers/serve_hybrid.py``'s ordered arrivals
(``arrivals_of``) and compiled-program scope maps (``program_scopes``),
imported too.

What is this file's own: the model and its bfloat16 weights from the
configuration file (`model_config`: the share is the file's
``n_layer``, ``vocab_size`` and ``assumed.experts_held``); the checks
behind ``correct`` (the reference is ``reference/nemotron_h_ref.py``;
beside the generated tokens' logits, four checks of one layer **on its
own input**, each with a limit in the workload's ``correctness`` block:
the first mixer's state as the engine's own leaves hold it, a mixer's
output, the attention layer's output, an expert layer's output on the
share); and the facts the metrics read (`flops_ssm.py`,
`flops_nemotron_h.py`). ``measure``'s arithmetic of the end-to-end
numbers is ``drivers/serve.py``'s, written out a fourth time because no
``measure`` takes its checks as an argument (`PERF.md`, section 7 (j)).

Workload file keys: as ``drivers/serve_hybrid.py``'s.
"""

import dataclasses

import numpy as np

from benchmarks.suite import harness, stats
from benchmarks.suite.drivers.serve import (install_spans, serve_loop,
                                            warm_up)
from benchmarks.suite.drivers.serve_hybrid import (arrivals_of,
                                                   program_scopes,
                                                   state_diff)
from benchmarks.suite.harness import clock
from benchmarks.suite.reference import nemotron_h_ref as ref

__all__ = ["build", "warm_up", "measure", "run"]

MIXER_LAYER = "layers_0"    # the state's check: nothing upstream of it


def model_config(config, group="serve", **extra):
    """The program's config class from a configuration file."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig

    names = {f.name for f in dataclasses.fields(NemotronHConfig)}
    kw = {k: v for k, v in config.items() if k in names}
    assumed, g = config["assumed"], config[group]
    kw.update(
        num_hidden_layers=config["n_layer"],
        hybrid_override_pattern=ref.pattern_of(config),
        initializer_range=assumed["initializer_range"],
        router_bias_range=assumed["router_bias_range"],
        experts_held=tuple(assumed["experts_held"]),
        dtype=getattr(jnp, g["compute_dtype"]),
        param_dtype=getattr(jnp, g["param_dtype"]))
    kw.update(extra)
    return NemotronHConfig(**kw)


def build(ctx):
    import jax
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.models.nemotron_h import (NemotronHLM,
                                                 init_nemotron_h_params)

    model = NemotronHLM(model_config(ctx.config))
    params = init_nemotron_h_params(
        model, jax.random.PRNGKey(ctx.seed % (2 ** 31)))
    inf = dict(ctx.workload["inference"])
    inf["seq_buckets"] = tuple(inf["seq_buckets"])
    inf["sampling_seed"] = ctx.seed % (2 ** 31)
    engine = InferenceEngine(model, params, config=inf)
    return engine, ContinuousBatchingScheduler(engine)


# --- the checks behind ``correct`` ----------------------------------------

def check_logits(ctx, engine, tracker, rids):
    """As the chat cell's: the reference's full forward over prompt +
    generated tokens (token by token through the mixers, no cache, a
    loop over the held experts) must put every generated token within
    ``logit_rtol`` x max|logit| of its position's largest logit. One
    padded length, so the reference compiles once."""
    rtol = ctx.workload["correctness"]["logit_rtol"]
    out = []
    for rid in rids:
        prompt, toks = tracker.prompts[rid], tracker.tokens[rid]
        seq = np.zeros(engine.max_seq, np.int32)
        seq[:len(prompt) + len(toks)] = prompt + toks
        rows = np.arange(len(prompt) - 1, len(prompt) + len(toks) - 1)
        lg = np.asarray(ref.forward(engine.params, seq, ctx.config,
                                    rows=rows)[0])
        scale = float(np.abs(lg).max())
        short = lg.max(axis=1) - lg[np.arange(len(toks)), toks]
        out.append({"rid": rid, "tokens": len(toks),
                    "prompt_len": len(prompt),
                    "max_shortfall": float(short.max()),
                    "tolerance": rtol * scale,
                    "shortfall_over_scale": float(short.max() / scale),
                    "ok": bool(short.max() <= rtol * scale)})
    return out


def check_state(ctx, engine, prompt, generated, slot=0, decode_steps=64,
                short=130, reference=None):
    """The first mixer's state **as the engine's own leaves hold it**,
    against the reference's at the same position
    (`serve_hybrid.state_diff`: the worst head over its own largest
    entry), in a slot that has had other tenants and through pages
    handed over in descending order, three times: after the prefill of
    ``prompt`` (ragged, so its last chunk is padded and the state has
    passed from chunk to chunk); after the prefill of its first
    ``short`` tokens alone (one chunk, mostly padding, short enough
    that what the slot held before would still show); and after
    ``decode_steps`` tokens fed to that through the decode program (the
    generated tokens, then the prompt's own again: any tokens do, both
    sides see the same). The layer's input is the embedding's norm, so
    nothing upstream is in the difference. Catches a padded tail
    leaking into the state, a stale state of the slot's last tenant, a
    state kept in bfloat16, heads on another B/C group.
    (``reference``: `tools/fault_readings_nemotron_h.py`'s way in.)"""
    cfg = ctx.config
    table = np.arange(engine.pages_per_row, 0, -1, dtype=np.int32)

    def engine_state():
        return np.asarray(engine.cache[MIXER_LAYER]["ssm"][slot])

    def ref_state(tokens):
        # padded to the length the logits' check compiled for
        seq = np.zeros(engine.max_seq, np.int32)
        seq[:len(tokens)] = tokens
        return np.asarray((reference or ref.forward)(
            engine.params, seq, cfg, state_at=len(tokens) - 1,
            layers=1)[1][MIXER_LAYER])

    prompt = list(prompt)
    engine.prefill(slot, prompt, table)
    after_long = state_diff(engine_state(), ref_state(prompt))
    head = prompt[:short]
    engine.prefill(slot, head, table)
    after_short = state_diff(engine_state(), ref_state(head))
    fed = (list(generated) + prompt[short:] + prompt)[:decode_steps]
    tokens = np.zeros(engine.max_batch, np.int32)
    positions = np.zeros(engine.max_batch, np.int32)
    tables = np.zeros((engine.max_batch, engine.pages_per_row), np.int32)
    tables[slot] = table
    for j, tok in enumerate(fed):
        tokens[slot], positions[slot] = tok, len(head) + j
        engine.decode(tokens, positions, tables)
    after_decode = state_diff(engine_state(), ref_state(head + fed))
    tol = ctx.workload["correctness"]["state_rtol"]
    return {"layer": MIXER_LAYER, "prompt_len": len(prompt),
            "pad_tokens": -len(prompt) % engine.prefill_chunk,
            "short_prompt": len(head), "decode_steps": len(fed),
            "after_prefill": after_long, "after_short_prefill": after_short,
            "after_decode": after_decode, "tolerance": tol,
            "ok": bool(max(after_long, after_short, after_decode) <= tol)}


def _readings(got, want, n, tol, decode_tol=None, **said):
    """A chunk's ``n`` tokens and the decode rows behind them against
    the reference, each over the reference's largest entry."""
    decode_tol = tol if decode_tol is None else decode_tol
    scale = np.abs(want).max()
    prefill = float(np.abs(got[:n] - want[:n]).max() / scale)
    decode = float(np.abs(got[n:] - want[n:]).max() / scale)
    return {**said, "prefill": prefill, "decode": decode, "tolerance": tol,
            "decode_tolerance": decode_tol,
            "ok": bool(prefill <= tol and decode <= decode_tol)}


def check_mixer(model_cfg, ref_cfg, params, seed, chunk, tol,
                reference=None):
    """One mixer (the first) on its own input: a ragged chunk through
    the program's prefill form (eight scan chunks, the state passed
    between them) from a zero state, then one token through its decode
    form, against the reference's token-by-token mixer on the same
    float32 input. Catches heads that read another B/C group, the gated
    norm over the whole width, the convolution's bias dropped."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.nemotron_h import MIXER, Mamba2Mixer

    name = model_cfg.names(MIXER)[0]
    p = params[name]["mixer"]
    n_valid = chunk - chunk // 7        # ragged
    x = jax.random.normal(jax.random.PRNGKey(seed % (2 ** 31)),
                          (n_valid + 1, model_cfg.hidden_size),
                          jnp.float32).astype(model_cfg.dtype)
    mixer = Mamba2Mixer(model_cfg)

    @jax.jit
    def program(p, x):
        leaves = {"ssm": jnp.zeros((1, model_cfg.mamba_n_heads,
                                    model_cfg.mamba_d_head,
                                    model_cfg.mamba_d_state), jnp.float32),
                  "conv": jnp.zeros((model_cfg.mamba_d_conv - 1, 1,
                                     model_cfg.conv_dim), model_cfg.dtype)}
        padded = jnp.zeros((1, chunk, x.shape[1]), x.dtype)
        padded = padded.at[0, :n_valid].set(x[:n_valid])
        slot = jnp.zeros((1,), jnp.int32)
        y, leaves = mixer.apply(
            {"params": p}, padded, leaves,
            jnp.arange(chunk, dtype=jnp.int32)[None], slot,
            jnp.full((1,), n_valid, jnp.int32))
        y1, _ = mixer.apply(
            {"params": p}, x[None, n_valid:], leaves,
            jnp.full((1, 1), n_valid, jnp.int32), slot,
            jnp.ones((1,), jnp.int32))
        return jnp.concatenate([y[0, :n_valid], y1[0]])

    reference = reference or (lambda p, x: ref.mamba(x, p, ref_cfg)[0])
    want = np.asarray(jax.jit(reference)(p, x.astype(jnp.float32)))
    got = np.asarray(program(p, x), np.float32)
    return _readings(got, want, n_valid, tol, layer=name, tokens=n_valid)


def check_attention(model_cfg, ref_cfg, params, seed, chunk, page_size,
                    impl, tol, decode_tol, reference=None):
    """The attention layer on its own input: a chunk through the dense
    prefill form into a small pool of its own, then one token through
    the decode form (the flash kernel where the cell serves with it: 16
    query heads to each of 2 key heads), against the reference's.
    Catches another score scale than ``head_dim^-0.5`` and a query
    group on the wrong key head. The decode reading has a limit of its
    own (one token's sound reading is well under a chunk's)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.cache import (init_kv_cache,
                                               page_pool_spec)
    from deepspeed_tpu.models.nemotron_h import (ATTENTION,
                                                 GroupedQueryAttention)

    name = model_cfg.names(ATTENTION)[0]
    p = params[name]["attn"]
    spec = page_pool_spec(
        1, chunk + page_size, n_layer=1,
        n_head=model_cfg.num_key_value_heads, head_dim=model_cfg.head_dim,
        compute_dtype=model_cfg.dtype, n_positions=chunk + page_size,
        page_size=page_size)
    x = jax.random.normal(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                          (chunk + 1, model_cfg.hidden_size),
                          jnp.float32).astype(model_cfg.dtype)
    layer = GroupedQueryAttention(model_cfg)

    @jax.jit
    def program(p, x):
        pool = init_kv_cache(spec)["h_0"]
        table = jnp.arange(1, spec.pages_per_row + 1,
                           dtype=jnp.int32)[None]
        y, pool = layer.apply(
            {"params": p}, x[None, :chunk], pool,
            jnp.arange(chunk, dtype=jnp.int32)[None], table,
            {"impl": "dense"})
        y1, _ = layer.apply(
            {"params": p}, x[None, chunk:], pool,
            jnp.full((1, 1), chunk, jnp.int32), table,
            {"impl": impl, "block_k": page_size})
        return jnp.concatenate([y[0], y1[0]])

    reference = reference or (lambda p, x: ref.attention(x, p, ref_cfg))
    want = np.asarray(jax.jit(reference)(p, x.astype(jnp.float32)))
    got = np.asarray(program(p, x), np.float32)
    return _readings(got, want, chunk, tol, decode_tol, layer=name,
                     tokens=chunk)


def check_experts(model_cfg, ref_cfg, params, seed, chunk, rows, tol,
                  reference=None):
    """One expert layer (the first) on its own input: a ragged chunk
    through its prefill shape and a decode step's rows (a third of them
    without a request) through its decode shape, against the
    reference's loop over the held experts in the latent, projected up,
    plus the shared expert. Also reads what share of the tokens the
    router's bias changes the chosen experts of (``bias_moves_choice``:
    were it 0, the bias would check nothing). Catches another
    activation, experts fed something else than the latent, another
    score function, the bias left out of the choice, weights not
    renormalised or not scaled, the shared expert left out or doubled,
    a pair of an expert held elsewhere leaking in."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.nemotron_h import EXPERTS, LatentExperts

    name = model_cfg.names(EXPERTS)[0]
    p = params[name]["experts"]
    first = model_cfg.experts_held[0]
    n_valid = chunk - chunk // 7        # ragged
    x = jax.random.normal(jax.random.PRNGKey((seed + 2) % (2 ** 31)),
                          (n_valid + rows, model_cfg.hidden_size),
                          jnp.float32).astype(model_cfg.dtype)
    live = np.arange(rows) % 3 != 2     # a third of the rows hold nothing
    layer = LatentExperts(model_cfg)

    @jax.jit
    def program(p, x):
        padded = jnp.zeros((1, chunk, x.shape[1]), x.dtype)
        padded = padded.at[0, :n_valid].set(x[:n_valid])
        y, c0 = layer.apply({"params": p}, padded,
                            jnp.arange(chunk)[None] < n_valid)
        y1, c1 = layer.apply({"params": p}, x[n_valid:, None],
                             jnp.asarray(live)[:, None])
        return jnp.concatenate([y[0, :n_valid], y1[:, 0]]), c0, c1

    reference = reference or (lambda p, x: ref.experts(x, p, ref_cfg, first))
    x32 = x.astype(jnp.float32)
    want = np.asarray(jax.jit(reference)(p, x32))
    got, c0, c1 = program(p, x)
    got = np.asarray(got, np.float32)
    # a row without a request: the routed part adds nothing, the shared
    # expert is the row's own business (the scheduler ignores the row)
    keep = np.concatenate([np.ones(n_valid, bool), live])
    want, got = want[keep], got[keep]
    # the share of tokens whose chosen experts the bias changes
    _, with_bias = ref.route(x32, p, ref_cfg)
    _, without = ref.route(
        x32, dict(p, e_score_correction_bias=jnp.zeros_like(
            p["e_score_correction_bias"])), ref_cfg)
    moved = float(np.mean(np.any(np.sort(np.asarray(with_bias), -1) !=
                                 np.sort(np.asarray(without), -1), -1)))
    pairs = (n_valid + int(live.sum())) * model_cfg.num_experts_per_tok
    counted = int(c0[0]) + int(c1[0])
    out = _readings(got, want, n_valid, tol, layer=name, tokens=n_valid,
                    rows=int(live.sum()), bias_moves_choice=moved,
                    pairs_routed=counted,
                    pairs_held=int(c0[1]) + int(c1[1]))
    out["ok"] = bool(out["ok"] and moved > 0.05 and counted == pairs)
    return out


def own_input_checks(ctx, engine, prompt, generated):
    corr = ctx.workload["correctness"]
    cfg = engine.model.config
    chunk = engine.prefill_chunk
    return {
        "state": check_state(ctx, engine, prompt, generated),
        "mixer": check_mixer(cfg, ctx.config, engine.params, ctx.seed,
                             chunk, corr["mixer_rtol"]),
        "attention": check_attention(
            cfg, ctx.config, engine.params, ctx.seed, chunk,
            engine.page_size, engine.attention_impl,
            corr["attention_rtol"], corr["attention_decode_rtol"]),
        "experts": check_experts(
            cfg, ctx.config, engine.params, ctx.seed, chunk,
            engine.max_batch, corr["expert_rtol"])}


# --- what the metrics read -------------------------------------------------

def ring_facts(t0, t1):
    """From the program's own spans that closed in ``[t0, t1)`` (the
    profiled segment), the means over its decode steps of what a step's
    span counts (the experts the step touched and the pairs they took,
    summed over the layers; the live rows whose state it moved), and
    over its prefills the chunks of a prompt."""
    from deepspeed_tpu.telemetry import spans

    closed = [r for r in spans.recent(t0) if r[2] < t1 and r[3]]

    def mean(path, key):
        vals = [r[3][key] for r in closed
                if r[0] == path and r[3].get(key) is not None]
        return float(np.mean(vals)) if vals else None

    step = "serve/step/decode"
    return {
        "moe_experts_touched_profiled": mean(step, "moe_experts_touched"),
        "moe_pairs_held_profiled": mean(step, "moe_pairs_held"),
        "moe_pairs_routed_profiled": mean(step, "moe_pairs_routed"),
        "moe_pairs_max_profiled": mean(step, "moe_pairs_max"),
        "ssm_rows_live_profiled": mean(step, "ssm_rows_live"),
        "ssm_rows_touched_profiled": mean(step, "ssm_rows_touched"),
        "prefill_chunks_profiled": mean("serve/step/admit/prefill",
                                        "chunks"),
        "prefill_pad_tokens_profiled": mean("serve/step/admit/prefill",
                                            "pad_tokens")}


# --- the run ---------------------------------------------------------------

def run(ctx):
    try:
        import deepspeed_tpu.models.nemotron_h  # noqa: F401
    except ImportError as e:
        # a program from before the model was added cannot run the cell
        ctx.log(f"the program under test has no Nemotron-H model: {e}")
        raise SystemExit(2)
    ctx.log("building the engine")
    engine, sched = build(ctx)
    ctx.log("warm-up")
    warm_up(ctx, engine, sched)
    scopes = None
    if ctx.trace:
        ctx.log("the compiled programs' scopes")
        scopes = program_scopes(engine, ctx.workload["trace"]["scope_marker"])
        install_spans(ctx, engine)
    result = measure(ctx, engine, sched)
    result.facts["program_scopes"] = scopes
    return result


def measure(ctx, engine, sched):
    """Ramp, window and drain on a warm engine, then the checks. The
    arithmetic of the end-to-end numbers is ``drivers/serve.py``'s."""
    wl, rec = ctx.workload, ctx.recorder
    traffic = wl["traffic"]
    arrivals = arrivals_of(ctx)
    counts_warm = engine.compile_counts()

    t0 = clock()
    w0 = t0 + traffic["ramp_s"]
    w1 = w0 + ctx.seconds
    t_end = w1 + traffic["drain_s"]
    profile, seg0 = None, w1
    if ctx.trace:
        seg0 = w1 - wl["trace"]["profile_s"]
        profile = (seg0, w1)
        rec.collect = True
    setup_s = w0 - ctx.t_process
    ctx.log(f"ramp {traffic['ramp_s']} s, window {ctx.seconds} s, "
            f"drain {traffic['drain_s']} s, {len(arrivals)} requests")
    compiles_before = ctx.compiles.n
    tracker, steps, late, trace = serve_loop(ctx, sched, arrivals, t0,
                                             t_end, profile)
    rec.collect = False
    compiles_in_run = ctx.compiles.n - compiles_before

    measured = [a.rid for a in arrivals if w0 <= t0 + a.due_s < w1]
    ttft, failed = [], []
    for rid in measured:
        got = tracker.stamps.get(rid)
        ttft.append((got[0] if got else t_end) - tracker.due[rid])
        reason = tracker.finish.get(rid)
        if not got or reason not in (None, "max_new_tokens"):
            failed.append(rid)
    gaps, tokens_in_window = [], 0
    for got in tracker.stamps.values():
        tokens_in_window += sum(w0 <= t < w1 for t in got)
        # from the second token on: the first two share a stamp
        gaps += [b - a for a, b in zip(got[1:], got[2:]) if w0 <= b < w1]
    in_window = [s for s in steps if w0 <= s[0] < w1]
    mid = 0.5 * (w0 + w1)

    finished = [r for r in measured
                if tracker.finish.get(r) == "max_new_tokens"]
    n_check = wl["correctness"]["requests"]
    # of the finished requests the shortest and the longest prompts:
    # one chunk of mostly padding, and the walk at its longest
    by_len = sorted(finished, key=lambda r: len(tracker.prompts[r]))
    checked = (by_len[:n_check // 2] + by_len[len(by_len) - (
        n_check - n_check // 2):]) if len(by_len) >= n_check else by_len
    ctx.log(f"checking {len(checked)} of {len(finished)} finished requests "
            f"against the reference")
    logits = check_logits(ctx, engine, tracker, checked)
    counts = engine.compile_counts()
    ctx.log("one layer of each kind on its own input")
    chunk = engine.prefill_chunk
    # of the checked prompts one with a padded tail, the longest such
    probe = max(checked or measured[:1],
                key=lambda r: (len(tracker.prompts[r]) % chunk > 0,
                               len(tracker.prompts[r])))
    own = own_input_checks(ctx, engine, tracker.prompts[probe],
                           tracker.tokens.get(probe) or [0])
    checks = {"reference": logits, "own_input": own,
              "compile_counts": counts,
              "compile_counts_after_warmup": counts_warm,
              "compiles_in_run": compiles_in_run}
    correct = bool(len(logits) == n_check
                   and all(r["ok"] for r in logits)
                   and all(c["ok"] for c in own.values())
                   and counts == counts_warm == {"prefill": 1, "decode": 1}
                   and compiles_in_run == 0)

    waits = [tracker.admitted[r] - tracker.due[r] for r in measured
             if r in tracker.admitted]
    rec.series["queue_wait"] = [max(0.0, w) for w in waits]
    rec.series["occupancy"] = [s[1] for s in in_window]
    rec.series["pool_fill"] = [s[4] for s in in_window
                               if s[4] is not None]
    end_to_end = {
        "serve_tokens_per_s": tokens_in_window / ctx.seconds,
        "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
        "itl_p95_ms": 1e3 * stats.percentile(gaps, 95),
    }
    gaps_ms = np.sort(1e3 * np.asarray(gaps))
    detail = {
        "requests_total": len(arrivals), "measured": len(measured),
        "failed": failed[:20],
        "finished_measured": len(finished),
        "ttft_ms": stats.summary([1e3 * x for x in ttft], 90),
        "itl_ms": stats.summary(gaps_ms.tolist(), 95),
        # the gaps round the 95th percentile: a gap is a decode step, or
        # a decode step and the prefills admitted before it
        "itl_percentiles_ms": {
            str(q): float(np.percentile(gaps_ms, q))
            for q in (50, 90, 93, 94, 95, 96, 97, 99)} if len(gaps) else {},
        "tokens_in_window": tokens_in_window,
        "steps_in_window": len(in_window),
        "mean_occupancy": float(np.mean([s[1] for s in in_window])),
        "mean_pool_fill": (float(np.mean(rec.series["pool_fill"]))
                           if rec.series["pool_fill"] else None),
        "pool_allocated_first_last": [in_window[0][5], in_window[-1][5]],
        "occupancy_halves": [
            float(np.mean([s[1] for s in in_window if s[0] < mid])),
            float(np.mean([s[1] for s in in_window if s[0] >= mid]))],
        "queue_depth_first_last": [in_window[0][2], in_window[-1][2]],
        "max_queue_depth": max(s[2] for s in in_window),
        "generator_late_ms": {"median": 1e3 * stats.percentile(late, 50),
                              "max": 1e3 * max(late)},
        "cache": engine.cache_facts(),
        "checks": checks,
    }
    profiled = [s[3] for s in steps if seg0 <= s[0] < w1]
    facts = {"kv_tokens_per_step": float(np.mean(
                 [s[3] for s in in_window])),
             # the profiled segment's own mean, which the decode
             # kernel's share of its roofline is reckoned from
             "kv_tokens_per_step_profiled": (
                 float(np.mean(profiled)) if profiled else None),
             "kv_bytes_per_element": np.dtype(engine.spec.dtype).itemsize,
             "prefill_chunk": engine.prefill_chunk,
             **ring_facts(seg0, w1)}
    detail["profiled_segment"] = {k: v for k, v in facts.items()
                                  if k.endswith("_profiled")}
    return harness.Result(
        correct=correct, attempted=len(measured), failed=len(failed),
        setup_s=setup_s, end_to_end=end_to_end, detail=detail,
        facts=facts, trace=trace)

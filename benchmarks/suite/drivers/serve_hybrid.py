"""Driver of the serving cells of a hybrid model (state-space mixers
beside attention layers, `deepspeed_tpu/models/granite_hybrid.py`):
``InferenceEngine`` + ``ContinuousBatchingScheduler`` built as
``inference/serve.py:main`` builds them, under ``drivers/serve.py``'s
open loop (its ``warm_up``, ``serve_loop``, ``Tracker`` and
``install_spans``, imported, so a token is stamped here as it is
there).

What is this file's own: the model and its bfloat16 weights from the
configuration file; the checks behind ``correct`` (the reference is
``reference/granite_hybrid_ref.py``, and beside the generated tokens'
logits there are three checks of one layer **on its own input**, each
with a limit in the workload's ``correctness`` block: a mixer's state
in the engine's own cache after a ragged prefill and after decoded
tokens, a mixer's output, an attention layer's output); the facts the
state-space metrics read (`flops_ssm.py`); and, traced, the compiled
programs' scope maps (`readers/program_scope_time.py`).

Workload file keys: as ``drivers/serve.py``'s, and ``traffic.order_seed``
(`arrivals_of`: the one order the trace's sizes arrive in, in every run).
"""

import dataclasses
import importlib

import numpy as np

from benchmarks.suite import harness, stats
from benchmarks.suite.drivers.serve import (install_spans, serve_loop,
                                            warm_up)
from benchmarks.suite.harness import clock
from benchmarks.suite.reference import granite_hybrid_ref as ref

__all__ = ["build", "warm_up", "measure", "run"]


def model_config(config, group="serve", **extra):
    """The program's config class from a configuration file."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig

    names = {f.name for f in dataclasses.fields(GraniteHybridConfig)}
    kw = {k: v for k, v in config.items() if k in names}
    kw["layer_types"] = tuple(config["layer_types"])
    kw["initializer_range"] = config["assumed"]["initializer_range"]
    g = config[group]
    kw.update(dtype=getattr(jnp, g["compute_dtype"]),
              param_dtype=getattr(jnp, g["param_dtype"]), **extra)
    return GraniteHybridConfig(**kw)


def build(ctx):
    import jax
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.models.granite_hybrid import (
        GraniteHybridLM, init_granite_hybrid_params)

    model = GraniteHybridLM(model_config(ctx.config))
    params = init_granite_hybrid_params(
        model, jax.random.PRNGKey(ctx.seed % (2 ** 31)))
    inf = dict(ctx.workload["inference"])
    inf["seq_buckets"] = tuple(inf["seq_buckets"])
    inf["sampling_seed"] = ctx.seed % (2 ** 31)
    engine = InferenceEngine(model, params, config=inf)
    return engine, ContinuousBatchingScheduler(engine)


# --- the checks behind ``correct`` ----------------------------------------

def check_logits(ctx, engine, tracker, rids):
    """As the chat cell's: the reference's full forward over prompt +
    generated tokens must put every generated token within
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
                    "max_shortfall": float(short.max()),
                    "tolerance": rtol * scale,
                    "shortfall_over_scale": float(short.max() / scale),
                    "ok": bool(short.max() <= rtol * scale)})
    return out


def state_diff(got, want):
    """The worst head's largest difference over that head's own largest
    entry: a state rounded at every call drifts in the heads that
    remember longest, which need not hold the largest entries."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    per_head = np.abs(got - want).max(axis=(1, 2)) / \
        np.abs(want).max(axis=(1, 2))
    return float(per_head.max())


def check_state(ctx, engine, prompt, generated, slot=0, decode_steps=64,
                short=130):
    """The first mixer's state **as the engine's cache holds it**,
    against the reference's at the same position (`state_diff`), in a
    slot that has had other tenants, three times: after the prefill of
    ``prompt`` (ragged, so its last chunk is padded and the state has
    passed from chunk to chunk); after the prefill of its first
    ``short`` tokens alone (one chunk, mostly padding, and short enough
    that what the slot held before would still show: a long prompt
    forgets it); and after ``decode_steps`` tokens fed to that through
    the decode program (the generated tokens, then the prompt's own
    again: any tokens do, both sides see the same; enough steps that a
    state rounded at every call drifts apart). The layer's input is the
    embedding's norm, so nothing upstream is in the difference. Catches
    a padded tail leaking into the state, a stale state of the slot's
    last tenant, and a state kept in bfloat16."""
    cfg = ctx.config
    layer = next(i for i, t in enumerate(cfg["layer_types"])
                 if t == ref.MAMBA)
    name = f"layers_{layer}"
    table = np.arange(1, engine.pages_per_row + 1, dtype=np.int32)

    def engine_state():
        return np.asarray(engine.cache[name]["ssm"][slot])

    def ref_state(tokens):
        # padded to the length the logits' check compiled for
        seq = np.zeros(engine.max_seq, np.int32)
        seq[:len(tokens)] = tokens
        return np.asarray(ref.forward(
            engine.params, seq, cfg, state_at=len(tokens) - 1,
            layers=layer + 1)[1][name])

    engine.prefill(slot, prompt, table)
    after_long = state_diff(engine_state(), ref_state(prompt))
    head = list(prompt[:short])
    engine.prefill(slot, head, table)
    after_short = state_diff(engine_state(), ref_state(head))
    fed = (list(generated) + list(prompt[short:]))[:decode_steps]
    tokens = np.zeros(engine.max_batch, np.int32)
    positions = np.zeros(engine.max_batch, np.int32)
    tables = np.zeros((engine.max_batch, engine.pages_per_row), np.int32)
    tables[slot] = table
    for j, tok in enumerate(fed):
        tokens[slot], positions[slot] = tok, len(head) + j
        engine.decode(tokens, positions, tables)
    after_decode = state_diff(engine_state(), ref_state(head + fed))
    tol = ctx.workload["correctness"]["state_rtol"]
    return {"layer": name, "prompt_len": len(prompt),
            "pad_tokens": -len(prompt) % engine.prefill_chunk,
            "short_prompt": len(head), "decode_steps": len(fed),
            "after_prefill": after_long, "after_short_prefill": after_short,
            "after_decode": after_decode, "tolerance": tol,
            "ok": bool(max(after_long, after_short, after_decode) <= tol)}


def check_mixer(model_cfg, ref_cfg, params, seed, chunk, tol,
                ref_params=None):
    """One mixer (the first) on its own input: a ragged chunk through
    the program's prefill form from a zero state, then one token through
    its decode form, against the reference's token-by-token mixer on the
    same float32 input (and the same weights; ``ref_params`` only where
    a test gives the program faulty ones)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.granite_hybrid import MAMBA, Mamba2Mixer

    name = model_cfg.names(MAMBA)[0]
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

    want = np.asarray(jax.jit(lambda p, x: ref.mamba(
        x.astype(jnp.float32), p, ref_cfg)[0])(
            (ref_params or params)[name]["mixer"], x))
    got = np.asarray(program(p, x), np.float32)
    scale = np.abs(want).max()
    prefill = float(np.abs(got[:-1] - want[:-1]).max() / scale)
    decode = float(np.abs(got[-1] - want[-1]).max() / scale)
    return {"layer": name, "tokens": n_valid, "prefill": prefill,
            "decode": decode, "tolerance": tol,
            "ok": bool(max(prefill, decode) <= tol)}


def check_attention(model_cfg, ref_cfg, params, seed, chunk, page_size,
                    impl, tol, ref_params=None):
    """One attention layer (the first) on its own input: a chunk through
    the dense prefill form into a small pool of its own, then one token
    through the decode form (the flash kernel where the cell serves with
    it), against the reference's. Catches another score scale than the
    configuration's and a query group on the wrong key head."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.cache import (init_kv_cache,
                                               page_pool_spec)
    from deepspeed_tpu.models.granite_hybrid import (
        ATTENTION, GroupedQueryAttention)

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

    want = np.asarray(jax.jit(lambda p, x: ref.attention(
        x.astype(jnp.float32), p, ref_cfg))(
            (ref_params or params)[name]["attn"], x))
    got = np.asarray(program(p, x), np.float32)
    scale = np.abs(want).max()
    prefill = float(np.abs(got[:-1] - want[:-1]).max() / scale)
    decode = float(np.abs(got[-1] - want[-1]).max() / scale)
    return {"layer": name, "tokens": chunk, "prefill": prefill,
            "decode": decode, "tolerance": tol,
            "ok": bool(max(prefill, decode) <= tol)}


def own_input_checks(ctx, engine, prompt, generated):
    corr = ctx.workload["correctness"]
    cfg = engine.model.config
    return {
        "state": check_state(ctx, engine, prompt, generated),
        "mixer": check_mixer(cfg, ctx.config, engine.params, ctx.seed,
                             engine.prefill_chunk, corr["mixer_rtol"]),
        "attention": check_attention(
            cfg, ctx.config, engine.params, ctx.seed,
            engine.prefill_chunk, engine.page_size, engine.attention_impl,
            corr["attention_rtol"])}


# --- what the state-space metrics read ------------------------------------

def program_scopes(engine, marker):
    """``{program: {instruction: op_name}}`` from the two compiled
    programs' texts (a cache hit of what warm-up compiled)."""
    from benchmarks.suite.readers import scope_time

    texts = {
        "prefill": engine._prefill.lower(
            *engine.prefill_lowering_args()).compile().as_text(),
        "decode": engine._decode.lower(
            *engine.decode_lowering_args()).compile().as_text()}
    return {k: scope_time.scopes_of(v, marker) for k, v in texts.items()}


def ring_facts(t0, t1):
    """From the program's own spans that closed in ``[t0, t1)`` (the
    profiled segment): the mean live rows of its decode steps (the
    state the segment's steps really moved, not the window's mean) and
    the mean chunks and padding of its prefills."""
    from deepspeed_tpu.telemetry import spans

    def mean(path, key):
        vals = [r[3][key] for r in spans.recent(t0)
                if r[0] == path and r[2] < t1 and r[3]
                and r[3].get(key) is not None]
        return float(np.mean(vals)) if vals else None

    return {
        "ssm_rows_live_profiled": mean("serve/step/decode",
                                       "ssm_rows_live"),
        "ssm_rows_touched_profiled": mean("serve/step/decode",
                                          "ssm_rows_touched"),
        "prefill_chunks_profiled": mean("serve/step/admit/prefill",
                                        "chunks"),
        "prefill_pad_tokens_profiled": mean("serve/step/admit/prefill",
                                            "pad_tokens")}


# --- the run ---------------------------------------------------------------

def arrivals_of(ctx):
    """The cell's trace **in one order**, its tokens drawn from
    ``--seed``. The generator lets a seed reorder the sizes inside
    blocks of four arrivals; at 0.8 of the knee that alone moves
    ``ttft_p90_ms`` by 9 % between seeds (which long prompts meet in one
    step decides the 21st longest wait of 211), more than the bound may
    hide. So the order is the traffic block's ``order_seed``, the same in
    every run, and what ``--seed`` draws is what cannot move a time: the
    tokens here and the weights in `build`. Every seed then does the
    same work in the same order."""
    traffic = ctx.workload["traffic"]
    gen = importlib.import_module(
        "benchmarks.suite.traffic." + traffic["generator"])
    vocab = ctx.config["vocab_size"]
    arrivals = gen.make(traffic, traffic["order_seed"],
                        seconds=ctx.seconds, vocab_size=vocab)
    rng = np.random.default_rng(ctx.seed)
    for a in arrivals:
        a.prompt = rng.integers(0, vocab, len(a.prompt)).tolist()
    return arrivals


def run(ctx):
    try:
        import deepspeed_tpu.models.granite_hybrid  # noqa: F401
    except ImportError as e:
        # a program from before the model was added cannot run the cell
        ctx.log(f"the program under test has no hybrid model: {e}")
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
    ctx.log(f"checking {n_check} of {len(finished)} finished requests "
            f"against the reference")
    logits = check_logits(ctx, engine, tracker, finished[:n_check])
    counts = engine.compile_counts()
    ctx.log("one layer of each kind on its own input")
    # of the checked prompts one with a padded tail, the longest such
    chunk = engine.prefill_chunk
    probe = max(finished[:n_check] or measured[:1],
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
    detail = {
        "requests_total": len(arrivals), "measured": len(measured),
        "failed": failed[:20],
        "finished_measured": len(finished),
        "ttft_ms": stats.summary([1e3 * x for x in ttft], 90),
        "itl_ms": stats.summary([1e3 * x for x in gaps], 95),
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
             # kernel's share of its roofline is reckoned from: the
             # window's mean is another load than the profiled steps'
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

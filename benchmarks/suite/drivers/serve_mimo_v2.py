"""Driver of the serving cells of a MiMo-V2 model held as a share
(`deepspeed_tpu/models/mimo_v2.py`: window-attention layers with a
learned sink beside full-attention layers, two groups of page layers
with key heads of their own, keys of 192 over values of 128, sigmoid
routing over the held experts): ``InferenceEngine`` +
``ContinuousBatchingScheduler`` built as ``inference/serve.py:main``
builds them, under ``drivers/serve.py``'s open loop (its ``warm_up``,
``serve_loop``, ``Tracker`` and ``install_spans``, imported, so a token
is stamped here as it is there), on ``drivers/serve_hybrid.py``'s
ordered arrivals (``arrivals_of``) and compiled-program scope maps
(``program_scopes``), imported too.

What is this file's own: the model and its bfloat16 weights from the
configuration file (`model_config`: the share is the file's ``n_layer``,
``vocab_size`` and ``assumed.experts_held``); the checks behind
``correct`` (the reference is ``reference/mimo_v2_ref.py``; beside the
generated tokens' logits, four checks with limits in the workload's
``correctness`` block: what a slot's full pages and ring hold after the
engine's own two programs have run a long prompt, a short one into the
same slot and decoded tokens, and a window layer's, a full layer's and
an expert layer's output **on its own input**); and the facts the
metrics read (`flops_mimo_v2.py`). ``measure``'s arithmetic of the
end-to-end numbers is ``drivers/serve.py``'s, written out a sixth time
because no ``measure`` takes its checks as an argument (`PERF.md`,
section 7 (j)).

Workload file keys: as ``drivers/serve_hybrid.py``'s.
"""

import dataclasses

import numpy as np

from benchmarks.suite import harness, stats
from benchmarks.suite.drivers.serve import (install_spans, serve_loop,
                                            warm_up)
from benchmarks.suite.drivers.serve_hybrid import arrivals_of, program_scopes
from benchmarks.suite.drivers.serve_qwen3_next import _off, _readings
from benchmarks.suite.harness import clock
from benchmarks.suite.reference import mimo_v2_ref as ref

__all__ = ["build", "warm_up", "measure", "run"]

FULL, WINDOW = "full", "window"


def model_config(config, group="serve", **extra):
    """The program's config class from a configuration file."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.mimo_v2 import MimoV2Config

    names = {f.name for f in dataclasses.fields(MimoV2Config)}
    kw = {k: v for k, v in config.items() if k in names}
    assumed, g = config["assumed"], config[group]
    kw.update(
        num_hidden_layers=config["n_layer"],
        hybrid_layer_pattern=tuple(config["hybrid_layer_pattern"]),
        moe_layer_freq=tuple(config["moe_layer_freq"]),
        initializer_range=assumed["initializer_range"],
        router_bias_range=assumed["router_bias_range"],
        sink_bias_mean=assumed["sink_bias_mean"],
        sink_bias_range=assumed["sink_bias_range"],
        experts_held=tuple(assumed["experts_held"]),
        dtype=getattr(jnp, g["compute_dtype"]),
        param_dtype=getattr(jnp, g["param_dtype"]))
    kw.update(extra)
    return MimoV2Config(**kw)


def build(ctx):
    import jax
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.models.mimo_v2 import MimoV2LM, init_mimo_v2_params

    model = MimoV2LM(model_config(ctx.config))
    params = init_mimo_v2_params(
        model, jax.random.PRNGKey(ctx.seed % (2 ** 31)))
    inf = dict(ctx.workload["inference"])
    inf["seq_buckets"] = tuple(inf["seq_buckets"])
    inf["sampling_seed"] = ctx.seed % (2 ** 31)
    engine = InferenceEngine(model, params, config=inf)
    return engine, ContinuousBatchingScheduler(engine)


# --- the checks behind ``correct`` ----------------------------------------

def _padded(tokens, chunk):
    seq = np.zeros(-(-len(tokens) // chunk) * chunk, np.int32)
    seq[:len(tokens)] = tokens
    return seq


def check_logits(ctx, params, chunk, tracker, rids, forward=None):
    """As the chat cell's: the reference's full forward over prompt +
    generated tokens (every query over the whole prefix under the
    explicit mask, the sink a dropped column, a loop over the held
    experts) must put every generated token within ``logit_rtol`` x
    max|logit| of its position's largest logit. Each sequence at its
    own length, padded to whole chunks (the longest is tens of
    thousands of tokens: the engine's pools are gone by now, see
    `measure`)."""
    rtol = ctx.workload["correctness"]["logit_rtol"]
    out = []
    for rid in rids:
        prompt, toks = tracker.prompts[rid], tracker.tokens[rid]
        rows = np.arange(len(prompt) - 1, len(prompt) + len(toks) - 1)
        lg = np.asarray((forward or ref.forward)(
            params, _padded(prompt + toks, chunk), ctx.config, rows=rows)[0])
        scale = float(np.abs(lg).max())
        short = lg.max(axis=1) - lg[np.arange(len(toks)), toks]
        out.append({"rid": rid, "tokens": len(toks),
                    "prompt_len": len(prompt),
                    "max_shortfall": float(short.max()),
                    "tolerance": rtol * scale,
                    "shortfall_over_scale": float(short.max() / scale),
                    "ok": bool(short.max() <= rtol * scale)})
    return out


def slot_readings(engine, prompt, generated, slot=0, decode_steps=256,
                  short=260):
    """What the engine's own two compiled programs leave of a prompt:
    ``[(tokens, {layer: (k, v)}, logits)]`` on the host, read out of the
    engine's two pools three times: after the prefill of ``prompt``
    (ragged, many calls: the ring has wrapped once a page); after the
    prefill of its first ``short`` tokens alone into the same slot, the
    same pages and the same ring (one call, mostly padding: a slot that
    has had a tenant); and after ``decode_steps`` tokens fed to that
    through the decode program (the generated tokens, then the prompt's
    own again: any tokens do). Of a full layer the rows are the
    sequence's positions, through the slot's pages (handed over in
    descending order); of a window layer the last ``sliding_window``
    positions, each read where its position says it lies in the ring
    (entry ``(p // page) % ring``, lane ``p % page``). ``logits`` is the
    program's whole logit row at the last of ``tokens``."""
    import jax.numpy as jnp

    cfg, page = engine.model.config, engine.page_size
    ring = engine.spec.ring_pages
    table = np.concatenate([
        np.arange(engine.pages_per_row, 0, -1, dtype=np.int32),
        np.arange(ring, 0, -1, dtype=np.int32) + slot * ring])
    full_names = set(cfg.names(FULL))

    def held(tokens, logits):
        n, kv = len(tokens), {}
        pages = jnp.asarray(table[:-(-n // page)])
        at = np.arange(max(0, n - cfg.sliding_window), n)
        entries = jnp.asarray(table[engine.pages_per_row:])
        for name, leaves in engine.cache.items():
            def rows(x, ids):
                # [pages, heads, width, page] -> [positions, heads, width]
                return np.moveaxis(np.asarray(leaves[x][ids], np.float32),
                                   -1, 1).reshape(
                                       (-1,) + leaves[x].shape[1:3])
            if name in full_names:
                kv[name] = tuple(rows(x, pages)[:n] for x in "kv")
            else:
                kv[name] = tuple(rows(x, entries)[at % (ring * page)]
                                 for x in "kv")
        return list(tokens), kv, np.asarray(logits, np.float32)

    prompt = list(prompt)
    stages = [held(prompt, engine.prefill(slot, prompt, table))]
    head = prompt[:short]
    stages.append(held(head, engine.prefill(slot, head, table)))
    fed = (list(generated) + prompt[short:] + prompt)[
        :min(decode_steps, engine.max_seq - len(head))]
    tokens = np.zeros(engine.max_batch, np.int32)
    positions = np.zeros(engine.max_batch, np.int32)
    tables = np.zeros((engine.max_batch, engine.table_width), np.int32)
    tables[slot] = table
    for j, tok in enumerate(fed):
        tokens[slot], positions[slot] = tok, len(head) + j
        logits = engine.decode(tokens, positions, tables)[1]
    stages.append(held(head + fed, logits[slot]))
    return stages


def check_slot(ctx, engine, prompt, generated, forward=None, stages=None,
               decode_steps=256):
    """What a slot's full pages and ring hold after the engine's own
    prefill and decode (`slot_readings`) against the reference's full
    forward over the same tokens.

    **The first layer** (a full layer whose input is the embedding's
    norm, so nothing upstream is in the difference): its pooled keys and
    values by the largest difference over the reference's largest entry
    (``first_rows``, limit ``rows_rtol``): rotary at the right base on
    the right entries, the value scale, a padded tail or a page in the
    wrong place. **Every later layer** (``deep_rows`` under
    ``deep_rows_rtol``, ``deep_logits`` under ``deep_logits_rtol``;
    `_off`: a norm): the other full layer's
    rows over the whole sequence, the five window layers' last 128
    positions as the ring holds them by position (a stale tenant's ring,
    a ring entry overwritten by a padded page, a wrap one page off), and
    the whole logit row at each stage's last token, which has been
    through both attention programs, the held experts and the head as
    the engine runs them.

    (``forward``: `tools/fault_readings_mimo_v2.py`'s way in;
    ``stages``: readings taken earlier, from an engine that is gone.)"""
    corr = ctx.workload["correctness"]
    cfg = engine.model.config
    if stages is None:
        stages = slot_readings(engine, prompt, generated,
                               decode_steps=decode_steps)
    chunk = engine.prefill_chunk
    first_name = cfg.names(FULL)[0]
    first, deep = [], {"rows": [], "logits": []}
    for tokens, kv, logits in stages:
        n = len(tokens)
        want_logits, want_kv = (forward or ref.forward)(
            engine.params, _padded(tokens, chunk), ctx.config, rows=[n - 1])
        for name, pair in want_kv.items():
            lo = 0 if name in cfg.names(FULL) else \
                max(0, n - cfg.sliding_window)
            for got, want in zip(kv[name], pair):
                want = np.asarray(want)[lo:n]
                if name == first_name:
                    first.append(float(np.abs(got - want).max() /
                                       np.abs(want).max()))
                else:
                    deep["rows"].append(_off(got, want))
        deep["logits"].append(_off(logits, np.asarray(want_logits)[0]))
    deep = {k: max(v) for k, v in deep.items()}
    tol = corr["rows_rtol"]
    deep_tol = {"rows": corr["deep_rows_rtol"],
                "logits": corr["deep_logits_rtol"]}
    return {"layer": first_name, "prompt_len": len(prompt),
            "pad_tokens": -len(prompt) % chunk,
            "short_prompt": len(stages[1][0]),
            "decode_steps": len(stages[2][0]) - len(stages[1][0]),
            "first_rows": max(first), "deep_rows": deep["rows"],
            "deep_logits": deep["logits"], "tolerance": tol,
            "deep_rows_tolerance": deep_tol["rows"],
            "deep_logits_tolerance": deep_tol["logits"],
            "ok": bool(max(first) <= tol and
                       all(v <= deep_tol[k] for k, v in deep.items()))}


def check_attention(model_cfg, ref_cfg, params, which, seed, chunk,
                    page_size, impl, tol, decode_tol, reference=None,
                    sound=None):
    """The first layer of kind ``which`` on its own input: two chunks
    through its prefill form into a small pool of its own (a full
    layer's second chunk walks two blocks; a window layer's reads the
    128 positions before it out of a ring that the first chunk has
    wrapped four times), the second ragged, then one token through its
    decode form (the flash kernel where the cell serves with it),
    against the reference's on the same float32 input. Catches another
    window than 128, a mask by ring entry and not by position, the sink
    left out or given a value, the other kind's rotary base, rotary on
    all 192 entries, another score scale than ``192^-0.5``, the value
    scale left out, a query group on the wrong key head. The decode
    reading has a limit of its own. (``sound``: the reference's weights where
    ``params`` are the program's faulty ones.)"""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.models.mimo_v2 import MimoAttention

    name = model_cfg.names(which)[0]
    p = params[name]["attn"]
    spec = model_cfg.cache_spec(1, 2 * chunk + page_size, page_size=page_size)
    n2 = chunk - chunk // 7             # the second chunk's real tokens
    n = chunk + n2
    x = jax.random.normal(
        jax.random.PRNGKey((seed + (1 if which == FULL else 3)) % (2 ** 31)),
        (n + 1, model_cfg.hidden_size), jnp.float32).astype(model_cfg.dtype)
    layer = MimoAttention(model_cfg, which)

    @jax.jit
    def program(p, x):
        pool = init_kv_cache(spec)[name]
        width = spec.ring_pages if which == WINDOW else spec.pages_per_row
        table = jnp.arange(width, 0, -1, dtype=jnp.int32)[None]
        padded = jnp.zeros((1, 2 * chunk, x.shape[1]), x.dtype)
        padded = padded.at[0, :n].set(x[:n])
        pos = jnp.arange(2 * chunk, dtype=jnp.int32)[None]
        y0, pool = layer.apply(
            {"params": p}, padded[:, :chunk], pool, pos[:, :chunk], table,
            jnp.full((1,), chunk, jnp.int32), {"impl": "dense"})
        y1, pool = layer.apply(
            {"params": p}, padded[:, chunk:], pool, pos[:, chunk:], table,
            jnp.full((1,), n2, jnp.int32), {"impl": "dense"})
        y2, _ = layer.apply(
            {"params": p}, x[None, n:], pool, jnp.full((1, 1), n, jnp.int32),
            table, jnp.ones((1,), jnp.int32),
            {"impl": impl, "block_k": page_size})
        return jnp.concatenate([y0[0], y1[0, :n2], y2[0]])

    reference = reference or (
        lambda p, x: ref.attention(x, p, ref_cfg, which))
    want = np.asarray(jax.jit(reference)(
        (sound or params)[name]["attn"], x.astype(jnp.float32)))
    got = np.asarray(program(p, x), np.float32)
    return _readings(got, want, n, tol, decode_tol, layer=name, kind=which,
                     tokens=n)


def check_experts(model_cfg, ref_cfg, params, seed, chunk, rows, tol,
                  reference=None, sound=None):
    """One expert layer (the first) on its own input: a ragged chunk
    through its prefill shape and a decode step's rows (a third of them
    without a request) through its decode shape, against the
    reference's loop over the held experts. Also reads that the pairs
    the program counted are tokens x ``num_experts_per_tok``, and that
    the reference's renormalised weights sum to 1 over all of a token's
    chosen experts, held here or not. Catches weights not renormalised,
    the bias let into the weights, a pair of an expert held elsewhere
    leaking in."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.mimo_v2 import RoutedExperts

    name = next(f"layers_{i}" for i in range(model_cfg.num_hidden_layers)
                if not model_cfg.is_dense(i))
    p = params[name]["experts"]
    first, held = model_cfg.experts_held
    n_valid = chunk - chunk // 7        # ragged
    x = jax.random.normal(jax.random.PRNGKey((seed + 2) % (2 ** 31)),
                          (n_valid + rows, model_cfg.hidden_size),
                          jnp.float32).astype(model_cfg.dtype)
    live = np.arange(rows) % 3 != 2     # a third of the rows hold nothing
    layer = RoutedExperts(model_cfg)

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
    want = np.asarray(jax.jit(reference)(
        (sound or params)[name]["experts"], x32))
    got, c0, c1 = program(p, x)
    got = np.asarray(got, np.float32)
    keep = np.concatenate([np.ones(n_valid, bool), live])
    want, got = want[keep], got[keep]
    weights, chosen = ref.route(x32, p, ref_cfg)
    off = float(np.abs(np.asarray(weights).sum(-1) - 1.0).max())
    held_share = float(np.mean((np.asarray(chosen) >= first) &
                               (np.asarray(chosen) < first + held)))
    pairs = (n_valid + int(live.sum())) * model_cfg.num_experts_per_tok
    counted = int(c0[0]) + int(c1[0])
    out = _readings(got, want, n_valid, tol, layer=name, tokens=n_valid,
                    rows=int(live.sum()), weights_sum_off=off,
                    held_share=held_share, pairs_routed=counted,
                    pairs_held=int(c0[1]) + int(c1[1]))
    out["ok"] = bool(out["ok"] and off < 1e-5 and counted == pairs)
    return out


def own_input_checks(ctx, engine, prompt, generated):
    corr = ctx.workload["correctness"]
    cfg = engine.model.config
    chunk = engine.prefill_chunk

    def attention(which):
        return check_attention(
            cfg, ctx.config, engine.params, which, ctx.seed, chunk,
            engine.page_size, engine.attention_impl,
            corr[f"{which}_rtol"], corr[f"{which}_decode_rtol"])

    return {
        "slot": check_slot(ctx, engine, prompt, generated),
        "window": attention(WINDOW), "full": attention(FULL),
        "experts": check_experts(
            cfg, ctx.config, engine.params, ctx.seed, chunk,
            engine.max_batch, corr["expert_rtol"])}


# --- what the metrics read -------------------------------------------------

def ring_facts(t0, t1, block):
    """From the program's own spans that closed in ``[t0, t1)`` (the
    profiled segment), the means over its decode steps of what a step's
    span counts (the experts the step touched and the pairs they took,
    summed over the layers; the rows whose KV block the kernels wrote
    back) and over its prefills: the calls of a prompt, its real tokens
    (``chunks x chunk - pad_tokens``), its query-key pairs ``n (n + 1) /
    2`` and the prefix positions its calls walked (call ``c`` of
    ``chunk`` tokens reads ``min((c + 1) chunk, n)``)."""
    from deepspeed_tpu.telemetry import spans

    closed = [r for r in spans.recent(t0) if r[2] < t1 and r[3]]

    def mean(path, key):
        vals = [r[3][key] for r in closed
                if r[0] == path and r[3].get(key) is not None]
        return float(np.mean(vals)) if vals else None

    step, prefill = "serve/step/decode", "serve/step/admit/prefill"
    counters = ("moe_experts_touched", "moe_pairs_held", "moe_pairs_routed",
                "kv_rows_written", "attn_blocks_in_window",
                "attn_blocks_visited_window")
    lens, pairs, walked = [], [], []
    for r in closed:
        if r[0] == prefill and r[3].get("chunks") is not None:
            n = r[3]["chunks"] * block - r[3]["pad_tokens"]
            lens.append(n)
            pairs.append(n * (n + 1) / 2)
            walked.append(sum(min((c + 1) * block, n)
                              for c in range(r[3]["chunks"])))
    return {**{f"{c}_profiled": mean(step, c) for c in counters},
            "prefill_chunks_profiled": mean(prefill, "chunks"),
            "prefill_pad_tokens_profiled": mean(prefill, "pad_tokens"),
            "prefill_tokens_profiled": float(np.mean(lens)) if lens else None,
            "prefill_pairs_profiled": float(np.mean(pairs)) if pairs
            else None,
            "prefill_prefix_tokens_profiled": float(np.mean(walked))
            if walked else None}


# --- the run ---------------------------------------------------------------

def run(ctx):
    try:
        import deepspeed_tpu.models.mimo_v2  # noqa: F401
    except ImportError as e:
        # a program from before the model was added cannot run the cell
        ctx.log(f"the program under test has no MiMo-V2 model: {e}")
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
    group_facts = sched.paging.facts()["groups"]

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
    # one call of mostly padding, and the walk at its longest
    by_len = sorted(finished, key=lambda r: len(tracker.prompts[r]))
    checked = (by_len[:n_check // 2] + by_len[len(by_len) - (
        n_check - n_check // 2):]) if len(by_len) >= n_check else by_len
    counts = engine.compile_counts()
    ctx.log("one layer of each kind on its own input, and a slot")
    chunk = engine.prefill_chunk
    # of the finished long prompts the shortest with a padded tail (over
    # 8 k tokens: the ring has wrapped 60 times and more; the longest is
    # the logits' check's): many calls, the last of them ragged
    long_ones = [r for r in finished
                 if len(tracker.prompts[r]) >= wl["correctness"][
                     "slot_prompt_min"]
                 and len(tracker.prompts[r]) % chunk]
    probe = min(long_ones, key=lambda r: len(tracker.prompts[r])) \
        if long_ones else max(finished or measured[:1],
                              key=lambda r: len(tracker.prompts[r]))
    own = own_input_checks(ctx, engine, tracker.prompts[probe],
                           tracker.tokens.get(probe) or [0])
    cache_facts = engine.cache_facts()
    # the reference's longest sequence needs the room: the two pools go
    # (the engine's programs are done: every reading of them is above)
    params = engine.params
    engine.cache = None
    ctx.log(f"checking {len(checked)} of {len(finished)} finished requests "
            f"against the reference")
    logits = check_logits(ctx, params, chunk, tracker, checked)
    engine.reset()      # fresh pools: a sweep measures on this engine again
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
    long_min = wl["correctness"]["slot_prompt_min"]
    by_class = {
        name: [1e3 * t for t, r in zip(ttft, measured)
               if (len(tracker.prompts[r]) >= long_min) == is_long]
        for name, is_long in (("short", False), ("long", True))}
    detail = {
        "requests_total": len(arrivals), "measured": len(measured),
        "failed": failed[:20],
        "finished_measured": len(finished),
        "ttft_ms": stats.summary([1e3 * x for x in ttft], 90),
        "ttft_ms_by_class": {k: stats.summary(v, 90) if v else None
                             for k, v in by_class.items()},
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
        "cache": cache_facts,
        "page_groups_at_end": group_facts,
        "checks": checks,
    }
    profiled = [s[3] for s in steps if seg0 <= s[0] < w1]
    facts = {"kv_tokens_per_step": float(np.mean(
                 [s[3] for s in in_window])),
             # the profiled segment's own means, which the decode
             # kernels' shares of their rooflines are reckoned from
             "kv_tokens_per_step_profiled": (
                 float(np.mean(profiled)) if profiled else None),
             "kv_bytes_per_element": np.dtype(engine.spec.dtype).itemsize,
             "attention_block_k": engine.attention_block_k,
             "prefill_chunk": engine.prefill_chunk,
             "sliding_window": engine.model.config.sliding_window,
             **ring_facts(seg0, w1, engine.prefill_chunk)}
    detail["profiled_segment"] = {k: v for k, v in facts.items()
                                  if k.endswith("_profiled")}
    return harness.Result(
        correct=correct, attempted=len(measured), failed=len(failed),
        setup_s=setup_s, end_to_end=end_to_end, detail=detail,
        facts=facts, trace=trace)

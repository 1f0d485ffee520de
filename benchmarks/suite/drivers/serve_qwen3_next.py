"""Driver of the serving cells of a Qwen3-Next model held as a share
(`deepspeed_tpu/models/qwen3_next.py`: Gated DeltaNet mixers whose
state is a matrix a head under a delta rule, a gated attention at head
size 256 every fourth block, renormalised softmax routing over the held
experts): ``InferenceEngine`` + ``ContinuousBatchingScheduler`` built as
``inference/serve.py:main`` builds them, under ``drivers/serve.py``'s
open loop (its ``warm_up``, ``serve_loop``, ``Tracker`` and
``install_spans``, imported, so a token is stamped here as it is
there), on ``drivers/serve_hybrid.py``'s ordered arrivals
(``arrivals_of``), compiled-program scope maps (``program_scopes``) and
``state_diff``, imported too.

What is this file's own: the model and its bfloat16 weights from the
configuration file (`model_config`: the share is the file's
``n_layer``, ``vocab_size`` and ``assumed.experts_held``); the checks
behind ``correct`` (the reference is ``reference/qwen3_next_ref.py``;
beside the generated tokens' logits, four checks with limits in the
workload's ``correctness`` block: what a slot holds after the engine's
own two programs have run a prompt (the first mixer's state and window
on its own input, tightly; every later layer's leaves, the pooled keys
and values and the whole logit row, by a norm), and a mixer's, an
attention layer's and an expert layer's output **on its own input**);
and the facts the metrics read
(`flops_qwen3_next.py`). ``measure``'s arithmetic of the end-to-end
numbers is ``drivers/serve.py``'s, written out a fifth time because no
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
from benchmarks.suite.reference import qwen3_next_ref as ref

__all__ = ["build", "warm_up", "measure", "run"]

MIXER_LAYER = "layers_0"    # the state's check: nothing upstream of it


def model_config(config, group="serve", **extra):
    """The program's config class from a configuration file."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig

    names = {f.name for f in dataclasses.fields(Qwen3NextConfig)}
    kw = {k: v for k, v in config.items() if k in names}
    assumed, g = config["assumed"], config[group]
    kw.update(
        num_hidden_layers=config["n_layer"],
        mlp_only_layers=tuple(config.get("mlp_only_layers", ())),
        initializer_range=assumed["initializer_range"],
        norm_weight_range=assumed["norm_weight_range"],
        delta_chunk_size=assumed["delta_chunk_size"],
        experts_held=tuple(assumed["experts_held"]),
        dtype=getattr(jnp, g["compute_dtype"]),
        param_dtype=getattr(jnp, g["param_dtype"]))
    kw.update(extra)
    return Qwen3NextConfig(**kw)


def build(ctx):
    import jax
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.models.qwen3_next import (Qwen3NextLM,
                                                 init_qwen3_next_params)

    model = Qwen3NextLM(model_config(ctx.config))
    params = init_qwen3_next_params(
        model, jax.random.PRNGKey(ctx.seed % (2 ** 31)))
    inf = dict(ctx.workload["inference"])
    inf["seq_buckets"] = tuple(inf["seq_buckets"])
    inf["sampling_seed"] = ctx.seed % (2 ** 31)
    engine = InferenceEngine(model, params, config=inf)
    return engine, ContinuousBatchingScheduler(engine)


# --- the checks behind ``correct`` ----------------------------------------

def check_logits(ctx, engine, tracker, rids):
    """As the chat cell's: the reference's full forward over prompt +
    generated tokens (the delta rule token by token, no cache, a loop
    over the held experts) must put every generated token within
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


def slot_readings(engine, prompt, generated, slot=0, decode_steps=256,
                  short=260):
    """What the engine's own two compiled programs leave of a prompt:
    ``[(tokens, {mixer: (S, window)}, {attention layer: (k, v)},
    logits)]`` on the host, read out of the engine's leaves and its pool
    (the slot's pages, handed over in descending order) three times:
    after the prefill of ``prompt`` (ragged, several calls); after the
    prefill of its first ``short`` tokens alone into the same slot (one
    call, mostly padding); and after ``decode_steps`` tokens fed to that
    through the decode program (the generated tokens, then the prompt's
    own again: any tokens do). ``logits`` is the program's whole logit
    row at the last of ``tokens``."""
    import jax.numpy as jnp

    table = np.arange(engine.pages_per_row, 0, -1, dtype=np.int32)

    def held(tokens, logits):
        n, states, kv = len(tokens), {}, {}
        pages = jnp.asarray(table[:-(-n // engine.page_size)])
        for name, leaves in engine.cache.items():
            if "gdn" in leaves:
                states[name] = (
                    np.asarray(leaves["gdn"][slot]),
                    np.asarray(leaves["conv"][:, slot], np.float32))
            else:
                # [pages, heads, head_dim, page] -> [n, heads, head_dim]
                kv[name] = tuple(np.moveaxis(
                    np.asarray(leaves[x][pages], np.float32), -1, 1
                ).reshape((-1,) + leaves[x].shape[1:3])[:n] for x in "kv")
        return list(tokens), states, kv, np.asarray(logits, np.float32)

    prompt = list(prompt)
    stages = [held(prompt, engine.prefill(slot, prompt, table))]
    head = prompt[:short]
    stages.append(held(head, engine.prefill(slot, head, table)))
    fed = (list(generated) + prompt[short:] + prompt)[
        :min(decode_steps, engine.max_seq - len(head))]
    tokens = np.zeros(engine.max_batch, np.int32)
    positions = np.zeros(engine.max_batch, np.int32)
    tables = np.zeros((engine.max_batch, engine.pages_per_row), np.int32)
    tables[slot] = table
    for j, tok in enumerate(fed):
        tokens[slot], positions[slot] = tok, len(head) + j
        logits = engine.decode(tokens, positions, tables)[1]
    stages.append(held(head + fed, logits[slot]))
    return stages


def _off(got, want):
    """The distance of two arrays over the reference's size (Frobenius:
    a near-tie among 512 experts that the two precisions decide
    differently moves one token's rows, which a largest entry would
    read and a norm does not)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check_state(ctx, engine, prompt, generated, reference=None,
                stages=None, decode_steps=256):
    """What a slot holds after the engine's own prefill and decode
    (`slot_readings`: a ragged multi-call prefill, a short one into the
    same slot, then ``decode_steps`` decoded tokens; in a slot
    that has had other tenants) against the reference's full forward
    over the same tokens, and with it the whole path the window drives.

    **The first mixer** (its input is the embedding's norm, so nothing
    upstream is in the difference): the float32 state by
    `serve_hybrid.state_diff`, the worst head over its own largest
    entry, and the convolution window over its largest entry. Catches a
    padded tail leaking into the state, a stale state of the slot's
    last tenant, ``beta`` or the decay left out. A state kept in
    bfloat16 gathers a rounding a step and still reads only 1.6 to 3
    times a sound one after 256, 512 or 1,024 steps (a sound state's own
    error is its bfloat16 inputs'), so it is held by what it is at rest:
    the share of the state's entries that are bfloat16 numbers
    (``state_bfloat16_share_max``).

    **Every later layer** (``deep_*``, one limit, ``deep_rtol``; `_off`:
    a norm, not a largest entry): the other five mixers' states, their
    windows and the two attention layers' pooled keys and values (the
    rows a slot keeps), and the whole logit row at each stage's last
    token. A later layer's input has been through every block before it
    in the compiled programs, so these hold the gated attention at head
    256 (dense prefill arm and the paged kernel), the held experts and
    the head as the engine runs them, where the checks of one layer on
    its own input below run stand-alone copies.

    (``reference``: `tools/fault_readings_qwen3_next.py`'s way in;
    ``stages``: readings taken earlier, from an engine that is gone.)"""
    corr = ctx.workload["correctness"]
    if stages is None:
        stages = slot_readings(engine, prompt, generated,
                               decode_steps=decode_steps)
    chunk = engine.prefill_chunk
    first, deep = [], {"state": [], "rows": [], "logits": []}
    for tokens, states, kv, logits in stages:
        n = len(tokens)
        # the longest at the length the logits' check compiled for, the
        # two short ones at one chunk's multiple
        seq = np.zeros(engine.max_seq if n == len(prompt)
                       else -(-n // chunk) * chunk, np.int32)
        seq[:n] = tokens
        want_logits, want_states, want_kv = (reference or ref.forward)(
            engine.params, seq, ctx.config, rows=[n - 1], state_at=n - 1)
        S, window = (np.asarray(a) for a in want_states[MIXER_LAYER])
        got_S, got_window = states[MIXER_LAYER]
        first.append((state_diff(got_S, S), float(
            np.abs(got_window - window).max() / np.abs(window).max())))
        for name, (S, window) in want_states.items():
            if name != MIXER_LAYER:
                deep["state"].append(_off(states[name][0], S))
                deep["rows"].append(_off(states[name][1], window))
        for name, pair in want_kv.items():
            deep["rows"] += [_off(got, np.asarray(want)[:n])
                             for got, want in zip(kv[name], pair)]
        if want_logits is not None:
            deep["logits"].append(_off(logits, np.asarray(want_logits)[0]))
    (after_long, _), (after_short, _), (after_decode, _) = first
    window = max(w for _, w in first)
    deep = {k: max(v) if v else None for k, v in deep.items()}
    tol, window_tol = corr["state_rtol"], corr["window_rtol"]
    deep_tol = corr["deep_rtol"]
    # the share of the first state's entries, after the decoded tokens,
    # that are bfloat16 numbers
    bits = np.asarray(stages[2][1][MIXER_LAYER][0], np.float32).view(
        np.uint32)
    bf16_share = float(np.mean(bits & 0xFFFF == 0))
    return {"layer": MIXER_LAYER, "prompt_len": len(prompt),
            "pad_tokens": -len(prompt) % chunk,
            "short_prompt": len(stages[1][0]),
            "decode_steps": len(stages[2][0]) - len(stages[1][0]),
            "after_prefill": after_long, "after_short_prefill": after_short,
            "after_decode": after_decode, "window": window,
            "deep_state": deep["state"], "deep_rows": deep["rows"],
            "deep_logits": deep["logits"],
            "state_bfloat16_share": bf16_share,
            "bfloat16_share_tolerance": corr["state_bfloat16_share_max"],
            "tolerance": tol, "window_tolerance": window_tol,
            "deep_tolerance": deep_tol,
            "ok": bool(max(after_long, after_short, after_decode) <= tol
                       and window <= window_tol
                       and bf16_share <= corr["state_bfloat16_share_max"]
                       and all(v is None or v <= deep_tol
                               for v in deep.values()))}


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
    """One Gated DeltaNet mixer (the first) on its own input: a ragged
    chunk through the program's prefill form (sixteen chunks of the
    delta rule, the state passed between them) from a zero state, then
    one token through its decode form, against the reference's
    token-by-token recurrence on the same float32 input. Catches
    ``beta`` or the decay left out, value heads on another key head,
    the L2 norms or the query's scale dropped, the gate or the gated
    norm wrong."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.qwen3_next import DELTA, GatedDeltaNet

    name = model_cfg.names(DELTA)[0]
    p = params[name]["mixer"]
    n_valid = chunk - chunk // 7        # ragged
    x = jax.random.normal(jax.random.PRNGKey(seed % (2 ** 31)),
                          (n_valid + 1, model_cfg.hidden_size),
                          jnp.float32).astype(model_cfg.dtype)
    mixer = GatedDeltaNet(model_cfg)

    @jax.jit
    def program(p, x):
        leaves = {
            "gdn": jnp.zeros((1, model_cfg.linear_num_value_heads,
                              model_cfg.linear_key_head_dim,
                              model_cfg.linear_value_head_dim),
                             jnp.float32),
            "conv": jnp.zeros((model_cfg.linear_conv_kernel_dim - 1, 1,
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

    reference = reference or (lambda p, x: ref.delta_net(x, p, ref_cfg)[0])
    want = np.asarray(jax.jit(reference)(p, x.astype(jnp.float32)))
    got = np.asarray(program(p, x), np.float32)
    return _readings(got, want, n_valid, tol, layer=name, tokens=n_valid)


def check_attention(model_cfg, ref_cfg, params, seed, chunk, page_size,
                    impl, tol, decode_tol, reference=None):
    """The first attention layer on its own input: a chunk through the
    dense prefill form into a small pool of its own (positions 0 to
    ``chunk`` - 1: the rotary angles run well past the first page), then
    one token at position ``chunk`` through the decode form (the flash
    kernel where the cell serves with it: 8 query heads to each of 2 key
    heads at head size 256), against the reference's. Catches another
    score scale than ``head_dim^-0.5``, a query group on the wrong key
    head, rotary on the wrong entries or none, the head norms' ``1 +
    w``, the output gate left out. The decode reading has a limit of
    its own (one token's sound reading is well under a chunk's)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.cache import (init_kv_cache,
                                               page_pool_spec)
    from deepspeed_tpu.models.qwen3_next import ATTENTION, GatedAttention

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
    layer = GatedAttention(model_cfg)

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
    reference's loop over the held experts plus the gated shared
    expert. Also reads that the pairs the program counted are tokens x
    ``num_experts_per_tok``, and that the reference's renormalised
    weights sum to 1 over all of a token's chosen experts, held here or
    not (``weights_sum_off``: the largest distance from 1). Catches
    weights not renormalised, another score function, the shared
    expert or its gate left out or doubled, a pair of an expert held
    elsewhere leaking in."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.qwen3_next import SparseExperts

    name = "layers_0"
    p = params[name]["experts"]
    first = model_cfg.experts_held[0]
    n_valid = chunk - chunk // 7        # ragged
    x = jax.random.normal(jax.random.PRNGKey((seed + 2) % (2 ** 31)),
                          (n_valid + rows, model_cfg.hidden_size),
                          jnp.float32).astype(model_cfg.dtype)
    live = np.arange(rows) % 3 != 2     # a third of the rows hold nothing
    layer = SparseExperts(model_cfg)

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
    weights, chosen = ref.route(x32, p, ref_cfg)
    off = float(np.abs(np.asarray(weights).sum(-1) - 1.0).max())
    held = model_cfg.experts_held[1]
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
    summed over the layers; the live rows whose state it moved; the
    rows whose KV block the kernel wrote back), and over its prefills
    the calls of a prompt."""
    from deepspeed_tpu.telemetry import spans

    closed = [r for r in spans.recent(t0) if r[2] < t1 and r[3]]

    def mean(path, key):
        vals = [r[3][key] for r in closed
                if r[0] == path and r[3].get(key) is not None]
        return float(np.mean(vals)) if vals else None

    step, prefill = "serve/step/decode", "serve/step/admit/prefill"
    counters = ("moe_experts_touched", "moe_pairs_held", "moe_pairs_routed",
                "moe_pairs_max", "gdn_rows_live", "gdn_rows_touched",
                "kv_rows_written")
    return {**{f"{c}_profiled": mean(step, c) for c in counters},
            "prefill_chunks_profiled": mean(prefill, "chunks"),
            "prefill_pad_tokens_profiled": mean(prefill, "pad_tokens")}


# --- the run ---------------------------------------------------------------

def run(ctx):
    try:
        import deepspeed_tpu.models.qwen3_next  # noqa: F401
    except ImportError as e:
        # a program from before the model was added cannot run the cell
        ctx.log(f"the program under test has no Qwen3-Next model: {e}")
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
    # one call of mostly padding, and the walk at its longest
    by_len = sorted(finished, key=lambda r: len(tracker.prompts[r]))
    checked = (by_len[:n_check // 2] + by_len[len(by_len) - (
        n_check - n_check // 2):]) if len(by_len) >= n_check else by_len
    ctx.log(f"checking {len(checked)} of {len(finished)} finished requests "
            f"against the reference")
    logits = check_logits(ctx, engine, tracker, checked)
    counts = engine.compile_counts()
    ctx.log("one layer of each kind on its own input")
    chunk = engine.prefill_chunk
    # of the finished prompts the longest with a padded tail (the longest
    # of all is as a rule one the generator clipped to a whole number of
    # calls): several calls, the last of them ragged
    probe = max(finished or measured[:1],
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
             "attention_block_k": engine.attention_block_k,
             "prefill_chunk": engine.prefill_chunk,
             **ring_facts(seg0, w1)}
    detail["profiled_segment"] = {k: v for k, v in facts.items()
                                  if k.endswith("_profiled")}
    return harness.Result(
        correct=correct, attempted=len(measured), failed=len(failed),
        setup_s=setup_s, end_to_end=end_to_end, detail=detail,
        facts=facts, trace=trace)

"""Driver of the serving cells of an LFM2 model with experts held as a
share (`deepspeed_tpu/models/lfm2_moe.py`: short convolutions between
two gates whose whole state is the window, grouped-query attention with
normed, rotated heads in the layers the published list names, two
leading dense layers, sigmoid routing with a choice bias over the held
experts): ``InferenceEngine`` + ``ContinuousBatchingScheduler`` built as
``inference/serve.py:main`` builds them.

Built from ``drivers/serve_qwen3_next.py``'s parts by import: its
``measure`` (ramp, window, drain, the end-to-end arithmetic, the facts
the metrics read) is that file's, unchanged; through it
``drivers/serve.py``'s open loop and ``drivers/serve_hybrid.py``'s
ordered arrivals and program scopes; the logits' check of sequences at
their own lengths is ``drivers/serve_mimo_v2.py``'s. ``measure`` there
takes its checks and its span facts from four names of its own module
(``check_logits``, ``own_input_checks``, ``ring_facts``, ``ref``) and
not as arguments, and that file may not be edited: :func:`measure` here
puts this file's four in their place for the length of the call, as
``drivers/serve_ling.py`` does (`PERF.md`, section 7 (j)).

What is this file's own: the model and its bfloat16 weights from the
configuration file (`model_config`); the reference
(``reference/lfm2_moe_ref.py``); and the checks behind ``correct``, all
from the timed engine at the timed sizes, with limits in the workload's
``correctness`` block: a generated token's logit (`check_logits`), what
a slot holds after the engine's own two programs (`check_slot`: the
eighteen windows, the six layers' pages), and a short-convolution
mixer, the attention and an expert layer **on its own input**
(`check_mixer`, `check_attention`, `check_experts`).

Workload file keys: as ``drivers/serve_hybrid.py``'s.
"""

import dataclasses
from unittest import mock

import numpy as np

from benchmarks.suite.drivers import serve_qwen3_next as parts
from benchmarks.suite.drivers.serve import install_spans, warm_up
from benchmarks.suite.drivers.serve_hybrid import program_scopes
from benchmarks.suite.drivers.serve_mimo_v2 import _padded
from benchmarks.suite.drivers.serve_mimo_v2 import \
    check_logits as check_padded_logits
from benchmarks.suite.drivers.serve_qwen3_next import _off, _readings
from benchmarks.suite.reference import lfm2_moe_ref as ref

__all__ = ["build", "warm_up", "measure", "run"]

MIXER_LAYER = "layers_0"    # the window's check: nothing upstream of it
# calls of the attention's own-input prefill: the last one's queries lie
# past position 4,096
CALLS = 5


def model_config(config, group="serve", **extra):
    """The program's config class from a configuration file."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.lfm2_moe import Lfm2MoeConfig

    names = {f.name for f in dataclasses.fields(Lfm2MoeConfig)}
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config.items() if k in names}
    assumed, g = config["assumed"], config[group]
    n_layer = config["n_layer"]
    kw.update(
        num_hidden_layers=n_layer,
        layer_types=tuple(config["layer_types"][:n_layer]),
        initializer_range=assumed["initializer_range"],
        norm_weight_range=assumed["norm_weight_range"],
        router_bias_range=assumed["router_bias_range"],
        experts_held=tuple(assumed["experts_held"]),
        dtype=getattr(jnp, g["compute_dtype"]),
        param_dtype=getattr(jnp, g["param_dtype"]))
    kw.update(extra)
    return Lfm2MoeConfig(**kw)


def build(ctx):
    import jax
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.models.lfm2_moe import (Lfm2MoeLM,
                                               init_lfm2_moe_params)

    model = Lfm2MoeLM(model_config(ctx.config))
    params = init_lfm2_moe_params(
        model, jax.random.PRNGKey(ctx.seed % (2 ** 31)))
    inf = dict(ctx.workload["inference"])
    inf["seq_buckets"] = tuple(inf["seq_buckets"])
    inf["sampling_seed"] = ctx.seed % (2 ** 31)
    engine = InferenceEngine(model, params, config=inf)
    return engine, ContinuousBatchingScheduler(engine)


# --- the checks behind ``correct`` ----------------------------------------

def check_logits(ctx, params, chunk, tracker, rids, forward=None):
    """`drivers/serve_mimo_v2.py:check_logits` (each sequence at its own
    length, padded to whole calls; a generated token's logit under its
    position's largest, over the largest |logit|) against this model's
    reference: the convolution an explicit sum over taps of ``b * x``,
    attention a head at a time over the whole prefix, a loop over the
    held experts."""
    return check_padded_logits(ctx, params, chunk, tracker, rids,
                               forward=forward or ref.forward)


def slot_readings(engine, prompt, generated, slot=0, decode_steps=256,
                  short=260):
    """What the engine's own two compiled programs leave of a prompt:
    ``[(tokens, {convolution layer: window}, {attention layer: (k, v)
    [n, 8, 64]}, logits)]`` on the host, read out of the engine's leaves
    and its pools (the slot's pages, handed over in descending order)
    three times: after the prefill of ``prompt`` (ragged, several
    calls); after the prefill of its first ``short`` tokens alone into
    the same slot and pages (one call, mostly padding: a slot that has
    had a tenant); and after ``decode_steps`` tokens fed to that through
    the decode program. ``logits`` is the program's whole logit row at
    the last of ``tokens``."""
    import jax.numpy as jnp

    table = np.arange(engine.pages_per_row, 0, -1, dtype=np.int32)

    def held(tokens, logits):
        n, windows, kv = len(tokens), {}, {}
        pages = jnp.asarray(table[:-(-n // engine.page_size)])
        for name, leaves in engine.cache.items():
            if "conv" in leaves:
                windows[name] = np.asarray(leaves["conv"][:, slot],
                                           np.float32)
            else:
                # [pages, heads, head_dim, page] -> [n, heads, head_dim]
                kv[name] = tuple(np.moveaxis(
                    np.asarray(leaves[x][pages], np.float32), -1, 1
                ).reshape((-1,) + leaves[x].shape[1:3])[:n] for x in "kv")
        return list(tokens), windows, kv, np.asarray(logits, np.float32)

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


def check_slot(ctx, engine, prompt, stages, forward=None):
    """What a slot holds after the engine's own prefill and decode
    (``stages``: `slot_readings` of ``prompt``, taken while the engine
    had its pools) against the reference's full forward over the same
    tokens.

    **The first mixer** (its input is the embedding's norm, so nothing
    upstream is in the difference): its window, the last two ``b * x``,
    over the reference's largest entry under ``window_rtol``, the
    largest of the three stages. Catches a padded tail let into the
    window, the slot's last tenant's window carried into a prompt, the
    input gate left out of what is stored (the window then holds ``x``).

    **Every later layer** (``deep_*`` under ``deep_rtol``; `_off`: a
    norm): the other seventeen windows and the six attention layers'
    pooled keys and values (``deep_rows``), and the whole logit row at
    each stage's last token (``deep_logits``), which has been through
    both programs' every layer.

    (``forward``: `tools/fault_readings_lfm2.py`'s way in.)"""
    corr = ctx.workload["correctness"]
    chunk = engine.prefill_chunk
    first, deep = [], {"rows": [], "logits": []}
    for tokens, windows, kv, logits in stages:
        n = len(tokens)
        want_logits, want_windows, want_kv = (forward or ref.forward)(
            engine.params, _padded(tokens, chunk), ctx.config, rows=[n - 1],
            state_at=n - 1)
        want = np.asarray(want_windows[MIXER_LAYER])
        first.append(float(np.abs(windows[MIXER_LAYER] - want).max() /
                           np.abs(want).max()))
        for name, want in want_windows.items():
            if name != MIXER_LAYER:
                deep["rows"].append(_off(windows[name], want))
        for name, (k, v) in want_kv.items():
            deep["rows"].append(_off(kv[name][0], np.asarray(k)[:n]))
            deep["rows"].append(_off(kv[name][1], np.asarray(v)[:n]))
        deep["logits"].append(_off(logits, np.asarray(want_logits)[0]))
    after_long, after_short, after_decode = first
    deep = {k: max(v) for k, v in deep.items()}
    tol, deep_tol = corr["window_rtol"], corr["deep_rtol"]
    return {"layer": MIXER_LAYER, "prompt_len": len(prompt),
            "pad_tokens": -len(prompt) % chunk,
            "short_prompt": len(stages[1][0]),
            "decode_steps": len(stages[2][0]) - len(stages[1][0]),
            "after_prefill": after_long, "after_short_prefill": after_short,
            "after_decode": after_decode,
            "deep_rows": deep["rows"], "deep_logits": deep["logits"],
            "tolerance": tol, "deep_tolerance": deep_tol,
            "ok": bool(max(first) <= tol
                       and all(v <= deep_tol for v in deep.values()))}


def mixer_inputs(model_cfg, seed, chunk, rows=4):
    """What `check_mixer` feeds the mixer: the ragged chunk's tokens and
    the decode step's rows ``x``, and what the padded tail and the
    slots' windows hold before the call (``tail``, ``stale``): not
    zeros, so that a tail or a tenant let in shows."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    C, dt = model_cfg.hidden_size, model_cfg.dtype
    n_valid = chunk - chunk // 7
    x = jax.random.normal(keys[0], (n_valid + rows, C), jnp.float32)
    tail = 3.0 * jax.random.normal(keys[1], (chunk, C), jnp.float32)
    stale = jax.random.normal(keys[2], (model_cfg.conv_L_cache - 1, rows, C),
                              jnp.float32)
    return x.astype(dt), tail.astype(dt), stale.astype(dt)


def check_mixer(model_cfg, ref_cfg, params, seed, chunk, tol,
                reference=None, sound=None, rows=4):
    """One short-convolution mixer (the first) on its own input: a
    ragged chunk through the program's prefill form into slot 1 of
    ``rows``, whose stale window the call must not read (it starts the
    prompt), then a decode step of every row of which slot 1 alone
    holds a request, against the reference's explicit sum over taps on
    the same float32 input. Also reads that the other rows' windows are
    what they were, to the bit. Catches either gate left out, the gates
    exchanged, an activation after the taps, the taps reversed, a stale
    window carried into a prompt, the padded tail entering the window.
    (``sound``: the reference's weights where ``params`` are the
    program's faulty ones.)"""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.lfm2_moe import CONV, ShortConv

    name = model_cfg.names(CONV)[0]
    p = params[name]["mixer"]
    n_valid = chunk - chunk // 7        # ragged
    slot = 1
    x, tail, stale = mixer_inputs(model_cfg, seed, chunk, rows)
    mixer = ShortConv(model_cfg)

    @jax.jit
    def program(p, x):
        padded = tail.at[:n_valid].set(x[:n_valid])[None]
        y, leaves = mixer.apply(
            {"params": p}, padded, {"conv": stale},
            jnp.arange(chunk, dtype=jnp.int32)[None],
            jnp.full((1,), slot, jnp.int32),
            jnp.full((1,), n_valid, jnp.int32))
        live = jnp.arange(rows) == slot
        y1, leaves = mixer.apply(
            {"params": p}, x[n_valid:, None], leaves,
            jnp.full((rows, 1), n_valid, jnp.int32),
            jnp.arange(rows, dtype=jnp.int32), live.astype(jnp.int32))
        moved = jnp.abs(jnp.where(
            live[None, :, None], 0.0,
            leaves["conv"].astype(jnp.float32) - stale.astype(jnp.float32))
        ).max()
        return jnp.concatenate([y[0, :n_valid], y1[slot]]), moved

    reference = reference or (lambda p, x: ref.short_conv(x, p, ref_cfg)[0])
    seq = jnp.concatenate([x[:n_valid], x[n_valid + slot][None]])
    want = np.asarray(jax.jit(reference)(
        (sound or params)[name]["mixer"], seq.astype(jnp.float32)))
    got, moved = program(p, x)
    out = _readings(np.asarray(got, np.float32), want, n_valid, tol,
                    layer=name, tokens=n_valid, rows=rows,
                    dead_rows_window_moved=float(moved))
    out["ok"] = bool(out["ok"] and float(moved) == 0.0)
    return out


def check_attention(model_cfg, ref_cfg, params, seed, chunk, page_size,
                    impl, tol, decode_tol, reference=None, sound=None):
    """The first attention layer on its own input: `CALLS` chunks
    through its prefill form into a small pool of its own, the last
    ragged and every query of it past position 4,096 (the rotary angles
    far from the origin), then one token through the decode form (the
    flash kernel where the cell serves with it), against the reference's
    attention a head at a time on the same float32 input. Catches the
    head norms left out or one weight for both, rotary at another base
    or over half the head, another score scale than 64^-0.5, a query
    head on another key head. The decode reading has a limit of its
    own."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.cache import (init_kv_cache,
                                               page_pool_spec)
    from deepspeed_tpu.models.lfm2_moe import (ATTENTION,
                                               NormedGroupedQueryAttention,
                                               rope_cos_sin)

    name = model_cfg.names(ATTENTION)[0]
    p = params[name]["attn"]
    span = CALLS * chunk + page_size
    spec = page_pool_spec(
        1, span, n_layer=1, n_head=model_cfg.num_key_value_heads,
        head_dim=model_cfg.head_dim, compute_dtype=model_cfg.dtype,
        n_positions=span, page_size=page_size)
    n_last = chunk - chunk // 7         # the last chunk's real tokens
    n = (CALLS - 1) * chunk + n_last
    x = jax.random.normal(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                          (n + 1, model_cfg.hidden_size),
                          jnp.float32).astype(model_cfg.dtype)
    layer = NormedGroupedQueryAttention(model_cfg)

    @jax.jit
    def program(p, x):
        pool = init_kv_cache(spec)["h_0"]
        table = jnp.arange(spec.pages_per_row, 0, -1, dtype=jnp.int32)[None]
        padded = jnp.zeros((CALLS * chunk, x.shape[1]), x.dtype)
        padded = padded.at[:n].set(x[:n]).reshape(CALLS, 1, chunk, -1)

        def call(pool, c):
            positions = (c * chunk + jnp.arange(chunk, dtype=jnp.int32))[None]
            y, pool = layer.apply(
                {"params": p}, padded[c], pool, positions, table,
                rope_cos_sin(model_cfg, positions),
                {"impl": impl, "block_k": page_size})
            return pool, y[0]

        pool, ys = jax.lax.scan(call, pool, jnp.arange(CALLS))
        at = jnp.full((1, 1), n, jnp.int32)
        y_last, _ = layer.apply(
            {"params": p}, x[None, n:], pool, at, table,
            rope_cos_sin(model_cfg, at),
            {"impl": impl, "block_k": page_size})
        return jnp.concatenate([ys.reshape(CALLS * chunk, -1)[:n],
                                y_last[0]])

    reference = reference or (lambda p, x: ref.attention(x, p, ref_cfg))
    want = np.asarray(jax.jit(reference)(
        (sound or params)[name]["attn"], x.astype(jnp.float32)))
    got = np.asarray(program(p, x), np.float32)
    return _readings(got, want, n, tol, decode_tol, layer=name, tokens=n,
                     calls=CALLS)


def check_experts(model_cfg, ref_cfg, params, seed, chunk, rows, tol,
                  reference=None, sound=None, route=None):
    """One expert layer (the first) on its own input: a ragged chunk
    through its prefill shape and a decode step's rows (a third of them
    without a request) through its decode shape, against the reference's
    loop over the held experts. Also reads that the pairs the program
    counted are tokens x ``num_experts_per_tok``, that the reference's
    weights sum to ``routed_scaling_factor`` (less the renormaliser's
    1e-6) over all of a token's chosen experts, held here or not, and
    that the program's routing function gives those weights
    (``route_weights_off``: the mean distance of a token's sorted
    weights, under 1e-5; the bias moves the choice and no weight).
    Catches the bias in the weights, the renormalisation left out,
    softmax for sigmoid, a pair of an expert held elsewhere let in."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.lfm2_moe import ROUTE_EPS, HeldExperts
    from deepspeed_tpu.moe.dropless import sigmoid_top_k

    name = next(f"layers_{i}" for i in range(model_cfg.num_hidden_layers)
                if not model_cfg.is_dense(i))
    p = params[name]["experts"]
    first, held = model_cfg.experts_held
    n_valid = chunk - chunk // 7        # ragged
    x = jax.random.normal(jax.random.PRNGKey((seed + 2) % (2 ** 31)),
                          (n_valid + rows, model_cfg.hidden_size),
                          jnp.float32).astype(model_cfg.dtype)
    live = np.arange(rows) % 3 != 2     # a third of the rows hold nothing
    layer = HeldExperts(model_cfg)

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
    weights, chosen = (np.asarray(a) for a in (route or ref.route)(
        x32, (sound or params)[name]["experts"], ref_cfg))
    # the program's own routing weights, a token's sorted, against the
    # reference's: both float32 at the highest precision
    mine = np.sort(np.asarray(sigmoid_top_k(
        p["expert_bias"], model_cfg.routed_scaling_factor,
        model_cfg.norm_topk_prob, eps=ROUTE_EPS)(
            x32, p["router"], model_cfg.num_experts_per_tok)[0]), -1)
    weights_off = float(np.abs(mine - np.sort(weights, -1)).mean())
    off = float(np.abs(weights.sum(-1) -
                       ref_cfg["routed_scaling_factor"]).max())
    held_share = float(np.mean((chosen >= first) & (chosen < first + held)))
    tokens = n_valid + int(live.sum())
    pairs = tokens * model_cfg.num_experts_per_tok
    counted = int(c0[0]) + int(c1[0])
    out = _readings(got, want, n_valid, tol, layer=name, tokens=n_valid,
                    rows=int(live.sum()), weights_sum_off=off,
                    route_weights_off=weights_off, held_share=held_share,
                    pairs_routed=counted,
                    pairs_held=int(c0[1]) + int(c1[1]))
    out["ok"] = bool(out["ok"] and off < 1e-5 and weights_off < 1e-5
                     and counted == pairs)
    return out


class _Checks:
    """`measure`'s three names for the length of one call. The slot's
    readings need the engine's pools, and the reference's float32
    activations of a 9 k sequence beside a float32 copy of a layer's
    weights need their room (the pools are 6 GB of the chip's 16), so
    the first check `measure` makes (the logits') takes the readings of
    the prompt that `measure` will hand `own_input_checks` (its rule: of
    the finished requests the longest with a padded tail), then lets the
    pools go; they come back empty for `measure`, which reads their
    facts."""

    def __init__(self):
        self.prompt, self.stages = None, None

    def check_logits(self, ctx, engine, tracker, rids):
        chunk = engine.prefill_chunk
        finished = [r for r, why in tracker.finish.items()
                    if why == "max_new_tokens" and r in tracker.tokens]
        if finished:
            probe = max(finished, key=lambda r: (
                len(tracker.prompts[r]) % chunk > 0,
                len(tracker.prompts[r])))
            self.stages = slot_readings(
                engine, tracker.prompts[probe], tracker.tokens[probe] or [0])
            self.prompt = list(tracker.prompts[probe])
        engine.cache = None
        return check_logits(ctx, engine.params, chunk, tracker, rids)

    def own_input_checks(self, ctx, engine, prompt, generated):
        corr = ctx.workload["correctness"]
        cfg = engine.model.config
        chunk = engine.prefill_chunk
        stages = self.stages
        if stages is None or list(prompt) != self.prompt:
            engine.reset()
            stages = slot_readings(engine, prompt, generated)
            engine.cache = None
        own = {
            "slot": check_slot(ctx, engine, prompt, stages),
            "mixer": check_mixer(cfg, ctx.config, engine.params, ctx.seed,
                                 chunk, corr["mixer_rtol"]),
            "attention": check_attention(
                cfg, ctx.config, engine.params, ctx.seed, chunk,
                engine.page_size, engine.attention_impl,
                corr["attention_rtol"], corr["attention_decode_rtol"]),
            "experts": check_experts(
                cfg, ctx.config, engine.params, ctx.seed, chunk,
                engine.max_batch, corr["expert_rtol"])}
        engine.reset()
        return own


# --- what the metrics read -------------------------------------------------

def ring_facts(t0, t1):
    """`drivers/serve_qwen3_next.py:ring_facts` with this model's
    counters: from the program's own spans that closed in ``[t0, t1)``
    (the profiled segment), the means over its decode steps of what a
    step's span counts, and over its prefills the calls of a prompt."""
    from deepspeed_tpu.telemetry import spans

    closed = [r for r in spans.recent(t0) if r[2] < t1 and r[3]]

    def mean(path, key):
        vals = [r[3][key] for r in closed
                if r[0] == path and r[3].get(key) is not None]
        return float(np.mean(vals)) if vals else None

    step, prefill = "serve/step/decode", "serve/step/admit/prefill"
    counters = ("moe_experts_touched", "moe_pairs_held", "moe_pairs_routed",
                "moe_pairs_max", "sconv_rows_live", "sconv_rows_touched",
                "kv_rows_written")
    return {**{f"{c}_profiled": mean(step, c) for c in counters},
            "prefill_chunks_profiled": mean(prefill, "chunks"),
            "prefill_pad_tokens_profiled": mean(prefill, "pad_tokens")}


# --- the run ---------------------------------------------------------------

def run(ctx):
    try:
        import deepspeed_tpu.models.lfm2_moe  # noqa: F401
    except ImportError as e:
        # a program from before the model was added cannot run the cell
        ctx.log(f"the program under test has no LFM2 model: {e}")
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
    """`drivers/serve_qwen3_next.py:measure` (ramp, window and drain on a
    warm engine, then the checks; the end-to-end arithmetic is
    ``drivers/serve.py``'s) with this model's checks, span facts and
    reference under the four names it reads them by."""
    checks = _Checks()
    with mock.patch.multiple(
            parts, check_logits=checks.check_logits,
            own_input_checks=checks.own_input_checks,
            ring_facts=ring_facts, ref=ref):
        return parts.measure(ctx, engine, sched)

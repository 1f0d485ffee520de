"""Driver of the serving cells of a Laguna model held as a share
(`deepspeed_tpu/models/laguna.py`: window layers of 72 query heads
beside full layers of 48 over the same 8 key heads, a sigmoid gate a
head, YaRN on half a head in the full layers, softmax routing times a
factor over the held experts beside a shared one): ``InferenceEngine`` +
``ContinuousBatchingScheduler`` built as ``inference/serve.py:main``
builds them.

Built from ``drivers/serve_mimo_v2.py``'s parts by import: its
``measure`` (ramp, window, drain, the end-to-end arithmetic, the facts
the metrics read), ``check_logits``, ``check_slot`` / ``slot_readings``
and ``ring_facts`` are that file's, unchanged; through it
``drivers/serve.py``'s open loop and ``drivers/serve_hybrid.py``'s
ordered arrivals and program scopes. ``measure`` there takes its checks
from two names of its own module (``own_input_checks``, ``ref``) and
not as arguments, and that file may not be edited: :func:`measure` here
puts this file's two in their place for the length of the call
(`PERF.md`, section 7 (j) says what a ``benchmark`` PR should do
instead).

What is this file's own: the model and its bfloat16 weights from the
configuration file (`model_config`); the reference
(``reference/laguna_ref.py``); and the three checks of one layer **on
its own input** (a window layer's 72 heads and a full layer's 48
through a prefill of several calls, the last ragged, and a decode step
through the kernel; the expert layer on a ragged chunk and a decode
step's rows).

Workload file keys: as ``drivers/serve_hybrid.py``'s.
"""

import dataclasses
from unittest import mock

import numpy as np

from benchmarks.suite.drivers import serve_mimo_v2 as parts
from benchmarks.suite.drivers.serve import install_spans, warm_up
from benchmarks.suite.drivers.serve_hybrid import program_scopes
from benchmarks.suite.drivers.serve_qwen3_next import _readings
from benchmarks.suite.reference import laguna_ref as ref

__all__ = ["build", "warm_up", "measure", "run"]

FULL, WINDOW = "full", "window"
# calls of a layer's own-input prefill: a full layer's last call lies
# past YaRN's original 8,192 positions, a window layer's second reads a
# ring the first has wrapped
CALLS = {FULL: 9, WINDOW: 2}


def model_config(config, group="serve", **extra):
    """The program's config class from a configuration file."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.laguna import LagunaConfig

    names = {f.name for f in dataclasses.fields(LagunaConfig)}
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config.items() if k in names}
    assumed, g = config["assumed"], config[group]
    kw.update(
        num_hidden_layers=config["n_layer"],
        initializer_range=assumed["initializer_range"],
        experts_held=tuple(assumed["experts_held"]),
        dtype=getattr(jnp, g["compute_dtype"]),
        param_dtype=getattr(jnp, g["param_dtype"]))
    kw.update(extra)
    return LagunaConfig(**kw)


def build(ctx):
    import jax
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.models.laguna import LagunaLM, init_laguna_params

    model = LagunaLM(model_config(ctx.config))
    params = init_laguna_params(
        model, jax.random.PRNGKey(ctx.seed % (2 ** 31)))
    inf = dict(ctx.workload["inference"])
    inf["seq_buckets"] = tuple(inf["seq_buckets"])
    inf["sampling_seed"] = ctx.seed % (2 ** 31)
    engine = InferenceEngine(model, params, config=inf)
    return engine, ContinuousBatchingScheduler(engine)


# --- the checks behind ``correct`` ----------------------------------------

def check_slot(ctx, engine, prompt, generated, **kw):
    """`drivers/serve_mimo_v2.py:check_slot` against this model's
    reference."""
    return parts.check_slot(ctx, engine, prompt, generated,
                            forward=ref.forward, **kw)


def check_logits(ctx, params, chunk, tracker, rids):
    """`drivers/serve_mimo_v2.py:check_logits` against this model's
    reference (for `tools/fault_readings_laguna.py`; inside `measure`
    that function finds the reference under its module's name)."""
    return parts.check_logits(ctx, params, chunk, tracker, rids,
                              forward=ref.forward)


def check_attention(model_cfg, ref_cfg, params, which, seed, chunk,
                    page_size, impl, tol, decode_tol, reference=None,
                    sound=None):
    """The first layer of kind ``which`` on its own input: `CALLS`
    chunks through its prefill form into a small pool of its own, the
    last ragged (a full layer's nine walk up to nine blocks, every query
    of the last past position 8,192, where YaRN's frequencies and plain
    ones have long parted; a window layer's second reads the 512
    positions before it out of a ring of five pages that the first has
    wrapped), then one token through its decode form (the flash kernel
    where the cell serves with it: 6 or 9 queries a key head), against
    the reference's on the same float32 input. Catches another window
    than 512, a mask by ring entry and not by position, the gate left
    out, taken from the next head or an element, plain rotary for YaRN,
    ``attention_factor`` left out, rotary on the other kind's part of a
    head or at its base, a query group on the wrong key head. The decode
    reading has a limit of its own. (``sound``: the reference's weights
    where ``params`` are the program's faulty ones.)"""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.models.laguna import LagunaAttention

    calls = CALLS[which]
    name = model_cfg.names(which)[0]
    p = params[name]["attn"]
    spec = model_cfg.cache_spec(1, calls * chunk + page_size,
                                page_size=page_size)
    n_last = chunk - chunk // 7         # the last chunk's real tokens
    n = (calls - 1) * chunk + n_last
    x = jax.random.normal(
        jax.random.PRNGKey((seed + (1 if which == FULL else 3)) % (2 ** 31)),
        (n + 1, model_cfg.hidden_size), jnp.float32).astype(model_cfg.dtype)
    layer = LagunaAttention(model_cfg, which)

    @jax.jit
    def program(p, x):
        pool = init_kv_cache(spec)[name]
        width = spec.ring_pages if which == WINDOW else spec.pages_per_row
        table = jnp.arange(width, 0, -1, dtype=jnp.int32)[None]
        padded = jnp.zeros((calls * chunk, x.shape[1]), x.dtype)
        padded = padded.at[:n].set(x[:n]).reshape(calls, 1, chunk, -1)

        def call(pool, c):
            y, pool = layer.apply(
                {"params": p}, padded[c], pool,
                (c * chunk + jnp.arange(chunk, dtype=jnp.int32))[None],
                table, jnp.where(c == calls - 1, n_last, chunk)[None],
                {"impl": "dense"})
            return pool, y[0]

        pool, ys = jax.lax.scan(call, pool, jnp.arange(calls))
        y_last, _ = layer.apply(
            {"params": p}, x[None, n:], pool, jnp.full((1, 1), n, jnp.int32),
            table, jnp.ones((1,), jnp.int32),
            {"impl": impl, "block_k": page_size})
        return jnp.concatenate([ys.reshape(calls * chunk, -1)[:n],
                                y_last[0]])

    reference = reference or (
        lambda p, x: ref.attention(x, p, ref_cfg, which))
    want = np.asarray(jax.jit(reference)(
        (sound or params)[name]["attn"], x.astype(jnp.float32)))
    got = np.asarray(program(p, x), np.float32)
    return _readings(got, want, n, tol, decode_tol, layer=name, kind=which,
                     tokens=n, calls=calls)


def check_experts(model_cfg, ref_cfg, params, seed, chunk, rows, tol,
                  reference=None, sound=None):
    """One expert layer (the first) on its own input: a ragged chunk
    through its prefill shape and a decode step's rows (a third of them
    without a request) through its decode shape, against the reference's
    loop over the held experts and its shared expert. Also reads that
    the pairs the program counted are tokens x ``num_experts_per_tok``,
    and that the reference's weights sum to ``moe_routed_scaling_factor``
    over all of a token's chosen experts, held here or not. Catches the
    factor or the renormalisation left out, another score than softmax,
    the shared expert left out or gated, a pair of an expert held
    elsewhere leaking in."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.laguna import SparseExperts

    name = next(f"layers_{i}" for i in range(model_cfg.num_hidden_layers)
                if not model_cfg.is_dense(i))
    p = params[name]["experts"]
    first, held = model_cfg.experts_held
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
    want = np.asarray(jax.jit(reference)(
        (sound or params)[name]["experts"], x32))
    got, c0, c1 = program(p, x)
    got = np.asarray(got, np.float32)
    keep = np.concatenate([np.ones(n_valid, bool), live])
    want, got = want[keep], got[keep]
    weights, chosen = ref.route(x32, p, ref_cfg)
    off = float(np.abs(np.asarray(weights).sum(-1) -
                       ref_cfg["moe_routed_scaling_factor"]).max())
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

    # the engine's own two programs first (`slot_readings`: the pools
    # onto the host); then the pools go, for the reference's float32
    # activations of a prompt of 9 k tokens and more need the room (with
    # them the first run peaked at 15.7 GB of 16), and come back empty
    # for `measure`, which reads their facts
    stages = parts.slot_readings(engine, prompt, generated)
    engine.cache = None
    own = {
        "slot": check_slot(ctx, engine, prompt, generated, stages=stages),
        "window": attention(WINDOW), "full": attention(FULL),
        "experts": check_experts(
            cfg, ctx.config, engine.params, ctx.seed, chunk,
            engine.max_batch, corr["expert_rtol"])}
    engine.reset()
    return own


# --- the run ---------------------------------------------------------------

def run(ctx):
    try:
        import deepspeed_tpu.models.laguna  # noqa: F401
    except ImportError as e:
        # a program from before the model was added cannot run the cell
        ctx.log(f"the program under test has no Laguna model: {e}")
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
    """`drivers/serve_mimo_v2.py:measure` (ramp, window and drain on a
    warm engine, then the checks; the end-to-end arithmetic is
    ``drivers/serve.py``'s) with this model's own-input checks and
    reference under the two names it reads them by."""
    with mock.patch.multiple(parts, own_input_checks=own_input_checks,
                             ref=ref):
        return parts.measure(ctx, engine, sched)

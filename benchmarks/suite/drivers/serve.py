"""Driver of serving cells: ``InferenceEngine`` +
``ContinuousBatchingScheduler`` built as ``inference/serve.py:main``
builds them, under open-loop traffic on the wall clock.

The program stamps no times (``Completion`` carries none, and its
``arrival_step`` runs on the step clock), so the driver submits each
request when it is due, calls ``sched.step()`` itself and reads every
row's ``generated`` after each step: a token is stamped when the step
that produced it returns: the first moment a caller of the scheduler
can read it. One ``step()`` admits and prefills a request (its first
token) and then decodes (its second), so the two carry the same stamp:
time to first token includes that decode step, and the gap between the
first and the second token, always 0 as seen from outside, is left out
of the gaps between tokens.

Workload file keys: ``traffic`` (``generator`` and its parameters),
``inference`` (the engine's ``inference`` block), ``warmup``,
``correctness`` and ``trace``.
"""

import importlib
import time

import numpy as np

from benchmarks.suite import harness, stats
from benchmarks.suite.harness import clock
from benchmarks.suite.reference import gpt2_ref


def build(ctx):
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)

    model = harness.gpt2_model(ctx.config, "serve")
    params = harness.seeded_params(model, ctx.seed)
    inf = dict(ctx.workload["inference"])
    inf["seq_buckets"] = tuple(inf["seq_buckets"])
    inf["sampling_seed"] = ctx.seed % (2 ** 31)
    engine = InferenceEngine(model, params, config=inf)
    return engine, ContinuousBatchingScheduler(engine)


def warm_up(ctx, engine, sched):
    """Every shape the traffic uses: the longest prompt walks every
    prefill chunk index, and a few decode steps follow."""
    from deepspeed_tpu.inference.scheduler import Request

    rng = np.random.default_rng(ctx.seed + 1)
    vocab = ctx.config["vocab_size"]
    reqs = [Request(rid=f"warm{i}", max_new_tokens=int(new),
                    prompt=rng.integers(0, vocab, int(n)).tolist())
            for i, (n, new) in enumerate(ctx.workload["warmup"])]
    done = sched.run(reqs)
    bad = [c.rid for c in done if c.finish_reason != "max_new_tokens"]
    if bad:
        raise RuntimeError(f"warm-up requests did not finish: {bad}")
    sched.completions.clear()


class Tracker:
    """Token stamps of every request, read off the scheduler."""

    def __init__(self, arrivals, t0):
        self.due = {a.rid: t0 + a.due_s for a in arrivals}
        self.stamps = {}        # rid -> [clock of each token]
        self.admitted = {}      # rid -> clock at the start of its step
        self.finish = {}        # rid -> finish_reason
        self.tokens = {}        # rid -> generated ids, once finished
        self.prompts = {a.rid: a.prompt for a in arrivals}
        self._seen_completions = 0

    def _stamp(self, rid, n_tokens, t_step, t_after):
        got = self.stamps.setdefault(rid, [])
        if not got:
            self.admitted[rid] = t_step
        got.extend([t_after] * (n_tokens - len(got)))

    def after_step(self, sched, t_step, t_after):
        for slot in sched.slots:
            if slot is not None:
                self._stamp(slot.request.rid, len(slot.generated),
                            t_step, t_after)
        for comp in sched.completions[self._seen_completions:]:
            if comp.tokens:
                self._stamp(comp.rid, len(comp.tokens), t_step, t_after)
            self.finish[comp.rid] = comp.finish_reason
            self.tokens[comp.rid] = list(comp.tokens)
        self._seen_completions = len(sched.completions)


def pool_fill(sched):
    """Shares of the paged pool's pages, by the program's own tables:
    (mapped by a live row, allocated at all). The second also counts the
    prompts' pages that the radix prefix cache keeps after their
    requests have ended, until the pool runs short. (None, None)
    without a pool."""
    if sched.paging is None:
        return None, None
    alloc = sched.paging.allocator
    live = sum(len(s.paging.pages) for s in sched.slots if s is not None)
    return (live / (alloc.n_pages - 1),
            alloc.resident_pages / (alloc.n_pages - 1))


def serve_loop(ctx, sched, arrivals, t0, t_end, profile=None):
    """Submit what is due, step, stamp; sleep to the next due time when
    nothing is live or queued. ``profile``: (start, stop) clocks between
    which the profiler runs. Returns the tracker and per-step facts."""
    from deepspeed_tpu.inference.scheduler import Request

    rec = ctx.recorder
    tracker = Tracker(arrivals, t0)
    late, steps, nxt = [], [], 0
    prof, trace = harness.Profiler(ctx), None
    profile = list(profile) if profile else None
    max_batch = sched.engine.max_batch
    try:
        while True:
            now = clock()
            if now >= t_end:
                break
            if profile and not prof.running and now >= profile[0]:
                prof.start()
                profile[0] = float("inf")
            if prof.running and now >= profile[1]:
                trace = prof.stop()
            with rec.span("generator"):
                while nxt < len(arrivals) and \
                        t0 + arrivals[nxt].due_s <= now:
                    a = arrivals[nxt]
                    late.append(now - (t0 + a.due_s))
                    with rec.span("submit"):
                        sched.submit(Request(
                            rid=a.rid, prompt=a.prompt,
                            max_new_tokens=a.max_new_tokens))
                    nxt += 1
            if not sched.queue and all(s is None for s in sched.slots):
                if nxt >= len(arrivals):
                    break
                with rec.span("idle_sleep"):
                    time.sleep(max(0.0, min(
                        t0 + arrivals[nxt].due_s, t_end) - clock()))
                continue
            t_step = clock()
            with rec.span("sched.step"):
                sched.step()
            t_after = clock()
            tracker.after_step(sched, t_step, t_after)
            live = [s for s in sched.slots if s is not None]
            steps.append((t_after, len(live) / max_batch, len(sched.queue),
                          sum(s.next_pos for s in live), *pool_fill(sched)))
    finally:
        if prof.running:
            trace = prof.stop()
    return tracker, steps, late, trace


def install_spans(ctx, engine):
    """The traced run's spans round the calls into the serving loop's
    layer: the engine's two programs."""
    rec = ctx.recorder
    prefill, decode = engine.prefill, engine.decode

    def spanned_prefill(*a, **kw):
        with rec.span("prefill"):
            return prefill(*a, **kw)

    def spanned_decode(*a, **kw):
        with rec.span("decode"):
            return decode(*a, **kw)

    engine.prefill, engine.decode = spanned_prefill, spanned_decode


def check_against_reference(ctx, engine, tracker, rids):
    """The reference's full forward over prompt + generated tokens must
    put every generated token within ``logit_rtol`` x max|logit| of its
    position's largest logit: with random weights near-ties are common,
    so tokens are not compared, logits are."""
    import jax
    import jax.numpy as jnp

    rtol = ctx.workload["correctness"]["logit_rtol"]
    n_pos = ctx.config["n_positions"]
    fwd = jax.jit(lambda p, x: gpt2_ref.logits(
        p, x, ctx.config["n_head"], ctx.config["layer_norm_epsilon"]))
    out = []
    for rid in rids:
        prompt, toks = tracker.prompts[rid], tracker.tokens[rid]
        seq = np.zeros((1, n_pos), np.int32)
        seq[0, :len(prompt) + len(toks)] = prompt + toks
        rows = np.arange(len(prompt) - 1, len(prompt) + len(toks) - 1)
        lg = np.asarray(fwd(engine.params, jnp.asarray(seq))[0, rows])
        scale = float(np.abs(lg).max())
        short = lg.max(axis=1) - lg[np.arange(len(toks)), toks]
        out.append({"rid": rid, "tokens": len(toks),
                    "max_shortfall": float(short.max()),
                    "tolerance": rtol * scale,
                    "ok": bool(short.max() <= rtol * scale)})
    return out


def run(ctx):
    ctx.log("building the engine")
    engine, sched = build(ctx)
    ctx.log("warm-up")
    warm_up(ctx, engine, sched)
    if ctx.trace:
        install_spans(ctx, engine)
    return measure(ctx, engine, sched)


def measure(ctx, engine, sched):
    """Ramp, window and drain on a warm engine, then the checks."""
    wl, rec = ctx.workload, ctx.recorder
    traffic = wl["traffic"]
    gen = importlib.import_module(
        "benchmarks.suite.traffic." + traffic["generator"])
    arrivals = gen.make(traffic, ctx.seed, seconds=ctx.seconds,
                        vocab_size=ctx.config["vocab_size"])
    counts_warm = engine.compile_counts()

    # the ramp is set-up: arrivals start before the window so that the
    # batch is in its steady state when the window opens
    t0 = clock()
    w0 = t0 + traffic["ramp_s"]
    w1 = w0 + ctx.seconds
    t_end = w1 + traffic["drain_s"]
    profile = None
    if ctx.trace:
        profile = (w1 - wl["trace"]["profile_s"], w1)
        rec.collect = True
    setup_s = w0 - ctx.t_process
    ctx.log(f"ramp {traffic['ramp_s']} s, window {ctx.seconds} s, "
            f"drain {traffic['drain_s']} s")
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
    ref = check_against_reference(ctx, engine, tracker, finished[:n_check])
    counts = engine.compile_counts()
    checks = {"reference": ref, "compile_counts": counts,
              "compile_counts_after_warmup": counts_warm,
              "compiles_in_run": compiles_in_run}
    correct = bool(len(ref) == n_check and all(r["ok"] for r in ref) and
                   counts == counts_warm == {"prefill": 1, "decode": 1}
                   and compiles_in_run == 0)

    # queue wait: due -> start of the step that admitted the request
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
        "checks": checks,
    }
    return harness.Result(
        correct=correct, attempted=len(measured), failed=len(failed),
        setup_s=setup_s, end_to_end=end_to_end, detail=detail,
        facts={"kv_tokens_per_step": float(np.mean(
                   [s[3] for s in in_window])),
               "kv_bytes_per_element":
                   np.dtype(engine.spec.dtype).itemsize},
        trace=trace)

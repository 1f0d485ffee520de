"""Paged KV cache numerics (`inference/cache.py` +
`inference/engine.py`'s two programs + `inference/paging.py` through
the scheduler).

Three layers of parity, all against the plain full-context forward or
a cold engine oracle:

- Teacher-forced engine parity: feed the SAME token sequence through
  the plain full-context forward and through chunked prefill +
  one-token decode steps over the pool, and compare the logits
  position by position (fp32 2e-6 — XLA reduction-order noise;
  quantized 0.2 — codec bound: measured int8 ~2e-3, f8e4m3fn ~1e-2),
  across {dense, flash} x {unrolled, scan} x {f32, int8, f8}. Teacher
  forcing keeps the comparison well-defined for quantized caches,
  where storage error can flip an argmax without any logit being
  wrong by more than the codec's bound. Two rows run concurrently at
  different lengths, crossing prefill-chunk, page and bucket
  boundaries. Page tables here are hand-built identity mappings; the
  engine never sees the allocator.
- Prefix-cache bit-identity: a radix prefix HIT resumes prefill
  mid-prompt on shared pages. Prefill is deterministic, so the warm
  request's greedy continuation must equal a cold engine running the
  full prompt from scratch EXACTLY (token-for-token), and the shared
  pages must survive a divergent sibling's writes untouched (COW:
  divergence lands in private pages).
- Session park/resume through the host-RAM tier: a parked session's
  pages evacuate to host (CRC-stamped) and page back in on resume;
  the resumed continuation must match the cold oracle exactly.

Every test ends on the 2-compile pin: allocator churn, prefix hits
and park/resume are host metadata and must never reach a jit boundary
(`engine.compile_counts() == {"prefill": 1, "decode": 1}`).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.scheduler import (
    ContinuousBatchingScheduler, Request)
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from tests.unit.test_inference_engine import identity_tables

_slow = pytest.mark.slow


def _build(scan_layers, kv_cache_dtype, impl="dense", **knobs):
    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=2, n_head=4, dtype=jnp.float32,
                     scan_layers=scan_layers)
    model = GPT2LMHead(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(model, params, config={
        "max_batch": 2, "seq_buckets": (16, 32), "prefill_chunk": 4,
        "kv_cache_dtype": kv_cache_dtype, "attention_impl": impl,
        "attention_block_k": 8, **knobs})
    return model, params, eng


# ---------------------------------------------------------------------------
# teacher-forced parity: paged pool vs the full-context forward
# ---------------------------------------------------------------------------

# the fast lane keeps the dense oracle rows plus one flash row; the
# rest of the flash matrix is slow-marked (interpret-mode Pallas under
# jit is compile-heavy on CPU).
CASES = [
    ("dense-unrolled-f32", "dense", False, None, 2e-6, ()),
    ("dense-scan-f32", "dense", True, None, 2e-6, ()),
    ("dense-unrolled-int8", "dense", False, "int8", 0.2, ()),
    ("dense-scan-f8e4m3fn", "dense", True, "f8e4m3fn", 0.2, ()),
    ("flash-unrolled-f32", "flash", False, None, 2e-6, ()),
    ("flash-scan-f32", "flash", True, None, 2e-6, (_slow,)),
    ("flash-unrolled-int8", "flash", False, "int8", 0.2, (_slow,)),
    ("flash-scan-int8", "flash", True, "int8", 0.2, (_slow,)),
    ("flash-unrolled-f8e4m3fn", "flash", False, "f8e4m3fn", 0.2, (_slow,)),
    ("flash-scan-f8e4m3fn", "flash", True, "f8e4m3fn", 0.2, (_slow,)),
]


@pytest.mark.parametrize(
    "name,impl,scan,kvdt,atol",
    [pytest.param(*c[:5], marks=c[5], id=c[0]) for c in CASES])
def test_paged_teacher_forced_parity(name, impl, scan, kvdt, atol):
    model, params, eng = _build(scan, kvdt, impl)
    # identity mapping: row r owns pages [1 + r*ppr, 1 + (r+1)*ppr)
    # (page 0 is the trash page and must never back live KV)
    tables = identity_tables(eng)

    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 64, 16).tolist(),
            rng.integers(0, 64, 24).tolist()]
    prompt_lens = [10, 14]   # mid-chunk and mid-page prefill frontiers

    refs = []
    for seq in seqs:
        full = model.apply({"params": params},
                           jnp.asarray([seq], jnp.int32),
                           deterministic=True)
        refs.append(np.asarray(full[0], np.float32))

    for slot, (seq, n) in enumerate(zip(seqs, prompt_lens)):
        last = eng.prefill(slot, seq[:n], page_table=tables[slot])
        np.testing.assert_allclose(last, refs[slot][n - 1], atol=atol,
                                   err_msg=f"{name}: prefill slot {slot}")

    pos = list(prompt_lens)
    while any(p < len(s) for p, s in zip(pos, seqs)):
        tokens = np.zeros(2, np.int32)
        positions = np.zeros(2, np.int32)
        live = []
        for r in range(2):
            if pos[r] < len(seqs[r]):
                tokens[r] = seqs[r][pos[r]]
                positions[r] = pos[r]
                live.append(r)
        _, logits = eng.decode(tokens, positions, page_tables=tables)
        for r in live:
            np.testing.assert_allclose(
                logits[r], refs[r][pos[r]], atol=atol,
                err_msg=f"{name}: decode row {r} pos {pos[r]}")
            pos[r] += 1

    assert eng.compile_counts() == {"prefill": 1, "decode": 1}


def test_trash_page_never_pollutes_live_rows():
    """An inactive decode row parks its write on page 0; the live
    row's logits must be unaffected by whatever garbage lands there."""
    model, params, eng = _build(False, None)
    tables = identity_tables(eng)
    rng = np.random.default_rng(1)
    seq = rng.integers(0, 64, 12).tolist()
    ref = np.asarray(model.apply(
        {"params": params}, jnp.asarray([seq], jnp.int32),
        deterministic=True)[0], np.float32)

    eng.prefill(0, seq[:8], page_table=tables[0])
    # row 1 is INACTIVE: its table is all-trash and its position churns
    tables[1] = 0
    for pos in range(8, 12):
        tokens = np.asarray([seq[pos], 63], np.int32)
        positions = np.asarray([pos, 0], np.int32)
        _, logits = eng.decode(tokens, positions, page_tables=tables)
        np.testing.assert_allclose(logits[0], ref[pos], atol=2e-6)


# ---------------------------------------------------------------------------
# prefix-cache hits are bit-identical to a cold full prefill
# ---------------------------------------------------------------------------

def _serve(sched, requests):
    for r in requests:
        sched.submit(r)
    sched.run()
    return {c.rid: c for c in sched.completions}


PREFIX_CASES = [
    pytest.param(False, None, id="unrolled-f32"),
    pytest.param(True, None, id="scan-f32", marks=_slow),
    pytest.param(False, "int8", id="unrolled-int8", marks=_slow),
    pytest.param(True, "int8", id="scan-int8", marks=_slow),
]


@pytest.mark.parametrize("scan,kvdt", PREFIX_CASES)
def test_prefix_hit_matches_cold_prefill(scan, kvdt):
    rng = np.random.default_rng(2)
    base = rng.integers(0, 64, 12).tolist()    # shared system prompt
    tail_a = rng.integers(0, 64, 2).tolist()
    tail_b = rng.integers(0, 64, 3).tolist()

    _, _, warm_eng = _build(scan, kvdt)
    warm = ContinuousBatchingScheduler(warm_eng)
    done = _serve(warm, [Request("a", base + tail_a, max_new_tokens=4)])
    assert not done["a"].prefix_hit
    done = _serve(warm, [Request("b", base + tail_b, max_new_tokens=4)])
    hit = done["b"]
    # page_size 8: one full shared page -> prefill resumes at token 8,
    # skipping its 2 chunks
    assert hit.prefix_hit
    assert hit.prefill_chunks_skipped == 2

    _, _, cold_eng = _build(scan, kvdt)
    cold = ContinuousBatchingScheduler(cold_eng)
    ref = _serve(cold, [Request("b", base + tail_b,
                                max_new_tokens=4)])["b"]
    assert not ref.prefix_hit
    assert hit.tokens == ref.tokens            # bit-identical greedy
    assert hit.finish_reason == ref.finish_reason

    assert warm_eng.compile_counts() == {"prefill": 1, "decode": 1}


def test_cow_divergence_leaves_shared_pages_intact():
    """After a sibling diverges past the shared span, re-running the
    ORIGINAL prompt must still reproduce its original continuation —
    the divergent writes landed in private pages, never the shared
    ones."""
    rng = np.random.default_rng(3)
    base = rng.integers(0, 64, 12).tolist()
    tail_a = rng.integers(0, 64, 2).tolist()
    tail_b = rng.integers(0, 64, 2).tolist()

    _, _, eng = _build(False, None)
    sched = ContinuousBatchingScheduler(eng)
    first = _serve(sched, [Request("a0", base + tail_a,
                                   max_new_tokens=4)])["a0"]
    _serve(sched, [Request("b", base + tail_b, max_new_tokens=4)])
    again = _serve(sched, [Request("a1", base + tail_a,
                                   max_new_tokens=4)])["a1"]
    assert again.prefix_hit
    assert again.tokens == first.tokens
    assert eng.compile_counts() == {"prefill": 1, "decode": 1}


def test_prefix_hit_under_a_chunk_of_two_pages_writes_no_shared_page():
    """``prefill_chunk`` 16 over pages of 8 with the prefix cache on:
    row "b" matches three of live row "a"'s pages and restarts at the
    chunk floor, 16. It shares the two pages before it; the third it
    fills again in a page of its own, so the bytes "a" reads and the
    tokens "a" goes on to generate are what they are without "b"."""
    rng = np.random.default_rng(5)
    base = rng.integers(0, 64, 24).tolist()         # three whole pages
    a = Request("a", base + rng.integers(0, 64, 5).tolist(),
                max_new_tokens=3)
    b = Request("b", base + rng.integers(0, 64, 3).tolist(),
                max_new_tokens=3)

    _, _, alone_eng = _build(False, None, seq_buckets=(48,),
                             prefill_chunk=16, page_size=8,
                             prefix_cache=False)
    alone = _serve(ContinuousBatchingScheduler(alone_eng),
                   [Request("a", a.prompt, max_new_tokens=3)])["a"]

    _, _, eng = _build(False, None, seq_buckets=(48,), prefill_chunk=16,
                       page_size=8)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(a)
    sched.step()                                    # "a" prefilled, live
    sched.step()                                    # its round's decode
    row_a = next(s for s in sched.slots if s is not None)
    pages_a = list(row_a.paging.pages[:3])
    before = jax.tree_util.tree_map(
        lambda leaf: np.asarray(leaf[np.asarray(pages_a)]), eng.cache)
    sched.submit(b)
    sched.step()                                    # "b" admitted
    row_b = next(s for s in sched.slots
                 if s is not None and s is not row_a)
    assert row_b.paging.prefix_hit and row_b.paging.start == 16
    assert row_b.paging.pages[:2] == pages_a[:2]
    assert row_b.paging.pages[2] not in row_a.paging.pages
    after = jax.tree_util.tree_map(
        lambda leaf: np.asarray(leaf[np.asarray(pages_a)]), eng.cache)
    jax.tree_util.tree_map(np.testing.assert_array_equal, before, after)
    sched.run()
    done = {c.rid: c for c in sched.completions}
    assert done["a"].tokens == alone.tokens
    cold = _serve(ContinuousBatchingScheduler(alone_eng),
                  [Request("b2", b.prompt, max_new_tokens=3)])["b2"]
    assert done["b"].tokens == cold.tokens
    assert eng.compile_counts() == {"prefill": 1, "decode": 1}


def test_prefix_cache_off_never_hits():
    rng = np.random.default_rng(4)
    base = rng.integers(0, 64, 12).tolist()
    _, _, eng = _build(False, None, prefix_cache=False)
    sched = ContinuousBatchingScheduler(eng)
    done = _serve(sched, [Request("a", base + [1], max_new_tokens=3)])
    done2 = _serve(sched, [Request("b", base + [2], max_new_tokens=3)])
    assert not done["a"].prefix_hit and not done2["b"].prefix_hit
    assert sched.paging.facts()["prefix_hits"] == 0


# ---------------------------------------------------------------------------
# session park/resume through the host-RAM tier
# ---------------------------------------------------------------------------

def test_host_parked_session_resumes_bit_exact():
    """Park threshold 0.9 forces the finished session's pages out to
    host RAM immediately; the follow-up request pages them back in and
    must continue exactly like a cold engine prefilling the whole
    history."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 64, 10).tolist()

    _, _, eng = _build(False, None, host_park_threshold=0.9)
    sched = ContinuousBatchingScheduler(eng)
    c0 = _serve(sched, [Request("r0", prompt, max_new_tokens=3,
                                session_id="s0")])["r0"]
    facts = sched.paging.facts()
    assert facts["sessions_parked_host"] == 1
    assert facts["pages_evacuated"] > 0

    follow = prompt + c0.tokens                # extends the parked KV
    c1 = _serve(sched, [Request("r1", follow, max_new_tokens=3,
                                session_id="s0")])["r1"]
    assert c1.resumed
    assert c1.prefill_chunks_skipped > 0
    facts = sched.paging.facts()
    assert facts["pages_paged_in"] > 0
    assert facts["sessions_resumed"] == 1

    _, _, cold_eng = _build(False, None)
    cold = ContinuousBatchingScheduler(cold_eng)
    ref = _serve(cold, [Request("r1", follow, max_new_tokens=3)])["r1"]
    assert c1.tokens == ref.tokens
    assert eng.compile_counts() == {"prefill": 1, "decode": 1}

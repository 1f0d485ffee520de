"""Cached-decode vs full-context parity
(`deepspeed_tpu/inference/engine.py` + `models/gpt2.py` cache path).

Teacher-forced parity: feed the SAME token sequence through (a) the
plain full-context forward and (b) chunked prefill + one-token decode
steps, and compare the logits position by position. Teacher forcing
(instead of comparing greedy generations) keeps the comparison
well-defined for quantized caches, where storage error can flip an
argmax without any logit being wrong by more than the codec's bound.

The matrix {dense, flash} x {unrolled, scan_layers} x {fp32 cache,
int8/f8 quantized} over two live rows is
`tests/unit/test_paged_parity.py::test_paged_teacher_forced_parity`.
Here the flash rows (`test_paged_flash_parity_with_a_dead_row`) run
the comparison through the pool and the kernel (`ops/pallas/
flash_decode.py`, interpret mode on CPU), whose grid
is one step a row: three cache rows of which the middle one never
holds a request (position 0, an all-trash table), the last row ending
on the last position of its last page, over every pool dtype; and they
read the two counters `engine.decode` puts on its span
(``kv_blocks_live``, ``kv_blocks_launched``). fp32 rows pin to 2e-6 —
the residue is XLA reduction-order noise from attending over the padded
[max_seq] buffer instead of the exact [T] context (the einsum
re-associates the same nonzero terms; a same-shape call is ulp-close).

Sampling sanity (`inference/sampling.py`): the in-program sampler's
degenerate corners collapse to greedy bit-exactly (temperature 0 by
the static-path contract, top_k=1 because the filter leaves one
token), and a hot temperature draws a different stream while staying
inside the top-k support.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from tests.unit.test_inference_engine import identity_tables

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


PAGED_CASES = [
    ("f32", None, 2e-6, ()),
    ("int8", "int8", 0.2, ()),
    ("bf16", "bf16", 0.05, ()),
    ("f8e4m3fn", "f8e4m3fn", 0.2, ()),
    ("f8e5m2", "f8e5m2", 0.4, ()),
]


@pytest.mark.parametrize(
    "kvdt,atol", [pytest.param(*c[1:3], marks=c[3], id=c[0])
                  for c in PAGED_CASES])
def test_paged_flash_parity_with_a_dead_row(kvdt, atol):
    from deepspeed_tpu.telemetry import spans

    model, params, eng = _build(False, kvdt, "flash", max_batch=3,
                                page_size=8)
    ppr = eng.pages_per_row                 # 4 pages of 8 positions
    tables = np.zeros((3, ppr), np.int32)   # row 1: no request
    tables[0] = 1 + np.arange(ppr)
    tables[2] = 1 + ppr + np.arange(ppr)
    rng = np.random.default_rng(0)
    # row 0 ends mid-page; row 2 on the last position of its last page
    seqs = {0: rng.integers(0, 64, 21).tolist(),
            2: rng.integers(0, 64, 32).tolist()}
    refs = {r: np.asarray(model.apply(
        {"params": params}, jnp.asarray([seq], jnp.int32),
        deterministic=True)[0], np.float32) for r, seq in seqs.items()}
    pos = {0: 10, 2: 14}
    for r, seq in seqs.items():
        last = eng.prefill(r, seq[:pos[r]], page_table=tables[r])
        np.testing.assert_allclose(last, refs[r][pos[r] - 1], atol=atol)

    while any(pos[r] < len(seqs[r]) for r in seqs):
        tokens = np.zeros(3, np.int32)
        positions = np.zeros(3, np.int32)
        step_tables = np.zeros_like(tables)
        live = [r for r in seqs if pos[r] < len(seqs[r])]
        for r in live:
            tokens[r] = seqs[r][pos[r]]
            positions[r] = pos[r]
            step_tables[r] = tables[r]
        since = spans.clock()
        _, logits = eng.decode(tokens, positions, page_tables=step_tables)
        for r in live:
            np.testing.assert_allclose(
                logits[r], refs[r][pos[r]], atol=atol,
                err_msg=f"decode row {r} pos {pos[r]}")
        # the counters of this step: blocks of 8 positions the live
        # rows hold, and the kernel's grid visits those and no other
        attrs, = [rec[3] for rec in spans.recent(since)
                  if rec[0] == "decode"]
        want = sum(pos[r] // 8 + 1 for r in live)
        assert attrs == {"kv_blocks_live": want,
                         "kv_blocks_launched": want,
                         # and it writes the live rows' blocks only
                         "kv_rows_live": len(live),
                         "kv_rows_written": len(live)}
        for r in live:
            pos[r] += 1
    assert pos[2] == 32 == eng.max_seq      # the last slot was attended
    assert eng.compile_counts() == {"prefill": 1, "decode": 1}


def test_single_chunk_prefill_is_ulp_close():
    """Ground truth for the fp32 tolerance above: when the cached path
    runs at the SAME padded shape as the reference (one full-buffer
    prefill chunk) the only residue is XLA fusion-order noise in the
    last float32 ulps (~1e-7 on this model) — orders tighter than any
    real numeric defect and than the matrix's 2e-6 bound."""
    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                     n_layer=2, n_head=4, dtype=jnp.float32)
    model = GPT2LMHead(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(model, params, config={
        "max_batch": 1, "seq_buckets": (16,), "prefill_chunk": 16})
    rng = np.random.default_rng(1)
    seq = rng.integers(0, 64, 16).tolist()

    ref = np.asarray(model.apply(
        {"params": params}, jnp.asarray([seq], jnp.int32),
        deterministic=True)[0], np.float32)
    # one chunk == one page == the whole row
    assert eng.page_size == 16 == eng.max_seq
    last = eng.prefill(0, seq, identity_tables(eng)[0])
    np.testing.assert_allclose(last, ref[-1], atol=5e-7)


# ---------------------------------------------------------------------------
# in-program sampling
# ---------------------------------------------------------------------------

def _generate(eng, prompt, steps):
    """Free-running generation on row 0; returns the token stream."""
    tables = identity_tables(eng)
    tables[1] = 0                       # row 1 holds no request
    last = eng.prefill(0, prompt, tables[0])
    toks = [eng.sample_first(last)]
    pos = len(prompt)
    for _ in range(steps):
        t = np.zeros(2, np.int32)
        p = np.zeros(2, np.int32)
        t[0] = toks[-1]
        p[0] = pos
        nxt, _ = eng.decode(t, p, tables)
        toks.append(int(nxt[0]))
        pos += 1
    return toks


SAMPLING_GREEDY_CASES = [
    # temperature 0 takes the static argmax path: the key is never
    # consumed, so ANY seed reproduces the greedy stream bit-exactly.
    pytest.param("temp0", {"temperature": 0.0, "sampling_seed": 123},
                 id="temp0"),
    # top_k=1 leaves exactly the argmax in the nucleus: categorical
    # over a one-token support IS greedy, whatever the key does.
    pytest.param("topk1", {"temperature": 0.7, "top_k": 1,
                           "sampling_seed": 7},
                 id="topk1", marks=pytest.mark.slow),
]


@pytest.mark.parametrize("name,knobs", SAMPLING_GREEDY_CASES)
def test_sampling_degenerate_corners_recover_greedy(name, knobs):
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 64, 6).tolist()
    _, _, greedy_eng = _build(False, None, "flash")
    greedy = _generate(greedy_eng, prompt, 8)
    _, _, eng = _build(False, None, "flash", **knobs)
    assert _generate(eng, prompt, 8) == greedy
    assert eng.compile_counts() == {"prefill": 1, "decode": 1}


@pytest.mark.slow
def test_hot_sampling_draws_within_topk_support():
    """temperature 0.9 + top_k 4: the stream is seed-reproducible,
    differs from greedy somewhere, and every draw stays inside the
    step's 4 highest logits (the filter's whole contract)."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 64, 6).tolist()
    knobs = {"temperature": 0.9, "top_k": 4, "sampling_seed": 11}
    _, _, eng_a = _build(False, None, "flash", **knobs)
    _, _, eng_b = _build(False, None, "flash", **knobs)
    _, _, greedy_eng = _build(False, None, "flash")

    # reproducibility: same seed, same stream
    def run(eng):
        tables = identity_tables(eng)
        tables[1] = 0
        last = eng.prefill(0, prompt, tables[0])
        toks = [eng.sample_first(last)]
        pos = len(prompt)
        draws = []
        for _ in range(10):
            t = np.zeros(2, np.int32)
            p = np.zeros(2, np.int32)
            t[0] = toks[-1]
            p[0] = pos
            nxt, logits = eng.decode(t, p, tables)
            draws.append((int(nxt[0]), np.asarray(logits[0])))
            toks.append(int(nxt[0]))
            pos += 1
        return toks, draws

    toks_a, draws = run(eng_a)
    toks_b, _ = run(eng_b)
    assert toks_a == toks_b
    for tok, logits in draws:
        top4 = set(np.argsort(logits)[-4:].tolist())
        assert tok in top4, (tok, sorted(top4))
    assert toks_a != _generate(greedy_eng, prompt, 10)

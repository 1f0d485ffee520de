"""The delta rule's two kernels (`deepspeed_tpu/ops/pallas/
gated_delta.py`) in Pallas interpret mode, through the entry points the
mixer calls (`ops/gated_delta.py`). The chunked form of a prefill call
(`gated_delta_chunked`, ISSUE 44) against the token-by-token recurrence
in float64: at the serving cell's widths (keys and values 128 wide,
chunks of 64, a call of 1,024 tokens, one key head and its two value
heads) and at the toy widths the engine tests use. The decode step over
a list of live rows (`gated_delta_step`, ISSUE 50) against the plain
masked pass it replaced (`gated_delta_step_plain`): live patterns,
input dtypes, rows and key-head groups, dead rows to the bit, and a
chain of steps. `tests/unit/test_tpu_compile_qwen3_next.py` compiles
both for the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import qwen3_next as qn
from deepspeed_tpu.ops import gated_delta

# (T, key heads, value heads, K, V, chunk)
CELL = (1024, 1, 2, 128, 128, 64)
TOY = (32, 2, 4, 16, 8, 8)
SHAPES = {"cell": CELL, "toy": TOY}


def recurrence(q, k, v, g, beta, state):
    """Token by token, float64 numpy; a key head serves ``Hv / Hk``
    consecutive value heads."""
    rep = v.shape[1] // q.shape[1]
    q, k, v, g, beta, S = (np.asarray(jnp.asarray(a, jnp.float32), np.float64)
                           for a in (q, k, v, g, beta, state))
    q, k = np.repeat(q, rep, 1), np.repeat(k, rep, 1)
    out = []
    for t in range(len(q)):
        S = np.exp(g[t])[:, None, None] * S
        d = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", S, k[t]))
        S = S + k[t][:, :, None] * d[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out).reshape(len(q), -1, v.shape[-1]), S


def case(seed, shape, dtype=jnp.float32):
    T, Hk, Hv, K, V, _ = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = (unit(jax.random.normal(ks[0], (T, Hk, K))) * K ** -0.5).astype(dtype)
    k = unit(jax.random.normal(ks[1], (T, Hk, K))).astype(dtype)
    v = jax.random.normal(ks[2], (T, Hv, V)).astype(dtype)
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (T, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, Hv)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (Hv, K, V))


def distance(got, want):
    return np.abs(np.asarray(got, np.float64) - want).max() / \
        max(np.abs(want).max(), 1e-30)


def close(got, want, limit=2e-5):
    assert distance(got, want) < limit, distance(got, want)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_whole_call_against_the_recurrence(shape, dtype):
    """``q``, ``k`` and ``v`` in the compute dtype (the recurrence reads
    the same rounded numbers): ``k k^T`` and ``q k^T`` accumulate in
    float32 and everything after them is float32."""
    shape = SHAPES[shape]
    q, k, v, g, beta, s0 = case(1, shape, jnp.dtype(dtype))
    o, s1 = gated_delta.gated_delta_chunked(q, k, v, g, beta, s0, shape[-1])
    want_o, want_s = recurrence(q, k, v, g, beta, s0)
    assert o.shape == want_o.shape and o.dtype == jnp.float32
    close(o, want_o)
    close(s1, want_s)


@pytest.mark.parametrize("n", [1, 63, 64, 65, 1000])
def test_a_ragged_tail(n):
    """``n`` real tokens of 1,024, the tail's ``g`` and ``beta`` zeroed
    as the mixer zeroes them: the state is the recurrence's after
    ``n``."""
    q, k, v, g, beta, s0 = case(2, CELL)
    real = (jnp.arange(CELL[0]) < n)[:, None]
    o, s1 = gated_delta.gated_delta_chunked(
        q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0), s0, 64)
    want_o, want_s = recurrence(q[:n], k[:n], v[:n], g[:n], beta[:n], s0)
    close(o[:n], want_o)
    close(s1, want_s)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_state_over_three_calls_is_one_long_calls(shape):
    T, Hk, Hv, K, V, Q = SHAPES[shape]
    T = min(T, 256)
    q, k, v, g, beta, s0 = case(3, (3 * T, Hk, Hv, K, V, Q))
    long_o, long_s = gated_delta.gated_delta_chunked(q, k, v, g, beta, s0, Q)
    s, outs = s0, []
    for i in range(3):
        at = slice(i * T, (i + 1) * T)
        o, s = gated_delta.gated_delta_chunked(
            q[at], k[at], v[at], g[at], beta[at], s, Q)
        outs.append(o)
    want_o, want_s = recurrence(q, k, v, g, beta, s0)
    close(jnp.concatenate(outs), want_o)
    close(s, want_s)
    close(long_s, want_s)
    close(long_o, want_o)


@pytest.mark.parametrize("slot", ["fresh", "carried"])
def test_a_fresh_slot_beside_a_carried_one(slot):
    """A fresh slot starts from zeros (the mixer zeroes the state it
    hands in), a carried one from the state it is handed: same tokens,
    another result."""
    q, k, v, g, beta, carried = case(4, TOY)
    s0 = jnp.zeros_like(carried) if slot == "fresh" else carried
    o, s1 = gated_delta.gated_delta_chunked(q, k, v, g, beta, s0, 8)
    want_o, want_s = recurrence(q, k, v, g, beta, s0)
    close(o, want_o)
    close(s1, want_s)
    other = recurrence(q, k, v, g, beta,
                       carried if slot == "fresh" else 0 * carried)[0]
    assert distance(o, other) > 0.05


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_chunk_of_alike_keys_under_beta_near_one(shape):
    """Every key of a call the same unit vector and ``beta`` 0.999 with
    no decay: ``A`` is then nearly all ones below the diagonal, its
    powers grow to binomials of the chunk's length before they vanish,
    and a series of them cancels digits a substitution does not lose.
    The limit is the plain form's own distance from float64, twice."""
    T, Hk, Hv, K, V, Q = SHAPES[shape]
    T = min(T, 2 * Q)
    q, k, v, _, _, s0 = case(5, (T, Hk, Hv, K, V, Q))
    k = jnp.broadcast_to(k[:1], k.shape)
    g = jnp.full((T, Hv), -1e-4)
    beta = jnp.full((T, Hv), 0.999)
    want_o, want_s = recurrence(q, k, v, g, beta, s0)
    plain_o, plain_s = gated_delta.gated_delta_chunked_plain(
        q, k, v, g, beta, s0, Q)
    o, s1 = gated_delta.gated_delta_chunked(q, k, v, g, beta, s0, Q)
    assert distance(o, want_o) < max(2 * distance(plain_o, want_o), 2e-6)
    assert distance(s1, want_s) < max(2 * distance(plain_s, want_s), 2e-6)


@pytest.mark.parametrize("g_at", [0.0, -20.0])
def test_the_decay_at_its_ends(g_at):
    """``g`` 0 keeps everything (a pure delta rule); ``g`` -20 a token
    forgets everything before it (``e^G`` underflows inside a chunk and
    nothing divides by it)."""
    T, Hk, Hv, K, V, Q = CELL
    q, k, v, _, beta, s0 = case(6, (128, Hk, Hv, K, V, Q))
    g = jnp.full((128, Hv), g_at)
    o, s1 = gated_delta.gated_delta_chunked(q, k, v, g, beta, s0, Q)
    want_o, want_s = recurrence(q, k, v, g, beta, s0)
    assert np.isfinite(np.asarray(o)).all()
    close(o, want_o)
    close(s1, want_s)


def test_the_state_comes_back_in_float32():
    """Not a bfloat16 number's worth of mantissa: most entries lose
    something when rounded to one."""
    q, k, v, g, beta, s0 = case(7, CELL, jnp.bfloat16)
    _, s1 = gated_delta.gated_delta_chunked(q, k, v, g, beta, s0, 64)
    assert s1.dtype == jnp.float32
    rounded = s1.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.mean(rounded != s1)) > 0.9


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused():
    q, k, v, g, beta, s0 = case(8, (12, 2, 4, 16, 8, 8))
    with pytest.raises(ValueError, match="multiple"):
        gated_delta.gated_delta_chunked(q, k, v, g, beta, s0, 8)


def test_an_engine_built_after_a_patch_prefills_through_it(monkeypatch):
    """`benchmarks/suite/tools/fault_readings_qwen3_next.py` makes its
    "bfloat16 state" by replacing the module attribute with a wrapper
    that rounds the returned state: the mixer calls through the module,
    so an engine built afterwards prefills through the wrapper."""
    sound, calls = gated_delta.gated_delta_chunked, []

    def rounded(*a, **kw):
        calls.append(a[0].shape)
        o, s = sound(*a, **kw)
        return o, jax.lax.reduce_precision(s, 8, 7)
    monkeypatch.setattr(gated_delta, "gated_delta_chunked", rounded)
    cfg = qn.qwen3_next_tiny()
    model = qn.Qwen3NextLM(cfg)
    eng = InferenceEngine(
        model, qn.init_qwen3_next_params(model, jax.random.PRNGKey(0)),
        config=dict(max_batch=4, seq_buckets=(64,), prefill_chunk=16,
                    page_size=8, attention_impl="dense"))
    eng.prefill(0, list(range(1, 14)), np.arange(1, eng.pages_per_row + 1))
    deltas = [name for name, leaf in eng.cache.items() if "gdn" in leaf]
    # the prefill program's trace went through it, the key heads not
    # repeated
    assert deltas and calls.count(
        (16, cfg.linear_num_key_heads, cfg.linear_key_head_dim)) >= \
        len(deltas)
    for name in deltas:
        s = eng.cache[name]["gdn"][0]
        assert float(jnp.abs(s).max()) > 0
        np.testing.assert_array_equal(
            np.asarray(s), np.asarray(s.astype(jnp.bfloat16), np.float32))


# --- the decode step over the live rows ---------------------------------------

# value heads, K, V of the step's toy rows
STEP_HEADS, STEP_K, STEP_V = 4, 16, 8
# the kernel and the plain pass do the same float32 products; only the
# order of a sum over K differs (the read ``S^T k``, then ``S^T q`` of a
# state that holds the first difference). A float32 sum of K terms in
# another order differs by at most K roundings of 2^-24 of the terms'
# absolute sum, which for unit keys is at most sqrt(K) of a column's
# largest entry: K sqrt(K) 2^-24 for a sum, and the two in a row with
# the outer product between them under 4 of that, of the largest entry.
STEP_TOL = 4 * STEP_K * STEP_K ** 0.5 * 2.0 ** -24
LIVE = {"none": lambda R: [], "all": lambda R: range(R),
        "one": lambda R: [1], "last": lambda R: [R - 1],
        "scattered": lambda R: [i for i in range(R) if i % 3 == 0 or i == 5]}


def step_case(seed, R, group, dtype):
    q, k, v, g, beta, _ = case(
        seed, (R, STEP_HEADS // group, STEP_HEADS, STEP_K, STEP_V, None),
        dtype)
    state = jax.random.normal(jax.random.PRNGKey(100 + seed),
                              (R, STEP_HEADS, STEP_K, STEP_V))
    return q, k, v, g, beta, state


def live_rows(pattern, R):
    live = np.zeros(R, bool)
    live[list(LIVE[pattern](R))] = True
    return live


@pytest.mark.parametrize("group", [1, 2], ids=["keys-1x", "keys-2x"])
@pytest.mark.parametrize("R", [4, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("pattern", sorted(LIVE))
def test_the_step_over_live_rows_against_the_plain_pass(pattern, dtype, R,
                                                        group):
    """A live row's state and output are the plain pass's within
    `STEP_TOL` of the largest entry; a dead row's state is the input's
    bytes and its output zero."""
    q, k, v, g, beta, state = step_case(R + group, R, group,
                                        jnp.dtype(dtype))
    live = live_rows(pattern, R)
    o, new = gated_delta.gated_delta_step(q, k, v, g, beta, state,
                                          jnp.asarray(live))
    want_o, want_s = gated_delta.gated_delta_step_plain(
        q, k, v, g, beta, state, jnp.asarray(live))
    assert o.dtype == new.dtype == jnp.float32
    assert o.shape == (R, STEP_HEADS, STEP_V) and new.shape == state.shape
    np.testing.assert_array_equal(np.asarray(new)[~live],
                                  np.asarray(state)[~live])
    assert not np.asarray(o)[~live].any()
    if live.any():
        close(np.asarray(o)[live], np.asarray(want_o, np.float64)[live],
              STEP_TOL)
        close(np.asarray(new)[live], np.asarray(want_s, np.float64)[live],
              STEP_TOL)
        # and it moved: the step is not the identity on a live row
        assert distance(np.asarray(new)[live],
                        np.asarray(state, np.float64)[live]) > 0.05


def test_sixty_four_steps_from_one_state_do_not_drift():
    """The same rows live for 64 steps, each from the last one's state:
    the kernel's chain stays within `STEP_TOL` a step of the plain
    pass's, the dead rows' states are the first step's input still, and
    the last step is the recurrence's 64th in float64."""
    R, steps = 4, 64
    live = live_rows("scattered", R)
    state = step_case(0, R, 2, jnp.float32)[-1]
    got, want, exact = state, state, np.asarray(state, np.float64)
    for t in range(steps):
        q, k, v, g, beta, _ = step_case(t + 1, R, 2, jnp.float32)
        o, got = gated_delta.gated_delta_step(q, k, v, g, beta, got,
                                              jnp.asarray(live))
        want_o, want = gated_delta.gated_delta_step_plain(
            q, k, v, g, beta, want, jnp.asarray(live))
        rows = [recurrence(q[r:r + 1], k[r:r + 1], v[r:r + 1], g[r:r + 1],
                           beta[r:r + 1], exact[r]) for r in range(R)]
        exact = np.stack([s if live[r] else exact[r]
                          for r, (_, s) in enumerate(rows)])
    close(np.asarray(got)[live], np.asarray(want, np.float64)[live],
          STEP_TOL * steps)
    close(np.asarray(o)[live], np.asarray(want_o, np.float64)[live],
          STEP_TOL * steps)
    close(np.asarray(got)[live], exact[live], STEP_TOL * steps)
    close(np.asarray(o)[live],
          np.stack([o_[0] for o_, _ in rows])[live], STEP_TOL * steps)
    np.testing.assert_array_equal(np.asarray(got)[~live],
                                  np.asarray(state)[~live])

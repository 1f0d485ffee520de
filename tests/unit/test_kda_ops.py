"""Kimi Delta Attention's two ops (`deepspeed_tpu/ops/kda.py`), the
Pallas kernels in interpret mode and their plain XLA twins, against the
token-by-token recurrence in float64: whole calls, ragged tails, state
carried between calls, the decay at its bound, a decay that is one
scalar a head against `ops/gated_delta.py`, and the decode step over a
list of live rows with dead rows untouched to the bit.
`tests/unit/test_tpu_compile_ling.py` compiles both for the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import gated_delta, kda

FORMS = {"kernel": kda.kda_chunked, "plain": kda.kda_chunked_plain}
# (T, H, K, V, chunk): the cell's widths (one grid step's two heads, two
# chunks), toy widths with an odd head count, a chunk under a block
SHAPES = {"cell": (128, 2, 128, 128, 64), "toy": (64, 3, 16, 8, 32),
          "small_chunk": (32, 2, 16, 8, 8)}


def recurrence(q, k, v, g, beta, state):
    """Token by token, float64 numpy, ``diag(e^g)`` on the state's rows."""
    q, k, v, g, beta, S = (np.asarray(jnp.asarray(a, jnp.float32), np.float64)
                           for a in (q, k, v, g, beta, state))
    out = []
    for t in range(len(q)):
        S = np.exp(g[t])[:, :, None] * S
        d = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", S, k[t]))
        S = S + k[t][:, :, None] * d[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), S


def case(seed, shape, dtype=jnp.float32):
    T, H, K, V, _ = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = (unit(jax.random.normal(ks[0], (T, H, K))) * K ** -0.5).astype(dtype)
    k = unit(jax.random.normal(ks[1], (T, H, K))).astype(dtype)
    v = jax.random.normal(ks[2], (T, H, V)).astype(dtype)
    # the bounded gate over its range: most channels near 0, some at -4
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (T, H, K)) - 2)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (H, K, V))


def distance(got, want):
    return np.abs(np.asarray(got, np.float64) - want).max() / \
        max(np.abs(want).max(), 1e-30)


def close(got, want, limit=2e-5):
    assert distance(got, want) < limit, distance(got, want)


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("shape,dtype", [
    ("cell", "bfloat16"), ("toy", "float32"), ("small_chunk", "float32")])
def test_a_whole_call_against_the_recurrence(shape, dtype, form):
    shape = SHAPES[shape]
    q, k, v, g, beta, s0 = case(1, shape, jnp.dtype(dtype))
    o, s1 = FORMS[form](q, k, v, g, beta, s0, shape[-1])
    want_o, want_s = recurrence(q, k, v, g, beta, s0)
    assert o.shape == want_o.shape and o.dtype == jnp.float32
    close(o, want_o)
    close(s1, want_s)


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("n", [1, 19, 33, 64])
def test_a_ragged_tail_and_a_state_carried_between_calls(n, form):
    """``n`` real tokens of 64 with ``g`` and ``beta`` zeroed behind
    them leave the state the recurrence has after ``n``; a second call
    carries on from it as one call over all of them does."""
    shape = SHAPES["toy"]
    q, k, v, g, beta, s0 = case(n, shape)
    real = jnp.arange(shape[0]) < n
    o, s1 = FORMS[form](q, k, v, jnp.where(real[:, None, None], g, 0.0),
                        jnp.where(real[:, None], beta, 0.0), s0, shape[-1])
    want_o, want_s = recurrence(q[:n], k[:n], v[:n], g[:n], beta[:n], s0)
    close(o[:n], want_o)
    close(s1, want_s)
    o2, s2 = FORMS[form](q, k, v, g, beta, s1, shape[-1])
    want_o2, want_s2 = recurrence(q, k, v, g, beta, want_s)
    close(o2, want_o2)
    close(s2, want_s2)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_every_g_at_its_bound_is_finite_and_right(form):
    """64 tokens at the cell's widths with every ``g`` at -4.99: ``G``
    reaches -319 inside the chunk and no exponent leaves a float32 (the
    kernel takes them against a sub-block's middle)."""
    shape = SHAPES["cell"][:1] + (2, 128, 128, 64)
    shape = (64,) + shape[1:]
    q, k, v, g, beta, s0 = case(2, shape)
    g = jnp.full_like(g, -4.99)
    o, s1 = FORMS[form](q, k, v, g, beta, s0, 64)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s1).all())
    want_o, want_s = recurrence(q, k, v, g, beta, s0)
    close(o, want_o)
    close(s1, want_s)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_g_constant_over_the_channels_is_the_gated_delta_rule(form):
    shape = SHAPES["toy"]
    q, k, v, g, beta, s0 = case(3, shape)
    scalar = g[..., 0]
    o, s1 = FORMS[form](q, k, v, jnp.broadcast_to(scalar[..., None], g.shape),
                        beta, s0, shape[-1])
    want_o, want_s = gated_delta.gated_delta_chunked_plain(
        q, k, v, scalar, beta, s0, shape[-1])
    close(o, np.asarray(want_o, np.float64))
    close(s1, np.asarray(want_s, np.float64))


def step_case(seed, R=5, H=3, K=16, V=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(ks[i], (R, H, K)) for i in (0, 1))
    v = jax.random.normal(ks[2], (R, H, V))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (R, H, K)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (R, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (R, H, K, V))


@pytest.mark.parametrize("live", [(1, 0, 1, 1, 0), (0, 0, 0, 0, 0),
                                  (1, 1, 1, 1, 1), (0, 0, 0, 0, 1)])
def test_a_step_over_the_live_rows_leaves_dead_rows_to_the_bit(live):
    q, k, v, g, beta, state = step_case(5)
    live = jnp.asarray(live, bool)
    o, s1 = kda.kda_step(q, k, v, g, beta, state, live)
    want_o, want_s = kda.kda_step_plain(q, k, v, g, beta, state, live)
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(s1, want_s, atol=1e-5)
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(s1)[dead],
                                  np.asarray(state)[dead])
    assert not np.asarray(o)[dead].any()
    # a live row against the recurrence's one token
    for r in np.flatnonzero(np.asarray(live)):
        want, S = recurrence(q[r][None], k[r][None], v[r][None], g[r][None],
                             beta[r][None], state[r])
        close(o[r], want[0])
        close(s1[r], S)

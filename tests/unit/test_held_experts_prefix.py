"""`moe/dropless.py:_held_moe`, the serving path on a share of the
experts: dispatch, activation, zeroing and combine run over the row
tiles that hold the held pairs and over no other (ISSUE 45). Held to a
plain loop over the tokens' pairs at every held count from none to all,
with the choice of experts handed in, so that the count is the test's
and not the router's."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import dropless
from deepspeed_tpu.moe.dropless import dropless_moe, softmax_top_k

E, HELD, FIRST, M, L, I = 12, 4, 3, 16, 8, 24
# tokens, top_k: a prefill call's shape in small (eight row tiles of
# 256), three tiles of 32, and a decode step's (all pairs one tile)
SHAPES = {"prefill": (256, 8), "tiles3": (24, 4), "decode": (4, 8)}


def tile_of(shape):
    n, k = SHAPES[shape]
    return math.gcd(n * k, 256)


def choice(n_tokens, top_k, n_held_pairs, mask, seed):
    """``[N, k]`` distinct experts a token, exactly ``n_held_pairs`` of
    them held by a live token (spread as evenly as ``top_k`` and the
    four held experts allow), and float32 weights."""
    rng = np.random.default_rng(seed)
    live = np.flatnonzero(mask)
    most = min(top_k, HELD)
    assert n_held_pairs <= most * len(live)
    counts = np.zeros(n_tokens, int)
    for j in range(n_held_pairs):
        counts[live[j % len(live)]] += 1
    counts[live] = rng.permutation(counts[live])
    # a dead token may choose held experts too: its pairs are not held
    counts[~mask] = rng.integers(0, most + 1, (~mask).sum())
    held = np.arange(FIRST, FIRST + HELD)
    other = np.setdiff1d(np.arange(E), held)
    chosen = np.stack([rng.permutation(np.concatenate([
        rng.permutation(held)[:c], rng.permutation(other)[:top_k - c]]))
        for c in counts])
    weights = rng.uniform(0.05, 1.0, (n_tokens, top_k)).astype(np.float32)
    return chosen.astype(np.int32), weights


def banks(two, width, seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    w_gate = None if two else 0.3 * jax.random.normal(k[0], (HELD, width, I))
    return (w_gate, 0.3 * jax.random.normal(k[1], (HELD, width, I)),
            0.3 * jax.random.normal(k[2], (HELD, I, width)))


def loop(taken, chosen, weights, mask, w_gate, w_up, w_down):
    """The layer as a loop over every token's pairs, in float64."""
    taken, w_up, w_down = (np.asarray(a, np.float64)
                           for a in (taken, w_up, w_down))
    y = np.zeros_like(taken)
    for n in np.flatnonzero(mask):
        for e, w in zip(chosen[n] - FIRST, weights[n]):
            if not 0 <= e < HELD:
                continue
            up = taken[n] @ w_up[e]
            if w_gate is None:
                hidden = np.maximum(up, 0.0) ** 2
            else:
                gate = taken[n] @ np.asarray(w_gate[e], np.float64)
                hidden = gate / (1.0 + np.exp(-gate)) * up
            y[n] += np.float64(w) * (hidden @ w_down[e])
    return y


@functools.partial(jax.jit, static_argnames=("top_k",))
def held_layer(x, rows, mask, chosen, weights, w_gate, w_up, w_down, *,
               top_k):
    # the router is not asked: the test's choice is the routing
    return dropless_moe(
        x, jnp.zeros((x.shape[1], E)), w_gate, w_up, w_down, top_k,
        route=lambda x, router, top_k: (weights, chosen, {}),
        first_expert=FIRST, token_mask=mask, rows=rows)


def held_counts(shape, masked):
    """Held counts worth a case: none, inside the first tile, on a
    tile's edge, a tile and a bit, as many as the live tokens can
    hold."""
    n, k = SHAPES[shape]
    tile = tile_of(shape)
    live = n - (n // 4 if masked else 0)
    most = min(k, HELD) * live
    return sorted({0, min(5, most), min(tile, most),
                   min(2 * tile, most), min(tile + 7, most), most})


CASES = [(shape, masked, two, apart, h)
         for shape, masked, two, apart in [
             ("tiles3", False, False, False), ("tiles3", True, False, False),
             ("tiles3", True, True, True), ("tiles3", False, True, False),
             ("tiles3", True, False, True), ("decode", True, False, False),
             ("decode", False, True, True), ("prefill", True, False, False)]
         for h in held_counts(shape, masked)]


@pytest.mark.parametrize(
    "shape,masked,two,apart,n_held_pairs", CASES,
    ids=[f"{s}-{'mask' if m else 'nomask'}-{'two' if t else 'three'}banks-"
         f"{'rows' if a else 'tokens'}-H{h}" for s, m, t, a, h in CASES])
def test_held_path_against_a_loop_over_pairs(shape, masked, two, apart,
                                             n_held_pairs):
    n, k = SHAPES[shape]
    tile = tile_of(shape)
    key = jax.random.split(jax.random.PRNGKey(n_held_pairs + 7), 2)
    x = jax.random.normal(key[0], (n, M))
    rows = jax.random.normal(key[1], (n, L)) if apart else None
    mask = np.arange(n) % 4 != 1 if masked else np.ones(n, bool)
    chosen, weights = choice(n, k, n_held_pairs, mask, n_held_pairs)
    w_gate, w_up, w_down = banks(two, L if apart else M, 3)
    y, stats = held_layer(
        x, rows, jnp.asarray(mask) if masked else None, jnp.asarray(chosen),
        jnp.asarray(weights), w_gate, w_up, w_down, top_k=k)
    taken = x if rows is None else rows
    want = loop(taken, chosen, weights, mask, w_gate, w_up, w_down)
    assert y.shape == taken.shape and y.dtype == taken.dtype
    np.testing.assert_allclose(y, want, atol=2e-5 * max(1, np.abs(want).max()))
    # a token without a request adds exactly nothing
    assert not np.asarray(y)[~mask].any()
    sizes = np.asarray(stats["tokens_per_expert"])
    assert sizes.sum() == n_held_pairs
    assert sizes.sum() + int(stats["dropped"]) == n * k
    np.testing.assert_array_equal(sizes, [
        ((chosen == FIRST + e) & mask[:, None]).sum() for e in range(HELD)])
    assert int(stats["rows_visited"]) == -(-n_held_pairs // tile) * tile


@pytest.mark.parametrize("two", [False, True], ids=["three_banks", "two"])
@pytest.mark.parametrize("shape", ["tiles3", "decode"])
def test_every_expert_held_and_every_token_live(shape, two):
    """H = P: the share is the whole layer, routed by the router, and
    no pair is dropped; the uncut path's result to summation order."""
    n, k = SHAPES[shape]
    key = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(key[0], (n, M))
    router = jax.random.normal(key[1], (M, E))
    w_gate = None if two else 0.3 * jax.random.normal(key[2], (E, M, I))
    w_up = 0.3 * jax.random.normal(key[3], (E, M, I))
    w_down = 0.3 * jax.random.normal(key[4], (E, I, M))
    y, stats = jax.jit(functools.partial(
        dropless_moe, top_k=k, first_expert=0))(x, router, w_gate, w_up,
                                                w_down)
    want, whole = jax.jit(functools.partial(dropless_moe, top_k=k))(
        x, router, w_gate, w_up, w_down)
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)
    assert int(stats["dropped"]) == 0
    assert int(stats["rows_visited"]) == n * k
    np.testing.assert_array_equal(stats["tokens_per_expert"],
                                  whole["tokens_per_expert"])


@pytest.mark.parametrize("n_held_pairs", [0, 5, 32, 39, 72])
def test_what_the_kernel_left_behind_the_held_rows_is_never_read(
        monkeypatch, n_held_pairs):
    """On the chip a grouped matmul visits no tile behind the groups and
    leaves there what was in memory. Here that memory is NaN after every
    grouped matmul, in every row behind the groups, also those of the
    last live tile: the result is the clean one, bit for bit."""
    n, k = SHAPES["tiles3"]
    mask = np.arange(n) % 4 != 1
    chosen, weights = choice(n, k, n_held_pairs, mask, 5)
    x = jax.random.normal(jax.random.PRNGKey(2), (n, M))
    args = (x, None, jnp.asarray(mask), jnp.asarray(chosen),
            jnp.asarray(weights), *banks(False, M, 4))
    clean, _ = held_layer.__wrapped__(*args, top_k=k)
    real = dropless.grouped_matmul

    def poisoned(rows, bank, group_sizes):
        out = real(rows, bank, group_sizes)
        behind = jnp.arange(out.shape[0]) >= group_sizes.sum()
        return jnp.where(behind[:, None], jnp.nan, out)

    monkeypatch.setattr(dropless, "grouped_matmul", poisoned)
    got, _ = held_layer.__wrapped__(*args, top_k=k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


def test_the_held_path_is_for_a_share_and_the_whole_layer_keeps_its_own():
    """``first_expert=None`` runs `_dropless_moe`, whose rows move by
    the ``custom_vjp`` gathers (differentiable); a share runs
    `_held_moe`, a loop to a bound on the device, and no other path
    reads ``first_expert``."""
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(key[0], (8, M))
    router = jax.random.normal(key[1], (M, E))
    w_gate, w_up = (0.3 * jax.random.normal(key[i], (E, M, I))
                    for i in (2, 3))
    w_down = 0.3 * jax.random.normal(key[4], (E, I, M))

    def loss(first):
        return lambda x: dropless_moe(
            x, router, w_gate, w_up, w_down, 2, route=softmax_top_k,
            first_expert=first)[0].sum()

    whole = str(jax.make_jaxpr(loss(None))(x))
    share = str(jax.make_jaxpr(loss(0))(x))
    assert "while" not in whole and "name=_gather_tokens" in whole
    assert share.count("while") >= 3 and "name=_gather" not in share
    assert np.isfinite(np.asarray(jax.grad(loss(None))(x))).all()


@pytest.mark.parametrize("model", ["mla_moe", "nemotron_h", "qwen3_next"])
def test_models_count_the_rows_their_dispatch_filled(model):
    """The jitted expert layer of the three models hands back
    ``rows_visited`` beside ``pairs_held`` among its layer's counters
    (`models/blocks.py:ExpertCounters`): whole tiles over the held
    pairs."""
    import importlib
    from deepspeed_tpu.models import blocks
    mod = importlib.import_module(f"deepspeed_tpu.models.{model}")
    assert mod.COUNTERS[:2] == ("moe_pairs_routed", "moe_pairs_held")
    assert mod.COUNTERS[-1] == "moe_rows_visited"
    n, k, first, held = 24, 4, 2, 4
    key = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(key[0], (n, M))
    mask = jnp.arange(n) % 5 != 4
    router = jax.random.normal(key[1], (M, E))
    w_gate, w_up = (0.3 * jax.random.normal(key[i], (held, M, I))
                    for i in (2, 3))
    w_down = 0.3 * jax.random.normal(key[4], (held, I, M))
    bias = jnp.zeros((E,), jnp.float32)
    if model == "mla_moe":
        _, c = blocks.sigmoid_held_experts(
            x, mask, router, bias, w_gate, w_up, w_down, top_k=k,
            scaling=1.0, renormalise=True, first_expert=first)
    elif model == "nemotron_h":
        _, c = mod._held_experts(x, x, mask, router, bias, w_up, w_down,
                                 top_k=k, scaling=1.0, renormalise=True,
                                 first_expert=first)
    else:
        _, c = mod._held_experts(x, mask, router, w_gate, w_up, w_down,
                                 top_k=k, first_expert=first)
    tile = math.gcd(n * k, 256)
    assert c.pairs_routed == int(mask.sum()) * k
    assert 0 < c.pairs_held <= c.pairs_routed
    assert c.rows_visited == -(-int(c.pairs_held) // tile) * tile

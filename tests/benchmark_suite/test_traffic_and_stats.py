"""The generators are functions of the seed; lengths honour their clips;
the percentile rule."""

import collections

import numpy as np
import pytest

from benchmarks.suite import stats
from benchmarks.suite.traffic import lm_batches, open_loop

from . import tiny

CHAT = tiny.workload("serve-gpt2-medium-chat")["traffic"]
BIG_SEED = 2 ** 31 + 12345


def arrivals(seed, seconds=30, **over):
    return open_loop.make(dict(CHAT, **over), seed, vocab_size=50257,
                          seconds=seconds)


def test_open_loop_is_a_function_of_the_seed():
    a, b, c = arrivals(BIG_SEED), arrivals(BIG_SEED), arrivals(7)
    assert a == b
    assert [x.prompt for x in a] != [x.prompt for x in c]


def test_open_loop_honours_clips_and_total():
    p, o = CHAT["prompt"], CHAT["output"]
    for a in arrivals(BIG_SEED, rate_per_s=20.0):
        assert p["min"] <= len(a.prompt) <= p["max"]
        assert 1 <= a.max_new_tokens <= o["max"]
        assert len(a.prompt) + a.max_new_tokens <= CHAT["max_total"] == 1023
        assert all(0 <= t < 50257 for t in a.prompt)
    cut = arrivals(3, rate_per_s=20.0, max_total=800)
    assert max(len(a.prompt) + a.max_new_tokens for a in cut) <= 800


def test_every_seed_replays_one_trace_in_another_order():
    a, b = arrivals(1, rate_per_s=5.0), arrivals(BIG_SEED, rate_per_s=5.0)
    sizes = lambda xs: collections.Counter(  # noqa: E731
        (len(x.prompt), x.max_new_tokens) for x in xs)
    assert sizes(a) == sizes(b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert [x.due_s for x in a] == [x.due_s for x in b]
    # a size moves only inside its block of consecutive arrivals
    k = open_loop.BLOCK
    for lo in range(0, len(a), k):
        assert sizes(a[lo:lo + k]) == sizes(b[lo:lo + k])
    n = round(5.0 * open_loop.horizon_s(CHAT, 30))
    assert len(a) == n and len({x.rid for x in a}) == n
    # the n arrivals span n / rate seconds less the first gap, in order
    due = [x.due_s for x in a]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < n / 5.0


def test_arrivals_are_poisson_at_the_cells_rate():
    xs = arrivals(1, rate_per_s=50.0, seconds=170)
    gaps = np.diff([x.due_s for x in xs])
    assert len(gaps) > 9000
    assert gaps.mean() == pytest.approx(1 / 50.0, rel=0.01)
    # exponential gaps: the standard deviation equals the mean
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.05)


def test_lm_batches_are_seeded_and_distinct():
    spec = tiny.workload("train-gpt2-medium-seq1024")["traffic"]
    a = lm_batches.make(spec, BIG_SEED, vocab_size=50257)
    b = lm_batches.make(spec, BIG_SEED, vocab_size=50257)
    x, y = a.next()["input_ids"], a.next()["input_ids"]
    assert x.shape == (8, 1024) and x.dtype == np.int32
    assert a.tokens_per_batch == 8192
    assert not np.array_equal(x, y)
    assert np.array_equal(x, b.next()["input_ids"])
    assert 0 <= x.min() and x.max() < 50257


@pytest.mark.parametrize("q,values,want", [
    (50, [4, 1, 3, 2], 2.5), (90, list(range(101)), 90.0),
    (95, [10.0], 10.0), (0, [3, 9], 3), (100, [3, 9], 9),
])
def test_percentile_matches_numpy(q, values, want):
    assert stats.percentile(values, q) == pytest.approx(want)
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


@pytest.mark.parametrize("n,q,ok", [
    (100, 90, True), (99, 90, False), (200, 95, True), (199, 95, False),
    (60, 90, False),
])
def test_tail_needs_ten_samples_beyond_it(n, q, ok):
    assert stats.tail_supported(n, q) is ok
    assert stats.summary(list(range(n)), q)["supported"] is ok
    assert stats.summary(list(range(n)), q)["n"] == n


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)

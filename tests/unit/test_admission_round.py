"""The admission round of `inference/scheduler.py` (PR 60): ``step()``
returns after each admitted prompt's prefill and the round's decode
follows, and the engine sees the calls it saw when a round was one
call.

The engine here is a recording stand-in: it logs every ``prefill``,
``sample_first`` and ``decode`` (a speculative one's ``draft`` and
``verify``) with its arguments, and the tokens it hands back depend on
those arguments and on how many calls came before, so a call moved,
dropped or changed moves every token after it.

``admission_round_parent.json`` is what the scheduler of the commit
before PR 60 (``6e13e50``, whose ``step()`` admitted every member of a
round and decoded in one call) gave for the same scripts. This file runs
on that commit as it runs on this one (``play_round`` steps until
``step_count`` moves, which there is one call), and ``python -m
tests.unit.test_admission_round`` writes the table: that is how it was
made.
"""

import json
import pathlib
import zlib

import numpy as np
import pytest

from deepspeed_tpu.inference.scheduler import (
    ContinuousBatchingScheduler,
    Request,
)
from deepspeed_tpu.telemetry import spans
from deepspeed_tpu.telemetry.spans import Span, clock, enclosing_attr
from tests.unit.test_inference_engine import StubEngine

VOCAB = 64


def crc(*args):
    return zlib.crc32(repr(args).encode())


def ints(a):
    return tuple(int(x) for x in np.asarray(a).ravel())


class Recording(StubEngine):
    """``log`` holds one line a call: its name, what a reader wants to
    see of it, and a digest of all its arguments and of the calls
    before it."""

    def __init__(self, n_pages=None, prefill_s=0.0, **kw):
        super().__init__(**kw)
        if n_pages is not None:         # a pool too small for its rows
            self.n_pages = n_pages
            self.cache = {"k": np.zeros((n_pages, 1), np.float32)}
        self.prefill_s = prefill_s
        self.log = []

    def note(self, name, shown, *args):
        digest = crc(name, args, len(self.log))
        self.log.append(f"{name}:{shown}:{digest:08x}")
        return digest

    def prefill(self, slot, prompt, page_table, start=0):
        # the engine's own span, with the attrs it takes over
        attrs = {"rid": enclosing_attr("rid"),
                 "rows_waiting": enclosing_attr("rows_waiting")}
        with Span("prefill", self.session, attrs):
            digest = self.note("prefill", f"{slot}:{len(prompt)}:{start}",
                               slot, ints(prompt), ints(page_table),
                               int(start))
            if self.prefill_s:
                end = clock() + self.prefill_s
                while clock() < end:
                    pass
        logits = np.zeros(VOCAB, np.float32)
        logits[1 + digest % (VOCAB - 1)] = 1.0
        return logits

    def sample_first(self, last_logits):
        token = int(np.argmax(last_logits))
        self.note("sample", token, token)
        return token

    def decode(self, tokens, positions, page_tables):
        live = int((np.asarray(positions) > 0).sum())
        digest = self.note("decode", live, ints(tokens), ints(positions),
                           ints(page_tables))
        nxt = np.asarray([(digest + 7 * r) % VOCAB
                          for r in range(self.max_batch)], np.int32)
        return nxt, None


class RecordingSpeculative:
    """The surface ``_spec_step`` drives: ``k`` drafted tokens of which
    a digest of the verify call's arguments accepts some."""
    k = 3

    def __init__(self, engine):
        self.engine = engine
        self.observed = []

    def draft_len(self):
        return 2

    def draft(self, cur, cur_pos, page_tables):
        digest = self.engine.note("draft", "", ints(cur), ints(cur_pos),
                                  ints(page_tables))
        return np.asarray([(digest + 5 * r) % VOCAB
                           for r in range(len(cur))], np.int32), None

    def verify(self, chunk, pos_chunk, draft_len, q_dists, page_tables):
        assert q_dists is None
        digest = self.engine.note("verify", "", ints(chunk),
                                  ints(pos_chunk), ints(draft_len),
                                  ints(page_tables))
        rows = len(draft_len)
        acc = np.asarray([(digest >> r) % (int(draft_len[r]) + 1)
                          for r in range(rows)], np.int32)
        out = np.asarray([[(digest + 3 * r + 11 * t) % VOCAB
                           for t in range(self.k + 1)]
                          for r in range(rows)], np.int32)
        return acc, out

    def observe(self, *counts):
        self.observed.append(counts)


def req(rid, n, new, **kw):
    """A request whose prompt is its rid's own tokens."""
    base = crc(rid)
    return Request(rid, [(base + 13 * i) % VOCAB for i in range(n)],
                   max_new_tokens=new, **kw)


def play_round(sched, late=()):
    """One admission round, whole: ``step()`` until the decode (or the
    idle tick) that ends it. ``late`` is submitted after the round's
    first return (in a commit whose round is one call, after that
    call). Returns ``step()``'s last answer."""
    before = sched.step_count
    late = list(late)
    while sched.step_count == before:
        alive = sched.step()
        while late:
            sched.submit(late.pop(0))
    return alive


def play(sched, script):
    """``script``: ``("submit", request)`` and ``("round", [late
    requests])`` in order, then rounds until nothing is left."""
    for action, arg in script:
        if action == "submit":
            sched.submit(arg)
        else:
            play_round(sched, arg)
    while sched.queue or any(s is not None for s in sched.slots):
        play_round(sched)


# name -> (the engine's arguments, the script)
PATTERNS = {
    "one_prompt": (
        dict(max_batch=2),
        [("submit", req("a", 5, 3)), ("round", [])]),
    "three_queued_two_rows": (
        dict(max_batch=2),
        [("submit", req("a", 5, 4)), ("submit", req("b", 9, 2)),
         ("submit", req("c", 3, 3)), ("round", [])]),
    "three_queued_four_rows": (
        dict(max_batch=4),
        [("submit", req("a", 5, 4)), ("submit", req("b", 9, 2)),
         ("submit", req("c", 3, 3)), ("round", [])]),
    "submitted_inside_a_round": (
        dict(max_batch=3),
        [("submit", req("a", 6, 3)), ("submit", req("b", 4, 3)),
         ("round", [req("c", 7, 2)]), ("round", [req("d", 2, 2)])]),
    "finishes_on_its_first_token": (
        dict(max_batch=2),
        [("submit", req("a", 5, 1)), ("submit", req("b", 4, 3)),
         ("submit", req("c", 6, 2)), ("round", [])]),
    "pool_refuses_in_mid_round": (
        # five pages for rows of up to four: the second prompt of three
        # pages does not fit beside the first
        dict(max_batch=3, n_pages=6),
        [("submit", req("a", 17, 3)), ("submit", req("b", 18, 2)),
         ("submit", req("c", 3, 2)), ("round", [])]),
}


def mix(seed=7, n=14):
    rng = np.random.default_rng(seed)
    return [req(f"m{i}", int(rng.integers(2, 21)), int(rng.integers(1, 7)),
                arrival_step=int(rng.integers(0, 12)))
            for i in range(n)]


def run_mix(speculative):
    eng = Recording(max_batch=3)
    if speculative:
        eng.speculative = RecordingSpeculative(eng)
    sched = ContinuousBatchingScheduler(eng)
    comps = sched.run(sorted(mix(), key=lambda r: r.arrival_step))
    return eng, sched, [(c.rid, c.tokens, c.finish_reason, c.slot, c.steps)
                        for c in comps]


def log_of(name):
    kw, script = PATTERNS[name]
    eng = Recording(**kw)
    sched = ContinuousBatchingScheduler(eng)
    play(sched, script)
    return eng, sched


# made by this file's ``__main__`` on the commit before PR 60:
# {"patterns": {name: log}, "mix": {"plain" | "speculative": {...}}}
PARENT_FILE = pathlib.Path(__file__).with_name(
    "admission_round_parent.json")
PARENT = json.loads(PARENT_FILE.read_text()) if PARENT_FILE.exists() \
    else None


# ---------------------------------------------------------------------------
# (a) the engine sees the parent's calls
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_engine_calls_are_the_parents(name):
    eng, sched = log_of(name)
    assert eng.log == PARENT["patterns"][name]
    assert not sched.queue and all(s is None for s in sched.slots)


def test_the_patterns_do_what_their_names_say():
    """The scripts reach the cases they are there for."""
    _, sched = log_of("three_queued_four_rows")
    assert [c.slot for c in sorted(sched.completions,
                                   key=lambda c: c.rid)] == [0, 1, 2]
    assert sched.rounds == 1
    _, sched = log_of("three_queued_two_rows")
    assert sched.rounds == 2
    eng, sched = log_of("finishes_on_its_first_token")
    done = {c.rid: c for c in sched.completions}
    assert done["a"].tokens and len(done["a"].tokens) == 1
    # a's row came free inside the round and c did not take it there
    assert done["a"].slot == 0 and done["b"].slot == 1
    assert [e.split(":")[0] for e in eng.log[:5]] == \
        ["prefill", "sample", "prefill", "sample", "decode"]
    eng, sched = log_of("pool_refuses_in_mid_round")
    kinds = [e.split(":")[0] for e in eng.log]
    # a alone, decoded to its end, before the pool can back b
    assert kinds[:4] == ["prefill", "sample", "decode", "decode"]
    assert len(sched.completions) == 3


# ---------------------------------------------------------------------------
# (b), (c) what a return means
# ---------------------------------------------------------------------------

def test_a_first_token_is_readable_when_its_step_returns():
    eng = Recording(max_batch=3)
    sched = ContinuousBatchingScheduler(eng)
    for r in (req("a", 5, 3), req("b", 7, 3), req("one", 4, 1)):
        sched.submit(r)
    for i, rid in enumerate(("a", "b")):
        before = clock()
        assert sched.step() is True
        after = clock()
        slot = sched.slots[i]
        assert slot.request.rid == rid and len(slot.generated) == 1
        # nothing was launched after the token was sampled
        assert eng.log[-1] == eng.log[2 * i + 1] and \
            eng.log[-1].startswith(f"sample:{slot.generated[0]}:")
        assert len(eng.log) == 2 * (i + 1)
        assert before <= slot.admit_t <= slot.token_t[0] \
            <= slot.first_return_t <= after
        # the members behind it are still queued
        assert len(sched.queue) == 2 - i
    # a request that ends on its first token is a completion by then
    assert sched.step() is True
    (comp,) = sched.completions
    assert comp.rid == "one" and comp.tokens == [int(
        eng.log[-1].split(":")[1])]
    assert comp.first_token_t <= comp.first_return_t == comp.finish_t
    assert sched.slots[2] is None and len(eng.log) == 6
    assert sched.step_count == 0
    # the round's decode: both live rows gain their second token
    sched.step()
    assert eng.log[-1].startswith("decode:2:") and sched.step_count == 1
    assert [len(s.generated) for s in sched.slots[:2]] == [2, 2]
    firsts = [s.first_return_t for s in sched.slots[:2]]
    sched.run()
    done = {c.rid: c for c in sched.completions}
    assert [done["a"].first_return_t, done["b"].first_return_t] == firsts


def test_a_request_submitted_inside_a_round_waits_for_the_next():
    eng = Recording(max_batch=3)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(req("a", 6, 3))
    sched.submit(req("b", 4, 3))
    sched.step()                                # a
    sched.submit(req("c", 7, 2))                # a row is free for it
    sched.step()                                # b
    assert [s and s.request.rid for s in sched.slots] == ["a", "b", None]
    sched.step()                                # the round's decode
    assert eng.log[-1].startswith("decode:2:")
    assert [s and s.request.rid for s in sched.slots] == ["a", "b", None]
    assert [r.rid for r in sched.queue] == ["c"]
    sched.step()                                # the next round: c
    assert [s and s.request.rid for s in sched.slots] == ["a", "b", "c"]
    assert sched.slots[2].admitted_step == 1
    assert sched.rounds == 2


# ---------------------------------------------------------------------------
# (d) what counts decode steps
# ---------------------------------------------------------------------------

def test_step_count_and_its_readers_count_decode_steps():
    eng = Recording(max_batch=2)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(req("a", 5, 3))
    sched.submit(req("b", 4, 4))
    sched.submit(req("late", 3, 2, arrival_step=2))
    counts = []
    while sched.step():
        counts.append(sched.step_count)
    decodes = sum(e.startswith("decode") for e in eng.log)
    assert sched.step_count == decodes == 3
    # an admitting return leaves the count where it was
    assert counts[:4] == [0, 0, 1, 2]
    done = {c.rid: c for c in sched.completions}
    # first token at admission, one more a decode step it was live for
    assert {r: c.steps for r, c in done.items()} == \
        {"a": 2, "b": 3, "late": 1}
    # late was due at decode step 2 and took a's row in the round after
    assert done["late"].slot == 0
    assert eng.log.index(next(e for e in eng.log
                              if e.startswith("prefill:0:3:"))) == 6


@pytest.mark.parametrize("max_steps", [0, 1, 2, 3, 50])
def test_run_bounds_decode_steps_not_returns(max_steps):
    eng = Recording(max_batch=3)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [req("a", 5, 4), req("b", 4, 4), req("c", 3, 4)]
    comps = {c.rid: c for c in sched.run(reqs, max_steps=max_steps)}
    decodes = sum(e.startswith("decode") for e in eng.log)
    assert decodes == min(max_steps, 3) and len(comps) == 3
    assert sched.step_count == decodes
    if max_steps == 0:
        # nothing ran: the three are recorded, empty and incomplete
        assert all(c.finish_reason == "incomplete" and not c.tokens
                   for c in comps.values()) and not eng.log
    elif max_steps < 3:
        # three admissions and max_steps decodes, cut with rows live
        assert all(c.finish_reason == "incomplete" and
                   len(c.tokens) == 1 + max_steps for c in comps.values())
    else:
        assert all(c.finish_reason == "max_new_tokens" and
                   len(c.tokens) == 4 for c in comps.values())
    # the scheduler is usable afterwards: no round left half open
    again = sched.run([req("d", 2, 2)])
    assert again[-1].rid == "d" and again[-1].tokens \
        and again[-1].finish_reason == "max_new_tokens"


# ---------------------------------------------------------------------------
# (e) run() gives the parent's completions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("speculative", [False, True],
                         ids=["plain", "speculative"])
def test_run_gives_the_parents_completions(speculative):
    eng, sched, comps = run_mix(speculative)
    want = PARENT["mix"]["speculative" if speculative else "plain"]
    assert [list(c) for c in comps] == [list(c) for c in want["comps"]]
    assert eng.log == want["log"]
    assert sched.step_count == want["step_count"]
    if speculative:
        assert "verify" in {e.split(":")[0] for e in eng.log}
        assert len(eng.speculative.observed) == sum(
            e.startswith("verify") for e in eng.log)


# ---------------------------------------------------------------------------
# (f) the round's stall is what the spans say
# ---------------------------------------------------------------------------

def test_round_prefill_s_is_the_sum_over_the_rounds_prefill_spans():
    since = clock()
    eng = Recording(max_batch=3, prefill_s=2e-3)
    sched = ContinuousBatchingScheduler(eng)
    play(sched, [("submit", req("a", 6, 3)), ("submit", req("b", 4, 5)),
                 ("round", [req("c", 7, 2)]), ("round", [req("d", 2, 2)])])
    now = clock()
    records = [r for r in spans.recent(since) if r[2] <= now]
    steps = [r for r in records if r[0] == "serve/step"]
    prefills = [r for r in records if r[0] == "serve/step/admit/prefill"]
    assert len(prefills) == 4
    decoding = [s for s in steps if not s[3]["admitted"]]
    held = checked = 0
    for _, _, _, attrs in decoding:
        if "round" not in attrs:
            assert attrs["round_prefills"] == 0 == attrs["round_prefill_s"]
            continue
        # the prefill spans of the returns of this round, as the reader
        # of the benchmark took them from inside one step's span: those
        # that began with a row waiting in decode
        members = [s for s in steps if s[3].get("round") == attrs["round"]
                   and s[3]["admitted"]]
        inside = [p for p in prefills
                  if any(s[1] <= p[1] and p[2] <= s[2] for s in members)]
        assert attrs["round_prefills"] == len(inside) == len(members)
        stalled = sum(p[2] - p[1] for p in inside
                      if p[3]["rows_waiting"] > 0)
        assert attrs["round_prefill_s"] == pytest.approx(stalled, abs=2e-4)
        assert (attrs["round_prefill_s"] == 0.0) == (stalled == 0.0)
        held += stalled > 0
        checked += 1
    # a's prefill found no row waiting; b's, c's and d's did
    assert checked == sched.rounds == 3 and held == 3
    first = next(a for _, _, _, a in decoding if a.get("round") == 1)
    assert 2e-3 <= first["round_prefill_s"] < 4e-3
    assert first["round_prefills"] == 2


if __name__ == "__main__":
    table = {"patterns": {name: log_of(name)[0].log
                          for name in sorted(PATTERNS)}, "mix": {}}
    for spec in (False, True):
        eng, sched, comps = run_mix(spec)
        table["mix"]["speculative" if spec else "plain"] = {
            "comps": comps, "log": eng.log,
            "step_count": sched.step_count}
    PARENT_FILE.write_text(json.dumps(table, indent=1) + "\n")
    print(f"wrote {PARENT_FILE}")

"""MiMo-V2 (window layers with a learned sink beside full layers, two
groups of page layers with key heads of their own, keys wider than
values, two rotary bases, sigmoid routing over a share of the experts)
through the serving engine against the plain reference
(`benchmarks/suite/reference/mimo_v2_ref.py`) at the tiny preset on the
CPU: logits and both pools after ragged chunked prefills into used
slots and decoded tokens beside dead rows; the ring against a
full-length cache under the window's mask; the sink's column; the two
rotary bases; the share test; the rings from admit to release; the
typed refusals; and that named faults fail the tolerance used."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.reference import mimo_v2_ref as ref
from deepspeed_tpu.inference.cache import (WindowRingUnsupported,
                                           cached_attention, init_kv_cache)
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.scheduler import (
    ContinuousBatchingScheduler, Request)
from deepspeed_tpu.models import mimo_v2 as mm

CHUNK, PAGE, SEQ, ROWS = 16, 8, 64, 3
INF = {"max_batch": ROWS, "seq_buckets": (SEQ,), "prefill_chunk": CHUNK,
       "page_size": PAGE, "attention_block_k": PAGE}
PER = SEQ // PAGE           # a row's pages in the full group
RING = 2                    # a window of 8 over pages of 8
TOL = 2e-4                  # float32 program against the reference


def ref_cfg(cfg, **extra):
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    out.update(n_layer=cfg.num_hidden_layers,
               assumed={"experts_held": list(cfg.experts_held)})
    out.update(extra)
    return out


@pytest.fixture(scope="module")
def tiny():
    cfg = mm.mimo_v2_tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    model = mm.MimoV2LM(cfg)
    return model, mm.init_mimo_v2_params(model, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["dense", "flash"])
def engine(request, tiny):
    model, params = tiny
    return InferenceEngine(model, params, config=dict(
        INF, attention_impl=request.param))


def table(row):
    """The row's table: its full pages in descending order (none where
    the allocator would have put it), then its ring, descending too."""
    full = np.arange((row + 1) * PER, row * PER, -1, dtype=np.int32)
    ring = np.arange((row + 1) * RING, row * RING, -1, dtype=np.int32)
    return np.concatenate([full, ring])


def pool_of(eng, row, n):
    """``{full layer: (k, v)}`` ``[n, heads, width]`` of the row's first
    ``n`` positions, and ``{window layer: (k, v)}`` of its last
    ``min(n, window)``, as the engine's two pools hold them."""
    cfg = eng.model.config
    out = {}
    for name, leaves in eng.cache.items():
        def rows(x, pages):
            got = np.moveaxis(np.asarray(leaves[x])[pages], -1, 1)
            return got.reshape((-1,) + leaves[x].shape[1:3])
        if name in cfg.names(mm.FULL):
            pages = table(row)[:-(-n // PAGE)]
            out[name] = tuple(rows(x, pages)[:n] for x in "kv")
        else:
            at = np.arange(max(0, n - cfg.sliding_window), n)
            ring = table(row)[PER:]
            out[name] = tuple(rows(x, ring)[at % (RING * PAGE)]
                              for x in "kv")
    return out


def decode_one(eng, slot, token, position):
    tokens = np.zeros(ROWS, np.int32)
    positions = np.zeros(ROWS, np.int32)
    tables = np.zeros((ROWS, PER + RING), np.int32)
    tokens[slot], positions[slot], tables[slot] = token, position, \
        table(slot)
    return np.asarray(eng.decode(tokens, positions, tables)[1][slot])


def test_presets():
    cfg = mm.mimo_v2_5_share()
    assert cfg.layer_kinds == (mm.FULL,) + (mm.WINDOW,) * 4 + \
        (mm.FULL, mm.WINDOW)
    assert [cfg.is_dense(i) for i in range(7)] == [True] + [False] * 6
    assert cfg.kind(mm.FULL)[:4] == (64, 4, 192, 128)
    assert cfg.kind(mm.WINDOW)[:4] == (64, 8, 192, 128)
    assert cfg.kind(mm.FULL).rotary_dim == 64 and \
        cfg.kind(mm.FULL).rope_theta == 1e7 and \
        cfg.kind(mm.WINDOW).rope_theta == 1e4
    assert cfg.kind(mm.WINDOW).sink and not cfg.kind(mm.FULL).sink
    spec = cfg.cache_spec(64, 33792, page_size=128, n_pages=6145)
    full, window = spec.page_groups
    assert (full.n_head, full.head_dim, full.v_dim, full.window,
            full.n_pages) == (4, 192, 128, 0, 6145)
    assert (window.n_head, window.window, window.n_pages) == \
        (8, 128, 64 * 2 + 1)
    # bytes a token: 5,120 in the full group, 25,600 in the window's
    assert (full.bytes_per_token(2), window.bytes_per_token(2)) == \
        (5120, 25600)
    assert (spec.pages_per_row, spec.ring_pages, spec.table_width) == \
        (264, 2, 266)
    whole = mm.MimoV2Config()
    assert whole.layer_kinds.count(mm.FULL) == 9 and \
        len(whole.layer_kinds) == 48
    assert [i for i, k in enumerate(whole.layer_kinds) if k == mm.FULL] == \
        [0, 5, 11, 17, 23, 29, 35, 41, 47]


@pytest.mark.parametrize("kw, match", [
    ({"n_group": 2}, "one group"),
    ({"n_shared_experts": 1}, "shared expert"),
    ({"attention_bias": True}, "no bias"),
    ({"experts_held": (14, 4)}, "experts_held"),
    ({"swa_num_key_value_heads": 3}, "key heads divide"),
])
def test_config_refuses_what_it_does_not_build(kw, match):
    with pytest.raises(ValueError, match=match):
        mm.mimo_v2_tiny(**kw)


# every raggedness of the last chunk; prompts of 1 to 3 chunks, up to
# five windows and five pages long
@pytest.mark.parametrize("n", [1, 7, 16, 17, 33, 41])
def test_engine_against_reference(engine, tiny, n):
    """Prefill in chunks, then decode through both pools,
    teacher-forced, in a slot that has had a tenant: logits and what the
    pools hold against the reference's full forward."""
    model, params = tiny
    cfg = ref_cfg(model.config)
    rng = np.random.default_rng(n)
    seq = rng.integers(0, 256, size=n + 6).astype(np.int32)
    slot = n % ROWS
    # a tenant before: another prompt through the same slot and pages
    engine.prefill(slot, list(rng.integers(0, 256, size=SEQ - 3)),
                   table(slot))
    want, want_kv = ref.forward(params, seq, cfg)
    want = np.asarray(want)
    got = engine.prefill(slot, list(seq[:n]), table(slot))
    scale = np.abs(want).max()
    assert np.abs(got - want[n - 1]).max() <= TOL * scale
    for t in range(n, n + 6):
        got = decode_one(engine, slot, seq[t], t)
        assert np.abs(got - want[t]).max() <= TOL * scale, t
    held = pool_of(engine, slot, n + 6)
    for name, (k, v) in want_kv.items():
        first = 0 if name in model.config.names(mm.FULL) else \
            max(0, n + 6 - model.config.sliding_window)
        for got_x, want_x in zip(held[name], (k, v)):
            want_x = np.asarray(want_x)[first:n + 6]
            assert np.abs(got_x - want_x).max() <= \
                TOL * np.abs(want_x).max(), name


@pytest.mark.parametrize("fault, extra", [
    ("window one short", {"sliding_window": 7}),
    ("window one long", {"sliding_window": 9}),
    ("sink left out", {"add_swa_attention_sink_bias": False}),
    ("window layers at the full layers' theta", {"swa_rope_theta": 1e7}),
    ("value scale left out", {"attention_value_scale": 1.0}),
])
def test_a_named_fault_fails_the_tolerance(tiny, fault, extra):
    """The comparison above is not blind: the reference with one named
    fault is further from the program than ``TOL``."""
    model, params = tiny
    engine = InferenceEngine(model, params, config=dict(
        INF, attention_impl="dense"))
    seq = np.random.default_rng(3).integers(0, 256, size=41).astype(np.int32)
    want = np.asarray(ref.forward(
        params, seq, ref_cfg(model.config, **extra), rows=[40])[0])[0]
    got = engine.prefill(0, list(seq), table(0))
    assert np.abs(got - want).max() > 10 * TOL * np.abs(want).max(), fault


def _window_layer(which=mm.WINDOW, **kw):
    cfg = mm.mimo_v2_tiny(dtype=jnp.float32, param_dtype=jnp.float32, **kw)
    layer = mm.MimoAttention(cfg, which)
    spec = cfg.cache_spec(2, SEQ, page_size=PAGE)
    name = cfg.names(which)[0]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, cfg.hidden_size))
    pool = init_kv_cache(spec)[name]
    tab = jnp.asarray([[3, 1]] if which == mm.WINDOW
                      else [list(range(PER, 0, -1))], jnp.int32)
    p = layer.init(jax.random.PRNGKey(2), x[:, :CHUNK], pool,
                   jnp.arange(CHUNK)[None], tab, jnp.asarray([CHUNK]),
                   {"impl": "dense", "block_k": PAGE})["params"]
    return cfg, layer, p, x, pool, tab


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_ring_equals_a_full_length_cache_under_the_windows_mask(impl):
    """A window layer over its ring of two pages, 40 tokens prefilled in
    ragged chunks and 24 decoded, against every key and value kept and
    the mask ``0 <= t - j < window``: token for token."""
    cfg, layer, p, x, pool, tab = _window_layer()
    out = []
    for c0, nv in ((0, 16), (16, 16), (32, 8)):
        y, pool = layer.apply(
            {"params": p}, x[:, c0:c0 + CHUNK], pool,
            jnp.arange(c0, c0 + CHUNK)[None], tab, jnp.asarray([nv]),
            {"impl": impl, "block_k": PAGE})
        out.append(y[0, :nv])
    for t in range(40, SEQ):
        y, pool = layer.apply(
            {"params": p}, x[:, t:t + 1], pool, jnp.asarray([[t]]), tab,
            jnp.asarray([1]), {"impl": impl, "block_k": PAGE})
        out.append(y[0])
    got = np.asarray(jnp.concatenate(out))
    want = np.asarray(ref.attention(x[0], p, ref_cfg(cfg), mm.WINDOW))
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()
    # the ring holds the last 16 positions and nothing else was touched
    assert pool["k"].shape[0] == 2 * RING + 1
    assert not np.asarray(pool["k"][2]).any()       # a page not the row's


def test_the_sink_takes_weight_and_gives_no_value():
    """With the sink a head's output is its output without, times one
    factor a (token, head) below 1: the sink's column joined the
    denominator and no value."""
    cfg, layer, p, x, pool, tab = _window_layer()
    kind = cfg.kind(mm.WINDOW)
    q = jax.random.normal(jax.random.PRNGKey(3), (1, CHUNK, kind.heads,
                                                  kind.head_dim))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, CHUNK, kind.kv_heads,
                                                  kind.head_dim))
    v = jax.random.normal(jax.random.PRNGKey(5), (1, CHUNK, kind.kv_heads,
                                                  kind.v_dim))

    def run(sink):
        return np.asarray(cached_attention(
            q, k, v, pool, jnp.arange(CHUNK)[None], jnp.float32, tab,
            scale=0.2, window=8, sink=sink, n_valid=jnp.asarray([CHUNK]),
            walk=True)[0][0])

    with_sink, without = run(jnp.asarray(p["sink"])), run(None)
    ratio = with_sink / without                     # [T, heads, v_dim]
    assert np.all(ratio < 1.0) and np.all(ratio > 0.0)
    assert np.abs(ratio - ratio[..., :1]).max() < 1e-4


def test_two_rotary_bases():
    """A full layer's keys turn at ``rope_theta``, a window layer's at
    ``swa_rope_theta``, each on the first 8 of 24 entries alone."""
    for which, theta in ((mm.FULL, 1e7), (mm.WINDOW, 1e4)):
        cfg, layer, p, x, pool, tab = _window_layer(which)
        _, pool = layer.apply(
            {"params": p}, x[:, :CHUNK], pool, jnp.arange(CHUNK)[None], tab,
            jnp.asarray([CHUNK]), {"impl": "dense", "block_k": PAGE})
        pages = np.asarray(tab[0][:2])
        got = np.moveaxis(np.asarray(pool["k"])[pages], -1, 1).reshape(
            CHUNK, -1, 24)
        kind = cfg.kind(which)
        raw = np.asarray(x[0, :CHUNK] @ p["k_proj"]).reshape(CHUNK, -1, 24)
        ang = np.arange(CHUNK)[:, None] * theta ** (-np.arange(0, 8, 2) / 8)
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        want = np.concatenate(
            [raw[..., :4] * cos - raw[..., 4:8] * sin,
             raw[..., 4:8] * cos + raw[..., :4] * sin, raw[..., 8:]], -1)
        if which == mm.WINDOW:      # ring: position p at p % 16
            want = want[np.arange(CHUNK) % (RING * PAGE)]
        assert kind.rotary_dim == 8
        assert np.abs(got - want).max() < 1e-5, which


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the four shares' expert outputs (experts
    0-3, 4-7, 8-11, 12-15, one router) add up to the reference's layer
    with all sixteen held."""
    whole = mm.mimo_v2_tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                            experts_held=(0, 16))
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, whole.hidden_size))
    mask = jnp.ones((1, 24), bool)
    p = mm.RoutedExperts(whole).init(jax.random.PRNGKey(8), x,
                                     mask)["params"]
    want = np.asarray(ref.experts(x[0], p, ref_cfg(whole), 0))
    total, pairs = 0, 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(whole, experts_held=(first, 4))
        mine = dict(p, **{b: p[b][first:first + 4]
                          for b in ("w_gate", "w_up", "w_down")})
        y, counters = mm.RoutedExperts(share).apply({"params": mine}, x,
                                                    mask)
        total = total + np.asarray(y[0])
        pairs += int(counters.pairs_held)
        one = np.asarray(ref.experts(x[0], mine, ref_cfg(share), first))
        assert np.abs(np.asarray(y[0]) - one).max() <= \
            TOL * np.abs(want).max()
    assert np.abs(total - want).max() <= TOL * np.abs(want).max()
    assert pairs == 24 * whole.num_experts_per_tok


def test_rings_from_admit_to_release(tiny):
    """Through the scheduler: a row holds ``ring_pages`` of the window
    group's pool whatever its length, ``facts()`` says so by group, and
    a finished row's ring goes back."""
    model, params = tiny
    engine = InferenceEngine(model, params, config=dict(
        INF, attention_impl="flash", max_new_tokens=8))
    assert engine.prefix_cache is False         # served with it off
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(0)
    for i, n in enumerate((41, 5, 23, 37)):
        sched.submit(Request(rid=i, prompt=list(rng.integers(0, 256, n)),
                             max_new_tokens=6))
    seen = 0
    while sched.step():
        facts = sched.paging.facts()
        live = sum(s is not None for s in sched.slots)
        window = facts["groups"]["window"]
        assert window["pages_live"] == live * RING
        assert window["pages_total"] == ROWS * RING
        assert window["bytes_live"] == live * RING * PAGE * 3 * 4 * 40 * 4
        assert facts["groups"]["full"]["pages_live"] == facts["pages_live"]
        seen = max(seen, live)
    assert seen == ROWS and len(sched.completions) == 4
    assert sched.paging.ring_pages_live == 0
    assert sched.paging.ring_allocator.free_pages == ROWS * RING
    assert engine.compile_counts() == {"prefill": 1, "decode": 1}
    facts = engine.cache_facts()
    assert facts["table_width"] == PER + RING
    assert facts["groups"]["window"]["n_pages"] == ROWS * RING + 1


def test_scheduler_tokens_equal_the_reference(tiny):
    """Greedy tokens of three requests served together equal the
    reference's argmax over prompt and answer, a token at a time."""
    model, params = tiny
    engine = InferenceEngine(model, params, config=dict(
        INF, attention_impl="flash"))
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(0, 256, n)) for n in (35, 9, 20)]
    for i, prompt in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=prompt, max_new_tokens=5))
    while sched.step():
        pass
    cfg = ref_cfg(model.config)
    for comp in sched.completions:
        seq = np.asarray(prompts[comp.rid] + comp.tokens, np.int32)
        rows = np.arange(len(prompts[comp.rid]) - 1, len(seq) - 1)
        logits = np.asarray(ref.forward(params, seq, cfg, rows=rows)[0])
        short = logits.max(1) - logits[np.arange(len(rows)), comp.tokens]
        assert short.max() <= TOL * np.abs(logits).max()


@pytest.mark.parametrize("feature, config", [
    ("inference.prefix_cache", {"prefix_cache": True}),
    ("inference.speculative", {"speculative": {"enabled": True, "k": 2}}),
    ("tier", {"tier": "prefill"}),
    ("tier", {"tier": "decode"}),
])
def test_what_moves_pages_refuses_a_ring(tiny, feature, config):
    model, params = tiny
    with pytest.raises(WindowRingUnsupported, match=feature):
        InferenceEngine(model, params, config=dict(INF, **config))


def test_sessions_and_page_moves_refuse_a_ring(tiny):
    model, params = tiny
    engine = InferenceEngine(model, params, config=INF)
    sched = ContinuousBatchingScheduler(engine)
    with pytest.raises(WindowRingUnsupported, match="park/resume"):
        sched.paging.admit([1, 2, 3], session_id="s", slot=0)
    with pytest.raises(WindowRingUnsupported, match="gather_pages"):
        engine.gather_pages([1])
    with pytest.raises(WindowRingUnsupported, match="handed-off"):
        sched.paging.adopt(None)

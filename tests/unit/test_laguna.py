"""Laguna (window layers of 18 query heads beside full layers of 12 over
the same 2 key heads, a sigmoid gate a head, YaRN on half a head in the
full layers and plain rotary on all of it in the window layers, softmax
routing times a factor over a share of the experts beside a shared one)
through the serving engine against the plain reference
(`benchmarks/suite/reference/laguna_ref.py`) at the tiny preset on the
CPU: logits and both pools after ragged chunked prefills into used
slots and decoded tokens; the gate; YaRN past the original context; the
routing function against a plain statement; the share test; the decode
kernel at 6 and 9 queries a key head and over a ring of five pages with
no sink; the rings from admit to release; and that named faults fail
the tolerance used."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.reference import laguna_ref as ref
from deepspeed_tpu.inference.cache import (WindowRingUnsupported,
                                           _dense_attend, init_kv_cache,
                                           paged_write_kv)
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.scheduler import (
    ContinuousBatchingScheduler, Request)
from deepspeed_tpu.models import laguna as lg
from deepspeed_tpu.moe.dropless import (router_logits, softmax_top_k,
                                        softmax_top_k_renorm,
                                        softmax_top_k_scaled)
from deepspeed_tpu.ops.pallas.flash_decode import flash_decode_paged

CHUNK, PAGE, SEQ, ROWS = 32, 4, 128, 3
INF = {"max_batch": ROWS, "seq_buckets": (SEQ,), "prefill_chunk": CHUNK,
       "page_size": PAGE, "attention_block_k": PAGE}
PER = SEQ // PAGE           # a row's pages in the full group
RING = 5                    # a window of 16 over pages of 4
TOL = 2e-4                  # float32 program against the reference


def ref_cfg(cfg, **extra):
    """The reference's dict of a program's configuration: the published
    keys (``rope_parameters`` nested as published), ``n_layer`` and the
    share."""
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    out["rope_parameters"] = {k: dict(v) for k, v in cfg.rope_parameters}
    out.update(n_layer=cfg.num_hidden_layers,
               assumed={"experts_held": list(cfg.experts_held)})
    out.update(extra)
    return out


def with_rope(cfg, which, **kw):
    rope = {k: dict(v) for k, v in cfg.rope_parameters}
    rope[which].update(kw)
    return rope


def tiny_cfg(**kw):
    return lg.laguna_tiny(dtype=jnp.float32, param_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def tiny():
    model = lg.LagunaLM(tiny_cfg())
    return model, lg.init_laguna_params(model, jax.random.PRNGKey(0))


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
        if name in cfg.names(lg.FULL):
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
    cfg = lg.laguna_s_2_1_share()
    assert cfg.layer_kinds == (lg.FULL,) + (lg.WINDOW,) * 3 + \
        (lg.FULL,) + (lg.WINDOW,) * 3
    assert [cfg.is_dense(i) for i in range(8)] == [True] + [False] * 7
    full, window = cfg.kind(lg.FULL), cfg.kind(lg.WINDOW)
    assert full[:5] == (48, 8, 128, 0, 64)
    assert window[:5] == (72, 8, 128, 512, 128)
    assert (full.rope_theta, window.rope_theta) == (5e5, 1e4)
    assert full.rope["rope_type"] == "yarn" and \
        full.rope["attention_factor"] == pytest.approx(
            0.1 * np.log(128) + 1)
    spec = cfg.cache_spec(64, 34816, page_size=128, n_pages=5633)
    g_full, g_window = spec.page_groups
    # two groups alike in heads and widths that differ by window alone
    assert dataclasses.replace(
        g_window, name=g_full.name, layers=g_full.layers, window=0,
        n_pages=g_full.n_pages) == g_full
    assert (g_full.n_head, g_full.head_dim, g_full.v_dim, g_full.n_pages) \
        == (8, 128, 128, 5633)
    assert (g_window.window, g_window.n_pages) == (512, 64 * 5 + 1)
    # bytes a token: 4,096 a layer; 8,192 in the full group (2 layers),
    # 24,576 in the window's (6)
    assert (g_full.bytes_per_token(2), g_window.bytes_per_token(2)) == \
        (8192, 24576)
    assert (spec.pages_per_row, spec.ring_pages, spec.table_width) == \
        (272, 5, 277)
    whole = lg.LagunaConfig()
    assert whole.layer_kinds.count(lg.FULL) == 12 and \
        len(whole.layer_kinds) == 48
    assert hash(whole) == hash(lg.LagunaConfig())   # a module's attribute


@pytest.mark.parametrize("kw, match", [
    ({"gating": "per-element"}, "gate a query head"),
    ({"attention_bias": True}, "no bias"),
    ({"moe_router_logit_softcapping": 30.0}, "no cap"),
    ({"experts_held": (14, 4)}, "experts_held"),
    ({"num_key_value_heads": 5}, "key heads divide"),
    ({"num_attention_heads_per_layer": (12, 18, 18, 18, 12, 6)},
     "alike in query heads"),
    ({"layer_types": ("full_attention",) * 5 + ("chunked_attention",)},
     "layers of"),
])
def test_config_refuses_what_it_does_not_build(kw, match):
    with pytest.raises(ValueError, match=match):
        lg.laguna_tiny(**kw)


# every raggedness of the last chunk; prompts of 1 to 3 chunks, up to
# five windows and twenty pages long, past YaRN's original 16 positions
@pytest.mark.parametrize("n", [1, 13, 32, 33, 66, 83])
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
        first = 0 if name in model.config.names(lg.FULL) else \
            max(0, n + 6 - model.config.sliding_window)
        for got_x, want_x in zip(held[name], (k, v)):
            want_x = np.asarray(want_x)[first:n + 6]
            assert np.abs(got_x - want_x).max() <= \
                TOL * np.abs(want_x).max(), name


def _faults(cfg):
    full, window = "full_attention", "sliding_attention"
    return {
        "window one short": {"sliding_window": 15},
        "window one long": {"sliding_window": 17},
        "plain rotary for YaRN": {"rope_parameters": with_rope(
            cfg, full, rope_type="default")},
        "attention_factor left out": {"rope_parameters": with_rope(
            cfg, full, attention_factor=1.0)},
        "rotary on all of a full layer's head": {
            "rope_parameters": with_rope(cfg, full,
                                         partial_rotary_factor=1.0)},
        "rotary on half a window layer's head": {
            "rope_parameters": with_rope(cfg, window,
                                         partial_rotary_factor=0.5)},
        "the factor 2.5 left out": {"moe_routed_scaling_factor": 1.0},
        "the renormalisation left out": {"norm_topk_prob": False},
    }


@pytest.mark.parametrize("fault", sorted(_faults(tiny_cfg())))
def test_a_named_fault_fails_the_tolerance(tiny, fault):
    """The comparison above is not blind: the reference with one named
    fault is further from the program than ``TOL``."""
    model, params = tiny
    engine = InferenceEngine(model, params, config=dict(
        INF, attention_impl="dense"))
    seq = np.random.default_rng(3).integers(0, 256, size=83).astype(np.int32)
    extra = _faults(model.config)[fault]
    want = np.asarray(ref.forward(
        params, seq, ref_cfg(model.config, **extra), rows=[82])[0])[0]
    got = engine.prefill(0, list(seq), table(0))
    assert np.abs(got - want).max() > 10 * TOL * np.abs(want).max(), fault


def _layer(which=lg.WINDOW, **kw):
    cfg = tiny_cfg(**kw)
    layer = lg.LagunaAttention(cfg, which)
    spec = cfg.cache_spec(2, SEQ, page_size=PAGE)
    name = cfg.names(which)[0]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, cfg.hidden_size))
    pool = init_kv_cache(spec)[name]
    tab = jnp.asarray([[7, 3, 9, 1, 4]] if which == lg.WINDOW
                      else [list(range(PER, 0, -1))], jnp.int32)
    p = layer.init(jax.random.PRNGKey(2), x[:, :CHUNK], pool,
                   jnp.arange(CHUNK)[None], tab, jnp.asarray([CHUNK]),
                   {"impl": "dense", "block_k": PAGE})["params"]
    return cfg, layer, p, x, pool, tab


def _through(layer, p, x, pool, tab, impl, prefilled=72):
    """The layer over ``x``: ragged chunks up to ``prefilled``, then a
    token at a time; ``(y [SEQ, C], the pool)``."""
    out = []
    for c0 in range(0, prefilled, CHUNK):
        nv = min(CHUNK, prefilled - c0)
        y, pool = layer.apply(
            {"params": p}, x[:, c0:c0 + CHUNK], pool,
            jnp.arange(c0, c0 + CHUNK)[None], tab, jnp.asarray([nv]),
            {"impl": impl, "block_k": PAGE})
        out.append(y[0, :nv])
    for t in range(prefilled, SEQ):
        y, pool = layer.apply(
            {"params": p}, x[:, t:t + 1], pool, jnp.asarray([[t]]), tab,
            jnp.asarray([1]), {"impl": impl, "block_k": PAGE})
        out.append(y[0])
    return np.asarray(jnp.concatenate(out)), pool


@pytest.mark.parametrize("impl", ["dense", "flash"])
@pytest.mark.parametrize("which", [lg.WINDOW, lg.FULL])
def test_one_layer_of_each_kind_token_for_token(which, impl):
    """A window layer (18 heads) over its ring of five pages and a full
    layer (12 heads) over its pages, 72 tokens prefilled in ragged
    chunks (the band in two blocks of the window) and 56 decoded,
    against every key and value kept under the kind's mask."""
    cfg, layer, p, x, pool, tab = _layer(which)
    got, pool = _through(layer, p, x, pool, tab, impl)
    want = np.asarray(ref.attention(x[0], p, ref_cfg(cfg), which))
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()
    if which == lg.WINDOW:
        # the ring's five pages and nothing else were touched
        assert pool["k"].shape[0] == 2 * RING + 1
        assert not np.asarray(pool["k"][2]).any()   # a page not the row's


def test_the_gate_is_a_sigmoid_a_head_of_the_layers_input():
    """With ``g_proj`` zero every gate is a half; with it, head ``h``'s
    part of the output projection's input is the ungated one times
    ``sigmoid(x W_g)_h``, one factor over the head's 16 entries."""
    cfg, layer, p, x, pool, tab = _layer(lg.FULL)
    xs = x[:, :CHUNK]
    # in the output projection's place: the entries of heads 0-3, each
    # to a column of its own
    keep = jnp.eye(cfg.kind(lg.FULL).heads * 16, cfg.hidden_size)

    def heads(params):
        y, _ = layer.apply(
            {"params": dict(params, o_proj=keep)}, xs, pool,
            jnp.arange(CHUNK)[None], tab, jnp.asarray([CHUNK]),
            {"impl": "dense", "block_k": PAGE})
        return np.asarray(y[0]).reshape(CHUNK, 4, 16)

    gated = heads(p)
    ungated = 2 * heads(dict(p, g_proj=jnp.zeros_like(p["g_proj"])))
    gate = np.asarray(jax.nn.sigmoid(xs[0] @ p["g_proj"]))[:, :4]
    assert 0.05 < gate.min() and gate.max() < 0.95 and gate.std() > 0.1
    assert np.abs(gated - ungated * gate[:, :, None]).max() <= \
        TOL * np.abs(ungated).max()
    # the next head's gate, or none, is another layer
    assert np.abs(gated - ungated * np.roll(gate, 1, 1)[:, :, None]).max() \
        > 100 * TOL * np.abs(ungated).max()


def test_yarn_past_the_original_context():
    """A full layer's keys at positions past the tiny
    ``original_max_position_embeddings`` of 16, on the first 8 of 16
    entries, against YaRN's formula in float64; plain rotary differs."""
    cfg, layer, p, x, pool, tab = _layer(lg.FULL)
    c0 = 2 * CHUNK                              # positions 64..95
    _, pool = layer.apply(
        {"params": p}, x[:, :CHUNK], pool, jnp.arange(c0, c0 + CHUNK)[None],
        tab, jnp.asarray([CHUNK]), {"impl": "dense", "block_k": PAGE})
    pages = np.asarray(tab[0])[c0 // PAGE:(c0 + CHUNK) // PAGE]
    got = np.moveaxis(np.asarray(pool["k"])[pages], -1, 1).reshape(
        CHUNK, -1, 16)
    raw = np.asarray(x[0, :CHUNK] @ p["k_proj"], np.float64).reshape(
        CHUNK, -1, 16)
    theta, factor, orig, r = 100.0, 8.0, 16, 8
    i = np.arange(r // 2)
    plain = theta ** (-2.0 * i / r)
    d = lambda turns: r * np.log(orig / (2 * np.pi * turns)) / \
        (2 * np.log(theta))                     # noqa: E731
    low, high = np.floor(d(2.0)), np.ceil(d(0.25))
    assert (low, high) == (0, 3)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    freq = plain * (1 - ramp) + plain / factor * ramp
    m = 0.1 * np.log(factor) + 1

    def turned(freq, m):
        ang = np.arange(c0, c0 + CHUNK)[:, None] * freq
        cos, sin = m * np.cos(ang)[:, None], m * np.sin(ang)[:, None]
        return np.concatenate(
            [raw[..., :4] * cos - raw[..., 4:8] * sin,
             raw[..., 4:8] * cos + raw[..., :4] * sin, raw[..., 8:]], -1)

    scale = np.abs(raw).max()
    assert np.abs(got - turned(freq, m)).max() < 1e-5 * scale
    assert np.abs(got - turned(plain, m)).max() > 0.1 * scale
    assert np.abs(got - turned(freq, 1.0)).max() > 0.1 * scale
    # a window layer: plain rotary at 1e4 on all 16 entries, unscaled
    cfg, layer, p, x, pool, tab = _layer(lg.WINDOW)
    _, pool = layer.apply(
        {"params": p}, x[:, :PAGE], pool, jnp.arange(c0, c0 + PAGE)[None],
        tab, jnp.asarray([PAGE]), {"impl": "dense", "block_k": PAGE})
    page = int(tab[0][(c0 // PAGE) % RING])
    got = np.moveaxis(np.asarray(pool["k"])[page], -1, 0)   # [4, 2, 16]
    raw = np.asarray(x[0, :PAGE] @ p["k_proj"], np.float64).reshape(
        PAGE, -1, 16)
    ang = np.arange(c0, c0 + PAGE)[:, None] * 1e4 ** (-np.arange(8) / 8.0)
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    want = np.concatenate([raw[..., :8] * cos - raw[..., 8:] * sin,
                           raw[..., 8:] * cos + raw[..., :8] * sin], -1)
    assert np.abs(got - want).max() < 1e-5 * np.abs(raw).max()


def test_the_routing_function_against_a_plain_statement():
    """``softmax_top_k_scaled``: softmax over all experts, the top k,
    their probabilities over their sum, times the factor; without
    renormalisation the probabilities as they are, times the factor."""
    k = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(k[0], (40, 16))
    router = jax.random.normal(k[1], (16, 12))
    logits = np.asarray(router_logits(x, router), np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    chosen = np.argsort(-probs, -1)[:, :3]
    taken = np.take_along_axis(probs, chosen, -1)
    w, e, aux = softmax_top_k_scaled(2.5)(x, router, 3)
    assert np.array_equal(np.asarray(e), chosen) and aux == {}
    assert np.abs(np.asarray(w) -
                  2.5 * taken / taken.sum(-1, keepdims=True)).max() < 1e-6
    assert np.abs(np.asarray(w).sum(-1) - 2.5).max() < 1e-5
    w, e, _ = softmax_top_k_scaled(2.5, renormalise=False)(x, router, 3)
    assert np.abs(np.asarray(w) - 2.5 * taken).max() < 1e-6
    # a factor of 1 is Qwen3-Next's function, bit for bit
    w1, e1, _ = softmax_top_k_scaled(1.0)(x, router, 3)
    w0, e0, _ = softmax_top_k_renorm(x, router, 3)
    assert np.array_equal(np.asarray(w1), np.asarray(w0)) and \
        np.array_equal(np.asarray(e1), np.asarray(e0))
    assert softmax_top_k(x, router, 3)[2].keys() == {"prob_sum", "z_sum"}


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the four shares' outputs (experts 0-3,
    4-7, 8-11, 12-15, one router), the shared expert counted once, add
    up to the reference's layer with all sixteen held."""
    whole = tiny_cfg(experts_held=(0, 16))
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, whole.hidden_size))
    mask = jnp.ones((1, 24), bool)
    p = lg.SparseExperts(whole).init(jax.random.PRNGKey(8), x,
                                     mask)["params"]
    want = np.asarray(ref.experts(x[0], p, ref_cfg(whole), 0))
    shared = np.asarray(ref.shared_expert(x[0], p))
    total, pairs = shared, 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(whole, experts_held=(first, 4))
        mine = dict(p, **{b: p[b][first:first + 4]
                          for b in ("w_gate", "w_up", "w_down")})
        y, counters = lg.SparseExperts(share).apply({"params": mine}, x,
                                                    mask)
        # every share computes the whole shared expert: counted once
        total = total + np.asarray(y[0]) - shared
        pairs += int(counters.pairs_held)
        one = np.asarray(ref.experts(x[0], mine, ref_cfg(share), first))
        assert np.abs(np.asarray(y[0]) - one).max() <= \
            TOL * np.abs(want).max()
    assert np.abs(total - want).max() <= TOL * np.abs(want).max()
    assert pairs == 24 * whole.num_experts_per_tok
    # the weights of a token's three experts add up to the factor
    w, _ = ref.route(x[0], p, ref_cfg(whole))
    assert np.abs(np.asarray(w).sum(-1) - 2.5).max() < 1e-5


@pytest.mark.parametrize("G, window", [(6, 0), (9, 32), (9, 0), (6, 32)])
def test_decode_kernel_at_six_and_nine_queries_a_key_head(G, window):
    """`flash_decode_paged` in interpret mode with a ``[H, G, D]`` query
    block whose ``G`` is no multiple of 8, over every page of a row and
    over a ring of five pages (a window of 32 over pages of 8) with no
    sink, rows at positions before and after the ring has wrapped and a
    row without a request, over pools of garbage: against the dense
    oracle over the pool the kernel wrote."""
    rng = np.random.default_rng(G + window)
    H, D, ps, B = 2, 16, 8, 5
    per = window // ps + 1 if window else 24
    n_pages = B * per + 1
    pool = {x: jnp.asarray(rng.normal(size=(n_pages, H, D, ps)) * 3,
                           jnp.float32) for x in "kv"}
    tables = np.zeros((B, per), np.int32)
    for r in (0, 1, 3, 4):                      # row 2 holds no request
        tables[r] = rng.permutation(np.arange(r * per + 1,
                                              (r + 1) * per + 1))
    pos = np.asarray([3, 31, 0, 77, 159])
    q = jnp.asarray(rng.normal(size=(B, 1, H * G, D)), jnp.float32)
    new = {x: jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
           for x in "kv"}
    out, held = flash_decode_paged(
        q, new, pool, jnp.asarray(pos), jnp.asarray(tables), block_k=ps,
        interpret=True, scale=0.3, window=window)
    assert out.shape == (B, 1, H * G, D) and not np.asarray(out[2]).any()
    # the step's keys went where a ring (or a row's pages) keeps them
    live = np.asarray([0, 1, 3, 4])
    written = paged_write_kv(
        pool, new["k"][live], new["v"][live], jnp.asarray(pos[live, None]),
        jnp.asarray(tables[live]), ring=bool(window))
    for x in "kv":
        assert np.array_equal(np.asarray(held[x]), np.asarray(written[x]))
    want = _dense_attend(q[live], held, jnp.asarray(pos[live, None]),
                         jnp.asarray(tables[live]), window, 0.3, None,
                         jnp.float32)
    assert np.abs(np.asarray(out)[live] - np.asarray(want)).max() < 2e-5
    # and the oracle against float64 over the keys the row really holds:
    # query head h over key head h // G
    k_all, v_all = np.asarray(held["k"]), np.asarray(held["v"])
    r, p = 4, 159
    lo = max(0, p - window + 1) if window else 0
    at = np.arange(lo, p + 1)
    entry = (at // ps) % per if window else at // ps
    ks = k_all[tables[r][entry], :, :, at % ps].astype(np.float64)
    vs = v_all[tables[r][entry], :, :, at % ps].astype(np.float64)
    for h in (0, G - 1, G, H * G - 1):
        s = 0.3 * ks[:, h // G] @ np.asarray(q[r, 0, h], np.float64)
        e = np.exp(s - s.max())
        got = np.asarray(out[r, 0, h])
        assert np.abs(got - (e / e.sum()) @ vs[:, h // G]).max() < 2e-5


def test_rings_from_admit_to_release(tiny):
    """Through the scheduler: a row holds ``ring_pages`` of the window
    group's pool whatever its length, ``facts()`` says so by group, and
    a finished row's ring goes back; a decode step's span carries the
    six counters of the expert layers."""
    model, params = tiny
    engine = InferenceEngine(model, params, config=dict(
        INF, attention_impl="flash", max_new_tokens=8))
    assert engine.prefix_cache is False         # served with it off
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(0)
    for i, n in enumerate((83, 5, 40, 70)):
        sched.submit(Request(rid=i, prompt=list(rng.integers(0, 256, n)),
                             max_new_tokens=6))
    seen = 0
    while sched.step():
        facts = sched.paging.facts()
        live = sum(s is not None for s in sched.slots)
        window = facts["groups"]["window"]
        assert window["pages_live"] == live * RING
        assert window["pages_total"] == ROWS * RING
        # 4 window layers x 2 key heads x (16 + 16) x 4 B a position
        assert window["bytes_live"] == live * RING * PAGE * 4 * 2 * 32 * 4
        assert facts["groups"]["full"]["pages_live"] == facts["pages_live"]
        seen = max(seen, live)
    assert seen == ROWS and len(sched.completions) == 4
    assert sched.paging.ring_pages_live == 0
    assert sched.paging.ring_allocator.free_pages == ROWS * RING
    assert engine.compile_counts() == {"prefill": 1, "decode": 1}
    facts = engine.cache_facts()
    assert facts["table_width"] == PER + RING
    assert facts["groups"]["window"]["n_pages"] == ROWS * RING + 1
    assert model.serve_counters == lg.COUNTERS
    from deepspeed_tpu.telemetry import spans
    steps = [r[3] for r in spans.recent(0) if r[0] == "serve/step/decode"
             and r[3] and "moe_pairs_max" in r[3]]
    assert max(s["moe_pairs_max"] for s in steps) > 0
    for s in steps:
        assert s["moe_experts_held"] == 4 * 5       # five expert layers
        assert s["moe_pairs_max"] <= s["moe_pairs_held"] <= \
            s["moe_pairs_routed"]
        assert s["attn_blocks_in_window"] <= s["attn_blocks_visited_window"]


def test_scheduler_tokens_equal_the_reference(tiny):
    """Greedy tokens of three requests served together equal the
    reference's argmax over prompt and answer, a token at a time."""
    model, params = tiny
    engine = InferenceEngine(model, params, config=dict(
        INF, attention_impl="flash"))
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(0, 256, n)) for n in (70, 9, 40)]
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
])
def test_what_moves_pages_refuses_a_ring_of_five(tiny, feature, config):
    model, params = tiny
    with pytest.raises(WindowRingUnsupported, match=feature):
        InferenceEngine(model, params, config=dict(INF, **config))


def test_engine_in_bfloat16(tiny):
    """The tiny model in bfloat16 (weights, activations, pools) serves
    through the flash path, its logits finite and near the float32
    reference's on the same weights rounded."""
    cfg = lg.laguna_tiny()
    model = lg.LagunaLM(cfg)
    params = lg.init_laguna_params(model, jax.random.PRNGKey(0))
    assert all(a.dtype == jnp.bfloat16
               for a in jax.tree_util.tree_leaves(params))
    engine = InferenceEngine(model, params, config=dict(
        INF, attention_impl="flash"))
    assert all(a.dtype == jnp.bfloat16
               for a in jax.tree_util.tree_leaves(engine.cache))
    seq = np.random.default_rng(2).integers(0, 256, size=70).astype(np.int32)
    got = np.asarray(engine.prefill(1, list(seq), table(1)))
    want = np.asarray(ref.forward(params, seq, ref_cfg(cfg), rows=[69])[0])[0]
    assert np.isfinite(got).all()
    # a near-tie among 16 experts may flip in bfloat16: a norm, loosely
    assert np.linalg.norm(got - want) <= 0.25 * np.linalg.norm(want)


# --- what this PR may not move ---------------------------------------------

@pytest.mark.parametrize("geometry", ["gqa", "int8", "latent", "mha"])
def test_decode_kernels_default_trace_is_unchanged(geometry):
    """6 and 9 queries a key head and a window without a sink run
    through the kernel as it was: the four digests of
    `test_flash_decode.py` still hold."""
    from tests.unit import test_flash_decode as tfd
    assert sorted(tfd.GEOMETRIES) == ["gqa", "int8", "latent", "mha"]
    tfd.test_the_defaults_are_traced_to_what_they_were(geometry)


@pytest.mark.parametrize("which", ["olmoe.moe", "qwen3_next.prefill",
                                   "qwen3_next.decode"])
def test_pinned_text_of_other_models_routing_is_unchanged(which):
    """`softmax_top_k_scaled` is a sibling of OLMoE's and Qwen3-Next's
    routing functions: the lowered text their own test files pin still
    holds."""
    if which.startswith("olmoe"):
        from tests.unit import test_nemotron_h as pins
        check = pins.test_accepted_tiny_programs_lower_to_the_text_they_lowered_to
    else:
        from tests.unit import test_qwen3_next as pins
        check = pins.test_tiny_programs_lower_to_the_text_they_lowered_to
    check(which)

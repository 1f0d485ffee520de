"""The hybrid model (Mamba-2 mixers beside grouped-query attention)
through the serving engine, against the plain reference
(`benchmarks/suite/reference/granite_hybrid_ref.py`), at the tiny
preset on the CPU: logits and states after ragged prefills and decoded
tokens, slots reused and rows in any order, the scan's two forms, the
grouped-query kernel, each typed refusal, and a must-fail case for each
of the benchmark driver's own-input checks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.drivers import serve_hybrid
from benchmarks.suite.reference import granite_hybrid_ref as ref
from deepspeed_tpu.inference.cache import (RecurrentStateUnsupported,
                                           cached_attention, init_kv_cache,
                                           page_pool_spec)
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.scheduler import (ContinuousBatchingScheduler,
                                               Request)
from deepspeed_tpu.models import granite_hybrid as gh
from deepspeed_tpu.ops import ssm
from tests.unit.test_flash_decode import attend

CHUNK, PAGE, SEQ, ROWS = 16, 8, 64, 3
INF = {"max_batch": ROWS, "seq_buckets": (SEQ,), "prefill_chunk": CHUNK,
       "page_size": PAGE, "attention_block_k": PAGE}


def ref_cfg(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


@pytest.fixture(scope="module")
def tiny():
    cfg = gh.granite_hybrid_tiny(dtype=jnp.float32,
                                 param_dtype=jnp.float32)
    model = gh.GraniteHybridLM(cfg)
    return model, gh.init_granite_hybrid_params(model,
                                                jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["dense", "flash"])
def engine(request, tiny):
    model, params = tiny
    return InferenceEngine(model, params, config=dict(
        INF, attention_impl=request.param))


def table(row):
    per = SEQ // PAGE
    return np.arange(1 + row * per, 1 + (row + 1) * per, dtype=np.int32)


def states_of(eng, slot):
    return {k: np.asarray(v["ssm"][slot]) for k, v in eng.cache.items()
            if "ssm" in v}


def decode_one(eng, slot, token, position):
    tokens = np.zeros(ROWS, np.int32)
    positions = np.zeros(ROWS, np.int32)
    tables = np.zeros((ROWS, SEQ // PAGE), np.int32)
    tokens[slot], positions[slot], tables[slot] = token, position, \
        table(slot)
    return eng.decode(tokens, positions, tables)[1][slot]


def test_presets():
    micro = gh.granite_4_0_h_micro()
    assert [i for i, t in enumerate(micro.layer_types)
            if t == gh.ATTENTION] == [5, 15, 25, 35]
    assert micro.conv_dim == 4352 and micro.d_inner == 4096
    spec = micro.cache_spec(48, 4608, page_size=128)
    assert spec.n_layer == 4 and spec.n_head == 8 and spec.head_dim == 64
    assert spec.state_bytes_per_slot == \
        36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    tiny_cfg = gh.granite_hybrid_tiny()
    assert tiny_cfg.layer_types == (gh.MAMBA, gh.MAMBA, gh.ATTENTION) * 2
    with pytest.raises(ValueError, match="layer_types"):
        gh.GraniteHybridConfig(num_hidden_layers=2,
                               layer_types=("mamba",))


# every raggedness of the last chunk, and prompts of 1 to 3 chunks
@pytest.mark.parametrize("n", [1, 5, 15, 16, 17, 23, 32, 33, 41])
def test_engine_against_reference(engine, tiny, n):
    """Prefill in chunks, then decode through the cache, teacher-forced:
    logits and every mixer's state against the reference's full
    forward. The slot was some other prompt's before (the fixture is
    shared), so a stale state would show."""
    model, params = tiny
    cfg = ref_cfg(model.config)
    toks = np.random.default_rng(n).integers(0, 256, n + 4).tolist()
    slot = n % ROWS
    want, _ = ref.forward(params, toks, cfg)
    last = engine.prefill(slot, toks[:n], table(slot))
    np.testing.assert_allclose(last, want[n - 1], atol=2e-6)
    _, at_prompt = ref.forward(params, toks, cfg, state_at=n - 1)
    for name, got in states_of(engine, slot).items():
        np.testing.assert_allclose(got, at_prompt[name], atol=2e-6)
    for j in range(4):
        lg = decode_one(engine, slot, toks[n + j], n + j)
        np.testing.assert_allclose(lg, want[n + j], atol=2e-6)
    _, at_end = ref.forward(params, toks, cfg)
    for name, got in states_of(engine, slot).items():
        np.testing.assert_allclose(got, at_end[name], atol=2e-6)
    assert engine.compile_counts() == {"prefill": 1, "decode": 1}


def test_dead_rows_keep_their_state(engine):
    """A decode step moves live rows' states only."""
    toks = list(range(3, 12))
    engine.prefill(0, toks, table(0))
    engine.prefill(2, toks[::-1], table(2))
    before = states_of(engine, 2), np.asarray(
        engine.cache["layers_0"]["conv"][:, 2])
    decode_one(engine, 0, 7, len(toks))
    after = states_of(engine, 2)
    for name in after:
        np.testing.assert_array_equal(after[name], before[0][name])
    np.testing.assert_array_equal(
        np.asarray(engine.cache["layers_0"]["conv"][:, 2]), before[1])


def test_slot_reused_gives_what_a_fresh_engine_gives(tiny):
    model, params = tiny
    rng = np.random.default_rng(7)
    first = Request("a", rng.integers(0, 256, 37).tolist(), 6)
    second = Request("b", rng.integers(0, 256, 21).tolist(), 6)
    used = InferenceEngine(model, params, config=dict(INF, max_batch=1))
    sched = ContinuousBatchingScheduler(used)
    sched.run([first])
    sched.run([second])
    fresh = ContinuousBatchingScheduler(
        InferenceEngine(model, params, config=dict(INF, max_batch=1)))
    fresh.run([dataclasses.replace(second, submit_t=None,
                                   arrival_t=None)])
    assert sched.completions[-1].slot == 0 == fresh.completions[-1].slot
    assert sched.completions[-1].tokens == fresh.completions[-1].tokens
    assert sched.paging.state_rows_live == 0


def test_rows_in_any_order_do_not_disturb_each_other(tiny):
    """Six requests of mixed lengths over three slots, admitted as slots
    free up and finishing out of order, against each alone."""
    model, params = tiny
    rng = np.random.default_rng(11)
    sizes = [(37, 3), (5, 9), (18, 5), (41, 2), (9, 7), (26, 4)]
    reqs = [Request(f"r{i}", rng.integers(0, 256, n).tolist(), new)
            for i, (n, new) in enumerate(sizes)]
    eng = InferenceEngine(model, params, config=INF)
    sched = ContinuousBatchingScheduler(eng)
    seen = []
    for r in reqs:
        sched.submit(r)
    while sched.step():
        seen.append((sched.paging.state_rows_live,
                     sum(s is not None for s in sched.slots)))
    assert all(live == rows for live, rows in seen)
    assert max(live for live, _ in seen) == ROWS
    together = {c.rid: c.tokens for c in sched.completions}
    assert [c.rid for c in sched.completions] != [r.rid for r in reqs]
    for r in reqs:
        alone = ContinuousBatchingScheduler(eng)
        done = alone.run([Request(r.rid, r.prompt, r.max_new_tokens)])
        assert done[0].tokens == together[r.rid], r.rid
    assert eng.compile_counts() == {"prefill": 1, "decode": 1}
    assert sched.paging.facts()["state_bytes_live"] == 0


def test_state_counters_on_the_spans(tiny):
    from deepspeed_tpu.telemetry import spans
    model, params = tiny
    eng = InferenceEngine(model, params, config=dict(
        INF, attention_impl="flash"))
    sched = ContinuousBatchingScheduler(eng)
    since = spans.clock()
    sched.run([Request("a", list(range(1, 22)), 4),
               Request("b", list(range(1, 6)), 3)])
    got = spans.recent(since)
    steps = [r[3] for r in got if r[0] == "serve/step"]
    per_slot = eng.spec.state_bytes_per_slot
    assert any(a["state_rows_live"] == 2 for a in steps)
    assert all(a["state_rows_total"] == ROWS and
               a["state_bytes_live"] == a["state_rows_live"] * per_slot
               for a in steps)
    decodes = [r[3] for r in got if r[0] == "serve/step/decode"]
    assert decodes and all(a["ssm_rows_touched"] == ROWS and
                           1 <= a["ssm_rows_live"] <= 2 for a in decodes)
    prefills = {r[3]["rid"]: r[3] for r in got
                if r[0] == "serve/step/admit/prefill"}
    assert (prefills["a"]["chunks"], prefills["a"]["pad_tokens"]) == (2, 11)
    assert (prefills["b"]["chunks"], prefills["b"]["pad_tokens"]) == (1, 11)
    text = eng._decode.lower(*eng.decode_lowering_args()).as_text(
        debug_info=True)
    for scope in ("ds_ssm_in_proj", "ds_ssm_conv", "ds_ssm_scan",
                  "ds_ssm_gate_norm", "ds_ssm_out_proj", "ds_ssm_decode"):
        assert scope in text, scope


# --- the scan's two forms --------------------------------------------------

def token_by_token(x, dt, A, B, C, state):
    ys = []
    for t in range(x.shape[0]):
        state = np.exp(dt[t] * A)[:, None, None] * state + \
            (dt[t][:, None] * x[t])[:, :, None] * B[t][None, None, :]
        ys.append((state * C[t][None, None, :]).sum(-1))
    return np.stack(ys), state


@pytest.mark.parametrize("T,chunk", [(8, 8), (24, 8), (32, 4), (16, 16)])
def test_chunked_scan_against_token_by_token(T, chunk):
    rng = np.random.default_rng(T + chunk)
    H, P, N = 4, 8, 16
    x = rng.normal(size=(T, H, P)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(T, H)) * 2)).astype(np.float32)
    dt[T - 3:] = 0.0                                # a padded tail
    A = -rng.uniform(1, 16, H).astype(np.float32)
    B, C = (rng.normal(size=(T, N)).astype(np.float32) for _ in "bc")
    carried = rng.normal(size=(H, P, N)).astype(np.float32)
    want_y, want_s = token_by_token(x, dt, A, B, C, carried)
    y, s = jax.jit(ssm.ssd_chunked_scan, static_argnums=6)(
        x, dt, A, B, C, carried, chunk)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s, want_s, rtol=2e-5, atol=2e-5)
    # the padded tail moved nothing: the state is the one after T - 3
    _, at_tail = token_by_token(x[:T - 3], dt[:T - 3], A, B[:T - 3],
                                C[:T - 3], carried)
    np.testing.assert_allclose(s, at_tail, rtol=2e-5, atol=2e-5)
    # and the decode step is the recurrence
    live = np.array([True, False])
    y1, s1 = ssm.ssm_decode_step(
        np.stack([x[0], x[1]]), np.stack([dt[0], dt[1]]), A,
        np.stack([B[0], B[1]]), np.stack([C[0], C[1]]),
        np.stack([carried, carried]), live)
    np.testing.assert_allclose(y1[0], want_y[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(s1[1], carried)


def test_conv_window_is_taken_at_the_true_end():
    rng = np.random.default_rng(3)
    K, C, T, n = 4, 6, 8, 5
    seq = rng.normal(size=(T, C)).astype(np.float32)
    window = rng.normal(size=(K - 1, C)).astype(np.float32)
    w = rng.normal(size=(K, C)).astype(np.float32)
    b = rng.normal(size=(C,)).astype(np.float32)
    out, win = ssm.causal_conv_prefill(seq, window, w, b, n)
    full = np.concatenate([window, seq])
    want = b + sum(w[k] * full[k:k + T] for k in range(K))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(win, seq[n - K + 1:n])
    # a prompt shorter than the window keeps what came before it
    _, short = ssm.causal_conv_prefill(seq, window, w, b, 1)
    np.testing.assert_array_equal(short, np.concatenate(
        [window[1:], seq[:1]]))
    # one step, two rows, the second not live
    step_out, moved = ssm.causal_conv_step(
        seq[:2], np.stack([window, window], 1), w, b,
        np.array([True, False]))
    np.testing.assert_allclose(step_out[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(moved[:, 0], np.concatenate(
        [window[1:], seq[:1]]))
    np.testing.assert_array_equal(moved[:, 1], window)


# --- grouped-query attention -----------------------------------------------

def pool_with(k, v, page):
    """``k``/``v`` ``[B, S, H, D]`` laid into a fresh pool, row b on
    pages 1 + b * per ..."""
    B, S, H, D = k.shape
    per = S // page
    spec = page_pool_spec(B, S, n_layer=1, n_head=H, head_dim=D,
                          compute_dtype=k.dtype, n_positions=S,
                          page_size=page)
    pool = init_kv_cache(spec)["h_0"]
    tables = 1 + np.arange(B * per, dtype=np.int32).reshape(B, per)
    for name, vals in (("k", k), ("v", v)):
        paged = np.moveaxis(np.asarray(vals).reshape(B, per, page, H, D),
                            2, 4).reshape(B * per, H, D, page)
        pool[name] = pool[name].at[1:].set(paged)
    return pool, jnp.asarray(tables)


@pytest.mark.parametrize("group,scale", [(4, 1 / 64), (2, None),
                                         (1, None), (1, 0.3)])
def test_grouped_query_decode_kernel_against_dense(group, scale):
    """``group`` query heads to a key head, query head h on key head
    h // group, the scores scaled by ``scale``; ``group`` 1 and no
    scale is GPT-2's call."""
    rng = np.random.default_rng(group)
    B, S, H, D, page = 3, 32, 2, 16, 8
    k, v = (rng.normal(size=(B, S, H, D)).astype(np.float32)
            for _ in "kv")
    q = rng.normal(size=(B, 1, H * group, D)).astype(np.float32)
    positions = np.array([5, 31, 16], np.int32)
    pool, tables = pool_with(k, v, page)
    # the kernel's read alone: the lane the pool holds goes in again
    got = attend(q, pool["k"], pool["v"], positions, tables,
                 block_k=page, scale=scale)
    sc = D ** -0.5 if scale is None else scale
    for b in range(B):
        n = positions[b] + 1
        for h in range(H * group):
            s = (k[b, :n, h // group] @ q[b, 0, h]) * sc
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ v[b, :n, h // group]
            np.testing.assert_allclose(got[b, 0, h], want, rtol=2e-5,
                                       atol=2e-5)
    # the dense path over the same pool agrees (it is the prefill's)
    new = jnp.zeros((B, 1, H, D), jnp.float32)
    dense, _ = cached_attention(
        jnp.asarray(q), new, new, pool,
        jnp.asarray(positions + 1)[:, None],
        jnp.float32, tables, scale=scale)
    # one more (zero) key at positions + 1: compare where it is masked
    # out by giving it no weight: recompute with the zero key included
    for b in range(B):
        n = positions[b] + 1
        for h in range(H * group):
            s = np.append(k[b, :n, h // group] @ q[b, 0, h], 0.0) * sc
            p = np.exp(s - s.max())
            want = (p / p.sum())[:n] @ v[b, :n, h // group]
            if n < S:
                np.testing.assert_allclose(dense[b, 0, h], want,
                                           rtol=2e-5, atol=2e-5)


def test_gpt2_call_of_the_decode_kernel_is_unchanged():
    """With as many query heads as the pool holds and no scale the
    kernel's body traces to what it traced to before it knew groups: 2-D
    scores ``[H, block_k]``, no group axis."""
    from deepspeed_tpu.ops.pallas import flash_decode as fd
    H, D, page = 4, 16, 8
    new = {"k": jnp.zeros((2, 1, H, D)), "v": jnp.zeros((2, 1, H, D))}
    pool = {"k": jnp.zeros((5, H, D, page)), "v": jnp.zeros((5, H, D, page))}
    args = (new, pool, jnp.zeros((2,), jnp.int32),
            jnp.ones((2, 2), jnp.int32))
    text = str(jax.make_jaxpr(lambda q, *a: fd.flash_decode_paged(
        q, *a, block_k=page))(jnp.zeros((2, 1, H, D)), *args))
    assert f"f32[{H},1,{D}]" in text and f"f32[{H},{page}]" in text
    grouped = str(jax.make_jaxpr(lambda q, *a: fd.flash_decode_paged(
        q, *a, block_k=page))(jnp.zeros((2, 1, 2 * H, D)), *args))
    assert f"f32[{H},2,{page}]" in grouped
    with pytest.raises(ValueError, match="whole number of query heads"):
        fd.flash_decode_paged(jnp.zeros((2, 1, H + 1, D)), *args)


# --- what cannot hold a state refuses, typed, before any trace -------------

def refusals(tiny):
    model, params = tiny
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))
    build = lambda **kw: InferenceEngine(      # noqa: E731
        model, params, config=dict(INF, **kw.pop("config", {})), **kw)

    def session():
        ContinuousBatchingScheduler(build()).submit(
            Request("a", [1, 2, 3], 2, session_id="s"))

    return {
        "prefix_cache": lambda: build(config={"prefix_cache": True}),
        "speculative": lambda: build(config={"speculative": {"k": 2}}),
        "prefill_tier": lambda: build(config={"tier": "prefill"}),
        "decode_tier": lambda: build(config={"tier": "decode"}),
        "model_axis": lambda: build(mesh=mesh),
        "park_resume": session,
        "gather_pages": lambda: build().gather_pages([1]),
        "scatter_pages": lambda: build().scatter_pages([1], {}),
        "resumed_prefill": lambda: build().prefill(
            0, list(range(40)), table(0), start=CHUNK),
    }


@pytest.mark.parametrize("what", [
    "prefix_cache", "speculative", "prefill_tier", "decode_tier",
    "model_axis", "park_resume", "gather_pages", "scatter_pages",
    "resumed_prefill"])
def test_typed_refusals(tiny, what, monkeypatch):
    traced = []
    monkeypatch.setattr(gh.GraniteHybridLM, "serve_apply",
                        lambda *a, **k: traced.append(1))
    with pytest.raises(RecurrentStateUnsupported) as e:
        refusals(tiny)[what]()
    assert "recurrent state" in str(e.value) and not traced


def test_hybrid_engine_builds_with_the_prefix_cache_off(tiny):
    model, params = tiny
    eng = InferenceEngine(model, params, config=INF)
    assert eng.recurrent and eng.prefix_cache is False
    assert ContinuousBatchingScheduler(eng).paging.radix is None
    # a model without a state keeps it on, through the same seam
    from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny
    gpt2 = GPT2LMHead(gpt2_tiny(dtype=jnp.float32))
    gparams = gpt2.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    geng = InferenceEngine(gpt2, gparams, config=INF)
    assert not geng.recurrent and geng.prefix_cache is True
    assert geng.spec == gpt2.cache_spec(ROWS, SEQ, page_size=PAGE)


def test_a_chunk_of_several_pages(tiny):
    """``prefill_chunk`` 16 over pages of 8 (the cell's 512 over 128):
    the same logits as a chunk inside one page."""
    model, params = tiny
    toks = np.random.default_rng(5).integers(0, 256, 29).tolist()
    outs = []
    for page in (8, 16, 32):
        eng = InferenceEngine(model, params, config=dict(
            INF, page_size=page, attention_block_k=8))
        per = SEQ // page
        outs.append(eng.prefill(0, toks, np.arange(1, per + 1)))
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-6)
    np.testing.assert_allclose(outs[0], outs[2], atol=2e-6)


# --- the driver's own-input checks must fail on a fault --------------------

TOL = 1e-4      # float32 model against float32 reference: sound is ~1e-6


class Ctx:
    def __init__(self, cfg):
        self.config = ref_cfg(cfg)
        self.workload = {"correctness": {"state_rtol": TOL}}


def test_own_input_checks_pass_sound(tiny):
    model, params = tiny
    cfg = model.config
    eng = InferenceEngine(model, params, config=dict(
        INF, attention_impl="flash"))
    prompt = list(range(2, 39))                 # 37: a tail of 11
    state = serve_hybrid.check_state(Ctx(cfg), eng, prompt, [5, 6, 7], short=9)
    assert state["ok"] and state["pad_tokens"] == 11, state
    assert serve_hybrid.check_mixer(cfg, ref_cfg(cfg), params, 1, CHUNK,
                                    TOL)["ok"]
    assert serve_hybrid.check_attention(cfg, ref_cfg(cfg), params, 1,
                                        CHUNK, PAGE, "flash", TOL)["ok"]


def round_state(fn):
    def rounded(*a, **k):
        y, s = fn(*a, **k)
        return y, jax.lax.reduce_precision(s, 8, 7)     # bfloat16
    return rounded


@pytest.mark.parametrize("fault", ["bf16_state", "unmasked_tail",
                                   "stale_state"])
def test_state_check_must_fail(tiny, monkeypatch, fault):
    model, params = tiny
    prompt = list(range(2, 39))
    if fault == "bf16_state":
        monkeypatch.setattr(ssm, "ssd_chunked_scan",
                            round_state(ssm.ssd_chunked_scan))
        monkeypatch.setattr(ssm, "ssm_decode_step",
                            round_state(ssm.ssm_decode_step))
    eng = InferenceEngine(model, params, config=dict(
        INF, attention_impl="flash"))
    if fault == "unmasked_tail":
        compiled = eng._prefill
        eng._prefill = lambda p, c, t, pos, pt, slots, n_valid: compiled(
            p, c, t, pos, pt, slots, jnp.full((1,), CHUNK, jnp.int32))
    if fault == "stale_state":
        # no chunk is taken for a prompt's first: the slot's last
        # tenant's state is carried into the new prompt
        eng.prefill(0, list(range(50, 70)), table(0))
        compiled = eng._prefill
        eng._prefill = lambda p, c, t, pos, *rest: compiled(
            p, c, t, pos + 1, *rest)
    got = serve_hybrid.check_state(Ctx(model.config), eng, prompt,
                                   [5, 6, 7], short=9)
    assert not got["ok"], got
    assert max(got["after_prefill"], got["after_decode"]) > 10 * TOL


@pytest.mark.parametrize("fault", ["decay_halved", "conv_bias_dropped"])
def test_mixer_check_must_fail(tiny, fault):
    """The program's mixer on weights that differ from the reference's
    in one leaf: a decay that is not the configuration's, a convolution
    without its bias."""
    model, params = tiny
    cfg = model.config
    mixer = dict(params["layers_0"]["mixer"])
    if fault == "decay_halved":
        mixer["A_log"] = mixer["A_log"] + np.log(0.5)
    else:
        mixer["conv_bias"] = jnp.zeros_like(mixer["conv_bias"])
    broken = dict(params, layers_0=dict(params["layers_0"], mixer=mixer))
    got = serve_hybrid.check_mixer(cfg, ref_cfg(cfg), broken, 1, CHUNK,
                                   TOL, ref_params=params)
    assert not got["ok"], got
    assert min(got["prefill"], got["decode"]) > 10 * TOL


@pytest.mark.parametrize("fault", ["wrong_scale", "wrong_key_head"])
def test_attention_check_must_fail(tiny, fault):
    model, params = tiny
    cfg = model.config
    program_cfg, program_params = cfg, params
    if fault == "wrong_scale":      # 1 / sqrt(head) for the multiplier
        program_cfg = dataclasses.replace(
            cfg, attention_multiplier=cfg.head_dim ** -0.5)
    else:                           # the key heads in another order
        attn = dict(params["layers_2"]["attn"])
        D = cfg.head_dim
        for name in ("k_proj", "v_proj"):
            w = attn[name].reshape(-1, cfg.num_key_value_heads, D)
            attn[name] = w[:, ::-1].reshape(attn[name].shape)
        program_params = dict(params, layers_2=dict(params["layers_2"],
                                                    attn=attn))
    got = serve_hybrid.check_attention(
        program_cfg, ref_cfg(cfg), program_params, 1, CHUNK, PAGE,
        "flash", TOL, ref_params=params)
    assert not got["ok"], got
    assert min(got["prefill"], got["decode"]) > 10 * TOL

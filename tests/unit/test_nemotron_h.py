"""Nemotron-H (a block is ONE sub-layer: a Mamba-2 mixer with several
B/C groups, an attention, or experts that work in a latent under
``relu^2``) through the serving engine against the plain reference
(`benchmarks/suite/reference/nemotron_h_ref.py`) at the tiny preset on
the CPU: logits, states and the page pool after ragged chunked prefills
into used slots and decoded tokens beside dead rows; the share test;
`dropless_moe`'s two-bank form over latent rows against a loop, forward
and gradient; the scan's and the step's group axis against the
token-by-token recurrence; the decode kernel at 2 key heads x 16 query
heads; and that OLMoE's, Kimi's and granite's tiny programs lower to
the text they lowered to before the group axis and the second expert
form existed."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.suite.reference import nemotron_h_ref as ref
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import nemotron_h as nh
from deepspeed_tpu.moe.dropless import dropless_moe, sigmoid_top_k
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.pallas.flash_decode import flash_decode_paged
from tests.unit import test_flash_decode as fd

CHUNK, PAGE, SEQ, ROWS = 16, 8, 64, 3
INF = {"max_batch": ROWS, "seq_buckets": (SEQ,), "prefill_chunk": CHUNK,
       "page_size": PAGE, "attention_block_k": PAGE}


def ref_cfg(cfg, **extra):
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    out.update(n_layer=cfg.num_hidden_layers,
               assumed={"experts_held": list(cfg.experts_held)}, **extra)
    return out


@pytest.fixture(scope="module")
def tiny():
    cfg = nh.nemotron_h_tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    model = nh.NemotronHLM(cfg)
    return model, nh.init_nemotron_h_params(model, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["dense", "flash"])
def engine(request, tiny):
    model, params = tiny
    return InferenceEngine(model, params, config=dict(
        INF, attention_impl=request.param))


def table(row):
    """The row's pages, in descending order: none where the allocator
    would have put it."""
    per = SEQ // PAGE
    return np.arange((row + 1) * per, row * per, -1, dtype=np.int32)


def states_of(eng, slot):
    return {k: np.asarray(v["ssm"][slot]) for k, v in eng.cache.items()
            if "ssm" in v}


def pool_of(eng, row, n):
    """``{layer: (k, v)}`` ``[n, heads, head_dim]`` of the row's first
    ``n`` positions as the engine's pool holds them."""
    out = {}
    for name, leaves in eng.cache.items():
        if "k" in leaves:
            pages = table(row)[:-(-n // PAGE)]
            out[name] = tuple(
                np.moveaxis(np.asarray(leaves[x])[pages], -1, 1).reshape(
                    (-1,) + leaves[x].shape[1:3])[:n] for x in "kv")
    return out


def decode_one(eng, slot, token, position):
    tokens = np.zeros(ROWS, np.int32)
    positions = np.zeros(ROWS, np.int32)
    tables = np.zeros((ROWS, SEQ // PAGE), np.int32)
    tokens[slot], positions[slot], tables[slot] = token, position, \
        table(slot)
    return np.asarray(eng.decode(tokens, positions, tables)[1][slot])


def test_presets():
    cfg = nh.nemotron_3_super_share()
    assert cfg.hybrid_override_pattern == "MEMEMEM*EME"
    assert [len(cfg.names(k)) for k in "ME*"] == [5, 5, 1]
    assert len(nh.PATTERN) == 88 and \
        [nh.PATTERN.count(k) for k in "ME*"] == [40, 40, 8]
    assert cfg.d_inner == 8192 and cfg.conv_dim == 10240
    spec = cfg.cache_spec(96, 5120, page_size=128, n_pages=3841)
    assert (spec.n_layer, spec.n_head, spec.head_dim) == (1, 2, 128)
    assert spec.state_bytes_per_slot == \
        5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    tiny_cfg = nh.nemotron_h_tiny()
    assert set(tiny_cfg.hybrid_override_pattern) == set("ME*")
    assert tiny_cfg.n_groups == 2 and tiny_cfg.num_experts_per_tok == 3
    with pytest.raises(ValueError, match="dense MLP"):
        nh.nemotron_h_tiny(hybrid_override_pattern="ME*-ME*E")
    with pytest.raises(ValueError, match="one group"):
        nh.nemotron_h_tiny(n_group=2)
    with pytest.raises(ValueError, match="experts_held"):
        nh.nemotron_h_tiny(experts_held=(6, 4))


# every raggedness of the last chunk, and prompts of 1 to 3 chunks
@pytest.mark.parametrize("n", [1, 7, 16, 17, 33, 41])
def test_engine_against_reference(engine, tiny, n):
    """Prefill in chunks, then decode through the cache, teacher-forced:
    logits, every mixer's state and every attention layer's pool
    against the reference's full forward. The slot was some other
    prompt's before (the fixture is shared), its pages too, and the
    other rows of a decode step hold no request."""
    model, params = tiny
    cfg = ref_cfg(model.config)
    toks = np.random.default_rng(n).integers(0, 256, n + 4).tolist()
    slot = n % ROWS
    want, at_end, kv = ref.forward(params, toks, cfg)
    last = engine.prefill(slot, toks[:n], table(slot))
    np.testing.assert_allclose(last, want[n - 1], atol=5e-6)
    _, at_prompt, _ = ref.forward(params, toks, cfg, state_at=n - 1)
    for name, got in states_of(engine, slot).items():
        np.testing.assert_allclose(got, at_prompt[name], atol=5e-6)
    for j in range(4):
        lg = decode_one(engine, slot, toks[n + j], n + j)
        np.testing.assert_allclose(lg, want[n + j], atol=5e-6)
    for name, got in states_of(engine, slot).items():
        np.testing.assert_allclose(got, at_end[name], atol=5e-6)
    pool = pool_of(engine, slot, n + 4)
    assert set(pool) == set(kv) == set(model.config.names(nh.ATTENTION))
    for name, (k, v) in pool.items():
        np.testing.assert_allclose(k, kv[name][0], atol=5e-6)
        np.testing.assert_allclose(v, kv[name][1], atol=5e-6)
    assert engine.compile_counts() == {"prefill": 1, "decode": 1}


def test_decode_counters_and_dead_rows(engine, tiny):
    """A step's span carries the expert layers' counters; a dead row
    keeps its state and routes nothing."""
    model, _ = tiny
    cfg = model.config
    toks = list(range(3, 12))
    engine.prefill(0, toks, table(0))
    engine.prefill(2, toks[::-1], table(2))
    before = states_of(engine, 2)
    from deepspeed_tpu.telemetry import spans
    t0 = spans.clock()
    decode_one(engine, 0, 7, len(toks))
    for name, got in states_of(engine, 2).items():
        np.testing.assert_array_equal(got, before[name])
    rec = [r for r in spans.recent(t0) if r[0].endswith("decode")
           and r[3] and "moe_pairs_routed" in r[3]][-1][3]
    layers = len(cfg.names(nh.EXPERTS))
    assert rec["moe_pairs_routed"] == cfg.num_experts_per_tok * layers
    assert rec["moe_experts_held"] == cfg.experts_held[1] * layers
    assert 0 <= rec["moe_pairs_held"] <= rec["moe_pairs_routed"]
    assert rec["moe_experts_touched"] <= rec["moe_pairs_held"]
    # one live row: a held expert gets at most one pair a layer
    assert rec["moe_pairs_max"] == (1 if rec["moe_pairs_held"] else 0)
    assert rec["ssm_rows_live"] == 1 and rec["ssm_rows_touched"] == ROWS


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The four shares' routed parts, each projected up, plus the shared
    expert counted once, equal the uncut reference's expert layer: the
    program on each share, the reference whole."""
    model, _ = tiny
    whole = dataclasses.replace(model.config, experts_held=(0, 8))
    layer = nh.LatentExperts(whole)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64), jnp.float32)
    mask = jnp.ones((1, 24), bool)
    p = layer.init(jax.random.PRNGKey(4), x, mask)["params"]
    want = np.asarray(ref.experts(x[0], p, ref_cfg(whole)))
    total, pairs = 0.0, 0
    for first in range(0, 8, 2):
        share = dataclasses.replace(whole, experts_held=(first, 2))
        ps = dict(p, w_up=p["w_up"][first:first + 2],
                  w_down=p["w_down"][first:first + 2])
        y, counters = nh.LatentExperts(share).apply({"params": ps}, x, mask)
        total = total + np.asarray(y[0])
        pairs += int(counters.pairs_held)
    shared = np.asarray(ref.shared(x[0], p))
    np.testing.assert_allclose(total - 3 * shared, want, atol=2e-5)
    assert pairs == 24 * 3          # every pair fell on exactly one share


def _loop_moe(x, rows, router, bias, w_up, w_down, top_k, first, mask):
    """``dropless_moe``'s two-bank form as a loop over tokens' pairs."""
    w, chosen, _ = sigmoid_top_k(bias, 5.0)(x, router, top_k)
    y = jnp.zeros_like(rows)
    for e in range(w_up.shape[0]):
        mine = jnp.where(chosen == e + first, w, 0.0).sum(-1) * mask
        y = y + mine[:, None] * (
            jnp.square(jax.nn.relu(rows @ w_up[e])) @ w_down[e])
    return y


def test_two_bank_experts_over_latent_rows_forward_and_gradient():
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    N, M, L, I, E, held, first, top_k = 24, 32, 16, 24, 8, 4, 2, 3
    x = jax.random.normal(k[0], (N, M))
    rows = jax.random.normal(k[1], (N, L))
    router = jax.random.normal(k[2], (M, E))
    bias = 0.1 * jax.random.normal(k[3], (E,))
    w_up = 0.3 * jax.random.normal(k[4], (held, L, I))
    w_down = 0.3 * jax.random.normal(k[5], (held, I, L))
    mask = jnp.arange(N) % 5 != 4

    def ours(rows, w_up, w_down):
        y, stats = dropless_moe(
            x, router, None, w_up, w_down, top_k,
            route=sigmoid_top_k(bias, 5.0), first_expert=first,
            token_mask=mask, rows=rows)
        return y, stats

    y, stats = ours(rows, w_up, w_down)
    want = _loop_moe(x, rows, router, bias, w_up, w_down, top_k, first, mask)
    assert y.shape == (N, L)
    np.testing.assert_allclose(y, want, atol=1e-4)
    assert not np.asarray(y)[~np.asarray(mask)].any()
    assert int(stats["tokens_per_expert"].sum()) + int(stats["dropped"]) \
        == N * top_k
    # the gradient, where one is taken: every expert held (the rows
    # behind a share's groups are no tile of the kernel's, forward or
    # backward, and a share is served, not trained)
    k2 = jax.random.split(jax.random.PRNGKey(1), 2)
    w_up = 0.3 * jax.random.normal(k2[0], (E, L, I))
    w_down = 0.3 * jax.random.normal(k2[1], (E, I, L))
    ones = jnp.ones((N,))

    def loss(fn):
        return lambda r, u, d: (fn(r, u, d) ** 2).sum()

    got = jax.grad(loss(lambda r, u, d: dropless_moe(
        x, router, None, u, d, top_k, route=sigmoid_top_k(bias, 5.0),
        rows=r)[0]), (0, 1, 2))(rows, w_up, w_down)
    wanted = jax.grad(loss(lambda r, u, d: _loop_moe(
        x, r, router, bias, u, d, top_k, 0, ones)), (0, 1, 2))(
            rows, w_up, w_down)
    for a, b in zip(got, wanted):
        scale = float(np.abs(b).max())
        np.testing.assert_allclose(a, b, atol=1e-4 * scale)


def test_two_bank_experts_over_latent_rows_under_a_data_mesh():
    """Traced under `placed_on_mesh` the two-bank form with rows apart
    from the tokens runs inside the same `shard_map` as the gated one
    (tokens and rows split over the rows axis, the banks whole): values
    as on one device. A share under a mesh is still refused."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.moe.dropless import ExpertExchangeUnsupported
    from deepspeed_tpu.ops.pallas.flash_attention import placed_on_mesh
    from deepspeed_tpu.parallel.mesh import build_mesh

    k = jax.random.split(jax.random.PRNGKey(2), 5)
    N, M, L, I, E = 64, 16, 8, 12, 4
    x, rows = jax.random.normal(k[0], (N, M)), jax.random.normal(k[1], (N, L))
    router = jax.random.normal(k[2], (M, E))
    w_up = 0.3 * jax.random.normal(k[3], (E, L, I))
    w_down = 0.3 * jax.random.normal(k[4], (E, I, L))

    def fn(x, rows, first=None):    # OLMoE's routing: the mesh path's
        return dropless_moe(x, router, None, w_up, w_down, 2, rows=rows,
                            first_expert=first)[0]

    mesh = build_mesh({"data": 4}, devices=jax.devices()[:4])

    def placed(x, rows, first=None):
        with placed_on_mesh(mesh, rows="data", heads="model"):
            return fn(x, rows, first)

    want = jax.jit(fn)(x, rows)
    split = NamedSharding(mesh, P("data"))
    got = jax.jit(placed)(jax.device_put(x, split),
                          jax.device_put(rows, split))
    assert "shard_map" in str(jax.make_jaxpr(placed)(x, rows))
    np.testing.assert_allclose(got, want, atol=1e-5)
    with pytest.raises(ExpertExchangeUnsupported):
        jax.make_jaxpr(lambda x, r: placed(x, r, 0))(x, rows)


def _recurrence(x, dt, A, B, C, state):
    """Token by token, float64 numpy; ``B`` / ``C`` ``[T, G, N]``."""
    x, dt, A, B, C, S = (np.asarray(a, np.float64)
                         for a in (x, dt, A, B, C, state))
    H, G = x.shape[1], B.shape[1]
    ys = []
    for t in range(len(x)):
        Bh, Ch = np.repeat(B[t], H // G, 0), np.repeat(C[t], H // G, 0)
        S = np.exp(dt[t] * A)[:, None, None] * S + \
            (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :]
        ys.append((S * Ch[:, None, :]).sum(-1))
    return np.stack(ys), S


def _scan_case(G, T=32, H=8, P=4, N=6):
    k = jax.random.split(jax.random.PRNGKey(G), 6)
    x = jax.random.normal(k[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B, C = (jax.random.normal(k[i], (T, G, N)) for i in (3, 4))
    return x, dt, A, B, C, jax.random.normal(k[5], (H, P, N))


@pytest.mark.parametrize("G", [1, 2, 8])
def test_scan_and_step_with_groups_against_the_recurrence(G):
    x, dt, A, B, C, s0 = _scan_case(G)
    want_y, want_s = _recurrence(x, dt, A, B, C, s0)
    y, s1 = ssm.ssd_chunked_scan(x, dt, A, B, C, s0, 8)
    np.testing.assert_allclose(y, want_y, atol=2e-4)
    np.testing.assert_allclose(s1, want_s, atol=2e-4)
    # the step: three rows, the middle one dead, each at its own token
    rows = np.array([3, 9, 17])
    live = jnp.array([True, False, True])
    state = jnp.stack([s0] * 3)
    ys, new = ssm.ssm_decode_step(x[rows], dt[rows], A, B[rows], C[rows],
                                  state, live)
    for i, t in enumerate(rows):
        wy, ws = _recurrence(x[t:t + 1], dt[t:t + 1], A, B[t:t + 1],
                             C[t:t + 1], s0)
        np.testing.assert_allclose(ys[i], wy[0], atol=1e-5)
        np.testing.assert_allclose(new[i], ws if live[i] else s0, atol=1e-5)


def test_one_group_with_or_without_the_axis():
    """``[T, N]`` maps (granite's call, whose program is pinned by its
    lowered text below) and ``[T, 1, N]`` maps give the same numbers."""
    x, dt, A, B, C, s0 = _scan_case(1)
    a = ssm.ssd_chunked_scan(x, dt, A, B[:, 0], C[:, 0], s0, 8)
    b = ssm.ssd_chunked_scan(x, dt, A, B, C, s0, 8)
    for u, v in zip(a, b):
        np.testing.assert_allclose(u, v, atol=1e-5)
    live = jnp.ones((4,), bool)
    state = jnp.stack([s0] * 4)
    a = ssm.ssm_decode_step(x[:4], dt[:4], A, B[:4, 0], C[:4, 0], state, live)
    b = ssm.ssm_decode_step(x[:4], dt[:4], A, B[:4], C[:4], state, live)
    for u, v in zip(a, b):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


# sha1 of the lowered (StableHLO) text at commit 08ffaf7, before the
# group axis, the two-bank form and the rows apart from the tokens
# existed: the three accepted configurations' tiny programs lower to
# what they lowered to then; Kimi's two as PR 45 left them (its held
# experts became `moe/dropless.py:_held_moe`, loops over the live row
# tiles; OLMoE's, the whole layer's path, is the hash it was). A later
# PR that changes one of them on purpose takes the new hash from this
# test's message.
LOWERED = {
    # PR 56: the mixers' chunked scan is `ops/pallas/ssd_prefill.py`'s
    # kernel (interpreted here); `test_ssd_prefill_kernel.py` holds it to
    # the XLA body this hash pinned
    "granite.prefill": "3f42ee9203bfe3d7447abb9d4db7a66b98cfa048",
    "granite.decode": "1d71efc46d13bf7dc4184849c231b2a5f3239723",
    # PR 59: Kimi's jitted expert layer is `models/blocks.py:
    # sigmoid_held_experts` (the prefill text moves in that name and
    # nothing else), and the layers' counters are int32 scalars by name
    # where they were a stacked vector taken apart by position (the
    # decode text moves in those int32 ops; every line that holds a
    # float type is the parent's, in the parent's order: `CHANGES.md`)
    "kimi.prefill": "4c2688c1d530308dafd7b69f6cffd5f69b18278e",
    "kimi.decode": "c40fd66a8119ab38538ad1f871d14b71139bc8f1",
    "olmoe.moe": "f8341d876a95c1be49af4102c0b9a7e49aa30274",
}


def _engine_texts(model, params):
    eng = InferenceEngine(model, params, config=dict(
        max_batch=4, seq_buckets=(64,), prefill_chunk=16, page_size=8,
        attention_impl="dense"))
    return {
        "prefill": eng._prefill.lower(
            *eng.prefill_lowering_args()).as_text(),
        "decode": eng._decode.lower(*eng.decode_lowering_args()).as_text()}


def _lowered(which):
    name, part = which.split(".")
    if name == "granite":
        from deepspeed_tpu.models import granite_hybrid as gh
        m = gh.GraniteHybridLM(gh.granite_hybrid_tiny())
        return _engine_texts(m, gh.init_granite_hybrid_params(
            m, jax.random.PRNGKey(0)))[part]
    if name == "kimi":
        from deepspeed_tpu.models import mla_moe as mm
        m = mm.MlaMoeLM(mm.mla_moe_tiny())
        return _engine_texts(m, mm.init_mla_moe_params(
            m, jax.random.PRNGKey(0)))[part]
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (32, 16))
    r = jax.random.normal(k[1], (16, 8))
    wg, wu = (jax.random.normal(k[i], (8, 16, 24)) for i in (2, 3))
    wd = jax.random.normal(k[4], (8, 24, 16))

    def loss(x, r, wg, wu, wd):
        y, st = dropless_moe(x, r, wg, wu, wd, 2)
        return (y ** 2).sum() + st["z_sum"] + st["prob_sum"].sum()

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, r, wg, wu, wd).as_text()


@pytest.mark.parametrize("which", sorted(LOWERED))
def test_accepted_tiny_programs_lower_to_the_text_they_lowered_to(which):
    got = hashlib.sha1(_lowered(which).encode()).hexdigest()
    assert got == LOWERED[which], (which, got)


@pytest.mark.parametrize("block_k", [fd.PAGE, fd.PAGE // 2])
def test_decode_kernel_at_two_key_heads_of_sixteen_queries(block_k):
    """The cell's attention geometry: 2 key/value heads, 16 query heads
    to each; the step's lane written, every query head over its own key
    head (head ``h`` reads ``h // 16``)."""
    heads, group, D = 2, 16, 32
    q, new, pool, positions, tables = fd._write_case(
        5, heads, D, group, "float32")
    out, got = flash_decode_paged(q, new, pool, positions, tables,
                                  block_k=block_k)
    assert out.shape == (len(q), 1, heads * group, D)
    k, v = np.asarray(got["k"]), np.asarray(got["v"])
    q5 = q.reshape(len(q), 1, heads, group, D)
    out5 = np.asarray(out).reshape(q5.shape)
    for g in range(group):
        np.testing.assert_allclose(
            out5[:, :, :, g],
            fd._paged_ref(q5[:, :, :, g], k, v, positions, tables),
            atol=2e-6)
    # swapping the key heads is seen: the grouping is not symmetric
    wrong = fd._paged_ref(q5[:, :, ::-1, 0], k, v, positions, tables)
    live = [b for b, (_, alive) in enumerate(fd.WRITE_ROWS) if alive]
    assert np.abs(out5[live, :, :, 0] - wrong[live][:, :, ::-1]).max() > 1e-2

"""Parity tests for the latency-hiding collective matmul library
(`parallel/collectives.py`).

Every chunked/overlapped primitive must compute EXACTLY what its
monolithic counterpart computes — forward AND gradients. ``chunks=1``
is bit-identical (same ops, just routed through the library); ``chunks
> 1`` reassociates the fp32 reductions, so those compare at tight fp32
tolerance. Oracles are the plain lax collectives (`psum`,
`psum_scatter`, `all_gather`, `all_to_all`) applied to the same shards
on the same mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.collectives import (
    OverlapPlan, SitePlan, all_gather_matmul_overlap, all_to_all_overlap,
    _chunk_slices, manual_axes, matmul_psum_overlap, matmul_reduce_scatter,
    overlap_plan, overlap_scope, psum_combine, psum_grad, ring_psum)
from deepspeed_tpu.parallel.mesh import build_mesh
from jax import shard_map

N = 4                        # model-parallel degree for the fast tests
B, T = 2, 3
K = 8                        # global contraction dim (K_loc = 2)
M_ODD = 10                   # output dim NOT divisible by chunks=4
M_EVEN = 8                   # output dim divisible by N (reduce-scatter)

CHUNK_GRID = [(1, False), (2, False), (2, True), (4, False), (4, True)]
# Each (chunks, bidirectional) point on the compile-heavy primitives is
# a fresh shard_map+grad jit (~7s on CPU): the fast lane keeps one
# representative chunked point per primitive inside the tier-1 wall
# budget, the rest of the grid rides the slow lane.
slow = pytest.mark.slow
CHUNK_GRID_TIERED = [(1, False),
                     pytest.param(2, False, marks=slow),
                     pytest.param(2, True, marks=slow),
                     pytest.param(4, False, marks=slow),
                     (4, True)]


def _mesh(n=N, axis="model"):
    return build_mesh({axis: n}, devices=jax.devices()[:n])


def _sharded(local_fn, mesh, in_specs, out_specs):
    return shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# ---------------------------------------------------------------------------
# chunk slicing
# ---------------------------------------------------------------------------

def test_chunk_slices_cover_and_spread():
    assert _chunk_slices(10, 4) == [(0, 3), (3, 3), (6, 2), (8, 2)]
    assert _chunk_slices(8, 4) == [(0, 2), (2, 2), (4, 2), (6, 2)]
    assert _chunk_slices(5, 1) == [(0, 5)]
    # more chunks than elements clamps to one element per chunk
    assert _chunk_slices(3, 8) == [(0, 1), (1, 1), (2, 1)]
    for size, chunks in ((10, 4), (7, 3), (1, 5), (16, 16)):
        slices = _chunk_slices(size, chunks)
        assert slices[0][0] == 0 and sum(s for _, s in slices) == size
        for (a, sa), (b, _) in zip(slices, slices[1:]):
            assert a + sa == b


# ---------------------------------------------------------------------------
# matmul + psum (replicated output)
# ---------------------------------------------------------------------------

def _psum_matmul_run(fn, m=M_ODD):
    """(loss, grad_a, grad_b) of ``fn(a_loc, b_loc)`` on a model=4 mesh:
    contraction dim sharded, output replicated (identity-cotangent
    convention: the replicated output's cotangent is taken ONCE)."""
    mesh = _mesh()
    a = _rand(0, (B, T, K))
    b = _rand(1, (K, m))
    w = _rand(2, (B, T, m))       # fixed cotangent weights (replicated)

    def local(a_loc, b_loc, w_loc):
        def loss(al, bl):
            return jnp.sum(fn(al, bl) * w_loc)
        l, g = jax.value_and_grad(loss, argnums=(0, 1))(a_loc, b_loc)
        return l, g[0], g[1]

    run = _sharded(
        local, mesh,
        (P(None, None, "model"), P("model", None), P(None, None, None)),
        (P(), P(None, None, "model"), P("model", None)))
    return [np.asarray(x) for x in run(a, b, w)], (a, b, w)


def _dense_psum_oracle(a, b, w):
    y = a @ b
    return (np.asarray(jnp.sum(y * w)),
            np.asarray(jnp.einsum("btm,km->btk", w, b)),
            np.asarray(jnp.einsum("btk,btm->km", a, w)))


@pytest.mark.parametrize("chunks,bidirectional", CHUNK_GRID)
def test_matmul_psum_overlap_matches_dense(chunks, bidirectional):
    """Sharded+overlapped == the unsharded matmul, fwd and both grads
    (the shard-assembled grads ARE the dense grads under the library's
    identity-cotangent convention)."""
    (l_c, ga_c, gb_c), (a, b, w) = _psum_matmul_run(
        lambda al, bl: matmul_psum_overlap(
            al, bl, "model", chunks=chunks, bidirectional=bidirectional))
    l_o, ga_o, gb_o = _dense_psum_oracle(a, b, w)
    np.testing.assert_allclose(l_c, l_o, rtol=1e-5)
    np.testing.assert_allclose(ga_c, ga_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gb_c, gb_o, rtol=1e-5, atol=1e-6)


def test_matmul_psum_overlap_chunks1_bitexact():
    """chunks=1 routes through the monolithic matmul + psum_combine —
    bit-identical, not merely close."""
    (l_c, ga_c, gb_c), _ = _psum_matmul_run(
        lambda al, bl: matmul_psum_overlap(al, bl, "model", chunks=1))
    (l_m, ga_m, gb_m), _ = _psum_matmul_run(
        lambda al, bl: psum_combine(al @ bl, "model"))
    assert np.array_equal(l_c, l_m)
    assert np.array_equal(ga_c, ga_m)
    assert np.array_equal(gb_c, gb_m)


def test_matmul_psum_overlap_nondividing_output():
    """chunks=4 over M=10 exercises the 3,3,2,2 remainder spread."""
    (l_c, _, _), (a, b, w) = _psum_matmul_run(
        lambda al, bl: matmul_psum_overlap(
            al, bl, "model", chunks=4, bidirectional=True))
    l_o, _, _ = _dense_psum_oracle(a, b, w)
    np.testing.assert_allclose(l_c, l_o, rtol=1e-5)


# ---------------------------------------------------------------------------
# matmul + reduce-scatter (sharded output)
# ---------------------------------------------------------------------------

def _rs_run(chunks, bidirectional):
    mesh = _mesh()
    a = _rand(3, (B, T, K))
    b = _rand(4, (K, M_EVEN))
    w = _rand(5, (B, T, M_EVEN))  # cotangent, sharded like the output

    def make(fn):
        def local(a_loc, b_loc, w_loc):
            def loss(al, bl):
                # sharded output: the per-shard local loss IS the
                # cotangent convention (each rank owns its slice)
                return jnp.sum(fn(al, bl) * w_loc)
            l, g = jax.value_and_grad(loss, argnums=(0, 1))(a_loc, b_loc)
            return l.reshape(1), g[0], g[1]
        return _sharded(
            local, mesh,
            (P(None, None, "model"), P("model", None),
             P(None, None, "model")),
            (P("model",), P(None, None, "model"), P("model", None)))

    chunked = make(lambda al, bl: matmul_reduce_scatter(
        al, bl, "model", chunks=chunks, bidirectional=bidirectional))
    oracle = make(lambda al, bl: lax.psum_scatter(
        al @ bl, "model", scatter_dimension=2, tiled=True))
    got = [jax.tree_util.tree_map(np.asarray, f(a, b, w))
           for f in (chunked, oracle)]
    dense = (np.asarray(jnp.sum((a @ b) * w)),
             np.asarray(jnp.einsum("btm,km->btk", w, b)),
             np.asarray(jnp.einsum("btk,btm->km", a, w)))
    return got, dense


@pytest.mark.parametrize("chunks,bidirectional", CHUNK_GRID_TIERED)
def test_matmul_reduce_scatter_matches_psum_scatter(chunks, bidirectional):
    """Chunked RS vs both the lax.psum_scatter oracle (same transpose:
    all-gather of the cotangents) and the dense ground truth — the total
    loss is the sum of the per-shard local losses."""
    ((l_c, ga_c, gb_c), (l_o, ga_o, gb_o)), (l_d, ga_d, gb_d) = _rs_run(
        chunks, bidirectional)
    np.testing.assert_allclose(l_c, l_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ga_c, ga_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gb_c, gb_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(l_c.sum(), l_d, rtol=1e-5)
    np.testing.assert_allclose(ga_c, ga_d, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gb_c, gb_d, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# all-gather + matmul (gathered contraction)
# ---------------------------------------------------------------------------

def _ag_run(chunks, bidirectional):
    mesh = _mesh()
    x = _rand(6, (B, T, K))       # gathered dim sharded: local K/N
    w_full = _rand(7, (K, M_ODD))  # replicated weight, full K rows
    cot = _rand(8, (B, T, M_ODD))

    def local(x_loc, w_loc, c_loc):
        def loss(xl, wl):
            # replicated output → identity transpose; the cotangent is
            # taken once (same on every rank)
            return jnp.sum(all_gather_matmul_overlap(
                xl, wl, "model", chunks=chunks,
                bidirectional=bidirectional) * c_loc)
        l, g = jax.value_and_grad(loss, argnums=(0, 1))(x_loc, w_loc)
        return l, g[0], g[1]

    run = _sharded(
        local, mesh,
        (P(None, None, "model"), P(None, None), P(None, None, None)),
        (P(), P(None, None, "model"), P(None, None)))
    got = [np.asarray(v) for v in run(x, w_full, cot)]
    dense = (np.asarray(jnp.sum((x @ w_full) * cot)),
             np.asarray(jnp.einsum("btm,km->btk", cot, w_full)),
             np.asarray(jnp.einsum("btk,btm->km", x, cot)))
    return got, dense


@pytest.mark.parametrize("chunks,bidirectional", CHUNK_GRID_TIERED)
def test_all_gather_matmul_matches_dense(chunks, bidirectional):
    (l_c, gx_c, gw_c), (l_o, gx_o, gw_o) = _ag_run(chunks, bidirectional)
    np.testing.assert_allclose(l_c, l_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gx_c, gx_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gw_c, gw_o, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# all-to-all (Ulysses brackets)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [1, pytest.param(2, marks=slow), 4])
def test_all_to_all_overlap_matches_lax(chunks):
    mesh = _mesh()
    H, D = 8, 4
    x = _rand(9, (B, N * T, H, D))     # seq sharded, all heads local
    cot = _rand(10, (B, N * T, H, D))  # out: full seq, heads sharded

    def make(fn):
        def local(x_loc, c_loc):
            def loss(xl):
                return jnp.sum(fn(xl) * c_loc)
            l, g = jax.value_and_grad(loss)(x_loc)
            return l.reshape(1), g
        return _sharded(local, mesh,
                        (P(None, "model", None, None),
                         P(None, None, "model", None)),
                        (P("model",), P(None, "model", None, None)))

    chunked = make(lambda xl: all_to_all_overlap(
        xl, "model", 2, 1, chunks=chunks))
    oracle = make(lambda xl: lax.all_to_all(
        xl, "model", split_axis=2, concat_axis=1, tiled=True))
    (l_c, g_c), (l_o, g_o) = [
        jax.tree_util.tree_map(np.asarray, f(x, cot))
        for f in (chunked, oracle)]
    # a permutation-only collective: bit-equal, no reassociation
    assert np.array_equal(l_c, l_o)
    assert np.array_equal(g_c, g_o)


# ---------------------------------------------------------------------------
# ring psum / backward-psum rings
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunks,bidirectional", CHUNK_GRID)
def test_ring_psum_matches_psum(chunks, bidirectional):
    mesh = _mesh()
    x = _rand(11, (N, T, M_ODD))

    def make(fn):
        return _sharded(lambda xl: fn(xl), mesh,
                        (P("model", None, None),), P(None, None))

    got = np.asarray(make(lambda xl: ring_psum(
        xl[0], "model", chunks=chunks, bidirectional=bidirectional))(x))
    want = np.asarray(make(lambda xl: lax.psum(xl[0], "model"))(x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("chunks", [1, 4])
def test_psum_grad_backward_matches_psum(chunks):
    """psum_grad: identity forward; cotangent summed over the axis —
    chunked rings must reduce to the same gradient as the monolithic."""
    mesh = _mesh()
    x = _rand(12, (B, T, M_ODD))      # replicated activations
    w = _rand(13, (N, B, T, M_ODD))   # rank-DEPENDENT cotangent weights

    def make(fn):
        def local(x_loc, w_loc):
            def loss(xl):
                return jnp.sum(fn(xl) * w_loc[0])
            return jax.grad(loss)(x_loc)
        return _sharded(local, mesh,
                        (P(None, None, None), P("model", None, None, None)),
                        P(None, None, None))

    got = np.asarray(make(lambda xl: psum_grad(
        xl, "model", chunks=chunks))(x, w))
    want = np.asarray(make(lambda xl: psum_grad(xl, "model"))(x, w))
    oracle = np.asarray(w.sum(0))     # sum of per-rank cotangents
    np.testing.assert_allclose(want, oracle, rtol=1e-6)
    if chunks == 1:
        assert np.array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# quantized wire: rings carrying int8/fp8 payloads + per-chunk scales
# ---------------------------------------------------------------------------

# Half a quantization step against the chunk absmax: the per-element
# decode error of one remote contribution (own contribution is exact).
_WIRE_REL = {"int8": 0.5 / 127.0, "f8e4m3fn": 2.0 ** -4}
WIRE_CODECS = ["int8", "f8e4m3fn"]
WIRE_GRID_TIERED = [(1, False),
                    pytest.param(2, False, marks=slow),
                    (2, True),
                    pytest.param(4, False, marks=slow),
                    (4, True)]


def _wire_bound(partials, codec):
    """Error budget of a quantized reduction: every REMOTE rank's
    contribution decodes within ``rel * chunk_absmax``; bound with the
    global absmax across ranks."""
    return (partials.shape[0] - 1) * float(
        np.abs(np.asarray(partials)).max()) * _WIRE_REL[codec] + 1e-6


@pytest.mark.parametrize("codec", WIRE_CODECS)
@pytest.mark.parametrize("chunks,bidirectional", WIRE_GRID_TIERED)
def test_ring_psum_wire_error_bounded(chunks, bidirectional, codec):
    """Quantized ring psum == exact psum within the codec's error budget
    (own contribution exact, each remote one within rel * absmax)."""
    mesh = _mesh()
    x = _rand(20, (N, T, M_ODD))

    def make(fn):
        return _sharded(lambda xl: fn(xl), mesh,
                        (P("model", None, None),), P(None, None))

    got = np.asarray(make(lambda xl: ring_psum(
        xl[0], "model", chunks=chunks, bidirectional=bidirectional,
        wire_dtype=codec, wire_chunk=16))(x))
    want = np.asarray(make(lambda xl: lax.psum(xl[0], "model"))(x))
    assert np.abs(got - want).max() <= _wire_bound(x, codec)


@pytest.mark.parametrize("codec", WIRE_CODECS)
@pytest.mark.parametrize("chunks,bidirectional", WIRE_GRID_TIERED)
def test_matmul_psum_overlap_wire_error_bounded(chunks, bidirectional,
                                                codec):
    """The overlapped row-parallel matmul with a quantized wire: forward
    within the codec budget of the exact dense product, and the
    transposed (chunk-granular, collective-free) backward still exact —
    quantization rides the wire, not the grads."""
    (l_c, ga_c, gb_c), (a, b, w) = _psum_matmul_run(
        lambda al, bl: matmul_psum_overlap(
            al, bl, "model", chunks=chunks, bidirectional=bidirectional,
            wire_dtype=codec, wire_chunk=16))
    l_o, ga_o, gb_o = _dense_psum_oracle(a, b, w)
    k_loc = K // N
    an, bn = np.asarray(a), np.asarray(b)
    partials = np.stack(
        [an[..., r * k_loc:(r + 1) * k_loc] @
         bn[r * k_loc:(r + 1) * k_loc] for r in range(N)])
    bound = _wire_bound(partials, codec)
    assert float(np.abs(l_c - l_o)) <= bound * float(
        np.abs(np.asarray(w)).sum())
    # backward: the combine's transpose is identity + local transposed
    # matmuls — independent of the wire, so grads match at fp32 parity
    np.testing.assert_allclose(ga_c, ga_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gb_c, gb_o, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("codec", WIRE_CODECS)
def test_wire_chunks1_bit_identical_to_monolithic(codec):
    """chunks=1 with a wire routes BOTH primitives through the same
    bracketed quantize -> monolithic-collective reference — bit-identical
    results, not merely close."""
    mesh = _mesh()
    a = _rand(21, (B, T, K))
    b = _rand(22, (K, M_ODD))

    def run(fn):
        return np.asarray(_sharded(
            fn, mesh, (P(None, None, "model"), P("model", None)),
            P(None, None, None))(a, b))

    overlap = run(lambda al, bl: matmul_psum_overlap(
        al, bl, "model", chunks=1, wire_dtype=codec, wire_chunk=16))
    monolithic = run(lambda al, bl: ring_psum(
        al @ bl, "model", chunks=1, wire_dtype=codec, wire_chunk=16))
    assert np.array_equal(overlap, monolithic)


@pytest.mark.parametrize("codec", WIRE_CODECS)
@pytest.mark.parametrize("chunks,bidirectional",
                         [(1, False), (2, True),
                          pytest.param(4, False, marks=slow)])
def test_ring_all_gather_wire_error_bounded(chunks, bidirectional, codec):
    """Quantized stripe gather (the stage-3 wire): each remote shard
    decodes within rel * its absmax; own shard exact."""
    from deepspeed_tpu.parallel.collectives import ring_all_gather
    mesh = _mesh()
    x = _rand(23, (N * T, M_ODD))     # gather dim 0, T rows per rank

    def local(xl):
        out, _dep = ring_all_gather(xl, "model", axis=0, chunks=chunks,
                                    bidirectional=bidirectional,
                                    wire_dtype=codec, wire_chunk=16)
        return out

    got = np.asarray(_sharded(local, mesh, (P("model", None),),
                              P(None, None))(x))
    want = np.asarray(x)
    assert got.shape == want.shape
    err = np.abs(got - want).max()
    assert err <= float(np.abs(want).max()) * _WIRE_REL[codec] + 1e-6


# ---------------------------------------------------------------------------
# plan / scope plumbing
# ---------------------------------------------------------------------------

def test_overlap_plan_site_resolution():
    plan = OverlapPlan(chunks=4, bidirectional=True,
                       sites={"ulysses": {"chunks": 2,
                                          "bidirectional": False},
                              "expert_combine": {"enabled": False}})
    assert plan.site("row_parallel") == SitePlan(4, True)
    assert plan.site("ulysses") == SitePlan(2, False)
    assert plan.site("expert_combine") is None


def test_overlap_scope_activates_and_restores():
    assert overlap_plan("row_parallel") is None
    plan = OverlapPlan(chunks=2)
    with overlap_scope(plan):
        assert overlap_plan("row_parallel") == SitePlan(2, False)
        with overlap_scope(None):       # nested disable
            assert overlap_plan("row_parallel") is None
        assert overlap_plan("row_parallel") == SitePlan(2, False)
    assert overlap_plan("row_parallel") is None


def test_tensor_parallel_overlap_config():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    def cfg(overlap):
        return DeepSpeedConfig(
            {"train_batch_size": 8,
             "tensor_parallel": {"overlap": overlap}}, world_size=1)

    tp = cfg({"enabled": True, "chunks": 4,
              "sites": {"ulysses": {"enabled": False}}}).tensor_parallel
    plan = tp.overlap_plan()
    assert plan == OverlapPlan(chunks=4, bidirectional=False,
                               sites={"ulysses": {"enabled": False}})
    assert plan.site("ulysses") is None
    assert cfg({"enabled": False}).tensor_parallel.overlap_plan() is None

    for bad in ({"enabled": "yes"},
                {"enabled": True, "chunks": 0},
                {"enabled": True, "chunks": 2.5},
                {"enabled": True, "sites": {"no_such_site": {}}},
                {"enabled": True, "sites": {"ulysses": {"bogus": 1}}},
                {"enabled": True, "sites": ["ulysses"]}):
        with pytest.raises(ValueError):
            cfg(bad)


# ---------------------------------------------------------------------------
# layer-level parity under an active plan
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ulysses_attention_chunked_matches_monolithic():
    mesh = build_mesh({"data": 2, "seq": 4}, devices=jax.devices()[:8])
    from deepspeed_tpu.parallel.sequence import ulysses_attention
    q = _rand(14, (2, 8, 8, 4))
    k = _rand(15, (2, 8, 8, 4))
    v = _rand(16, (2, 8, 8, 4))
    base = np.asarray(ulysses_attention(q, k, v, mesh, causal=True))
    with overlap_scope(OverlapPlan(chunks=2)):
        chunked = np.asarray(ulysses_attention(q, k, v, mesh, causal=True))
    np.testing.assert_allclose(chunked, base, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_expert_combine_overlap_matches_monolithic():
    from deepspeed_tpu.moe.expert_pipe import ExpertParallelFFNLayer
    from deepspeed_tpu.moe.layer import MoEConfig

    mesh = _mesh(axis="expert")
    layer = ExpertParallelFFNLayer(
        d_model=8, hidden_dim=16,
        moe=MoEConfig(num_experts=N, top_k=2, capacity_factor=2.0))
    x = _rand(17, (2, 4, 8))
    params = layer.init(jax.random.PRNGKey(0), x)
    cot = _rand(18, (2, 4, 8))

    expert_specs = {k: (P(*(["expert"] + [None] * (v.ndim - 1)))
                        if k.startswith("expert_")
                        else P(*([None] * v.ndim)))
                    for k, v in params.items()}

    def make(plan):
        def local(p, x_loc, c_loc):
            with manual_axes(("expert",)), overlap_scope(plan):
                def loss(pp):
                    return jnp.sum(layer.apply(pp, x_loc) * c_loc)
                return jax.value_and_grad(loss)(p)
        return _sharded(local, mesh,
                        (expert_specs, P(None, None, None),
                         P(None, None, None)),
                        (P(), expert_specs))

    (l_m, g_m), (l_c, g_c) = [
        jax.tree_util.tree_map(np.asarray, make(plan)(params, x, cot))
        for plan in (None, OverlapPlan(chunks=2))]
    np.testing.assert_allclose(l_c, l_m, rtol=1e-5)
    for key in params:
        np.testing.assert_allclose(g_c[key], g_m[key], rtol=2e-4,
                                   atol=1e-6, err_msg=key)


# ---------------------------------------------------------------------------
# audit rule on synthetic HLO
# ---------------------------------------------------------------------------

def test_rule_overlap_flags_missing_permutes():
    from deepspeed_tpu.analysis.rules import StepContext, rule_overlap

    blocking = "%ar = f32[8]{0} all-reduce(%x), replica_groups={}\n"
    permutes = "".join(
        f"%cp{i} = f32[8]{{0}} collective-permute(%x), "
        "source_target_pairs={{0,1}}\n" for i in range(3))

    def ctx(hlo, **kw):
        base = dict(flavor="pipeline_tp", n_devices=8, pipeline=True,
                    overlap_enabled=True, overlap_chunks=4)
        base.update(kw)
        return StepContext(hlo_text=hlo, **base)

    # promised chunks=4 but no permutes in the program → finding
    assert any(f.rule == "overlap"
               for f in rule_overlap(ctx(blocking)))
    # >= chunks-1 permutes, no repeated all-reduce → clean
    assert rule_overlap(ctx(permutes)) == []
    # rule is scoped: disabled overlap or non-pipeline steps are exempt
    assert rule_overlap(ctx(blocking, overlap_enabled=False)) == []
    assert rule_overlap(ctx(blocking, pipeline=False)) == []


# ---------------------------------------------------------------------------
# whole-pipeline parity + lowered-HLO pin (slow)
# ---------------------------------------------------------------------------

def _pipe_tp_run(overlap):
    from tests.pipeline_fixtures import tiny_tp_pipeline_module
    from deepspeed_tpu.runtime.pipe.pipeline import (
        build_pipeline_parts, make_pipeline_value_and_grad_fn)

    mesh = build_mesh({"pipe": 2, "model": 2, "data": 2},
                      devices=jax.devices()[:8])
    module = tiny_tp_pipeline_module(vocab=32, d_model=8, n_head=4,
                                     seq=8, ids_key="ids",
                                     labels_key="labels")
    rng = np.random.default_rng(0)
    micro = {"ids": rng.integers(0, 32, (2, 8)).astype(np.int32),
             "labels": rng.integers(0, 32, (2, 8)).astype(np.int32)}
    parts = build_pipeline_parts(module, num_stages=2,
                                 rng=jax.random.PRNGKey(0),
                                 example_micro=micro)
    fn = jax.jit(make_pipeline_value_and_grad_fn(parts, mesh, 4,
                                                 overlap=overlap))
    batch = {"ids": rng.integers(0, 32, (16, 8)).astype(np.int32),
             "labels": rng.integers(0, 32, (16, 8)).astype(np.int32)}
    args = (parts.params, batch, None, jnp.float32(1.0))
    compiled = fn.lower(*args).compile()
    loss, grads = compiled(*args)
    return (float(loss), jax.tree_util.tree_map(np.asarray, grads),
            compiled.as_text())


@pytest.mark.full_compile
@pytest.mark.slow
def test_pipe_tp_overlap_parity_and_hlo_pin():
    """The acceptance pin: with chunks=4 the lowered 1F1B TP step (a)
    matches the monolithic step's loss/grads, (b) executes >= chunks-1
    collective-permutes, and (c) runs NO in-loop all-reduce — a rewired
    row-parallel site regressing to blocking form would."""
    from deepspeed_tpu.analysis.hlo import collective_counts, collective_ops

    loss_off, grads_off, _ = _pipe_tp_run(None)
    loss_on, grads_on, hlo = _pipe_tp_run(
        OverlapPlan(chunks=4, bidirectional=True))
    np.testing.assert_allclose(loss_on, loss_off, rtol=1e-5)
    flat_off, _ = jax.tree_util.tree_flatten(grads_off)
    flat_on, _ = jax.tree_util.tree_flatten(grads_on)
    assert len(flat_on) == len(flat_off) and len(flat_on) > 0
    for a, b in zip(flat_off, flat_on):
        np.testing.assert_allclose(b, a, rtol=3e-4, atol=1e-6)

    counts = collective_counts(hlo)
    assert counts.get("collective-permute", 0) >= 3, counts
    in_loop_ar = [op for op in collective_ops(hlo)
                  if op["op"] == "all-reduce" and op["multiplier"] > 1]
    assert in_loop_ar == [], in_loop_ar


@pytest.mark.full_compile
@pytest.mark.slow
def test_audit_pipeline_tp_flavor_clean():
    """End-to-end: the ds_tpu_audit pipeline_tp flavor (overlap enabled,
    chunks=4) compiles, steps, and yields zero findings — including the
    overlap rule's permute pin and the recompile detector."""
    from deepspeed_tpu.analysis.audit import audit_flavors

    reports = audit_flavors(["pipeline_tp"], steps=2)
    rep = reports["pipeline_tp"]
    assert rep.findings == [], rep.to_text()

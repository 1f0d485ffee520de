"""fp8 end-to-end training pins (`deepspeed_tpu/ops/fp8.py` + the
quantized collective wire).

Four halves:

- codec properties: the f8e4m3fn/f8e5m2 chunk codecs from the shared
  registry (`runtime/comm/codecs.py`) — absmax exactness, bounded
  roundtrip error, int8 backward compatibility, wire packing.
- delayed-scaling primitives: scale bootstrap, history roll-in, and the
  grad-as-state-update contract of the ``in_qdq``/``out_qdq`` pair (the
  history's "gradient" IS the next step's history).
- engine integration: state discovery + amax convergence on GPT-2-tiny,
  and the 24-step fp8-vs-bf16 loss-curve parity.
- HLO pins: fp8 operand/cotangent dtypes present in the lowered step,
  ring-gather wire bytes <= 0.30x the bf16 baseline, the ``fp8`` audit
  rule's seeded violations, and the stock fp8 flavor auditing clean.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.fp8 import (
    E4M3_MAX, E5M2_MAX, Fp8Plan, compute_scale, fp8_dot_general, fp8_plan,
    fp8_scope, in_qdq, init_history, init_state_bundle, out_qdq,
    quantize_dequantize, update_history)
from deepspeed_tpu.runtime.comm.codecs import (
    CODECS, decode_chunks, decode_wire, encode_chunks, encode_wire,
    get_codec, wire_nbytes)

CHUNK = 64

# Round-to-nearest cast error of the fp8 formats: half a ulp relative
# for normals (mantissa bits m -> 2^-(m+1)), plus half the smallest
# subnormal step (absolute, in scale units) near zero.
_FP8_ERR = {"f8e4m3fn": (2.0 ** -4, 2.0 ** -10),   # m=3, min subnormal 2^-9
            "f8e5m2": (2.0 ** -3, 2.0 ** -17)}     # m=2, min subnormal 2^-16


# ---------------------------------------------------------------- codec

@pytest.mark.parametrize("name", ["f8e4m3fn", "f8e5m2"])
def test_fp8_codec_absmax_exact(name):
    """The absmax element of each chunk scales to exactly qmax, which is
    representable — the codec is exact at the extremes (like int8's
    +-127 pin)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, CHUNK)).astype(np.float32)
    q, scales = encode_chunks(jnp.asarray(x.reshape(-1)), CHUNK, name)
    back = np.asarray(decode_chunks(q, scales)).reshape(4, CHUNK)
    idx = np.abs(x).argmax(axis=1)
    rows = np.arange(4)
    np.testing.assert_allclose(back[rows, idx], x[rows, idx], rtol=1e-6)


@pytest.mark.parametrize("name", ["f8e4m3fn", "f8e5m2"])
def test_fp8_codec_error_bounded(name):
    """Saturating RNE cast: per-element error <= half-ulp relative plus
    half the subnormal step of the scaled value."""
    rel, sub = _FP8_ERR[name]
    qmax = CODECS[name].qmax
    rng = np.random.default_rng(1)
    x = (rng.normal(size=(8 * CHUNK,)) *
         rng.choice([1e-3, 1.0, 100.0], size=8 * CHUNK)).astype(np.float32)
    q, scales = encode_chunks(jnp.asarray(x), CHUNK, name)
    assert q.dtype == CODECS[name].dtype
    back = np.asarray(decode_chunks(q, scales))
    err = np.abs(back - x)
    step = np.repeat(np.asarray(scales), CHUNK) * qmax  # = chunk absmax
    bound = rel * np.abs(x) + sub * step + 1e-12
    assert (err <= bound).all(), (err / np.maximum(bound, 1e-30)).max()


def test_int8_codec_is_legacy_quantize_chunks():
    """The registry's int8 codec must stay bit-for-bit the PR 1
    quantize/dequantize pair the bracketed all-reduce ships."""
    from deepspeed_tpu.runtime.comm.quantized import (
        dequantize_chunks as legacy_dq, quantize_chunks as legacy_q)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8 * CHUNK,)).astype(np.float32))
    q, s = encode_chunks(x, CHUNK, "int8")
    ql, sl = legacy_q(x, CHUNK)
    assert np.array_equal(np.asarray(q), np.asarray(ql))
    assert np.array_equal(np.asarray(s), np.asarray(sl))
    assert np.array_equal(np.asarray(decode_chunks(q, s)),
                          np.asarray(legacy_dq(ql, sl)))


@pytest.mark.parametrize("name", ["int8", "f8e4m3fn", "f8e5m2"])
@pytest.mark.parametrize("shape", [(7,), (3, 50), (4, 8, 8)])
def test_wire_roundtrip(name, shape):
    """encode_wire/decode_wire: one u8 buffer of the advertised size,
    decoding back within codec error (zero-padding stays internal)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    wire = encode_wire(x, name, chunk_size=CHUNK)
    assert wire.dtype == jnp.uint8 and wire.ndim == 1
    assert wire.size == wire_nbytes(shape, name, CHUNK)
    back = decode_wire(wire, name, shape, jnp.float32, CHUNK)
    assert back.shape == shape and back.dtype == jnp.float32
    # worst-case per-element error against the chunk absmax: half a
    # quantization step for int8, half a ulp at the top binade for fp8
    qmax = get_codec(name).qmax
    rel = 0.5 / qmax if name == "int8" else _FP8_ERR[name][0]
    bound = float(jnp.max(jnp.abs(x))) * rel + 1e-7
    assert float(jnp.max(jnp.abs(back - x))) <= bound
    zero = jnp.zeros(shape, jnp.float32)
    wz = encode_wire(zero, name, chunk_size=CHUNK)
    assert not np.asarray(
        decode_wire(wz, name, shape, jnp.float32, CHUNK)).any()


# ----------------------------------------- delayed-scaling primitives

def test_compute_scale_bootstrap_and_margin():
    h = init_history(8)
    assert float(compute_scale(h, E4M3_MAX)) == pytest.approx(
        1.0 / E4M3_MAX)
    h = h.at[3].set(100.0)
    assert float(compute_scale(h, E4M3_MAX)) == pytest.approx(
        100.0 / E4M3_MAX)
    assert float(compute_scale(h, E4M3_MAX, margin=2)) == pytest.approx(
        400.0 / E4M3_MAX)


def test_update_history_rolls_amax_in_front():
    h = jnp.arange(1.0, 5.0)
    x = jnp.asarray([[-7.0, 3.0]])
    np.testing.assert_allclose(np.asarray(update_history(h, x)),
                               [7.0, 1.0, 2.0, 3.0])


def test_in_qdq_grad_is_updated_history():
    """Differentiating w.r.t. the history returns the ROLLED history —
    the engine's state update — while x gets the straight-through grad."""
    x = jnp.asarray([1.0, -3.0, 0.5])
    h = init_history(4).at[0].set(2.0)

    def loss(x, h):
        return jnp.sum(in_qdq(x, h) * jnp.asarray([1.0, 2.0, 3.0]))

    (gx, gh) = jax.grad(loss, argnums=(0, 1))(x, h)
    np.testing.assert_allclose(np.asarray(gx), [1.0, 2.0, 3.0])
    np.testing.assert_allclose(np.asarray(gh), [3.0, 2.0, 0.0, 0.0])


def test_out_qdq_backward_quantizes_cotangent():
    """Identity forward; backward qdq-quantizes the cotangent to f8e5m2
    against the delayed scale and returns the cotangent's amax roll-in
    as the history update."""
    y = jnp.asarray([1.0, 2.0])
    cot = jnp.asarray([0.003, -0.021])
    h = init_history(4).at[0].set(0.02)

    def loss(y, h):
        return jnp.sum(out_qdq(y, h) * cot)

    (gy, gh) = jax.grad(loss, argnums=(0, 1))(y, h)
    scale = 0.02 / E5M2_MAX
    want = quantize_dequantize(cot, jnp.float32(scale), E5M2_MAX,
                               jnp.float8_e5m2)
    np.testing.assert_allclose(np.asarray(gy), np.asarray(want))
    np.testing.assert_allclose(np.asarray(gh), [0.021, 0.02, 0.0, 0.0])


def test_fp8_dot_general_scope_routing():
    """No scope -> plain dot (bit-identical); discovery mode records the
    per-site trace-order keys; a site override disables its dots."""
    a = jnp.asarray(np.random.default_rng(4).normal(
        size=(4, 8)).astype(np.float32))
    b = jnp.asarray(np.random.default_rng(5).normal(
        size=(8, 2)).astype(np.float32))
    dn = (((1,), (0,)), ((), ()))
    assert np.array_equal(np.asarray(fp8_dot_general(a, b, dn)),
                          np.asarray(a @ b))
    assert fp8_plan() is None
    plan = Fp8Plan(sites={"skipme": {"enabled": False}})
    keys = []
    with fp8_scope(plan, discover=keys):
        assert fp8_plan() is plan
        fp8_dot_general(a, b, dn, site="dense")
        fp8_dot_general(a, b, dn, site="dense")
        out = fp8_dot_general(a, b, dn, site="skipme")
    assert keys == ["dense:0", "dense:1"]
    assert np.array_equal(np.asarray(out), np.asarray(a @ b))
    assert fp8_plan() is None


def test_fp8_config_validation():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    def cfg(fp8):
        return DeepSpeedConfig({"train_batch_size": 8, "fp8": fp8},
                               world_size=1)

    c = cfg({"enabled": True, "margin": 1, "amax_history_len": 4})
    plan = c.fp8.plan()
    assert plan == Fp8Plan(margin=1, amax_history_len=4, sites={})
    assert c.fp8.active_wire_dtype() is None
    c = cfg({"wire": {"enabled": True, "dtype": "int8"}})
    assert c.fp8.plan() is None and c.fp8.active_wire_dtype() == "int8"
    for bad in ({"enabled": "yes"},
                {"enabled": True, "amax_history_len": 0},
                {"enabled": True, "margin": -1},
                {"wire": {"enabled": True, "dtype": "fp4"}},
                {"enabled": True, "sites": {"dense": {"chunks": 2}}}):
        with pytest.raises(ValueError):
            cfg(bad)
    with pytest.raises(ValueError):
        DeepSpeedConfig(
            {"train_batch_size": 8,
             "fp8": {"wire": {"enabled": True}},
             "comm_quantization": {"enabled": True}}, world_size=1)


# ----------------------------------------------- engine integration

def _fp8_overrides():
    return dict(
        bf16={"enabled": True},
        zero_optimization={"stage": 3, "gather_chunks": 2},
        fp8={"enabled": True,
             "wire": {"enabled": True, "dtype": "f8e4m3fn"}})


def test_fp8_state_discovery_and_amax_convergence():
    """The eval_shape discovery pass finds every GPT-2 Dense dot site;
    training on a fixed batch fills the amax histories with a converged
    (tight-spread) activation range — the delayed scale is live."""
    from tests.model.common import base_gpt2_config, gpt2_train_curve
    steps = 6
    curve, engine = gpt2_train_curve(
        base_gpt2_config(**_fp8_overrides()), steps=steps)
    assert curve[-1] < curve[0]
    state = engine._fp8_state
    sites = {k.split(":")[0] for k in state}
    assert {"c_attn", "c_proj", "c_fc"} <= sites
    for key, bundle in state.items():
        assert set(bundle) == {"in", "kernel", "out"}
        h = np.asarray(bundle["in"])
        assert (h[:steps] > 0).all(), key
        # activations drift as the loss drops, but the per-step amax on a
        # fixed batch stays the same order of magnitude (measured <=1.5x
        # over 6 steps); a blown-up scale would show orders here
        filled = h[h > 0]
        assert filled.max() / filled.min() < 3.0, (key, h)
        assert float(compute_scale(bundle["in"], E4M3_MAX)) > 0


@pytest.mark.slow
def test_fp8_vs_bf16_training_parity_24_steps():
    """fp8 delayed scaling + quantized gather wire must track the bf16
    loss curve — quantization noise, not divergence (measured ~4% max
    pointwise on this fixed-batch toy; pinned at 10%)."""
    from tests.model.common import (assert_curves_close, base_gpt2_config,
                                    gpt2_train_curve)
    bf16, _ = gpt2_train_curve(
        base_gpt2_config(bf16={"enabled": True}), steps=24)
    fp8, _ = gpt2_train_curve(
        base_gpt2_config(**_fp8_overrides()), steps=24)
    assert_curves_close(bf16, fp8, rtol=0.10, name="fp8-vs-bf16")


# ------------------------------------------------- HLO + audit pins

@functools.lru_cache(maxsize=None)
def _lowered_fp8_hlo(fp8_on=True):
    from deepspeed_tpu.analysis.audit import (_engine_fn_args,
                                              build_flavor_engine)
    overrides = None if fp8_on else {"fp8": {"enabled": False}}
    engine, batch = build_flavor_engine("fp8", overrides)
    engine.train_batch(batch)
    fn, args = _engine_fn_args(engine, engine._shard_batch(batch),
                               jax.random.PRNGKey(1),
                               jnp.asarray(1e-3, jnp.float32))
    return fn.lower(*args).compile().as_text()


@pytest.mark.full_compile
def test_fp8_hlo_dtypes_and_wire_ratio_pin():
    """The lowered fp8 step must contain f8e4m3fn forward operands AND
    f8e5m2 backward cotangents, and its ZeRO-3 ring-gather ppermute
    bytes must be <= 0.30x the identical bf16 engine's (1-byte payload
    + per-chunk scales vs the full-precision wire; measured ~0.27x)."""
    from deepspeed_tpu.analysis.hlo import collective_bytes, fp8_value_counts
    hlo_fp8 = _lowered_fp8_hlo()
    hlo_bf16 = _lowered_fp8_hlo(fp8_on=False)
    counts = fp8_value_counts(hlo_fp8)
    e4 = sum(n for dt, n in counts.items() if dt.startswith("f8e4m3"))
    assert e4 > 0, counts
    assert counts.get("f8e5m2", 0) > 0, counts
    assert fp8_value_counts(hlo_bf16) == {}
    ring = collective_bytes(hlo_fp8, by_dtype=True).get(
        "collective-permute", {})
    base = collective_bytes(hlo_bf16, by_dtype=True).get(
        "collective-permute", {})
    assert set(ring) <= {"u8", "s8"}, ring     # quantized wire only
    ratio = sum(ring.values()) / sum(base.values())
    assert ratio <= 0.30, (ratio, ring, base)


@pytest.mark.full_compile
def test_rule_fp8_seeded_violations():
    """fp8-enabled context over a program with NO fp8 values (or no
    quantized wire) must raise the rule's errors; non-fp8 contexts are
    exempt."""
    from deepspeed_tpu.analysis.rules import SEV_ERROR, StepContext, rule_fp8
    plain = ("HloModule m\n"
             "ENTRY e {\n"
             "  p = f32[4,4]{1,0} parameter(0)\n"
             "  a = f32[4,4]{1,0} all-reduce(p), replica_groups={}\n"
             "  ROOT d = f32[4,4]{1,0} dot(p, a)\n"
             "}\n")
    assert rule_fp8(StepContext(hlo_text=plain)) == []
    findings = rule_fp8(StepContext(hlo_text=plain, fp8_enabled=True,
                                    fp8_wire_dtype="f8e4m3fn"))
    assert {f.severity for f in findings} == {SEV_ERROR}
    msgs = " ".join(f.message for f in findings)
    assert "f8e4m3" in msgs and "f8e5m2" in msgs
    assert len(findings) == 3              # no fwd, no bwd, no wire
    # a real fp8 step satisfies the same rule (subset of the flavor
    # audit below, pinned here against the rule in isolation)
    hlo = _lowered_fp8_hlo()
    assert rule_fp8(StepContext(hlo_text=hlo, fp8_enabled=True,
                                fp8_wire_dtype="f8e4m3fn")) == []


@pytest.mark.full_compile
@pytest.mark.slow
def test_audit_fp8_flavor_clean():
    """The stock fp8 flavor — GPT-2-tiny, delayed scaling, quantized
    ZeRO-3 gather wire — audits with zero findings and one compile."""
    from deepspeed_tpu.analysis import audit_engine, build_flavor_engine
    engine, batch = build_flavor_engine("fp8")
    report = audit_engine(engine, batch, steps=2)
    assert report.flavor == "fp8"
    assert report.findings == []
    assert report.stats["compile_cache_size"] == 1

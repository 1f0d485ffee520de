"""Unit tests for the HLO parser core (`deepspeed_tpu/analysis/hlo.py`).

A flat count takes every collective ONCE even when it sits inside a
``while``/``scan`` body; `analysis/hlo.py` accounts trip-count-aware.
These tests pin that against a *real* lowered scan-with-psum program plus
synthetic HLO for the formats jax's CPU lowering doesn't emit (fp8
dtypes, ``backend_config`` trip counts, infeed/outfeed).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.analysis.hlo import (
    collective_bytes,
    computation_multipliers,
    estimate_peak_memory,
    host_transfer_ops,
    input_output_aliases,
    ring_send_bytes,
    split_computations,
    while_loops,
)
from jax import shard_map

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

SCAN_TRIPS = 6
SCAN_WIDTH = 4


def _scan_psum_hlo():
    """Lower a scan whose body carries a psum: one all-reduce in the
    while-loop body, executed SCAN_TRIPS times."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))

    def body(carry, x):
        return carry + jax.lax.psum(x, "d"), jnp.float32(0.0)

    def f(xs):
        out, _ = jax.lax.scan(body, jnp.zeros(xs.shape[1:], jnp.float32),
                              xs)
        return out

    mapped = shard_map(f, mesh=mesh, in_specs=(P(None, "d"),),
                       out_specs=P("d"), check_vma=False)
    xs = jnp.ones((SCAN_TRIPS, SCAN_WIDTH), jnp.float32)
    return jax.jit(mapped).lower(xs).compile().as_text()


def test_scan_body_collectives_weighted_by_trip_count():
    """The historical limitation: a psum inside a 6-trip scan used to
    count once; trip-aware accounting multiplies it by 6."""
    hlo = _scan_psum_hlo()
    flat = collective_bytes(hlo, trip_aware=False)
    aware = collective_bytes(hlo)   # trip-aware is the default now
    assert flat["all-reduce"] > 0
    assert aware["all-reduce"] == SCAN_TRIPS * flat["all-reduce"]
    assert aware["total"] == SCAN_TRIPS * flat["total"]


def test_scan_lowers_to_while_with_known_trip_count():
    hlo = _scan_psum_hlo()
    loops = [l for l in while_loops(hlo) if l["has_collectives"]]
    assert len(loops) == 1
    assert loops[0]["trip_count"] == SCAN_TRIPS
    mults = computation_multipliers(hlo)
    assert mults[loops[0]["body"]] == SCAN_TRIPS


def test_donated_args_appear_in_alias_map():
    @jax.jit
    def f(x, y):
        return x + 1.0, y * 2.0

    donated = jax.jit(lambda x, y: (x + 1.0, y * 2.0),
                      donate_argnums=(0, 1))
    x = jnp.ones((128,)), jnp.ones((128,))
    hlo_plain = f.lower(*x).compile().as_text()
    hlo_don = donated.lower(*x).compile().as_text()
    assert input_output_aliases(hlo_plain) == []
    aliased = {a["param_number"] for a in input_output_aliases(hlo_don)}
    assert aliased == {0, 1}


def test_host_callback_detected_as_host_transfer():
    def on_host(x):
        return np.asarray(x) * 2.0

    @jax.jit
    def f(x):
        return jax.pure_callback(
            on_host, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    hlo = f.lower(jnp.ones((8,))).compile().as_text()
    hits = host_transfer_ops(hlo)
    assert hits, "pure_callback custom-call should register as host transfer"
    assert any(h["kind"] == "host-callback" for h in hits)


# ---------------------------------------------------------------------------
# synthetic HLO: formats the CPU backend doesn't emit
# ---------------------------------------------------------------------------

FP8_SYNTH = """
  %ar8 = f8e4m3fn[1024]{0} all-reduce(f8e4m3fn[1024]{0} %p0)
  %ag8 = f8e5m2[2048]{0} all-gather(f8e5m2[256]{0} %p1)
  %rs8 = f8e4m3b11fnuz[512]{0} reduce-scatter(f8e4m3b11fnuz[4096]{0} %p2)
"""


def test_fp8_dtypes_in_byte_table():
    """fp8 collectives (quantized comm on fp8-capable chips) count at one
    byte per element."""
    v = collective_bytes(FP8_SYNTH)
    assert v["all-reduce"] == 1024
    assert v["all-gather"] == 2048
    assert v["reduce-scatter"] == 512


BACKEND_TRIP_SYNTH = """\
HloModule synth, entry_computation_layout={(f32[64])->f32[64]}

%body.1 (p: f32[64]) -> f32[64] {
  %p = f32[64]{0} parameter(0)
  ROOT %ar = f32[64]{0} all-reduce(f32[64]{0} %p), to_apply=%add
}

%cond.1 (p: f32[64]) -> pred[] {
  %p2 = f32[64]{0} parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: f32[64]) -> f32[64] {
  %a = f32[64]{0} parameter(0)
  ROOT %w = f32[64]{0} while(f32[64]{0} %a), condition=%cond.1, \
body=%body.1, backend_config={"known_trip_count":{"n":"7"}}
}
"""


def test_backend_config_trip_count_parsed():
    loops = while_loops(BACKEND_TRIP_SYNTH)
    assert len(loops) == 1 and loops[0]["trip_count"] == 7
    v = collective_bytes(BACKEND_TRIP_SYNTH)
    assert v["all-reduce"] == 7 * 64 * 4


def test_unknown_trip_count_counts_once_and_is_flagged():
    synth = BACKEND_TRIP_SYNTH.replace(
        ', backend_config={"known_trip_count":{"n":"7"}}', "")
    loops = while_loops(synth)
    assert len(loops) == 1 and loops[0]["trip_count"] is None
    assert loops[0]["has_collectives"]
    # falls back to flat counting rather than dropping the op
    assert collective_bytes(synth)["all-reduce"] == 64 * 4


HEADERLESS_SYNTH = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %p0)
  %cp = f32[256]{0} collective-permute(f32[256]{0} %p1)
"""


def test_headerless_snippet_falls_back_to_flat_scan():
    """Raw op dumps without computation headers (the old module's input
    format) still parse — backward compatibility for existing pins."""
    comps, entry = split_computations(HEADERLESS_SYNTH)
    assert comps == {} and entry is None
    v = collective_bytes(HEADERLESS_SYNTH)
    assert v["all-reduce"] == 4096
    assert v["collective-permute"] == 1024
    rs = ring_send_bytes(HEADERLESS_SYNTH, n_devices=4)
    assert rs["total"] > 0


def test_infeed_outfeed_and_host_transfer_sends_detected():
    synth = """
  %if = (f32[8]{0}, token[]) infeed(token[] %tok)
  %of = token[] outfeed(f32[8]{0} %x, token[] %tok2)
  %snd = (f32[8]{0}, u32[], token[]) send(f32[8]{0} %y, token[] %tok3), \
is_host_transfer=true
"""
    kinds = sorted({h["kind"] for h in host_transfer_ops(synth)})
    assert kinds == ["host-transfer", "infeed", "outfeed"]


# ---------------------------------------------------------------------------
# static peak memory (estimate_peak_memory)
# ---------------------------------------------------------------------------

def _scheduled(fn, *args):
    """Scheduled HLO text: only ``compile().as_text()`` carries the
    ``is_scheduled=true`` line order the liveness walk depends on (the
    pre-compile ``lower().as_text()`` is NOT in execution order)."""
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def _xla_peak(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def test_peak_memory_tracks_buffer_assignment_on_simple_chain():
    """On a straight-line program pure liveness and XLA's buffer
    assignment agree to a few percent."""
    def f(x):
        y = jnp.tanh(x @ x)
        return jnp.sum(y * y)

    compiled, hlo = _scheduled(f, jnp.ones((256, 256), jnp.float32))
    est = estimate_peak_memory(hlo)
    assert est["parameter_bytes"] == 256 * 256 * 4
    assert est["peak_bytes"] >= est["parameter_bytes"]
    ratio = est["peak_bytes"] / max(_xla_peak(compiled), 1)
    assert 0.9 <= ratio <= 1.5, ratio


def test_peak_memory_is_donation_aware():
    """A donated in-place update reuses the argument's buffer: the
    donated lowering's estimate must come in strictly below the
    un-donated one, and the aliased root bytes must be reported."""
    def update(x):
        return x * 0.5 + 1.0

    x = jnp.ones((512, 512), jnp.float32)
    plain = jax.jit(update).lower(x).compile()
    donated = jax.jit(update, donate_argnums=(0,)).lower(x).compile()
    est_plain = estimate_peak_memory(plain.as_text())
    est_don = estimate_peak_memory(donated.as_text())
    assert est_plain["donated_output_bytes"] == 0
    assert est_don["donated_output_bytes"] >= 512 * 512 * 4
    assert est_don["peak_bytes"] < est_plain["peak_bytes"]


def test_peak_memory_while_body_counts_once_not_per_trip():
    """A loop's *footprint* must not scale with its trip count (unlike
    its collective volume): the same body at 2 vs 64 trips peaks the
    same."""
    def loop(trips):
        def f(x):
            def body(c, _):
                return jnp.tanh(c @ c), jnp.float32(0)
            out, _ = jax.lax.scan(body, x, None, length=trips)
            return out
        return f

    x = jnp.ones((128, 128), jnp.float32)
    _, hlo2 = _scheduled(loop(2), x)
    _, hlo64 = _scheduled(loop(64), x)
    e2 = estimate_peak_memory(hlo2)
    e64 = estimate_peak_memory(hlo64)
    assert e2["peak_bytes"] > 0
    # identical body => (near-)identical peak; allow compiler wiggle
    assert e64["peak_bytes"] <= 1.2 * e2["peak_bytes"]


def test_headerless_snippet_peak_is_flat():
    est = estimate_peak_memory(HEADERLESS_SYNTH)
    assert est["peak_bytes"] > 0
    assert est["parameter_bytes"] == 0


def test_peak_memory_orders_dense_above_zero_stages():
    """The ZeRO claim, statically: sharding optimizer state across the
    8-device data axis must lower the per-device static peak — dense >
    ZeRO-1 >= ZeRO-2 >= ZeRO-3 (under 16-bit compute every stage keeps
    the fp32 masters sharded; stage 3 gathers on use what stages 1 and 2
    gather once a step) — and each estimate must sit inside the
    tolerance band of XLA's own buffer assignment (liveness is an upper
    bound; buffer reuse can only push the real number down)."""
    from deepspeed_tpu.analysis.audit import (
        _engine_fn_args, build_flavor_engine)

    peaks, ratios = {}, {}
    for flavor in ("dense", "zero1", "zero2", "zero3"):
        engine, batch = build_flavor_engine(flavor)
        engine.train_batch(batch)
        placed = engine._shard_batch(batch)
        fn, args = _engine_fn_args(
            engine, placed, jax.random.PRNGKey(0),
            jnp.asarray(1e-3, jnp.float32))
        compiled = fn.lower(*args).compile()
        est = estimate_peak_memory(compiled.as_text())
        peaks[flavor] = est["peak_bytes"]
        ratios[flavor] = est["peak_bytes"] / max(_xla_peak(compiled), 1)

    assert peaks["dense"] > peaks["zero1"], peaks
    assert peaks["zero1"] >= peaks["zero2"], peaks
    assert peaks["zero2"] >= peaks["zero3"], peaks
    # dense-family ratios measure ~1.0 on CPU; keep a band wide enough
    # for backend drift but tight enough to catch a broken walk.
    for flavor, r in ratios.items():
        assert 0.8 <= r <= 1.3, (flavor, r, ratios)

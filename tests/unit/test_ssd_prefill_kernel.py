"""The chunked Mamba-2 scan's kernel (`ops/pallas/ssd_prefill.py`, ISSUE
56) in Pallas interpret mode on the CPU, at toy shapes: against the plain
XLA body it replaces (`ops/ssm.py:ssd_chunked_scan_xla`) and against the
recurrence itself, token by token in float64; which calls it takes; and
the prefill span's count of the calls it took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.pallas import ssd_prefill

H, P, N, Q = 8, 16, 16, 8


def inputs(T, groups, dtype, seed=0, state=True, n_valid=None):
    """A call's operands as a mixer makes them: ``x``, ``B``, ``C`` out
    of a SiLU, ``dt`` out of a softplus and 0 on a padded tail, decays
    of e^-0.001 to e^-1.6 a token."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    maps = (T, N) if groups == 1 else (T, groups, N)
    x = jax.nn.silu(jax.random.normal(k[0], (T, H, P))).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2.0)
    if n_valid is not None:
        dt = jnp.where(jnp.arange(T)[:, None] < n_valid, dt, 0.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.77))
    B = jax.nn.silu(jax.random.normal(k[3], maps)).astype(dtype)
    C = jax.nn.silu(jax.random.normal(k[4], maps)).astype(dtype)
    s0 = jax.random.normal(k[5], (H, P, N)) if state else \
        jnp.zeros((H, P, N))
    return x, dt, A, B, C, s0


def recurrence(x, dt, A, B, C, state):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t
    C_t``, float64, a token at a time."""
    x, dt, A, B, C, s = (np.asarray(a.astype(jnp.float32), np.float64)
                         for a in (x, dt, A, B, C, state))
    if B.ndim == 2:
        B, C = B[:, None], C[:, None]
    B, C = (np.repeat(m, H // m.shape[1], axis=1) for m in (B, C))
    y = np.zeros(x.shape)
    for t in range(x.shape[0]):
        s = np.exp(dt[t] * A)[:, None, None] * s + \
            (dt[t][:, None] * x[t])[:, :, None] * B[t][:, None, :]
        y[t] = np.einsum("hpn,hn->hp", s, C[t])
    return y, s


def off(got, want):
    want = np.asarray(want, np.float64)
    return np.abs(np.asarray(got, np.float64) - want).max() / \
        np.abs(want).max()


CASES = {
    # T, groups, incoming state, real tokens (None: all)
    "one-chunk": (Q, 1, True, None),
    "three-chunks": (3 * Q, 1, True, None),
    "groups": (2 * Q, 4, True, None),
    "from-zero": (2 * Q, 2, False, None),
    "ragged-tail": (3 * Q, 1, True, 2 * Q + 3),
    "ragged-tail-groups": (2 * Q, 2, True, 5),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_xla_body_and_the_recurrence(case, dtype):
    T, groups, state, n_valid = CASES[case]
    args = inputs(T, groups, dtype, state=state, n_valid=n_valid)
    assert ssm.ssd_kernel_takes(T, H, P, N, groups, Q, dtype)
    y, s = ssd_prefill.ssd_chunked_scan(*args, Q)
    y_xla, s_xla = ssm.ssd_chunked_scan_xla(*args, Q)
    assert y.dtype == s.dtype == jnp.float32
    assert y.shape == (T, H, P) and s.shape == (H, P, N)
    # the same products in the same dtypes: float32 rounding apart
    assert off(y, y_xla) < 2e-6 and off(s, s_xla) < 2e-6
    y64, s64 = recurrence(*args)
    tol = 1e-2 if dtype == jnp.bfloat16 else 2e-5
    assert off(y, y64) < tol and off(s, s64) < tol
    # and no further from the recurrence than the body it replaces
    assert off(y, y64) < 1.5 * off(y_xla, y64) + 1e-6
    assert off(s, s64) < 1.5 * off(s_xla, s64) + 1e-6


@pytest.mark.parametrize("groups", [2])
def test_row_blocks_meet_the_columns_before_their_end(monkeypatch, groups):
    """A chunk taller than a block of rows (256 against 128 on the chip;
    here 16 against 8) is walked a block at a time, each against the
    columns up to its own last: the same ``y`` and state."""
    monkeypatch.setattr(ssd_prefill, "_ROWS", 8)
    ssd_prefill._scan_call.clear_cache()    # the blocks are read at trace
    try:
        args = inputs(32, groups, jnp.bfloat16, n_valid=27)
        y, s = ssd_prefill.ssd_chunked_scan(*args, 16)
    finally:
        ssd_prefill._scan_call.clear_cache()
    y_xla, s_xla = ssm.ssd_chunked_scan_xla(*args, 16)
    assert off(y, y_xla) < 2e-6 and off(s, s_xla) < 2e-6


def test_padding_moves_nothing():
    """``dt`` 0 on the tail: the state after the call is the state after
    the real tokens, whatever the padded ``x``, ``B`` and ``C`` hold."""
    T, n = 2 * Q, Q + 3
    x, dt, A, B, C, s0 = inputs(T, 2, jnp.float32, n_valid=n)
    _, s = ssd_prefill.ssd_chunked_scan(x, dt, A, B, C, s0, Q)
    junk = x.at[n:].set(7.0), dt, A, B.at[n:].set(-3.0), C, s0
    y_j, s_j = ssd_prefill.ssd_chunked_scan(*junk, Q)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_j))
    _, s64 = recurrence(x[:n], dt[:n], A, B[:n], C[:n], s0)
    assert off(s, s64) < 2e-5


@pytest.mark.parametrize("groups", [1, 4])
def test_a_calls_state_feeds_the_next(groups):
    """Two calls of two chunks against one call of four: the state is
    carried from call to call as from chunk to chunk."""
    x, dt, A, B, C, s0 = inputs(4 * Q, groups, jnp.bfloat16)
    y, s = ssd_prefill.ssd_chunked_scan(x, dt, A, B, C, s0, Q)
    half = 2 * Q
    y1, s1 = ssd_prefill.ssd_chunked_scan(x[:half], dt[:half], A, B[:half],
                                          C[:half], s0, Q)
    y2, s2 = ssd_prefill.ssd_chunked_scan(x[half:], dt[half:], A, B[half:],
                                          C[half:], s1, Q)
    assert off(jnp.concatenate([y1, y2]), y) < 2e-6
    assert off(s2, s) < 2e-6


def test_scan_dispatches_by_shape(monkeypatch):
    """`ssm.ssd_chunked_scan` runs the kernel where it takes the call
    and the XLA body where it does not: a scan chunk that is no whole
    number of sublanes. (Traced, not run.)"""
    ran = []
    monkeypatch.setattr(
        ssd_prefill, "ssd_chunked_scan",
        lambda *a: ran.append("kernel") or ssm.ssd_chunked_scan_xla(*a))

    def shapes(T, chunk):
        return jax.eval_shape(lambda *a: ssm.ssd_chunked_scan(*a, chunk),
                              *inputs(T, 2, jnp.float32))
    shapes(2 * Q, Q)
    assert ran == ["kernel"]
    assert not ssm.ssd_kernel_takes(12, H, P, N, 2, 4, jnp.float32)
    y, s = shapes(12, 4)
    assert ran == ["kernel"] and y.shape == (12, H, P)
    with pytest.raises(ValueError, match="multiple"):
        shapes(12, 8)


@pytest.mark.parametrize("shape,takes", [
    # T, H, P, N, G, chunk: the two serving cells' calls
    ((512, 64, 64, 128, 1, 256), True),
    ((1024, 128, 64, 128, 8, 128), True),
    ((512, 64, 64, 128, 1, 64), False),     # a chunk under a lane tile
    ((512, 64, 64, 64, 1, 256), False),     # a state under a lane tile
    ((512, 8, 64, 128, 8, 256), False),     # one head of 64 a group
    ((384, 64, 64, 128, 1, 256), False),    # no whole chunks
])
def test_what_the_compiled_kernel_takes(monkeypatch, shape, takes):
    """On the chip the blocks must be whole tiles (here the chip is
    stood in for, as `test_tpu_compile.py` does)."""
    from tests.unit.test_tpu_compile import _compiled_not_interpreted
    _compiled_not_interpreted(monkeypatch,
                              "deepspeed_tpu.ops.pallas.ssd_prefill")
    assert ssm.ssd_kernel_takes(*shape, jnp.bfloat16) == takes
    if takes:
        T, H_, P_, N_, G, chunk = shape
        hb = ssd_prefill.head_block(H_, G, P_, N_, chunk, 2)
        assert hb == 16 and (H_ // G) % hb == 0


# --- the prefill span's counter ---------------------------------------------

CHUNK, PAGE, SEQ = 16, 8, 64


@pytest.mark.parametrize("scan_chunk,taken", [(8, True), (4, False)])
def test_prefill_span_counts_the_scans_the_kernel_took(scan_chunk, taken):
    """Chunk calls times mixers, all of them through the kernel or none:
    a toy hybrid whose scan chunk is whole sublanes, and one whose is
    not."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models import granite_hybrid as gh
    from deepspeed_tpu.telemetry import spans

    cfg = gh.granite_hybrid_tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                                 mamba_chunk_size=scan_chunk)
    model = gh.GraniteHybridLM(cfg)
    params = gh.init_granite_hybrid_params(model, jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, config={
        "max_batch": 2, "seq_buckets": (SEQ,), "prefill_chunk": CHUNK,
        "page_size": PAGE, "attention_block_k": PAGE})
    t0 = spans.clock()
    eng.prefill(0, list(range(1, 38)), np.arange(1, SEQ // PAGE + 1))
    attrs = [r for r in spans.recent(t0) if r[0] == "prefill"][-1][3]
    mixers = len(cfg.names(gh.MAMBA))
    assert attrs["chunks"] == 3 and mixers == 4
    assert attrs["ssd_scan_calls"] == 3 * mixers
    assert attrs["ssd_scan_calls_kernel"] == (3 * mixers if taken else 0)
    # and the program is what the counter says: the kernel's jitted
    # call, traced once for the four mixers, or no such function
    text = eng._prefill.lower(*eng.prefill_lowering_args()).as_text()
    assert text.count("func.func private @_scan_call") == int(taken)

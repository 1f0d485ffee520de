"""The band of a window layer's prefill chunk as one Pallas kernel
(`ops/pallas/window_prefill.py`, ISSUE 52), in interpret mode at small
geometries: against XLA's band (``impl="dense"``, the parity oracle) and
against a float32 ``jax.numpy`` reference a head at a time that knows no
ring, no block and no running softmax: the whole history under the mask
``0 <= t - j < window``. ``window - 1`` and ``window + 1`` must fail the
tolerance that ``window`` passes. The call site's rule (the kernel where
the window is longer than its smallest query block:
`cache.band_kernel_takes`) is tested last and lifted for the rest, so
that small windows reach the kernel here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import cache as kvc
from deepspeed_tpu.ops.pallas import KernelGeometryError, window_prefill_band
from deepspeed_tpu.ops.pallas import window_prefill as wp

# largest absolute error against the float32 reference, values of unit
# scale in bfloat16: sound readings 0.001-0.016 (the kernel, XLA's band,
# one against the other), a window off by one 0.18 or more
TOL = 0.025
PAGE = 8


@pytest.fixture
def every_window(monkeypatch):
    """``impl="flash"`` reaches the kernel whatever the window."""
    monkeypatch.setattr(kvc, "band_kernel_takes",
                        lambda impl, window: impl == "flash")


# name: T, window, H, G, D, Dv, c0, n_valid, sink
CASES = {
    "nine_queries_a_key_head": (256, 128, 1, 9, 128, 128, 512, 256, False),
    "six_queries_a_key_head": (256, 128, 2, 6, 128, 128, 256, 256, False),
    "sink_keys_192_values_128": (256, 128, 2, 2, 192, 128, 256, 256, True),
    "first_chunk": (256, 128, 2, 3, 128, 128, 0, 256, False),
    "first_chunk_sink": (128, 128, 1, 2, 64, 32, 0, 128, True),
    "ring_wrapped": (128, 64, 2, 3, 64, 64, 896, 128, False),
    "ragged_tail": (256, 128, 1, 4, 128, 128, 256, 139, False),
    "ragged_tail_first_chunk": (256, 64, 1, 2, 64, 64, 0, 77, True),
    "window_does_not_divide_chunk": (96, 64, 2, 2, 32, 32, 192, 96, False),
    "window_longer_than_chunk": (64, 160, 1, 2, 32, 32, 320, 64, False),
    "window_no_multiple_of_a_tile": (256, 100, 1, 2, 128, 128, 256, 256,
                                     True),
}


def history(T, W, H, G, D, Dv, c0, sink, seed=0, dtype=jnp.bfloat16):
    """A row's keys and values from position 0 to the chunk's end, the
    chunk's queries, and the ring as the engine would hold it before the
    chunk is written: position ``p < c0`` at entry ``p`` modulo the
    ring's span (the last one written there wins)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n = c0 + T
    q = jax.random.normal(ks[0], (T, H * G, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (n, H, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (n, H, Dv), jnp.float32).astype(dtype)
    sk = 2.0 + jax.random.normal(ks[3], (H * G,), jnp.float32) \
        if sink else None
    ring_pages = W // PAGE + 1
    span = ring_pages * PAGE
    # a pool of the ring's pages behind a trash page and a stranger's,
    # holding noise where the prompt has written nothing
    pool = {}
    for name, full, d in (("k", k, D), ("v", v, Dv)):
        held = np.asarray(jax.random.normal(
            ks[3], (span, H, d), jnp.float32)) * 3.0
        for p in range(c0):
            held[p % span] = np.asarray(full[p], np.float32)
        pages = held.reshape(ring_pages, PAGE, H, d).transpose(0, 2, 3, 1)
        noise = np.asarray(jax.random.normal(ks[0], (2, H, d, PAGE)))
        pool[name] = jnp.asarray(np.concatenate([noise, pages]), dtype)
    ring = jnp.arange(2, 2 + ring_pages, dtype=jnp.int32)[None]
    return q, k, v, sk, pool, ring


def reference(q, k, v, c0, window, scale, sink):
    """float32, a head at a time, over the whole history."""
    T, Hq, _ = q.shape
    G = Hq // k.shape[1]
    t = c0 + np.arange(T)[:, None]
    j = np.arange(k.shape[0])[None]
    seen = (t - j >= 0) & (t - j < window)
    out = []
    for h in range(Hq):
        s = np.asarray(q[:, h], np.float32) @ \
            np.asarray(k[:, h // G], np.float32).T * scale
        s = np.where(seen, s, -np.inf)
        if sink is not None:
            s = np.concatenate([s, np.full((T, 1), float(sink[h]))], 1)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        out.append(p[:, :k.shape[0]] @ np.asarray(v[:, h // G], np.float32))
    return np.stack(out, 1)


def attend(case, impl, window=None, dtype=jnp.bfloat16):
    T, W, H, G, D, Dv, c0, n_valid, sink = CASES[case]
    q, k, v, sk, pool, ring = history(T, W, H, G, D, Dv, c0, sink,
                                      dtype=dtype)
    positions = (c0 + jnp.arange(T, dtype=jnp.int32))[None]
    y = kvc.window_prefill_attention(
        q[None], k[None, c0:], v[None, c0:], pool, positions, ring,
        window=window or W, scale=D ** -0.5, compute_dtype=dtype, sink=sk,
        n_valid=jnp.asarray([n_valid], jnp.int32), impl=impl)
    assert y.shape == (1, T, H * G, Dv) and y.dtype == dtype
    want = reference(q, k, v, c0, W, D ** -0.5, sk)
    return np.asarray(y[0], np.float32), want, n_valid


def worst(y, want, n_valid):
    return float(np.abs(y[:n_valid] - want[:n_valid]).max())


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_band(every_window, case):
    """The kernel against the reference and against XLA's band, on the
    chunk's real tokens; both within the tolerance of the reference, and
    of one another."""
    y, want, n_valid = attend(case, "flash")
    dense, _, _ = attend(case, "dense")
    assert np.isfinite(y).all()
    assert worst(y, want, n_valid) < TOL
    assert worst(dense, want, n_valid) < TOL
    assert worst(y, dense, n_valid) < TOL


@pytest.mark.parametrize("case", ["nine_queries_a_key_head", "ring_wrapped",
                                  "first_chunk_sink",
                                  "window_no_multiple_of_a_tile"])
def test_kernel_in_float32_is_the_reference(every_window, case):
    """The same walk on float32 operands (interpret mode multiplies them
    exactly): what is left is the order of the sums."""
    y, want, n_valid = attend(case, "flash", dtype=jnp.float32)
    assert worst(y, want, n_valid) < 2e-5


@pytest.mark.parametrize("off", [-1, 1])
@pytest.mark.parametrize("case", ["nine_queries_a_key_head",
                                  "sink_keys_192_values_128",
                                  "ring_wrapped"])
def test_a_window_off_by_one_fails_the_tolerance(every_window, case, off):
    T, W = CASES[case][:2]
    if off > 0:
        # the ring of a window one longer holds one position more: the
        # kernel alone, handed that position
        _, W, H, G, D, Dv, c0, n_valid, sink = CASES[case]
        q, k, v, sk, _, _ = history(T, W, H, G, D, Dv, c0, sink)
        y = window_prefill_band(
            q, k[c0 - W - 1:c0], v[c0 - W - 1:c0], k[c0:], v[c0:], c0,
            n_valid, window=W + 1, scale=D ** -0.5, sink=sk)
        want = reference(q, k, v, c0, W, D ** -0.5, sk)
        y = np.asarray(y, np.float32)
    else:
        y, want, n_valid = attend(case, "flash", window=W - 1)
    assert worst(y, want, n_valid) > 2 * TOL


def test_rows_behind_the_real_tokens_come_back_zero_by_the_block(
        every_window):
    """A block of queries wholly behind ``n_valid`` is not attended: the
    ragged case's second block of 128 is zero, and the rows behind the
    real ones in the first block are finite."""
    y, _, n_valid = attend("ragged_tail", "flash")
    assert n_valid < wp.QUERY_BLOCK * 2 < y.shape[0] + 1
    assert np.isfinite(y).all()
    assert not y[wp.QUERY_BLOCK * 2:].any()
    assert y[:wp.QUERY_BLOCK * 2].any(-1).any(-1).all()


def test_block_shapes_follow_from_the_geometry():
    bf16 = jnp.bfloat16
    # Laguna's window layers: 128 queries of 9 heads over their 640 keys
    assert wp.band_blocks(1024, 512, 9, 128, 128, bf16) == (128, 512, 640)
    # MiMo's: 128 queries of 8 heads over 256 keys of 192
    assert wp.band_blocks(1024, 128, 8, 192, 128, bf16) == (128, 128, 256)
    # a window off a tile's edge is padded in front
    assert wp.band_blocks(1024, 511, 9, 128, 128, bf16) == (128, 512, 640)
    assert wp.band_blocks(1024, 513, 9, 128, 128, bf16) == (128, 640, 768)
    # a chunk that 128 does not divide goes whole, on sublane tiles
    assert wp.band_blocks(96, 60, 2, 32, 32, bf16) == (96, 64, 160)
    # a window whose scores do not fit one block walks several
    bq, front, bk = wp.band_blocks(1024, 8192, 9, 128, 128, bf16)
    assert (bq, front) == (128, 8192) and (bq + front) % bk == 0
    assert bk < bq + front and bk % 128 == 0
    with pytest.raises(KernelGeometryError):
        wp.band_blocks(100, 64, 2, 32, 32, bf16)        # no sublane tile
    with pytest.raises(KernelGeometryError):
        wp.band_blocks(1024, 2 ** 20, 9, 128, 128, bf16)


def test_a_walk_of_several_blocks_is_the_band(every_window, monkeypatch):
    """Blocks smaller than a query block's keys: the running max and sum
    carry from block to block, a first chunk's blocks before the prompt
    are skipped."""
    real = wp.band_blocks

    def small(T, window, G, D, Dv, dtype):
        bq, front, _ = real(T, window, G, D, Dv, dtype)
        return bq, front, 64

    monkeypatch.setattr(wp, "band_blocks", small)
    wp._band_call.clear_cache()
    try:
        for case in ("six_queries_a_key_head", "first_chunk_sink",
                     "ragged_tail"):
            y, want, n_valid = attend(case, "flash")
            assert worst(y, want, n_valid) < TOL, case
    finally:
        wp._band_call.clear_cache()


def test_refuses_what_it_cannot_read():
    q, k, v, sk, _, _ = history(64, 32, 2, 2, 32, 32, 64, False)
    with pytest.raises(ValueError, match="window_prefill_band takes"):
        window_prefill_band(q, k[:31], v[:32], k[64:], v[64:], 64, 64,
                            window=32, scale=1.0)


def test_the_kernel_takes_a_window_longer_than_its_query_block():
    """The call site's rule: ``impl="flash"`` reaches the kernel where
    the window is longer than the kernel's smallest query block, and
    XLA's band (the same program as ``"dense"``) where it is not."""
    assert wp.QUERY_BLOCK == 128
    assert [kvc.band_kernel_takes("flash", w)
            for w in (8, 128, 129, 512)] == [False, False, True, True]
    assert not kvc.band_kernel_takes("dense", 512)

    def kernels(window, impl):
        T, H, G, D = 128, 1, 2, 32
        q, k, v, _, pool, ring = history(T, window, H, G, D, D, T, False)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, pool: kvc.window_prefill_attention(
                q, k, v, pool, (T + jnp.arange(T, dtype=jnp.int32))[None],
                ring, window=window, scale=1.0, compute_dtype=q.dtype,
                impl=impl))(q[None], k[None, T:], v[None, T:], pool)
        return str(jaxpr).count("pallas_call")

    assert kernels(136, "flash") == 1
    assert kernels(128, "flash") == kernels(136, "dense") == 0

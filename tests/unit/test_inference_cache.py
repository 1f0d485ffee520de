"""Paged KV cache pins (`deepspeed_tpu/inference/cache.py`).

Pure cache-op tests — no model compiles: spec resolution, zero init of
the unrolled, stacked and quantized pools, quantized storage roundtrip
error bounds through the shared codec registry, positioned writes/reads
through a page table (including a recycled page's overwrite) and the
causal position mask against a dense reference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.cache import (
    KVCacheSpec,
    _dequantize,
    _quantize,
    cache_dtype_census,
    cached_attention,
    init_kv_cache,
    kv_cache_nbytes,
    kv_partition_specs,
    paged_read_kv,
    paged_write_kv,
    payload_shape,
    spec_for_model,
)
from deepspeed_tpu.models.gpt2 import GPT2Config


def _spec(**kw):
    kw.setdefault("n_layer", 2)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq", 16)
    kw.setdefault("n_head", 2)
    kw.setdefault("head_dim", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("n_pages", kw["max_batch"] * kw["max_seq"]
                  // kw["page_size"] + 1)
    return KVCacheSpec(**kw)


def _tables(spec):
    """Row ``b`` owns pages ``b * pages_per_row + 1 ...`` in order: the
    pool read back through them is the contiguous ``[B, S, H, D]``."""
    ppr = spec.pages_per_row
    return jnp.asarray(1 + np.arange(spec.max_batch * ppr, dtype=np.int32)
                       .reshape(spec.max_batch, ppr))


def _cfg(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("n_positions", 32)
    kw.setdefault("n_embd", 8)
    kw.setdefault("n_layer", 2)
    kw.setdefault("n_head", 2)
    return GPT2Config(**kw)


class TestSpecResolution:
    def test_default_dtype_follows_model(self):
        spec = spec_for_model(_cfg(dtype=jnp.float32), 2, 16, page_size=4)
        assert spec.dtype == jnp.float32 and spec.codec is None
        assert (spec.n_layer, spec.max_batch, spec.max_seq) == (2, 2, 16)
        assert spec.head_dim == 4 and not spec.stacked
        # every row at full length, plus the trash page
        assert (spec.page_size, spec.n_pages, spec.pages_per_row) == \
            (4, 9, 4)

    def test_explicit_dtypes_and_codecs(self):
        cfg = _cfg(dtype=jnp.float32)
        assert spec_for_model(cfg, 2, 16, "bf16", 4).dtype == jnp.bfloat16
        assert spec_for_model(cfg, 2, 16, "f32", 4).dtype == jnp.float32
        s = spec_for_model(cfg, 2, 16, "int8", 4)
        assert s.codec == "int8" and s.dtype == jnp.int8
        s = spec_for_model(cfg, 2, 16, "f8e4m3fn", 4)
        assert s.codec == "f8e4m3fn" and s.dtype == jnp.float8_e4m3fn

    def test_scan_layers_sets_stacked(self):
        assert spec_for_model(_cfg(scan_layers=True), 2, 16,
                              page_size=4).stacked

    def test_unknown_dtype_rejected(self):
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            spec_for_model(_cfg(), 2, 16, "e5m2", 4)

    def test_seq_past_n_positions_rejected(self):
        with pytest.raises(ValueError, match="n_positions"):
            spec_for_model(_cfg(n_positions=8), 2, 16, page_size=4)

    @pytest.mark.parametrize("page_size", [0, 5, 32])
    def test_a_spec_always_has_a_page_size(self, page_size):
        with pytest.raises(ValueError, match="page_size"):
            spec_for_model(_cfg(), 2, 16, page_size=page_size)


class TestInitAndFacts:
    def test_unrolled_layout(self):
        spec = _spec(dtype=jnp.float32)
        cache = init_kv_cache(spec)
        assert sorted(cache) == ["h_0", "h_1"]
        # [n_pages, H, D, page_size]: positions minor-most
        assert payload_shape(spec) == (9, 2, 4, 4)
        assert cache["h_0"]["k"].shape == payload_shape(spec)
        assert cache["h_0"]["v"].dtype == jnp.float32
        assert "k_scale" not in cache["h_0"]
        # 2 layers x 2 buffers x 9 pages x 2*4*4 f32
        assert kv_cache_nbytes(cache) == 2 * 2 * 9 * 2 * 4 * 4 * 4

    def test_stacked_layout(self):
        cache = init_kv_cache(_spec(stacked=True, n_layer=3))
        assert sorted(cache) == ["h"]
        assert cache["h"]["k"].shape == (3, 9, 2, 4, 4)

    def test_quantized_layout_adds_scales(self):
        cache = init_kv_cache(_spec(dtype=jnp.int8, codec="int8"))
        layer = cache["h_0"]
        assert layer["k"].dtype == jnp.int8
        # one scale per (page, head, position): the payload less head_dim
        assert layer["k_scale"].shape == (9, 2, 4)
        assert layer["k_scale"].dtype == jnp.float32

    def test_default_pool_holds_every_row_at_full_length(self):
        """`n_pages` unset: `max_batch * max_seq` positions of K and V a
        layer (what a per-row buffer would hold) and one trash page."""
        spec = spec_for_model(_cfg(dtype=jnp.float32), 3, 16, page_size=8)
        assert spec.n_pages == 3 * 2 + 1
        cache = init_kv_cache(spec)
        per_position = 2 * spec.n_head * spec.head_dim * 4
        assert kv_cache_nbytes(cache) == spec.n_layer * per_position * (
            3 * 16 + spec.page_size)

    def test_census_excludes_scales(self):
        cache = init_kv_cache(_spec(dtype=jnp.int8, codec="int8"))
        assert cache_dtype_census(cache) == {"int8": 4}
        cache = init_kv_cache(_spec(dtype=jnp.bfloat16, stacked=True))
        assert cache_dtype_census(cache) == {"bfloat16": 2}

    def test_partition_specs_match_structure(self):
        spec = _spec(dtype=jnp.int8, codec="int8")
        ps = kv_partition_specs(spec)
        tree_paths = jax.tree_util.tree_structure(ps)
        cache_paths = jax.tree_util.tree_structure(init_kv_cache(spec))
        assert tree_paths == cache_paths
        # heads are the pool's axis 1, with no trailing None after them
        assert tuple(ps["h_0"]["k"]) == (None, "model")
        assert tuple(ps["h_0"]["k_scale"]) == (None, "model")
        stacked = kv_partition_specs(_spec(stacked=True))
        assert tuple(stacked["h"]["k"]) == (None, None, "model")


class TestQuantization:
    @pytest.mark.parametrize("codec,rtol", [("int8", 1 / 127),
                                            ("f8e4m3fn", 2 ** -3),
                                            ("f8e5m2", 2 ** -2)])
    def test_roundtrip_error_bounded(self, codec, rtol):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(2, 8, 2, 4)), jnp.float32)
        q, scale = _quantize(x, codec)
        back = _dequantize(q, scale, jnp.float32)
        absmax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
        assert np.all(np.abs(np.asarray(back) - np.asarray(x))
                      <= rtol * absmax + 1e-7)

    def test_zero_vector_roundtrips_exactly(self):
        x = jnp.zeros((1, 2, 1, 4), jnp.float32)
        q, scale = _quantize(x, "int8")
        assert np.all(np.asarray(scale) == 0.0)
        assert np.all(np.asarray(_dequantize(q, scale, jnp.float32)) == 0)


class TestWriteRead:
    def test_positioned_write_roundtrip(self):
        spec = _spec(dtype=jnp.float32)
        layer = init_kv_cache(spec)["h_0"]
        tables = _tables(spec)
        rng = np.random.default_rng(1)
        k = jnp.asarray(rng.normal(size=(2, 4, 2, 4)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 4, 2, 4)), jnp.float32)
        # row 0 writes at 0..3, row 1 at 8..11
        pos = jnp.asarray([[0, 1, 2, 3], [8, 9, 10, 11]], jnp.int32)
        layer = paged_write_kv(layer, k, v, pos, tables)
        kf, vf = paged_read_kv(layer, tables, jnp.float32)
        assert np.array_equal(np.asarray(kf[0, 0:4]), np.asarray(k[0]))
        assert np.array_equal(np.asarray(kf[1, 8:12]), np.asarray(k[1]))
        assert np.all(np.asarray(kf[0, 4:]) == 0)
        assert np.all(np.asarray(vf[1, :8]) == 0)
        # row 1's positions 8..11 are its third page, and only that
        assert np.array_equal(
            np.asarray(layer["k"][int(tables[1, 2])]),
            np.asarray(k[1]).transpose(1, 2, 0))
        assert np.all(np.asarray(layer["k"][int(tables[1, 0])]) == 0)

    def test_overwrite_replaces_a_pages_previous_tenant(self):
        spec = _spec(dtype=jnp.float32)
        layer = init_kv_cache(spec)["h_0"]
        tables = _tables(spec)
        ones = jnp.ones((2, 4, 2, 4), jnp.float32)
        pos = jnp.asarray([[0, 1, 2, 3]] * 2, jnp.int32)
        layer = paged_write_kv(layer, ones, ones, pos, tables)
        twos = 2.0 * ones
        # the rows swap pages: each page gets a new tenant
        layer = paged_write_kv(layer, twos, twos, pos, tables[::-1])
        kf, _ = paged_read_kv(layer, tables, jnp.float32)
        assert np.all(np.asarray(kf[:, :4]) == 2.0)

    def test_quantized_write_read(self):
        spec = _spec(dtype=jnp.int8, codec="int8")
        layer = init_kv_cache(spec)["h_0"]
        tables = _tables(spec)
        rng = np.random.default_rng(2)
        k = jnp.asarray(rng.normal(size=(2, 4, 2, 4)), jnp.float32)
        pos = jnp.asarray([[4, 5, 6, 7]] * 2, jnp.int32)
        layer = paged_write_kv(layer, k, k, pos, tables)
        kf, vf = paged_read_kv(layer, tables, jnp.float32)
        absmax = np.max(np.abs(np.asarray(k)), axis=-1, keepdims=True)
        assert np.all(np.abs(np.asarray(kf[:, 4:8]) - np.asarray(k))
                      <= absmax / 127 + 1e-7)


class TestCachedAttention:
    def test_matches_dense_causal_reference(self):
        """One full-prefix call must reproduce plain causal attention."""
        B, T, H, D = 2, 6, 2, 4
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        spec = _spec(dtype=jnp.float32, max_seq=8)
        layer = init_kv_cache(spec)["h_0"]
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        y, _ = cached_attention(q, k, v, layer, pos, jnp.float32,
                                _tables(spec))

        qn, kn, vn = (np.asarray(a).transpose(0, 2, 1, 3)
                      for a in (q, k, v))       # [B, H, T, D]
        att = qn @ kn.transpose(0, 1, 3, 2) / np.sqrt(D)
        mask = np.tril(np.ones((T, T), bool))
        att = np.where(mask, att, -np.inf)
        att = np.exp(att - att.max(-1, keepdims=True))
        att /= att.sum(-1, keepdims=True)
        ref = (att @ vn).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(y), ref, atol=1e-5)

    def test_stale_slots_are_masked(self):
        """Junk beyond the live prefix must not leak into attention."""
        B, H, D = 1, 2, 4
        spec = _spec(dtype=jnp.float32, max_batch=1, max_seq=8)
        layer = init_kv_cache(spec)["h_0"]
        tables = _tables(spec)
        poison = 1e6 * jnp.ones((B, 4, H, D), jnp.float32)
        layer = paged_write_kv(layer, poison, poison,
                               jnp.asarray([[4, 5, 6, 7]], jnp.int32),
                               tables)
        rng = np.random.default_rng(4)
        q = jnp.asarray(rng.normal(size=(B, 2, H, D)), jnp.float32)
        kv = jnp.asarray(rng.normal(size=(B, 2, H, D)), jnp.float32)
        pos = jnp.asarray([[0, 1]], jnp.int32)
        y_poisoned, _ = cached_attention(q, kv, kv, layer, pos,
                                         jnp.float32, tables)
        clean = init_kv_cache(spec)["h_0"]
        y_clean, _ = cached_attention(q, kv, kv, clean, pos, jnp.float32,
                                      tables)
        assert np.array_equal(np.asarray(y_poisoned),
                              np.asarray(y_clean))

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


class TestFlashDecodeStepWrites:
    """A flash decode step (``T == 1``) makes its write inside the
    kernel (PR 33): `cached_attention` calls no `paged_write_kv` for it.
    The dense decode step, which keeps the loop, is the oracle: output
    and the whole pool."""

    # (codec, tolerance on the output: the dense path dequantizes the
    # pool to compute dtype, the kernel rescales scores and weights)
    STORAGE = [(None, 2e-6), ("int8", 2e-6), ("f8e4m3fn", 2e-6)]

    @staticmethod
    def _step(codec, group=1, max_batch=4, seed=5):
        from deepspeed_tpu.runtime.comm.codecs import CODECS
        dtype = jnp.float32 if codec is None else CODECS[codec].dtype
        spec = _spec(dtype=dtype, codec=codec, max_batch=max_batch,
                     max_seq=16, page_size=8)
        rng = np.random.default_rng(seed)
        H, D = spec.n_head, spec.head_dim
        layer = init_kv_cache(spec)["h_0"]
        tables = np.asarray(_tables(spec)).copy()
        # rows 0 and 3 hold requests (8 and 3 tokens in), rows 1 and 2
        # none: position 0, an all-trash table
        tables[1:3] = 0
        fill = jnp.asarray(rng.normal(size=(max_batch, 8, H, D)),
                           jnp.float32)
        for b, n in ((0, 8), (3, 3)):
            layer = paged_write_kv(
                layer, fill[b:b + 1, :n], fill[b:b + 1, :n],
                jnp.arange(n, dtype=jnp.int32)[None], tables[b:b + 1])
        q = jnp.asarray(rng.normal(size=(max_batch, 1, H * group, D)),
                        jnp.float32)
        k, v = (jnp.asarray(rng.normal(size=(max_batch, 1, H, D)),
                            jnp.float32) for _ in "kv")
        pos = jnp.asarray([[8], [0], [0], [3]], jnp.int32)
        return q, k, v, layer, pos, jnp.asarray(tables)

    @pytest.mark.parametrize("block_k", [8, 4], ids=["block=page",
                                                     "block<page"])
    @pytest.mark.parametrize("codec,atol", STORAGE)
    @pytest.mark.parametrize("group", [1, 4])
    def test_flash_step_equals_dense_step(self, group, codec, atol,
                                          block_k):
        q, k, v, layer, pos, tables = self._step(codec, group)
        y_d, dense = cached_attention(q, k, v, layer, pos, jnp.float32,
                                      tables)
        y_f, flash = cached_attention(q, k, v, layer, pos, jnp.float32,
                                      tables, impl="flash",
                                      block_k=block_k)
        live = [0, 3]
        np.testing.assert_allclose(np.asarray(y_f)[live],
                                   np.asarray(y_d)[live], atol=atol)
        assert not np.asarray(y_f)[[1, 2]].any()
        assert set(flash) == set(layer)
        for name in layer:
            # the whole pool but the trash page, where the loop puts the
            # rows without a request and the kernel puts nothing
            np.testing.assert_array_equal(np.asarray(flash[name])[1:],
                                          np.asarray(dense[name])[1:],
                                          err_msg=name)
            np.testing.assert_array_equal(np.asarray(flash[name])[0],
                                          np.asarray(layer[name])[0])
        # row 0 began a page (position 8 is its second page's lane 0)
        assert np.asarray(flash["k"])[int(tables[0, 1]), ..., 0].any()

    def test_the_flash_step_traces_no_loop_and_no_slab_write(self):
        """What went: the 48-slab loop, two leaves a layer. The dense
        step and a verify chunk (``B > 1, T > 1``) keep it."""
        q, k, v, layer, pos, tables = self._step(None)

        def prims(fn, *args):
            """Primitives of the traced program, the bodies of nested
            jits included, a kernel's own body not."""
            out, todo = set(), [jax.make_jaxpr(fn)(*args).jaxpr]
            while todo:
                for eqn in todo.pop().eqns:
                    out.add(eqn.primitive.name)
                    if eqn.primitive.name != "pallas_call":
                        todo += [getattr(p, "jaxpr", p)
                                 for p in eqn.params.values()
                                 if hasattr(getattr(p, "jaxpr", p), "eqns")]
            return out

        def step(**kw):
            return prims(lambda *a: cached_attention(
                *a, jnp.float32, tables, **kw), q, k, v, layer, pos)
        def loops(ps):      # a `fori_loop` of known length traces to a scan
            return bool({"scan", "while"} & ps) and \
                "dynamic_update_slice" in ps

        flash = step(impl="flash", block_k=8)
        assert "pallas_call" in flash and not loops(flash)
        assert not {"scan", "while", "dynamic_update_slice"} & flash
        assert loops(step()) and "pallas_call" not in step()
        chunk = jnp.concatenate([pos, pos + 1], 1)
        verify = prims(lambda q, k, v: cached_attention(
            q, k, v, layer, chunk, jnp.float32, tables, impl="flash",
            block_k=8), *(jnp.tile(x, (1, 2, 1, 1)) for x in (q, k, v)))
        assert loops(verify) and "pallas_call" not in verify

    def test_write_tokens_has_the_callers_it_is_kept_for(self):
        """`_write_tokens` stays for speculative verify and the dense
        decode step; the flash decode arm must not reach it."""
        import inspect

        from deepspeed_tpu.inference import cache
        assert "_write_tokens(" not in inspect.getsource(
            cache._flash_attend_paged)
        src = inspect.getsource(cache.cached_attention)
        assert src.index("_flash_attend_paged(") < src.index(
            "paged_write_kv(")
        callers = [name for name, fn in inspect.getmembers(
            cache, inspect.isfunction)
            if "_write_tokens(" in inspect.getsource(fn)
            and name != "_write_tokens"]
        assert callers == ["paged_write_kv"]


# ---------------------------------------------------------------------------
# groups of page layers: heads of their own, values narrower than keys, a
# window over a ring (ISSUE 47)
# ---------------------------------------------------------------------------

def _two_groups(**kw):
    from deepspeed_tpu.inference.cache import page_pool_spec
    kw.setdefault("groups", (("full", ("f0", "f1"), 2, 24, 16, 0),
                             ("window", ("w0",), 4, 24, 16, 16)))
    return page_pool_spec(3, 64, n_layer=3, n_head=2, head_dim=24,
                          compute_dtype=jnp.float32, n_positions=64,
                          page_size=8, **kw)


def test_two_groups_in_one_spec():
    from deepspeed_tpu.inference.cache import page_pool_spec, payload_shape
    spec = _two_groups()
    full, window = spec.page_groups
    assert (spec.n_layer, spec.layers) == (3, ("f0", "f1", "w0"))
    assert (spec.pages_per_row, spec.ring_pages, spec.table_width) == \
        (8, 3, 11)
    assert full.n_pages == 3 * 8 + 1 and window.n_pages == 3 * 3 + 1
    cache = init_kv_cache(spec)
    assert {n: (l["k"].shape, l["v"].shape) for n, l in cache.items()} == {
        "f0": ((25, 2, 24, 8), (25, 2, 16, 8)),
        "f1": ((25, 2, 24, 8), (25, 2, 16, 8)),
        "w0": ((10, 4, 24, 8), (10, 4, 16, 8))}
    assert payload_shape(spec, window, "v") == (10, 4, 16, 8)
    assert kv_cache_nbytes(cache) == 4 * 8 * (
        2 * 25 * 2 * 40 + 10 * 4 * 40)
    assert full.bytes_per_token(2) == 2 * 2 * 40 * 2
    # a spec that lists no group is its one group, as it ever was
    plain = page_pool_spec(3, 64, n_layer=2, n_head=2, head_dim=24,
                           compute_dtype=jnp.float32, n_positions=64,
                           page_size=8)
    (one,) = plain.page_groups
    assert (one.layers, one.n_head, one.head_dim, one.v_dim, one.window,
            one.n_pages) == (("h_0", "h_1"), 2, 24, 24, 0, 25)
    assert plain.groups == () and plain.ring_pages == 0 and \
        plain.table_width == plain.pages_per_row == 8


@pytest.mark.parametrize("kw, match", [
    ({"groups": (("a", ("x",), 2, 24, 16, 16), ("b", ("y",), 2, 24, 16, 8))},
     "one window"),
    ({"groups": (("a", ("x",), 2, 24, 16, 12),)}, "whole number of pages"),
    ({"groups": (("a", ("x",), 2, 16, 24, 0),)}, "no wider than its keys"),
    ({"kv_cache_dtype": "int8"}, "plain storage"),
    ({"stacked": True}, "plain storage"),
])
def test_groups_refuse_what_they_do_not_hold(kw, match):
    with pytest.raises(ValueError, match=match):
        _two_groups(**kw)


def test_each_typed_refusal_of_a_ring():
    from deepspeed_tpu.inference.cache import (WindowRingUnsupported,
                                               kv_partition_specs,
                                               refuse_window_ring,
                                               split_table)
    spec = _two_groups()
    with pytest.raises(WindowRingUnsupported, match="the prefix cache") as e:
        refuse_window_ring(spec, "the prefix cache", "why")
    assert e.value.feature == "the prefix cache"
    refuse_window_ring(_two_groups(groups=(("f", ("x",), 2, 24, 16, 0),)),
                       "anything", "no ring, nothing refused")
    with pytest.raises(ValueError, match="groups of page layers"):
        kv_partition_specs(spec)
    full, ring = split_table(jnp.arange(22).reshape(2, 11), spec.ring_pages)
    assert full.shape == (2, 8) and ring.tolist() == [[8, 9, 10],
                                                      [19, 20, 21]]
    whole, none = split_table(jnp.arange(8)[None], 0)
    assert whole.shape == (1, 8) and none.shape == (1, 0)


def test_a_ring_write_sends_padding_to_the_trash_page():
    """A chunk of four pages into a ring of three, two and a half of
    them real: the ring's entries hold pages 0, 1 and 2 of the chunk,
    the padded page lands on the trash page and overwrites nothing."""
    from deepspeed_tpu.inference.cache import paged_write_kv
    spec = _two_groups()
    pool = init_kv_cache(spec)["w0"]
    k = jnp.broadcast_to(jnp.arange(32.0)[None, :, None, None] + 1,
                         (1, 32, 4, 24))
    v = k[..., :16]
    ring = jnp.asarray([[7, 2, 5]], jnp.int32)
    out = paged_write_kv(pool, k, v, jnp.arange(32)[None], ring, ring=True,
                         n_valid=jnp.asarray([20]))
    held = np.asarray(out["k"])[:, 0, 0]            # [pages, lanes]
    assert held[7].tolist() == list(range(1, 9))
    assert held[2].tolist() == list(range(9, 17))
    assert held[5].tolist() == list(range(17, 25))  # its padded lanes too
    assert held[0].tolist() == list(range(25, 33))  # the trash page
    assert not held[[1, 3, 4, 6, 8, 9]].any()
    # without n_valid the padded page would have taken entry 0's place
    out = paged_write_kv(pool, k, v, jnp.arange(32)[None], ring, ring=True)
    assert np.asarray(out["k"])[7, 0, 0].tolist() == list(range(25, 33))


@pytest.mark.parametrize("chunk", [8, 16, 24])
def test_window_band_equals_the_dense_oracle(chunk):
    """A window layer's chunks (narrower than, equal to and wider than
    the window; the last ragged) through the band, each token then
    through the dense oracle by position: the same outputs as every key
    and value kept under the window's mask."""
    from deepspeed_tpu.inference.cache import cached_attention
    rng = np.random.default_rng(chunk)
    spec = _two_groups()
    pool = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), jnp.float32) * 7,
        init_kv_cache(spec)["w0"])
    N, n_pre = 60, 2 * chunk + 5
    q = rng.normal(size=(N, 8, 24)).astype(np.float32)
    k = rng.normal(size=(N, 4, 24)).astype(np.float32)
    v = rng.normal(size=(N, 4, 16)).astype(np.float32)
    sink = jnp.asarray(rng.normal(size=8), jnp.float32)
    ring = jnp.asarray([[4, 9, 1]], jnp.int32)
    got = []
    for c0 in range(0, n_pre, chunk):
        nv = min(chunk, n_pre - c0)
        pad = lambda a: jnp.asarray(np.concatenate(     # noqa: E731
            [a[c0:c0 + nv], np.full((chunk - nv,) + a.shape[1:], 50.0,
                                    np.float32)]))[None]
        y, pool = cached_attention(
            pad(q), pad(k), pad(v), pool, jnp.arange(c0, c0 + chunk)[None],
            jnp.float32, ring, scale=0.2, window=16, sink=sink,
            n_valid=jnp.asarray([nv]), walk=True)
        got.append(np.asarray(y[0, :nv]))
    for t in range(n_pre, N):
        y, pool = cached_attention(
            jnp.asarray(q[t])[None, None], jnp.asarray(k[t])[None, None],
            jnp.asarray(v[t])[None, None], pool, jnp.asarray([[t]]),
            jnp.float32, ring, scale=0.2, window=16, sink=sink, walk=True)
        got.append(np.asarray(y[0]))
    got = np.concatenate(got)
    for t in range(N):
        lo = max(0, t - 15)
        for h in range(8):
            s = 0.2 * k[lo:t + 1, h // 2] @ q[t, h]
            s = np.concatenate([s, [float(sink[h])]])
            w = np.exp(s - s.max())
            w /= w.sum()
            want = w[:-1] @ v[lo:t + 1, h // 2]
            assert np.abs(got[t, h] - want).max() < 2e-5, (t, h)


def test_a_window_or_a_sink_goes_with_walk():
    from deepspeed_tpu.inference.cache import cached_attention
    spec = _two_groups()
    pool = init_kv_cache(spec)["w0"]
    z = jnp.zeros((1, 1, 4, 24))
    with pytest.raises(ValueError, match="walk=True"):
        cached_attention(z, z, z[..., :16], pool, jnp.zeros((1, 1), jnp.int32),
                         jnp.float32, jnp.ones((1, 3), jnp.int32), window=16)

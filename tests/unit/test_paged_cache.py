"""Paged KV cache host machinery (`deepspeed_tpu/inference/paging.py`
+ the pool ops of `inference/cache.py` and `analysis/rules.py`).

Everything here is admission-time metadata, so most of the file is
pure-python over a duck-typed engine stub: the allocator's free-list /
refcount discipline (page 0 is the reserved trash page and is never
handed out), the radix tree's whole-page prefix matching with LRU leaf
eviction, the host store's CRC-stamped park/take round trip, and the
:class:`PagedCacheManager` admission ladder — prefix hits map shared
pages copy-on-write and resume prefill mid-prompt, parked sessions
evacuate to host RAM under pressure and page back in on resume, and a
dry pool makes ``admit`` return None without leaking references.

The jax end pins the paged pool's static geometry
(`cache.spec_for_model`: trash-page minimum, divisibility, the
every-row-full default) and the `rule_decode` paged contract
(host-transfer ops and degenerate page geometry are errors), and holds
the pool's one memory order (`[n_pages, H, D, page_size]`, written a
page slab at a time) against plain contiguous `[B, S, H, D]` arrays
written and attended over in the test, layer by layer. Whole-model numerics
ride `test_paged_parity.py`.
"""

import numpy as np
import pytest

from deepspeed_tpu.inference.paging import (
    TRASH_PAGE,
    HostPageStore,
    PageAllocator,
    PagedCacheManager,
    RadixPrefixCache,
)


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

class TestPageAllocator:
    def test_trash_page_requires_two(self):
        with pytest.raises(ValueError, match="n_pages must be >= 2"):
            PageAllocator(1)

    def test_alloc_never_hands_out_trash(self):
        alloc = PageAllocator(5)
        pages = [alloc.alloc() for _ in range(4)]
        assert TRASH_PAGE not in pages
        assert sorted(pages) == [1, 2, 3, 4]

    def test_exhaustion_returns_none(self):
        alloc = PageAllocator(3)
        assert alloc.alloc() is not None
        assert alloc.alloc() is not None
        assert alloc.alloc() is None
        assert alloc.free_pages == 0
        assert alloc.resident_pages == 2

    def test_free_list_is_lifo(self):
        # recently freed pages are re-used first (hot working set)
        alloc = PageAllocator(4)
        a, b = alloc.alloc(), alloc.alloc()
        alloc.decref(b)
        assert alloc.alloc() == b
        alloc.decref(a)
        assert alloc.alloc() == a

    def test_refcounts_share_and_release(self):
        alloc = PageAllocator(3)
        p = alloc.alloc()
        alloc.incref(p)
        assert alloc.refcount(p) == 2
        alloc.decref(p)
        assert alloc.free_pages == 1      # still held by one ref
        alloc.decref(p)
        assert alloc.free_pages == 2
        assert alloc.resident_pages == 0

    def test_ref_misuse_raises(self):
        alloc = PageAllocator(3)
        p = alloc.alloc()
        with pytest.raises(ValueError, match="trash page"):
            alloc.incref(TRASH_PAGE)
        with pytest.raises(ValueError, match="incref on free page"):
            alloc.incref(p + 1 if p + 1 < 3 else p - 1)
        alloc.decref(p)
        with pytest.raises(ValueError, match="decref on free page"):
            alloc.decref(p)


# ---------------------------------------------------------------------------
# radix prefix cache
# ---------------------------------------------------------------------------

def _radix(n_pages=8, page_size=4):
    alloc = PageAllocator(n_pages)
    return alloc, RadixPrefixCache(alloc, page_size)


class TestRadixPrefixCache:
    def test_miss_then_hit(self):
        alloc, radix = _radix()
        prompt = list(range(10))               # 2 full pages + tail
        assert radix.match(prompt) == []
        assert (radix.hits, radix.misses) == (0, 1)

        pages = [alloc.alloc(), alloc.alloc()]
        radix.insert(prompt, pages)
        assert len(radix) == 2
        assert radix.match(prompt) == pages
        assert (radix.hits, radix.misses) == (1, 1)
        # interned nodes hold their own reference per page
        assert all(alloc.refcount(p) == 2 for p in pages)

    def test_match_is_longest_interned_prefix(self):
        alloc, radix = _radix()
        prompt = list(range(8))
        pages = [alloc.alloc(), alloc.alloc()]
        radix.insert(prompt, pages)
        # same first page, divergent second page -> one-page match
        other = prompt[:4] + [99, 98, 97, 96]
        assert radix.match(other) == pages[:1]
        # sub-page prompts never match (whole-page sharing only)
        assert radix.match(prompt[:3]) == []

    def test_reinsert_is_idempotent(self):
        alloc, radix = _radix()
        prompt = list(range(8))
        pages = [alloc.alloc(), alloc.alloc()]
        radix.insert(prompt, pages)
        radix.insert(prompt, pages)            # same tokens, same KV
        assert len(radix) == 2
        assert all(alloc.refcount(p) == 2 for p in pages)

    def test_evict_one_drops_lru_leaf_first(self):
        alloc, radix = _radix()
        a = list(range(8))
        b = a[:4] + [50, 51, 52, 53]
        pa = [alloc.alloc(), alloc.alloc()]
        radix.insert(a, pa)
        pb_tail = alloc.alloc()
        radix.insert(b, [pa[0], pb_tail])
        radix.match(a)                         # a's leaf is now MRU
        for p in pa + [pb_tail]:
            alloc.decref(p)                    # rows released; radix holds

        assert radix.evict_one()               # b's tail: the LRU leaf
        assert len(radix) == 2
        assert alloc.refcount(pb_tail) == 0
        # the shared interior node anchors its live descendant
        assert radix.match(a) == pa
        assert radix.evict_one() and radix.evict_one()
        assert not radix.evict_one()           # tree empty
        assert alloc.resident_pages == 0


# ---------------------------------------------------------------------------
# host page store
# ---------------------------------------------------------------------------

class TestHostPageStore:
    def test_park_take_round_trip(self):
        store = HostPageStore()
        tree = {"k": np.arange(12, dtype=np.float32).reshape(3, 4),
                "v": np.ones((3, 4), np.float32)}
        store.park("s0", tree)
        assert "s0" in store and len(store) == 1
        assert store.nbytes == 2 * 3 * 4 * 4
        out = store.take("s0")
        np.testing.assert_array_equal(out["k"], tree["k"])
        assert "s0" not in store and store.nbytes == 0

    def test_corruption_is_detected(self):
        store = HostPageStore()
        tree = {"k": np.zeros((2, 2), np.float32)}
        store.park("s0", tree)
        tree["k"][0, 0] = 7.0                  # rot the parked snapshot
        with pytest.raises(RuntimeError, match="CRC mismatch"):
            store.take("s0")

    def test_drop_is_idempotent(self):
        store = HostPageStore()
        store.park("s0", {"k": np.zeros(2, np.float32)})
        store.drop("s0")
        store.drop("s0")
        assert len(store) == 0


# ---------------------------------------------------------------------------
# paged cache manager (admission / COW / park / resume ladder)
# ---------------------------------------------------------------------------

class _PoolEngine:
    """Duck-typed engine stub: the manager only reads geometry facts,
    moves pages through gather/scatter, and checks the park threshold —
    none of which needs a compiled program."""

    def __init__(self, n_pages=6, page_size=4, pages_per_row=4,
                 prefill_chunk=4, prefix_cache=True,
                 host_park_threshold=0.0):
        self.n_pages = n_pages
        self.page_size = page_size
        self.pages_per_row = pages_per_row
        self.prefill_chunk = prefill_chunk
        self.prefix_cache = prefix_cache
        self.host_park_threshold = host_park_threshold
        rng = np.random.default_rng(0)
        self.cache = {"k": rng.standard_normal(
            (n_pages, page_size, 2, 2)).astype(np.float32)}

    def gather_pages(self, page_ids):
        return {"k": self.cache["k"][np.asarray(page_ids)].copy()}

    def scatter_pages(self, page_ids, host_pages):
        self.cache["k"][np.asarray(page_ids)] = host_pages["k"]


def _mgr(**kw):
    eng = _PoolEngine(**kw)
    return eng, PagedCacheManager(eng)


class TestPagedCacheManager:
    def test_cold_admit_allocates_ceil_pages(self):
        _, mgr = _mgr()
        row = mgr.admit(list(range(10)))       # ceil(10/4) = 3 pages
        assert len(row.pages) == 3
        assert row.start == 0 and not row.prefix_hit
        assert row.prefill_chunks == 3 and row.prefill_chunks_skipped == 0
        assert mgr.prefix_misses == 1
        assert mgr.facts()["pages_resident"] == 3

    def test_prefix_hit_shares_pages_and_skips_chunks(self):
        _, mgr = _mgr()
        prompt = list(range(8))
        first = mgr.admit(prompt)
        mgr.after_prefill(first, prompt)

        again = mgr.admit(prompt)
        # the LAST prompt token always prefills: m = (8-1)//4 = 1 even
        # though both pages are interned
        assert again.prefix_hit and again.start == 4
        assert again.pages[0] == first.pages[0]
        assert again.pages[1] != first.pages[1]     # private tail page
        assert again.prefill_chunks == 1
        assert again.prefill_chunks_skipped == 1
        assert (mgr.prefix_hits, mgr.prefix_misses) == (1, 1)
        # shared page: first row + radix + second row
        assert mgr.allocator.refcount(first.pages[0]) == 3

    def test_cow_divergence_allocates_private_pages(self):
        _, mgr = _mgr(n_pages=8)
        a = list(range(8))
        ra = mgr.admit(a)
        mgr.after_prefill(ra, a)
        b = a[:4] + [60, 61, 62, 63]
        rb = mgr.admit(b)
        assert rb.prefix_hit and rb.start == 4
        assert rb.pages[0] == ra.pages[0]
        # divergence past the shared span writes a PRIVATE page — the
        # shared page is never copied and never written
        assert rb.pages[1] != ra.pages[1]
        assert len({ra.pages[1], rb.pages[1]}) == 2

    def test_a_chunk_of_several_pages_shares_whole_chunks_only(self):
        # chunk 8 over pages of 4: three pages match, and the third lies
        # in the chunk prefill restarts in, which writes both its pages
        _, mgr = _mgr(n_pages=12, pages_per_row=5, prefill_chunk=8)
        a = list(range(20))
        ra = mgr.admit(a)
        mgr.after_prefill(ra, a)
        b = a[:12] + [60, 61, 62, 63, 64]
        rb = mgr.admit(b)
        assert rb.prefix_hit and rb.start == 8
        assert rb.pages[:2] == ra.pages[:2]
        assert not set(rb.pages[2:]) & set(ra.pages)
        assert rb.prefill_chunks == 2 and rb.prefill_chunks_skipped == 1
        # the matched third page stays the first row's and the tree's
        assert mgr.allocator.refcount(ra.pages[2]) == 2
        # one matched page is less than a chunk: no hit, nothing shared
        rc = mgr.admit(a[:4] + [70, 71, 72, 73, 74])
        assert not rc.prefix_hit and rc.start == 0
        assert not set(rc.pages) & set(ra.pages)

    def test_dry_pool_defers_without_leaking(self):
        _, mgr = _mgr(n_pages=4)               # 3 usable pages
        live = mgr.admit(list(range(8)))       # takes 2, still mapped
        assert live is not None
        free_before = mgr.allocator.free_pages
        assert mgr.admit(list(range(100, 108))) is None
        assert mgr.allocator.free_pages == free_before

    def test_pressure_evicts_radix_leaves(self):
        _, mgr = _mgr(n_pages=4)
        prompt = list(range(8))
        row = mgr.admit(prompt)
        mgr.after_prefill(row, prompt)
        mgr.release(row)                       # only radix refs remain
        assert mgr.facts()["radix_nodes"] == 2
        # a non-matching prompt needs 3 pages; only 1 is free, so the
        # ladder must evict interned leaves to satisfy it
        row2 = mgr.admit(list(range(50, 60)))
        assert row2 is not None and len(row2.pages) == 3
        assert mgr.facts()["radix_nodes"] < 2

    def test_ensure_position_grows_and_caps(self):
        _, mgr = _mgr(n_pages=6, pages_per_row=2)
        row = mgr.admit([1, 2, 3])             # 1 page
        assert mgr.ensure_position(row, 3) is True       # same page
        assert mgr.ensure_position(row, 4) is True       # grows
        assert len(row.pages) == 2
        assert mgr.ensure_position(row, 8) is False      # table full

    def test_session_park_and_resume_skips_history(self):
        _, mgr = _mgr(n_pages=8)
        prompt = list(range(8))
        row = mgr.admit(prompt, session_id="s")
        kv_tokens = prompt + [9]               # one generated token's KV
        mgr.release(row, kv_tokens=kv_tokens, session_id="s")
        assert mgr.facts()["sessions_parked_device"] == 1

        follow = prompt + [9, 10, 11]          # extends the history
        r2 = mgr.admit(follow, session_id="s")
        assert r2.resumed and not r2.prefix_hit
        # frontier 8 covers pages 0-1; prefill restarts at its chunk
        # floor and only runs the tail
        assert r2.start == 8
        assert r2.prefill_chunks_skipped == 2
        assert mgr.sessions_resumed == 1

    def test_resume_requires_prompt_extension(self):
        _, mgr = _mgr(n_pages=8)
        prompt = list(range(8))
        row = mgr.admit(prompt, session_id="s")
        mgr.release(row, kv_tokens=prompt + [9], session_id="s")
        # a DIFFERENT prompt on the session must not reuse its KV
        r2 = mgr.admit(list(range(40, 48)), session_id="s")
        assert not r2.resumed and r2.start == 0

    def test_host_tier_round_trip_preserves_pool_bytes(self):
        eng, mgr = _mgr(n_pages=8, host_park_threshold=0.9)
        prompt = list(range(8))
        row = mgr.admit(prompt, session_id="s")
        pages = list(row.pages)
        want = eng.cache["k"][np.asarray(pages)].copy()
        # threshold 0.9 > free fraction: release evacuates straight to
        # the host tier and frees the device pages
        mgr.release(row, kv_tokens=prompt, session_id="s")
        facts = mgr.facts()
        assert facts["sessions_parked_host"] == 1
        assert facts["sessions_parked_device"] == 0
        assert facts["pages_evacuated"] == 2
        assert facts["host_tier_bytes"] > 0
        eng.cache["k"][np.asarray(pages)] = 0.0    # pages recycled

        r2 = mgr.admit(prompt + [9], session_id="s")
        assert r2.resumed
        assert mgr.facts()["pages_paged_in"] == 2
        got = eng.cache["k"][np.asarray(r2.pages[:2])]
        np.testing.assert_array_equal(got, want)

    def test_facts_account_for_trash_page(self):
        _, mgr = _mgr(n_pages=6)
        f = mgr.facts()
        assert f["pages_free"] + f["pages_resident"] == f["n_pages"] - 1
        assert f["page_bytes"] * f["n_pages"] == \
            _PoolEngine(n_pages=6).cache["k"].nbytes


# ---------------------------------------------------------------------------
# static pool geometry (spec_for_model)
# ---------------------------------------------------------------------------

class TestPagedSpec:
    def _cfg(self):
        import jax.numpy as jnp
        from deepspeed_tpu.models.gpt2 import GPT2Config
        return GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                          n_layer=2, n_head=4, dtype=jnp.float32)

    def test_every_row_full_default(self):
        from deepspeed_tpu.inference.cache import spec_for_model
        spec = spec_for_model(self._cfg(), 2, 32, page_size=8)
        assert spec.pages_per_row == 4
        assert spec.n_pages == 2 * 4 + 1       # + the trash page

    def test_page_size_must_divide_max_seq(self):
        from deepspeed_tpu.inference.cache import spec_for_model
        with pytest.raises(ValueError, match="divisor of max_seq"):
            spec_for_model(self._cfg(), 2, 32, page_size=12)

    def test_n_pages_floor_guards_trash_page(self):
        from deepspeed_tpu.inference.cache import spec_for_model
        with pytest.raises(ValueError, match="n_pages must be >= 2"):
            spec_for_model(self._cfg(), 2, 32, page_size=8, n_pages=1)

    def test_pool_shape_and_quantized_scales(self):
        from deepspeed_tpu.inference.cache import (init_kv_cache,
                                                   spec_for_model)
        spec = spec_for_model(self._cfg(), 2, 32, "int8", page_size=16)
        cache = init_kv_cache(spec)
        # positions minor-most: [n_pages, n_head, head_dim, page_size]
        assert cache["h_0"]["k"].shape == (5, 4, 8, 16)
        assert cache["h_0"]["k_scale"].shape == (5, 4, 16)


# ---------------------------------------------------------------------------
# the pool against contiguous arrays, one layer
# ---------------------------------------------------------------------------

PAGE, SEQ, HEADS, DIM = 128, 256, 2, 8
STORAGE = {"float32": (np.float32, None), "bfloat16": ("bfloat16", None),
           "int8": (np.int8, "int8")}


def _plain_write(plain, k, v, positions, codec):
    """The reference's write: ``k`` / ``v`` ``[B, T, H, D]`` go into
    contiguous ``[B, S, H, D]`` arrays (scales ``[B, S, H]``) at
    ``positions`` ``[B, T]``, quantized as the pool quantizes."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.cache import _quantize
    new = {"k": k, "v": v}
    if codec:
        new["k"], new["k_scale"] = _quantize(k, codec)
        new["v"], new["v_scale"] = _quantize(v, codec)
    rows = np.arange(positions.shape[0])[:, None]
    return {name: leaf.at[rows, jnp.asarray(positions)].set(
        new[name].astype(leaf.dtype)) for name, leaf in plain.items()}


def _plain_attend(plain, x, positions, codec):
    """Write, then each query over its row up to its own position."""
    import jax
    import jax.numpy as jnp
    q, k, v = x
    plain = _plain_write(plain, k, v, positions, codec)
    full = {n: plain[n].astype(jnp.float32) for n in ("k", "v")}
    if codec:
        full = {n: full[n] * plain[n + "_scale"][..., None] for n in full}
    att = jnp.einsum("bthd,bshd->bhts", q, full["k"]) / np.sqrt(DIM)
    seen = np.arange(SEQ)[None, None] <= np.asarray(positions)[:, :, None]
    att = jax.nn.softmax(jnp.where(seen[:, None], att, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", att, full["v"]), plain


def _plain_and_pool(storage, tables, rng):
    """Contiguous arrays filled with random keys and values at every
    position, and the pool that holds the same bytes under ``tables``
    (laid out here with numpy, not by the code under test). Pages that
    no table names, the trash page among them, hold garbage."""
    import jax.numpy as jnp

    dtype, codec = STORAGE[storage]
    B = tables.shape[0]
    shape = (B, SEQ, HEADS, DIM)
    plain = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if codec:
        plain["k_scale"] = plain["v_scale"] = jnp.zeros(shape[:-1],
                                                        jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(2))
    plain = _plain_write(plain, k, v,
                         np.broadcast_to(np.arange(SEQ), (B, SEQ)), codec)
    n_pages = int(tables.max()) + 2
    pool = {}
    for name, leaf in plain.items():
        leaf = np.asarray(leaf)
        buf = rng.standard_normal(
            (n_pages,) + leaf.shape[2:] + (PAGE,)).astype(leaf.dtype)
        for b, j in np.ndindex(*tables.shape):
            if tables[b, j]:
                buf[tables[b, j]] = np.moveaxis(
                    leaf[b, j * PAGE:(j + 1) * PAGE], 0, -1)
        pool[name] = jnp.asarray(buf)
    return plain, pool


def _assert_same_bytes(plain, pool, tables, rows):
    """Each named row's pages hold exactly the contiguous row's bytes."""
    for name, leaf in plain.items():
        leaf, buf = np.asarray(leaf), np.asarray(pool[name])
        for b in rows:
            for j, page in enumerate(tables[b]):
                np.testing.assert_array_equal(
                    np.moveaxis(buf[page], -1, 0),
                    leaf[b, j * PAGE:(j + 1) * PAGE], err_msg=name)


@pytest.mark.parametrize("storage", list(STORAGE))
class TestPoolAgainstContiguous:
    def _attend(self, pool, x, positions, impl, tables):
        import jax.numpy as jnp
        from deepspeed_tpu.inference import cache
        q, k, v = x
        return cache.cached_attention(
            q, k, v, pool, jnp.asarray(positions), jnp.float32,
            jnp.asarray(tables), impl=impl, block_k=PAGE)

    def _new(self, rng, rows, tokens):
        import jax.numpy as jnp
        return [jnp.asarray(rng.standard_normal((rows, tokens, HEADS, DIM)),
                            jnp.float32) for _ in range(3)]

    @pytest.mark.parametrize("impl", ["dense", "flash"])
    def test_decode_writes_land_at_their_positions(self, storage, impl):
        # in-page offsets 0, 1 and 127, the first slot past a page
        # boundary, the last slot of a row; rows 5 and 6 are inactive:
        # position 0 through a table of zeros, both on the trash page
        rng = np.random.default_rng(0)
        positions = np.array([0, 1, 127, 128, 255, 0, 0])[:, None]
        tables = np.array([[1, 2], [3, 4], [5, 6], [7, 8], [9, 10],
                           [0, 0], [0, 0]], np.int32)
        plain, pool = _plain_and_pool(storage, tables, rng)
        x = self._new(rng, 7, 1)
        want, plain = _plain_attend(plain, x, positions,
                                    STORAGE[storage][1])
        got, pool = self._attend(pool, x, positions, impl, tables)
        np.testing.assert_allclose(got[:5], want[:5], atol=2e-6, rtol=1e-5)
        _assert_same_bytes(plain, pool, tables, range(5))

    def test_speculative_chunk_straddles_a_page(self, storage):
        # row 0 writes 126..129 over the boundary, row 2's chunk runs
        # off its one allocated page onto the trash page
        rng = np.random.default_rng(1)
        positions = np.array([126, 0, 125])[:, None] + np.arange(4)
        tables = np.array([[1, 2], [3, 4], [5, 0]], np.int32)
        plain, pool = _plain_and_pool(storage, tables, rng)
        x = self._new(rng, 3, 4)
        want, plain = _plain_attend(plain, x, positions,
                                    STORAGE[storage][1])
        got, pool = self._attend(pool, x, positions, "dense", tables)
        np.testing.assert_allclose(got[:2], want[:2], atol=2e-6, rtol=1e-5)
        # row 2 sees its own page only up to 127; 128 is on the trash
        np.testing.assert_allclose(got[2, :3], want[2, :3], atol=2e-6,
                                   rtol=1e-5)
        _assert_same_bytes(plain, pool, tables, range(2))
        _assert_same_bytes(plain, pool, tables[:, :1], [2])

    @pytest.mark.parametrize("start", [0, 64, 192])
    def test_prefill_chunk_into_one_page(self, storage, start):
        rng = np.random.default_rng(2)
        positions = start + np.arange(64)[None]
        tables = np.array([[2, 1]], np.int32)
        plain, pool = _plain_and_pool(storage, tables, rng)
        x = self._new(rng, 1, 64)
        want, plain = _plain_attend(plain, x, positions,
                                    STORAGE[storage][1])
        got, pool = self._attend(pool, x, positions, "dense", tables)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)
        _assert_same_bytes(plain, pool, tables, [0])


# ---------------------------------------------------------------------------
# rule_decode paged contract (seeded violations)
# ---------------------------------------------------------------------------

_PAGE_FACTS = {"page_size": 8, "n_pages": 9, "pages_per_row": 4,
               "max_seq": 32}


class TestRuleDecodePaged:
    def test_clean_paged_context_passes(self):
        from deepspeed_tpu.analysis.rules import StepContext, rule_decode
        ctx = StepContext(hlo_text="",
                          decode_page_facts=dict(_PAGE_FACTS))
        assert rule_decode(ctx) == []

    def test_host_transfer_in_paged_decode_is_error(self):
        from deepspeed_tpu.analysis.rules import (SEV_ERROR, StepContext,
                                                  rule_decode)
        hlo = ("%of = token[] outfeed(f32[2,8]{1,0} %pages, "
               "token[] %tok)")
        ctx = StepContext(hlo_text=hlo,
                          decode_page_facts=dict(_PAGE_FACTS))
        findings = rule_decode(ctx)
        assert [f.severity for f in findings] == [SEV_ERROR]
        assert "host transfer" in findings[0].message

    def test_degenerate_geometry_is_error(self):
        from deepspeed_tpu.analysis.rules import (SEV_ERROR, StepContext,
                                                  rule_decode)
        ctx = StepContext(
            hlo_text="",
            decode_page_facts={"page_size": 0, "n_pages": 1,
                               "pages_per_row": 0, "max_seq": 32})
        findings = rule_decode(ctx)
        assert [f.severity for f in findings] == [SEV_ERROR]
        assert "degenerate" in findings[0].message

    def test_table_must_cover_max_seq(self):
        from deepspeed_tpu.analysis.rules import (SEV_ERROR, StepContext,
                                                  rule_decode)
        bad = dict(_PAGE_FACTS, pages_per_row=3)   # 3*8 != 32
        ctx = StepContext(hlo_text="", decode_page_facts=bad)
        findings = rule_decode(ctx)
        assert [f.severity for f in findings] == [SEV_ERROR]
        assert "trash page" in findings[0].message

    def test_a_step_that_serves_nothing_is_not_judged(self):
        """No serving fact at all (a train step's context): the rule
        says nothing, whatever the program holds."""
        from deepspeed_tpu.analysis.rules import StepContext, rule_decode
        ctx = StepContext(hlo_text="%of = token[] outfeed(f32[2] %x)")
        assert rule_decode(ctx) == []


# ---------------------------------------------------------------------------
# host page corruption: typed error + drop-and-re-prefill recovery
# ---------------------------------------------------------------------------

class TestHostPageCorruption:
    def test_take_raises_typed_error_and_drops_snapshot(
            self, fault_registry):
        from deepspeed_tpu.inference.paging import HostPageCorruptError
        store = HostPageStore()
        fault_registry.inject_page_corruption(session_id="s0")
        store.park("s0", {"k": np.zeros((2, 2), np.float32)})
        with pytest.raises(HostPageCorruptError) as exc:
            store.take("s0")
        assert exc.value.session_id == "s0"
        assert exc.value.bad_leaves
        # rotted bytes are useless to every future caller: popped
        assert "s0" not in store

    def test_manager_recovers_with_cold_reprefill(self, fault_registry):
        eng, mgr = _mgr(n_pages=8, host_park_threshold=0.9)
        prompt = list(range(8))
        row = mgr.admit(prompt, session_id="s")
        fault_registry.inject_page_corruption(session_id="s")
        # threshold 0.9: release evacuates to the host tier, where the
        # armed fault rots one byte AFTER the CRCs were stamped
        mgr.release(row, kv_tokens=prompt, session_id="s")
        assert mgr.facts()["sessions_parked_host"] == 1

        r2 = mgr.admit(prompt + [9], session_id="s")
        # the engine did NOT crash: the session fell back to a cold
        # admission (full re-prefill from the prompt), counter bumped
        assert r2 is not None
        assert not r2.resumed and r2.start == 0
        assert mgr.host_pages_corrupt == 1
        assert mgr.facts()["host_pages_corrupt"] == 1
        assert mgr.facts()["sessions_parked_host"] == 0

    def test_unfaulted_round_trip_still_clean(self, fault_registry):
        eng, mgr = _mgr(n_pages=8, host_park_threshold=0.9)
        prompt = list(range(8))
        row = mgr.admit(prompt, session_id="s")
        mgr.release(row, kv_tokens=prompt, session_id="s")
        r2 = mgr.admit(prompt + [9], session_id="s")
        assert r2.resumed and mgr.host_pages_corrupt == 0


# ---------------------------------------------------------------------------
# a window group's rings (ISSUE 47)
# ---------------------------------------------------------------------------

class _RingEngine(_PoolEngine):
    """The stub with a spec of two groups: two full layers and three
    window layers over a window of two pages."""

    def __init__(self, max_batch=3, **kw):
        import jax.numpy as jnp
        from deepspeed_tpu.inference.cache import page_pool_spec
        kw.setdefault("prefix_cache", False)
        super().__init__(**kw)
        self.max_batch = max_batch
        self.spec = page_pool_spec(
            max_batch, self.page_size * self.pages_per_row, n_layer=5,
            n_head=2, head_dim=6, compute_dtype=jnp.bfloat16,
            n_positions=1024, page_size=self.page_size,
            n_pages=self.n_pages,
            groups=(("full", ("a", "b"), 2, 6, 4, 0),
                    ("window", ("c", "d", "e"), 4, 6, 4,
                     2 * self.page_size)))


class TestWindowRings:
    def _mgr(self, **kw):
        eng = _RingEngine(**kw)
        return eng, PagedCacheManager(eng)

    def test_a_row_takes_a_ring_whatever_its_length(self):
        _, mgr = self._mgr(n_pages=12)
        assert (mgr.ring_pages, mgr.table_width) == (3, 4 + 3)
        short = mgr.admit(list(range(3)), slot=0)
        long = mgr.admit(list(range(15)), slot=1)
        assert len(short.ring) == len(long.ring) == 3
        assert (len(short.pages), len(long.pages)) == (1, 4)
        assert not set(short.ring) & set(long.ring)
        assert TRASH_PAGE not in short.ring + long.ring
        # the table: pages from the left, the ring as the last entries
        t = long.table(mgr.table_width)
        assert list(t[:4]) == long.pages and list(t[4:]) == long.ring
        t = short.table(mgr.table_width)
        assert list(t[:4]) == short.pages + [0] * 3
        assert list(t[4:]) == short.ring
        # growing a row takes full pages and never a ring page
        assert mgr.ensure_position(short, 4) and len(short.ring) == 3
        assert mgr.ring_pages_live == 6

    def test_facts_by_group(self):
        _, mgr = self._mgr(n_pages=12)
        mgr.admit(list(range(9)), slot=0)
        groups = mgr.facts()["groups"]
        # bf16: 2 layers x 2 heads x (6 + 4) and 3 x 4 x 10, x 4 a page
        assert groups["full"] == {
            "window": 0, "layers": 2, "pages_live": 3, "pages_total": 11,
            "page_bytes": 320, "bytes_live": 960, "bytes_total": 3520}
        assert groups["window"] == {
            "window": 8, "layers": 3, "pages_live": 3, "pages_total": 9,
            "page_bytes": 960, "bytes_live": 2880, "bytes_total": 8640}
        assert mgr.page_bytes() == 320 and mgr.facts()["ring_pages"] == 3

    def test_rings_are_reused_after_release(self):
        _, mgr = self._mgr(n_pages=20)
        rows = [mgr.admit([1, 2, 3], slot=i) for i in range(3)]
        assert mgr.ring_allocator.free_pages == 0
        # more rows than the pool has rings: nothing leaks
        free = mgr.allocator.free_pages
        assert mgr.admit([1, 2, 3], slot=0) is None
        assert mgr.allocator.free_pages == free
        gone = list(rows[1].ring)
        mgr.release(rows[1])
        assert rows[1].ring == [] and mgr.ring_pages_live == 6
        again = mgr.admit([4, 5], slot=1)
        assert sorted(again.ring) == sorted(gone)
        for row in (rows[0], rows[2], again):
            mgr.release(row)
        assert mgr.ring_allocator.free_pages == 9 and mgr.pages_live == 0

    def test_what_parks_or_hands_off_refuses_a_ring(self):
        from deepspeed_tpu.inference.cache import WindowRingUnsupported
        _, mgr = self._mgr()
        with pytest.raises(WindowRingUnsupported, match="park/resume"):
            mgr.admit([1, 2], session_id="s", slot=0)
        row = mgr.admit([1, 2], slot=0)
        with pytest.raises(WindowRingUnsupported, match="park/resume"):
            mgr.release(row, kv_tokens=[1, 2], session_id="s")
        with pytest.raises(WindowRingUnsupported, match="handed-off"):
            mgr.adopt(row)

    def test_a_spec_without_groups_has_no_ring(self):
        _, mgr = _mgr()
        assert mgr.ring_pages == 0 and mgr.ring_allocator is None
        assert mgr.table_width == mgr.pages_per_row
        row = mgr.admit([1, 2, 3])
        assert row.ring == [] and mgr.facts()["groups"] == {}

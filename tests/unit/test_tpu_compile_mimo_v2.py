"""`test_tpu_compile.py` for MiMo-V2 (ISSUE 47): the decode kernel at
the cell's two geometries (4 key heads of 16 queries and 8 of 8, keys
of 192 over values of 128; the window group over a ring of two pages
with a sink) and both serving programs of the share at the published
widths, and since ISSUE 52 the band of a window layer's prefill chunk
as one kernel, compiled (not interpreted) for a described ``v5e:2x2``
chip. A
file of its own, as `test_tpu_compile_qwen3_next.py` is; the fixtures
and helpers are `test_tpu_compile.py`'s."""

import jax
import jax.numpy as jnp
import pytest

from tests.unit.test_tpu_compile import (       # noqa: F401 (fixtures)
    PAGE, _compiled_not_interpreted, chip, kernel_grids, topo)

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

# the cell's engine: 64 rows, a bucket of 33,792 (264 pages), a full pool
# of 6,144 pages and the trash page, 64 rings of two pages
ROWS, BUCKET, PAGES, CHUNK = 64, 33792, 6145, 1024
GROUPS = {"full": (4, 16, BUCKET // PAGE, 0), "window": (8, 8, 2, 128)}


def test_decode_geometry_with_values_narrower_than_keys():
    from deepspeed_tpu.ops.pallas import flash_decode as fd

    for heads in (4, 8):
        assert fd.check_decode_geometry(PAGE, PAGE, jnp.bfloat16, heads, 192,
                                        False, v_dim=128) == PAGE
    wide = fd.paged_vmem_bytes(8, 192, PAGE, jnp.bfloat16, False)
    narrow = fd.paged_vmem_bytes(8, 192, PAGE, jnp.bfloat16, False,
                                 v_dim=128)
    # two slots of a V block 64 sublanes narrower
    assert wide - narrow == 2 * 8 * 64 * PAGE * 2
    assert narrow < fd.PAGED_VMEM_BUDGET // 3


@pytest.mark.parametrize("group", sorted(GROUPS))
def test_decode_kernel_of_each_group_compiles(chip, group):
    """The decode kernel over each group's pool: keys of 192, values of
    128, one grid step a row, nothing pool-shaped copied; the window
    group's over a ring of two pages a row with a sink a query head."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.ops.pallas.flash_decode import flash_decode_paged

    heads, queries, per, window = GROUPS[group]
    n_pages = ROWS * 2 + 1 if window else PAGES
    bf16 = jnp.bfloat16
    pool = {"k": chip((n_pages, heads, 192, PAGE), bf16),
            "v": chip((n_pages, heads, 128, PAGE), bf16)}
    new = {"k": chip((ROWS, 1, heads, 192), bf16),
           "v": chip((ROWS, 1, heads, 128), bf16)}
    q = chip((ROWS, 1, heads * queries, 192), bf16)
    sink = chip((heads * queries,), jnp.float32) if window else None

    def fn(pool, q, new, pos, pt, sink):
        return flash_decode_paged(q, new, pool, pos, pt, interpret=False,
                                  scale=192 ** -0.5, window=window,
                                  sink=sink)
    lowered = jax.jit(fn, donate_argnums=0).lower(
        pool, q, new, chip((ROWS,), jnp.int32),
        chip((ROWS, per), jnp.int32), sink)
    assert kernel_grids(lowered.as_text()) == [(ROWS,)]
    text = lowered.compile().as_text()
    assert "ds_flash_decode_paged" in text
    for leaf in pool.values():
        assert payload_shaped_copies(text, leaf.shape) == []


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_mimo_v2_serving_programs_compile(chip, monkeypatch, program):
    """Both programs of the share at its published widths (the dense
    layer, a window layer and a full layer with their experts: layers 0,
    1 and 5's kinds), cache donated, as the engine calls them: a prefill
    chunk of 1,024 and a decode step of 64 rows over a table of 264
    pages and a ring of 2. No ``[heads, chunk, bucket]`` array, nothing
    pool-shaped copied, each attention program under its scope."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.models import mimo_v2 as mm

    for name in ("deepspeed_tpu.ops.pallas.flash_decode",
                 "deepspeed_tpu.moe.dropless"):
        _compiled_not_interpreted(monkeypatch, name)
    cfg = mm.mimo_v2_5_share(n_layer=3, hybrid_layer_pattern=(0, 1, 0))
    model = mm.MimoV2LM(cfg)
    spec = cfg.cache_spec(ROWS, BUCKET, page_size=PAGE, n_pages=PAGES)
    abstract = lambda tree: jax.tree_util.tree_map(     # noqa: E731
        lambda a: chip(a.shape, a.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: mm.init_mimo_v2_params(model, k), jax.random.PRNGKey(0)))
    cache = abstract(jax.eval_shape(lambda: init_kv_cache(spec)))
    i32 = lambda *shape: chip(shape, jnp.int32)         # noqa: E731
    width = spec.table_width
    assert width == BUCKET // PAGE + 2

    if program == "prefill":
        def fn(params, cache, tokens, positions, table, slots, n_valid):
            return model.serve_apply(params, cache, tokens, positions,
                                     table, slots, n_valid)
        args = (i32(1, CHUNK), i32(1, CHUNK), i32(1, width), i32(1), i32(1))
    else:
        def fn(params, cache, tokens, positions, tables):
            live = (tables[:, 0] != 0).astype(jnp.int32)
            return model.serve_apply(
                params, cache, tokens[:, None], positions[:, None], tables,
                jnp.arange(ROWS, dtype=jnp.int32), live,
                attn_impl="flash", attn_block_k=PAGE)
        args = (i32(ROWS), i32(ROWS), i32(ROWS, width))
    compiled = jax.jit(fn, donate_argnums=1).lower(
        params, cache, *args).compile()
    text = compiled.as_text()
    # three grouped matmuls in each of the two expert layers
    import re
    assert len(re.findall(r"%gmm[.\w]* = ", text)) == 6
    kinds = ("ds_attn_prefill_full", "ds_attn_prefill_window") \
        if program == "prefill" else \
        ("ds_attn_decode_full", "ds_attn_decode_window")
    for scope in kinds + ("ds_moe_route", "ds_moe_dispatch",
                          "ds_moe_experts", "ds_moe_combine"):
        assert scope in text, scope
    assert (text.count("ds_flash_decode_paged") > 0) == (program == "decode")
    # nothing as long as the bucket: no [64, 1024, 33792] scores, no
    # gathered [33792, heads, width] view of a row
    assert f",{BUCKET}]" not in text and f"[{BUCKET}," not in text
    for shape in ((PAGES, 4, 192, PAGE), (PAGES, 4, 128, PAGE),
                  (ROWS * 2 + 1, 8, 192, PAGE), (ROWS * 2 + 1, 8, 128, PAGE)):
        assert payload_shaped_copies(text, shape) == []
    mem = compiled.memory_analysis()
    # a full layer's walk holds a block's [64, 1024, 1024] float32 scores
    # and little else: temporaries stay under 2 GB
    assert mem.temp_size_in_bytes < 2 * 2 ** 30, mem.temp_size_in_bytes


def test_window_band_kernel_compiles(chip, monkeypatch):
    """The band's kernel at the cell's geometry (64 query heads over 8
    key heads, keys of 192 over values of 128, window 128, a sink a query
    head): a grid step a key head and 128 queries of its 8 heads. The
    cell's call site keeps XLA's band at this window
    (`cache.band_kernel_takes`: 128 is no longer than the kernel's query
    block, and XLA's band is the faster there on the chip), so the kernel
    is compiled by itself."""
    from deepspeed_tpu.inference.cache import band_kernel_takes
    from deepspeed_tpu.ops.pallas import window_prefill as wp

    _compiled_not_interpreted(monkeypatch,
                              "deepspeed_tpu.ops.pallas.window_prefill")
    assert not band_kernel_takes("flash", 128)
    bf16 = jnp.bfloat16

    def fn(q, kb, vb, kn, vn, bounds, sink):
        return wp.window_prefill_band(
            q, kb, vb, kn, vn, bounds[0], bounds[1], window=128,
            scale=192 ** -0.5, sink=sink)
    lowered = jax.jit(fn).lower(
        chip((CHUNK, 64, 192), bf16), chip((128, 8, 192), bf16),
        chip((128, 8, 128), bf16), chip((CHUNK, 8, 192), bf16),
        chip((CHUNK, 8, 128), bf16), chip((2,), jnp.int32),
        chip((64,), jnp.float32))
    assert kernel_grids(lowered.as_text()) == [(8, CHUNK // 128)]
    assert "ds_window_prefill_band" in lowered.compile().as_text()

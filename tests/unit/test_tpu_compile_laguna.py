"""`test_tpu_compile.py` for Laguna (ISSUE 51): the decode kernel at the
cell's two geometries (8 key heads of 128 under 6 and under 9 queries a
key head: a ``[H, G, D]`` query block whose ``G`` is no multiple of the 8
sublanes; the window group over a ring of five pages with no sink) and
both serving programs of the share at the published widths, and since
ISSUE 52 the band of a window layer's prefill chunk as one kernel,
compiled (not interpreted) for a described ``v5e:2x2`` chip. A file of
its own,
as `test_tpu_compile_mimo_v2.py` is; the fixtures and helpers are
`test_tpu_compile.py`'s."""

import re

import jax
import jax.numpy as jnp
import pytest

from tests.unit.test_tpu_compile import (       # noqa: F401 (fixtures)
    PAGE, _compiled_not_interpreted, chip, kernel_grids, topo)

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

# the cell's engine: 64 rows, a bucket of 34,816 (272 pages), a full pool
# of 5,632 pages and the trash page, 64 rings of five pages
ROWS, BUCKET, PAGES, CHUNK, RING = 64, 34816, 5633, 1024, 5
GROUPS = {"full": (6, BUCKET // PAGE, 0), "window": (9, RING, 512)}


@pytest.mark.parametrize("group", sorted(GROUPS))
def test_decode_kernel_of_each_group_compiles(chip, group):
    """The decode kernel over each group's pool at 6 and at 9 queries a
    key head, as it is (no padding of the group axis): one grid step a
    row, nothing pool-shaped copied; the window group's over a ring of
    five pages a row, ``window=512`` with ``sink=None``."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.ops.pallas.flash_decode import flash_decode_paged

    queries, per, window = GROUPS[group]
    n_pages = ROWS * RING + 1 if window else PAGES
    bf16 = jnp.bfloat16
    pool = {x: chip((n_pages, 8, 128, PAGE), bf16) for x in "kv"}
    new = {x: chip((ROWS, 1, 8, 128), bf16) for x in "kv"}
    q = chip((ROWS, 1, 8 * queries, 128), bf16)

    def fn(pool, q, new, pos, pt):
        return flash_decode_paged(q, new, pool, pos, pt, interpret=False,
                                  scale=128 ** -0.5, window=window)
    lowered = jax.jit(fn, donate_argnums=0).lower(
        pool, q, new, chip((ROWS,), jnp.int32),
        chip((ROWS, per), jnp.int32))
    assert kernel_grids(lowered.as_text()) == [(ROWS,)]
    text = lowered.compile().as_text()
    assert "ds_flash_decode_paged" in text
    for leaf in pool.values():
        assert payload_shaped_copies(text, leaf.shape) == []


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_laguna_serving_programs_compile(chip, monkeypatch, program):
    """Both programs of the share at its published widths (the dense
    full layer, a window layer and a full layer with their experts:
    layers 0, 1 and 4's kinds), cache donated, as the engine calls them:
    a prefill chunk of 1,024 and a decode step of 64 rows over a table
    of 272 pages and a ring of 5. No ``[heads, chunk, bucket]`` array,
    nothing pool-shaped copied, each attention program and the gate
    under its scope."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.models import laguna as lg

    for name in ("deepspeed_tpu.ops.pallas.flash_decode",
                 "deepspeed_tpu.moe.dropless"):
        _compiled_not_interpreted(monkeypatch, name)
    cfg = lg.laguna_s_2_1_share(
        n_layer=3, layer_types=("full_attention", "sliding_attention",
                                "full_attention"),
        num_attention_heads_per_layer=(48, 72, 48))
    model = lg.LagunaLM(cfg)
    spec = cfg.cache_spec(ROWS, BUCKET, page_size=PAGE, n_pages=PAGES)
    abstract = lambda tree: jax.tree_util.tree_map(     # noqa: E731
        lambda a: chip(a.shape, a.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: lg.init_laguna_params(model, k), jax.random.PRNGKey(0)))
    cache = abstract(jax.eval_shape(lambda: init_kv_cache(spec)))
    i32 = lambda *shape: chip(shape, jnp.int32)         # noqa: E731
    width = spec.table_width
    assert width == BUCKET // PAGE + RING

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
    assert len(re.findall(r"%gmm[.\w]* = ", text)) == 6
    kinds = ("ds_attn_prefill_full", "ds_attn_prefill_window") \
        if program == "prefill" else \
        ("ds_attn_decode_full", "ds_attn_decode_window")
    for scope in kinds + ("ds_attn_gate", "ds_moe_route", "ds_moe_dispatch",
                          "ds_moe_experts", "ds_moe_combine",
                          "ds_moe_shared"):
        assert scope in text, scope
    assert (text.count("ds_flash_decode_paged") > 0) == (program == "decode")
    # nothing as long as the bucket: no [72, 1024, 34816] scores, no
    # gathered [34816, heads, width] view of a row
    assert f",{BUCKET}]" not in text and f"[{BUCKET}," not in text
    for shape in ((PAGES, 8, 128, PAGE), (ROWS * RING + 1, 8, 128, PAGE)):
        assert payload_shaped_copies(text, shape) == []
    mem = compiled.memory_analysis()
    # a window layer's band holds [2, 72, 512, 1024] float32 scores and
    # their exponentials, a full layer's walk a block's [48, 1024, 1024]:
    # temporaries stay under 2 GB
    assert mem.temp_size_in_bytes < 2 * 2 ** 30, mem.temp_size_in_bytes


def test_window_band_kernel_compiles(chip, monkeypatch):
    """The band of a window layer's chunk as one kernel at the cell's
    geometry (72 query heads over 8 key heads of 128, window 512, a ring
    of five pages, no sink), through `cached_attention` under
    ``impl="flash"`` as the prefill program calls it: a grid step a key
    head and 128 queries of its 9 heads, under the standing scope, and no
    ``[.., 512, 1024]`` scores left in the program."""
    from deepspeed_tpu.inference import cache as kvc

    _compiled_not_interpreted(monkeypatch,
                              "deepspeed_tpu.ops.pallas.window_prefill")
    bf16 = jnp.bfloat16
    pool = {x: chip((ROWS * RING + 1, 8, 128, PAGE), bf16) for x in "kv"}

    def fn(pool, q, k, v, positions, table, n_valid):
        return kvc.cached_attention(
            q, k, v, pool, positions, bf16, table, impl="flash",
            scale=128 ** -0.5, window=512, n_valid=n_valid, walk=True)
    lowered = jax.jit(fn, donate_argnums=0).lower(
        pool, chip((1, CHUNK, 72, 128), bf16), chip((1, CHUNK, 8, 128), bf16),
        chip((1, CHUNK, 8, 128), bf16), chip((1, CHUNK), jnp.int32),
        chip((1, RING), jnp.int32), chip((1,), jnp.int32))
    assert kernel_grids(lowered.as_text()) == [(8, CHUNK // 128)]
    text = lowered.compile().as_text()
    assert "ds_attn_prefill_window/" in text
    assert "ds_window_prefill_band" in text
    assert "512,1024]" not in text and "1024,1536]" not in text

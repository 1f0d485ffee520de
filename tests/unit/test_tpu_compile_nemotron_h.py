"""`test_tpu_compile.py` for Nemotron-H (ISSUE 41): the decode kernel at
the cell's attention geometry and both serving programs of the share at
the published widths, compiled (not interpreted) for a described
``v5e:2x2`` chip. A file of its own, because a run of that file's 75
cases is four minutes; the fixtures and helpers are its."""

import jax
import jax.numpy as jnp
import pytest

from tests.unit.test_tpu_compile import (       # noqa: F401 (fixtures)
    PAGE, _compiled_not_interpreted, chip, chunk_kernel_calls, decode_call,
    held_experts_calls, kernel_grids, scores_of_a_bucket, topo)

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

# the cell's engine: 96 rows, a bucket of 5,120 (40 pages), a pool of
# 3,840 pages and the trash page, 2 key heads of 128
ROWS, BUCKET, PAGES, CHUNK = 96, 5120, 3841, 1024


def test_two_key_heads_of_sixteen_queries_decode_compiles(chip):
    """The decode kernel with 16 query heads to each of 2 key heads of
    128, 96 rows over 40 pages a row: one grid step a row, nothing
    pool-shaped copied."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies

    fn, args = decode_call(chip, ROWS, 2, 128, "bfloat16", BUCKET // PAGE,
                           group=16)
    lowered = jax.jit(fn, donate_argnums=0).lower(*args)
    assert kernel_grids(lowered.as_text()) == [(ROWS,)]
    text = lowered.compile().as_text()
    assert "ds_flash_decode_paged" in text
    assert payload_shaped_copies(text, args[0]["k"].shape) == []


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_nemotron_h_serving_programs_compile(chip, monkeypatch, program):
    """Both programs of the share at its published widths (a mixer, an
    expert layer and the attention layer of the eleven blocks: the
    kinds repeat), cache donated, as the engine calls them: a prefill
    chunk of 1024 (eight scan chunks) in a slot and a decode step of 96
    rows. Two grouped matmuls an expert layer, not three; the state and
    the pool are updated where they lie; every scope the benchmark's
    metrics read is in the compiled text."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.models import nemotron_h as nh

    for name in ("deepspeed_tpu.ops.pallas.flash_decode",
                 "deepspeed_tpu.ops.pallas.ssd_prefill",
                 "deepspeed_tpu.ops.pallas.chunk_prefill",
                 "deepspeed_tpu.moe.dropless"):
        _compiled_not_interpreted(monkeypatch, name)
    cfg = nh.nemotron_3_super_share(n_layer=3,
                                    hybrid_override_pattern="ME*")
    model = nh.NemotronHLM(cfg)
    spec = cfg.cache_spec(ROWS, BUCKET, page_size=PAGE, n_pages=PAGES)
    abstract = lambda tree: jax.tree_util.tree_map(     # noqa: E731
        lambda a: chip(a.shape, a.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: nh.init_nemotron_h_params(model, k),
        jax.random.PRNGKey(0)))
    cache = abstract(jax.eval_shape(lambda: init_kv_cache(spec)))
    i32 = lambda *shape: chip(shape, jnp.int32)         # noqa: E731
    per_row = BUCKET // PAGE

    if program == "prefill":
        def fn(params, cache, tokens, positions, table, slots, n_valid):
            return model.serve_apply(params, cache, tokens, positions,
                                     table, slots, n_valid,
                                     attn_impl="flash", attn_block_k=PAGE)
        args = (i32(1, CHUNK), i32(1, CHUNK), i32(1, per_row), i32(1),
                i32(1))
    else:
        def fn(params, cache, tokens, positions, tables):
            live = (tables[:, 0] != 0).astype(jnp.int32)
            return model.serve_apply(
                params, cache, tokens[:, None], positions[:, None], tables,
                jnp.arange(ROWS, dtype=jnp.int32), live,
                attn_impl="flash", attn_block_k=PAGE)
        args = (i32(ROWS), i32(ROWS), i32(ROWS, per_row))
    compiled = jax.jit(fn, donate_argnums=1).lower(
        params, cache, *args).compile()
    text = compiled.as_text()
    # two grouped matmuls (up, down), as before the held path became a
    # loop over live tiles (ISSUE 45: 10 a decode program of the cell's
    # five expert layers), and the layer's unwritten buffer of sorted
    # rows; in decode the attention kernel
    pairs = (CHUNK if program == "prefill" else ROWS) * \
        cfg.num_experts_per_tok
    # (a prefill's fourth kernel since ISSUE 56: the mixer's chunked
    # scan, `ds_ssd_prefill`, over the mixer's own ``x`` and ``y``: no
    # copy of either layout of 1024 x 8192)
    # (and its fifth since ISSUE 58: the attention layer's chunk, told
    # "flash" as the engine tells it: the chunk's kernel under
    # ds_attn_prefill_plain, no [.., 1024, 5120] float32 scores left)
    assert held_experts_calls(text, pairs, cfg.moe_latent_size) == \
        (5 if program == "prefill" else 4, 2, 1)
    assert chunk_kernel_calls(text) == \
        ((1, 1) if program == "prefill" else (0, 0))
    assert scores_of_a_bucket(text, CHUNK, BUCKET) == []
    for tokens in ((CHUNK, 8192), (CHUNK, 128, 64)):
        assert payload_shaped_copies(text, tokens) == []
    for scope in ("ds_ssm_in_proj", "ds_ssm_conv", "ds_ssm_scan",
                  "ds_ssm_gate_norm", "ds_ssm_out_proj", "ds_moe_route",
                  "ds_moe_dispatch", "ds_moe_experts", "ds_moe_combine",
                  "ds_moe_latent_down", "ds_moe_latent_up", "ds_moe_shared",
                  "ds_ssd_prefill" if program == "prefill"
                  else "ds_ssm_decode"):
        assert scope in text, scope
    assert ("ds_flash_decode_paged" in text) == (program == "decode")
    assert payload_shaped_copies(text, (ROWS, 128, 64, 128)) == []
    assert payload_shaped_copies(text, (PAGES, 2, 128, PAGE)) == []
    # every cache leaf goes out where it came in
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == cache_bytes
    # beside the weights and the cache a call holds under 1 GB; the
    # prefill program 157 MB where the dense arm's held 687 MB
    assert memory.temp_size_in_bytes < \
        (0.25e9 if program == "prefill" else 1e9)


@pytest.mark.parametrize("rows", [44, 2112])
def test_grouped_matmul_compiles_at_any_row_count(chip, monkeypatch, rows):
    """2 decode rows x 22 pairs are 44 rows of the grouped matmul, whose
    tile would be 4 rows, which Mosaic refuses (my chip run, PR 41: an
    engine of 2 rows could not compile its decode program): the rows are
    padded to whole sublanes. 96 rows x 22 = 2,112 need none."""
    from deepspeed_tpu.moe import dropless

    _compiled_not_interpreted(monkeypatch, "deepspeed_tpu.moe.dropless")

    def fn(x, bank, sizes):
        return dropless.grouped_matmul(x, bank, sizes)

    text = jax.jit(fn).lower(
        chip((rows, 1024), jnp.bfloat16),
        chip((128, 1024, 2688), jnp.bfloat16),
        chip((128,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
    assert (f"bf16[{rows},2688]" in text)

"""`test_tpu_compile.py` for LFM2-8B-A1B (ISSUE 57): both serving
programs of the share at the published widths and the whole depth (the
cell's 192 rows, its bucket of 9,216 and its pool of 3,840 pages),
compiled (not interpreted) for a described ``v5e:2x2`` chip. The
fixtures and helpers are `test_tpu_compile.py`'s."""

import jax
import jax.numpy as jnp
import pytest

from tests.unit.test_tpu_compile import (       # noqa: F401 (fixtures)
    PAGE, _compiled_not_interpreted, chip, chunk_kernel_calls,
    scores_of_a_bucket, topo)

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

ROWS, BUCKET, PAGES, CHUNK = 192, 9216, 3841, 1024


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_the_share_at_published_widths_compiles(chip, monkeypatch, program):
    """Both serving programs of `lfm2_8b_a1b_share` (24 layers, 18
    convolutions and 6 attentions as the published list has them, 8 of
    32 experts) with the cell's 192 rows, bucket and pool: every new
    scope in the program's text, the six decode kernels of a step, every
    cache leaf out where it came in, no window- or pool-shaped copy, and
    a call's temporaries beside the weights and the cache under 2 GB.
    Since ISSUE 58 the prefill program's six attention layers each call
    the chunk's kernel (`ds_flash_prefill_paged`) under
    ``ds_attn_prefill_plain``: no ``[.., 1024, 9216]`` float32 scores are
    left, and the call's temporaries are 297 MB where the dense arm's
    program held 1,508 MB."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.models import lfm2_moe as lf

    for name in ("deepspeed_tpu.ops.pallas.flash_decode",
                 "deepspeed_tpu.ops.pallas.chunk_prefill",
                 "deepspeed_tpu.moe.dropless"):
        _compiled_not_interpreted(monkeypatch, name)
    cfg = lf.lfm2_8b_a1b_share()
    model = lf.Lfm2MoeLM(cfg)
    spec = cfg.cache_spec(ROWS, BUCKET, page_size=PAGE, n_pages=PAGES)
    abstract = lambda tree: jax.tree_util.tree_map(     # noqa: E731
        lambda a: chip(a.shape, a.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: lf.init_lfm2_moe_params(model, k), jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == \
        2_526_625_216
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
    for name in ("ds_sconv_mixer", "ds_sconv_in_proj", "ds_sconv_taps",
                 "ds_sconv_out_proj", "ds_attn_qkv", "ds_attn_qk_norm",
                 "ds_attn_out", "ds_mlp", "ds_experts", "ds_moe_route",
                 "ds_moe_experts", "ds_head",
                 "ds_attn_prefill_plain" if program == "prefill"
                 else "ds_attn_decode_plain"):
        assert name in text, name
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "ds_flash_decode_paged"
               in line]
    assert len(kernels) == (6 if program == "decode" else 0)
    assert chunk_kernel_calls(text) == \
        ((6, 6) if program == "prefill" else (0, 0))
    assert scores_of_a_bucket(text, CHUNK, BUCKET) == []
    assert payload_shaped_copies(text, (2, ROWS, 2048)) == []
    assert payload_shaped_copies(text, (PAGES, 8, 64, PAGE)) == []
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    assert cache_bytes == 6 * 2 * PAGES * 8 * 64 * PAGE * 2 + \
        ROWS * 147_456
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == cache_bytes
    assert memory.temp_size_in_bytes < \
        (0.5e9 if program == "prefill" else 2e9), memory.temp_size_in_bytes

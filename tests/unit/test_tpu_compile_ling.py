"""`test_tpu_compile.py` for Ling-3.0 (ISSUE 55): the per-channel delta
rule's two kernels at the cell's widths and both serving programs of the
share at the published widths (the cell's 64 rows, its bucket of 34,816
and its latent pool), compiled (not interpreted) for a described
``v5e:2x2`` chip. The fixtures and helpers are `test_tpu_compile.py`'s."""

import jax
import jax.numpy as jnp
import pytest

from tests.unit.test_tpu_compile import (       # noqa: F401 (fixtures)
    PAGE, _compiled_not_interpreted, chip, kernel_grids, topo)

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

ROWS, BUCKET, PAGES, CHUNK = 64, 34816, 4097, 1024
H, K, Q = 32, 128, 64


def test_the_chunked_kda_kernel_compiles(chip, monkeypatch):
    """One prefill call's delta rule at the cell's widths: 1,024 tokens,
    32 heads of 128 x 128, chunks of 64. One Mosaic kernel whose grid is
    two heads by two chunks a step; ``q``, ``k``, ``v`` go in as they
    lie, and nothing of XLA's triangular solve is left."""
    from deepspeed_tpu.ops import kda
    from deepspeed_tpu.ops.pallas.kda import KDA_SCAN_NAME

    _compiled_not_interpreted(monkeypatch, "deepspeed_tpu.ops.pallas.kda")
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (chip((CHUNK, H, K), bf16), chip((CHUNK, H, K), bf16),
            chip((CHUNK, H, K), bf16), chip((CHUNK, H, K), f32),
            chip((CHUNK, H), f32), chip((H, K, K), f32))
    lowered = jax.jit(lambda *a: kda.kda_chunked(*a, Q)).lower(*args)
    assert kernel_grids(lowered.as_text()) == [(H // 2, CHUNK // Q // 2)]
    text = lowered.compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert KDA_SCAN_NAME in text and "riangular" not in text
    big = [line for line in text.splitlines()
           if (" copy(" in line or " transpose(" in line)
           and "bf16[%d,%d]" % (CHUNK, H * K) in line]
    assert big == []


def test_the_kda_step_kernel_compiles(chip, monkeypatch):
    """One layer's decode step at the cell's widths: 64 slots of 32
    heads of 128 x 128 float32. One Mosaic kernel, a grid step a slot;
    the state goes out where it came in and nothing state-shaped is
    copied round it."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.ops import kda
    from deepspeed_tpu.ops.pallas.kda import KDA_STEP_NAME

    _compiled_not_interpreted(monkeypatch, "deepspeed_tpu.ops.pallas.kda")
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (chip((ROWS, H, K), bf16), chip((ROWS, H, K), bf16),
            chip((ROWS, H, K), bf16), chip((ROWS, H, K), f32),
            chip((ROWS, H), f32), chip((ROWS, H, K, K), f32),
            chip((ROWS,), jnp.bool_))
    lowered = jax.jit(kda.kda_step, donate_argnums=5).lower(*args)
    assert kernel_grids(lowered.as_text()) == [(ROWS,)]
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert KDA_STEP_NAME in text
    assert payload_shaped_copies(text, (ROWS, H, K, K)) == []
    assert compiled.memory_analysis().alias_size_in_bytes == \
        ROWS * H * K * K * 4


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_the_share_at_published_widths_compiles(chip, monkeypatch, program):
    """Both serving programs of `ling_3_flash_share` (layers K K | K K K
    M K K, 128 of 512 experts) with the cell's 64 rows, bucket and
    latent pool: the seven KDA kernels and the latent layer's kernel of
    each program under their scopes, every cache leaf out where it came
    in, no state- or pool-shaped copy, and a call's temporaries beside
    the weights and the cache under 2 GB."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.models import ling_hybrid as lh
    from deepspeed_tpu.ops.pallas.kda import KDA_SCAN_NAME, KDA_STEP_NAME

    for name in ("deepspeed_tpu.ops.pallas.flash_decode",
                 "deepspeed_tpu.ops.pallas.latent_prefill",
                 "deepspeed_tpu.ops.pallas.kda",
                 "deepspeed_tpu.moe.dropless"):
        _compiled_not_interpreted(monkeypatch, name)
    cfg = lh.ling_3_flash_share()
    model = lh.LingHybridLM(cfg)
    spec = cfg.cache_spec(ROWS, BUCKET, page_size=PAGE, n_pages=PAGES)
    abstract = lambda tree: jax.tree_util.tree_map(     # noqa: E731
        lambda a: chip(a.shape, a.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: lh.init_ling_hybrid_params(model, k),
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
    kernel = KDA_SCAN_NAME if program == "prefill" else KDA_STEP_NAME
    scope = "ds_kda_scan/" if program == "prefill" else "ds_kda_step/"
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and kernel in line]
    assert len(calls) == 7 and all(scope in line for line in calls)
    assert "riangular" not in text
    for name in ("ds_kda_mixer", "ds_kda_gate", "ds_attn_gate",
                 "ds_mla_project", "ds_mlp", "ds_moe_route",
                 "ds_moe_experts", "ds_moe_shared",
                 "ds_mla_prefill_attn" if program == "prefill"
                 else "ds_mla_decode_attn"):
        assert name in text, name
    assert ("ds_flash_decode_paged" in text) == (program == "decode")
    assert ("ds_flash_prefill_latent" in text) == (program == "prefill")
    assert payload_shaped_copies(text, (ROWS, H, K, K)) == []
    assert payload_shaped_copies(text, (PAGES, 1, 576, PAGE)) == []
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == cache_bytes
    assert memory.temp_size_in_bytes < 2e9, memory.temp_size_in_bytes

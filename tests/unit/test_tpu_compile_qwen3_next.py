"""`test_tpu_compile.py` for Qwen3-Next (ISSUE 43): the decode kernel at
the cell's attention geometry (head size 256, 8 query heads to each of
2 key heads), the chunked delta rule's kernel at the cell's widths
(ISSUE 44), the delta rule's step over the live rows (ISSUE 50) and both
serving programs of the share at the published widths, compiled (not
interpreted) for a described ``v5e:2x2`` chip. A
file of its own, as `test_tpu_compile_nemotron_h.py` is; the fixtures
and helpers are `test_tpu_compile.py`'s."""

import jax
import jax.numpy as jnp
import pytest

from tests.unit.test_tpu_compile import (       # noqa: F401 (fixtures)
    PAGE, _compiled_not_interpreted, chip, chunk_kernel_calls, decode_call,
    held_experts_calls, kernel_grids, scores_of_a_bucket, topo)

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

# the cell's engine: 128 rows, a bucket of 5,120 (40 pages), a pool of
# 4,096 pages and the trash page, 2 key heads of 256
ROWS, BUCKET, PAGES, CHUNK = 128, 5120, 4097, 1024


def test_decode_geometry_at_head_size_256():
    """Twice the VMEM a block of a head that any accepted cell asks for,
    far inside the budget: 2 heads x 256 x 128 positions, two slots each
    of keys and values."""
    from deepspeed_tpu.ops.pallas import flash_decode as fd

    assert fd.check_decode_geometry(PAGE, PAGE, jnp.bfloat16, 2, 256,
                                    False) == PAGE
    need = fd.paged_vmem_bytes(2, 256, PAGE, jnp.bfloat16, False)
    assert need == 2 * fd.paged_vmem_bytes(2, 128, PAGE, jnp.bfloat16, False)
    assert need < fd.PAGED_VMEM_BUDGET // 8
    with pytest.raises(fd.KernelGeometryError):
        fd.check_decode_geometry(4096, 4096, jnp.float32, 2, 256, False)


def test_two_key_heads_of_eight_queries_at_256_decode_compiles(chip):
    """The decode kernel with 8 query heads to each of 2 key heads of
    256, 128 rows over 40 pages a row: one grid step a row, nothing
    pool-shaped copied."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies

    fn, args = decode_call(chip, ROWS, 2, 256, "bfloat16", BUCKET // PAGE,
                           group=8)
    lowered = jax.jit(fn, donate_argnums=0).lower(*args)
    assert kernel_grids(lowered.as_text()) == [(ROWS,)]
    text = lowered.compile().as_text()
    assert "ds_flash_decode_paged" in text
    assert payload_shaped_copies(text, args[0]["k"].shape) == []


def test_the_chunked_delta_rule_kernel_compiles(chip, monkeypatch):
    """One prefill call's delta rule at the cell's widths: 1,024 tokens,
    16 key heads of 128 serving 32 value heads of 128, chunks of 64. One
    Mosaic kernel whose grid is a key head by two chunks a step; the
    unrepeated ``[T, Hk, K]`` queries and keys go in as they lie (no
    copy or transpose of an operand), and nothing of XLA's triangular
    solve is left."""
    from deepspeed_tpu.ops import gated_delta
    from deepspeed_tpu.ops.pallas.gated_delta import GATED_DELTA_NAME

    _compiled_not_interpreted(monkeypatch,
                              "deepspeed_tpu.ops.pallas.gated_delta")
    T, Hk, Hv, K, V, Q = CHUNK, 16, 32, 128, 128, 64
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (chip((T, Hk, K), bf16), chip((T, Hk, K), bf16),
            chip((T, Hv, V), bf16), chip((T, Hv), f32), chip((T, Hv), f32),
            chip((Hv, K, V), f32))
    lowered = jax.jit(
        lambda *a: gated_delta.gated_delta_chunked(*a, Q)).lower(*args)
    assert kernel_grids(lowered.as_text()) == [(Hk, T // Q // 2)]
    text = lowered.compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert GATED_DELTA_NAME in text
    assert "riangular" not in text
    big = [line for line in text.splitlines()
           if (" copy(" in line or " transpose(" in line)
           and ("[%d,%d]" % (T, Hk * K) in line
                or "[%d,%d]" % (T, Hv * V) in line)]
    assert big == []


def test_the_delta_rule_step_kernel_compiles(chip, monkeypatch):
    """One layer's decode step at the cell's widths: 128 slots of 32
    value heads of 128 x 128 float32 under 16 key heads. One Mosaic
    kernel, a grid step a slot (a step behind the list of live rows
    stays on the block it has); the state goes out where it came in,
    and nothing state-shaped is copied, selected or broadcast round
    it."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.ops import gated_delta
    from deepspeed_tpu.ops.pallas.gated_delta import GATED_DELTA_STEP_NAME

    _compiled_not_interpreted(monkeypatch,
                              "deepspeed_tpu.ops.pallas.gated_delta")
    Hk, Hv, K, V = 16, 32, 128, 128
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (chip((ROWS, Hk, K), bf16), chip((ROWS, Hk, K), bf16),
            chip((ROWS, Hv, V), bf16), chip((ROWS, Hv), f32),
            chip((ROWS, Hv), f32), chip((ROWS, Hv, K, V), f32),
            chip((ROWS,), jnp.bool_))
    lowered = jax.jit(gated_delta.gated_delta_step,
                      donate_argnums=5).lower(*args)
    assert kernel_grids(lowered.as_text()) == [(ROWS,)]
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert GATED_DELTA_STEP_NAME in text
    assert payload_shaped_copies(text, (ROWS, Hv, K, V)) == []
    assert _state_shaped(text, Hv, K, V) == [GATED_DELTA_STEP_NAME]
    assert compiled.memory_analysis().alias_size_in_bytes == \
        ROWS * Hv * K * V * 4


def _state_shaped(text, Hv, K, V):
    """The name of every instruction of a compiled program that takes or
    gives a whole ``[ROWS, Hv, K, V]`` float32 state, but the program's
    parameters and what only passes one on (a tuple and its parts, a
    bitcast), a Mosaic call by its kernel's name."""
    import re

    state = "f32[%d,%d,%d,%d]" % (ROWS, Hv, K, V)
    names = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = .*? ([\w-]+)\(", line)
        if not m or state not in line or m.group(2) in (
                "parameter", "tuple", "get-tuple-element", "bitcast"):
            continue
        kernel = re.search(r'/(ds_\w+)/pallas_call', line) \
            if "tpu_custom_call" in line else None
        names.append(kernel.group(1) if kernel else m.group(1))
    return names


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_qwen3_next_serving_programs_compile(chip, monkeypatch, program):
    """Both programs of the share at its published widths, cache
    donated, as the engine calls them: a prefill chunk of 1024 (sixteen
    chunks of the delta rule) in a slot, over one period (three Gated
    DeltaNet blocks and an attention block, each with its expert
    layer), and a decode step of 128 rows over the cell's two periods.
    Three grouped matmuls an expert layer; in prefill one delta-rule
    kernel a Gated DeltaNet block (no triangular solve of XLA's), in
    decode one step kernel a Gated DeltaNet block, the only
    instruction that names a whole state; the state and the pool are
    updated where they lie; every scope the benchmark's metrics read is
    in the compiled text."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.models import qwen3_next as qn
    from deepspeed_tpu.ops.pallas.gated_delta import GATED_DELTA_STEP_NAME

    for name in ("deepspeed_tpu.ops.pallas.flash_decode",
                 "deepspeed_tpu.ops.pallas.gated_delta",
                 "deepspeed_tpu.ops.pallas.chunk_prefill",
                 "deepspeed_tpu.moe.dropless"):
        _compiled_not_interpreted(monkeypatch, name)
    periods = {"prefill": 1, "decode": 2}[program]
    cfg = qn.qwen3_next_80b_share(n_layer=4 * periods)
    model = qn.Qwen3NextLM(cfg)
    spec = cfg.cache_spec(ROWS, BUCKET, page_size=PAGE, n_pages=PAGES)
    abstract = lambda tree: jax.tree_util.tree_map(     # noqa: E731
        lambda a: chip(a.shape, a.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: qn.init_qwen3_next_params(model, k),
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
    # a period's Mosaic calls: three grouped matmuls (gate, up, down) in
    # each of its four blocks, as before the held path became a loop over
    # live tiles (ISSUE 45), and a block's unwritten buffer of sorted
    # rows; in prefill the three delta rules' kernel, in decode the
    # three delta rules' step (ISSUE 50) and the attention block's;
    # since ISSUE 58 the prefill's attention block's too, told "flash"
    # as the engine tells it: the chunk's kernel under
    # ds_attn_prefill_plain, no [.., 1024, bucket] float32 scores left
    pairs = (CHUNK if program == "prefill" else ROWS) * \
        cfg.num_experts_per_tok
    assert held_experts_calls(text, pairs, cfg.hidden_size) == \
        ({"prefill": 20, "decode": 40}[program], 12 * periods, 4 * periods)
    assert chunk_kernel_calls(text) == \
        ((1, 1) if program == "prefill" else (0, 0))
    assert scores_of_a_bucket(text, CHUNK, BUCKET) == []
    assert text.count("ds_gated_delta_chunked") >= \
        (3 if program == "prefill" else 0)
    assert "riangular" not in text
    for scope in ("ds_gdn_conv", "ds_attn_gate", "ds_moe_route",
                  "ds_moe_dispatch", "ds_moe_experts", "ds_moe_combine",
                  "ds_moe_shared",
                  "ds_gdn_scan" if program == "prefill" else "ds_gdn_step"):
        assert scope in text, scope
    assert ("ds_flash_decode_paged" in text) == (program == "decode")
    assert payload_shaped_copies(text, (ROWS, 32, 128, 128)) == []
    assert payload_shaped_copies(text, (PAGES, 2, 256, PAGE)) == []
    if program == "decode":
        # six step kernels, each under the mixer's scope, and no fusion
        # that takes or gives a whole state: the kernel's aliased
        # operand is the only thing that names one
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line
                 and GATED_DELTA_STEP_NAME in line]
        assert len(calls) == 6
        assert all("ds_gdn_step/" in line for line in calls)
        assert _state_shaped(text, 32, 128, 128) == \
            [GATED_DELTA_STEP_NAME] * 6
    # every cache leaf goes out where it came in
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == cache_bytes
    # beside the weights and the cache a call holds under 2 GB; the
    # prefill program (at the cell's bucket of 9,216: 101 MB where the
    # dense arm's held 687 MB) a quarter of one
    assert memory.temp_size_in_bytes < \
        (0.25e9 if program == "prefill" else 2e9), memory.temp_size_in_bytes

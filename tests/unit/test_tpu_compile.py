"""Every Pallas kernel on the main paths compiles for the chip.

The TPU compiler is installed here without a TPU: it compiles for a
chip that is *described* (`v5e:2x2`), which is what catches the faults
interpret mode cannot see — block shapes that break the (8, 128) rule,
scalar stores to VMEM, too much VMEM. Shapes are GPT-2 350M's (16 heads
x 64) at the sizes `chip_smoke.py` runs. A compile that passes is not a
chip run: nothing executes, so results and times are not covered here.

The topology is described inside the `topo` fixture and nowhere else —
only one process may load the TPU library, so no import, `skipif`,
`parametrize` argument or conftest hook may touch it (xdist workers all
import this file). All cases live in this one file for the same reason.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# reads compiled programs: the compiler's normal pipeline (tests/conftest.py)
pytestmark = pytest.mark.full_compile

B, T, H, D = 8, 1024, 16, 64          # GPT-2 350M: 16 heads x 64
PAGE = 128                            # chip_smoke.py's page size
# the serve cell's pool (`benchmarks/suite`): 48 rows of 8 pages + trash
ROWS, N_PAGES = 48, 385


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import (
        compilation_cache as jax_cc)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology executable is written to the persistent
    # cache but cannot be read back without a chip: keep it off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax_cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    jax_cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` -> an abstract array on the first chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# what the training flash kernels see in the benchmark's cells
# (`benchmarks/suite`): GPT-2 medium's 8 rows of 1024 x 16 heads x 64; two
# of GPT-2 XL's rows a chip with its 25 heads (the last lane group half
# full); OLMoE's 2 rows of 4096 x 16 heads x 128, and the one row its
# driver hands the program and the reference together (`both`)
OLMOE_SHAPE = (2, 4096, 16, 128)
FLASH_SHAPES = {"gpt2-350m": (B, T, H, D), "gpt2-xl-25-heads": (2, T, 25, D),
                "olmoe-t4096-d128": OLMOE_SHAPE,
                "olmoe-both-1-row": (1, 4096, 16, 128),
                # folded: a head size that is no divisor or multiple of
                # the 128 lanes (GPT-2 2.7B's 80)
                "d80-folded": (2, T, 32, 80)}


def _flash_entry(shape, **kw):
    """The kernel entry `flash_attention` wraps, with its default
    blocks: the public function picks interpret mode off the first
    device (the CPU here), so the tests steer this one."""
    import inspect
    fa = _flash_module()
    bound = inspect.signature(fa.flash_attention).parameters
    blocks = [fa._fit_block(bound[b].default, shape[1])
              for b in ("block_q", "block_k")]

    def fwd(q, k, v, key_bias=None, seed=None):
        return fa._flash_pallas(
            q, k, v, key_bias, seed, 0, kw.get("causal", True),
            shape[-1] ** -0.5, *blocks, kw.get("dropout", 0.0), None, False)
    return fwd


def _flash_module():
    import importlib
    # the package re-exports the function under the module's name
    return importlib.import_module(
        "deepspeed_tpu.ops.pallas.flash_attention")


@pytest.mark.parametrize("shape", list(FLASH_SHAPES))
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_compiles(chip, grad, shape):
    shape = FLASH_SHAPES[shape]
    fwd = _flash_entry(shape)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    x = chip(shape, jnp.bfloat16)
    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    text = compiled_text(fn, x, x, x)
    assert "tpu_custom_call" in text
    for name in ("ds_flash_fwd",) + (("ds_flash_dq", "ds_flash_dkv")
                                     if grad else ()):
        assert name in text


@pytest.mark.parametrize("case", ["gpt2-dropout", "xl-dropout",
                                  "bert-bias-dropout", "t4096-dropout"])
def test_flash_attention_variants_compile(chip, case):
    """What no cell runs: in-kernel dropout (its hash and positions are
    more score-sized values in VMEM, at tiles of 1024), the key bias with
    its gradient, a call that is not causal."""
    shape, causal, bias = {
        "gpt2-dropout": ((B, T, H, D), True, False),
        "xl-dropout": ((2, T, 25, D), True, False),
        "bert-bias-dropout": ((8, 512, 16, 64), False, True),
        "t4096-dropout": ((1, 4096, 4, 128), True, False)}[case]
    fwd = _flash_entry(shape, causal=causal, dropout=0.1)

    def loss(q, k, v, key_bias, seed):
        return fwd(q, k, v, key_bias, seed).astype(jnp.float32).sum()

    x = chip(shape, jnp.bfloat16)
    text = compiled_text(
        jax.grad(loss, argnums=(0, 1, 2) + ((3,) if bias else ())),
        x, x, x, chip(shape[:2], jnp.float32) if bias else None,
        chip((), jnp.int32))
    for name in ("ds_flash_fwd", "ds_flash_dq", "ds_flash_dkv"):
        assert name in text


def test_flash_kernel_grids(chip):
    """Steps a kernel launches at the cells' shapes, read from the
    lowered calls: (rows, lane groups, live tiles). Medium: 8 rows x 8
    pairs of heads x the one tile T 1024 makes; OLMoE: 2 x 16 heads x 10
    of 16 tiles (the six the causal mask kills are no part of the walk).
    Until PR 30 the grid was (rows x heads, q tiles, kv tiles) over the
    whole square: 128 x 2 x 2 and 32 x 8 x 8."""
    for shape, grid in (((B, T, H, D), (8, 8, 1)),
                        ((2, T, 25, D), (2, 13, 1)),
                        (OLMOE_SHAPE, (2, 16, 10))):
        fwd = _flash_entry(shape)
        x = chip(shape, jnp.bfloat16)
        lowered = jax.jit(jax.grad(
            lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))).lower(x, x, x)
        assert kernel_grids(lowered.as_text()) == [grid] * 3, shape


@pytest.mark.parametrize("bank", [(64, 2048, 1024), (64, 1024, 2048)],
                         ids=["gate-up", "down"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_grouped_matmul_compiles(chip, monkeypatch, grad, bank):
    """The experts' grouped matmul (`megablox.gmm` in the tiles
    `moe/dropless.py` picks) at the OLMoE cell's shapes: 65,536
    token-expert pairs over 64 experts; differentiated, the rows'
    gradient is the same kernel and the bank's is `tgmm`."""
    from deepspeed_tpu.moe.dropless import grouped_matmul

    _compiled_not_interpreted(monkeypatch, "deepspeed_tpu.moe.dropless")

    def loss(rows, w, group_sizes):
        return grouped_matmul(rows, w, group_sizes).astype(
            jnp.float32).sum()

    args = (chip((8192 * 8, bank[1]), jnp.bfloat16),
            chip(bank, jnp.bfloat16), chip((bank[0],), jnp.int32))
    fn = jax.grad(loss, argnums=(0, 1)) if grad else grouped_matmul
    text = compiled_text(fn, *args)
    # an instruction is named after the innermost jit around its kernel;
    # a sum's gradient needs no forward product
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == (2 if grad else 1)
    assert sum("tgmm" in line.split("=")[0] for line in calls) == \
        (1 if grad else 0)
    assert all("gmm" in line.split("=")[0] for line in calls)


# every storage dtype `inference.kv_cache_dtype` accepts: the model's
# compute dtype (f32 for a served checkpoint, bf16 otherwise), the two
# plain overrides, and the three codecs
KV_DTYPES = ["float32", "bfloat16", "int8", "float8_e4m3fn", "float8_e5m2"]


# (heads a device holds, head size): GPT-2 medium (the serve cell), GPT-2
# XL's 25 heads, one shard of medium under TP = 4, OLMoE's head size
DECODE_GEOMETRIES = {"medium": (16, 64), "xl": (25, 64), "tp4": (4, 64),
                     "d128": (16, 128)}


def decode_call(chip, rows, heads, head_dim, kv_dtype, per, group=1,
                **kw):
    """``(fn, args)``: `flash_decode_paged` compiled, not interpreted,
    over abstract arrays on the chip: ``rows`` rows of ``per`` pages,
    the pool (``fn``'s first argument, to donate) and the step's new
    leaves in ``kv_dtype``, scale leaves beside a codec's. A served
    checkpoint computes in f32 (q and the cache both); the other
    storage dtypes sit under a bf16 model."""
    from deepspeed_tpu.ops.pallas.flash_decode import flash_decode_paged

    dt = jnp.dtype(kv_dtype)
    n_pages = rows * per + 1
    pool = {"k": chip((n_pages, heads, head_dim, PAGE), dt)}
    new = {"k": chip((rows, 1, heads, head_dim), dt)}
    if dt.itemsize == 1:
        pool["k_scale"] = chip((n_pages, heads, PAGE), jnp.float32)
        new["k_scale"] = chip((rows, 1, heads), jnp.float32)
    for tree in (pool, new):
        tree.update({"v" + name[1:]: leaf for name, leaf in tree.items()})
    q = chip((rows, 1, heads * group, head_dim),
             dt if kv_dtype == "float32" else jnp.bfloat16)

    def fn(pool, q, new, pos, pt):
        return flash_decode_paged(q, new, pool, pos, pt, interpret=False,
                                  **kw)
    return fn, (pool, q, new, chip((rows,), jnp.int32),
                chip((rows, per), jnp.int32))


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
@pytest.mark.parametrize("geometry", list(DECODE_GEOMETRIES))
def test_flash_decode_compiles(chip, geometry, kv_dtype):
    """Every pool dtype and geometry, the step's write with it: the new
    lanes rolled to their place, selected into the block in float32 and
    rounded back to the storage dtype, the block DMA'd back. With the
    pool donated the call's aliasing holds: nothing pool-shaped is
    copied round the kernel."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies

    heads, head_dim = DECODE_GEOMETRIES[geometry]
    fn, args = decode_call(chip, B, heads, head_dim, kv_dtype, T // PAGE)
    text = jax.jit(fn, donate_argnums=0).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert payload_shaped_copies(text, args[0]["k"].shape) == []


# (rows, tokens a row): the decode step, one prefill chunk, a
# speculative verify round
PAGED_PROGRAMS = {"decode": (ROWS, 1), "prefill": (1, 64),
                  "verify": (ROWS, 4)}


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
@pytest.mark.parametrize("program", list(PAGED_PROGRAMS))
def test_paged_layer_copies_no_pool(chip, monkeypatch, program, kv_dtype):
    """One layer of the serving programs over the paged pool, as
    `inference/cache.py:cached_attention` runs it with the pool donated:
    the write, then the flash kernel (decode) or the gathered view and
    the dense attention (prefill, verify). The pool stays where it is:
    no `copy` in the program has the shape of a pool leaf. Positions
    minor-most in memory and every write indexed on the page axis alone
    are what hold that."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.inference import cache

    _compiled_not_interpreted(monkeypatch,
                              "deepspeed_tpu.ops.pallas.flash_decode")
    dt = jnp.dtype(kv_dtype)
    compute = jnp.float32 if kv_dtype == "float32" else jnp.bfloat16
    rows, tokens = PAGED_PROGRAMS[program]
    pool = (N_PAGES, H, D, PAGE)
    layer = {"k": chip(pool, dt), "v": chip(pool, dt)}
    if dt.itemsize == 1:
        layer["k_scale"] = layer["v_scale"] = chip((N_PAGES, H, PAGE),
                                                   jnp.float32)
    x = chip((rows, tokens, H, D), compute)

    def fn(q, k, v, layer, positions, tables):
        return cache.cached_attention(q, k, v, layer, positions, compute,
                                      impl="flash", page_table=tables)

    text = jax.jit(fn, donate_argnums=3).lower(
        x, x, x, layer, chip((rows, tokens), jnp.int32),
        chip((rows, T // PAGE), jnp.int32)).compile().as_text()
    assert ("tpu_custom_call" in text) == (program == "decode")
    # the kernel takes the 4-D pool as it is (`ANY` memory)
    assert payload_shaped_copies(text, pool) == []
    # the decode step's write is the kernel's (PR 33): the program
    # holds no loop and no update of a slab of a pool leaf; the other
    # two keep theirs (`_write_chunk`, `_write_tokens`)
    leaf = "[%d,%d,%d,%d]" % pool       # an op's result precedes its opcode
    slab_writes = [line for line in text.splitlines()
                   if leaf in line.partition("dynamic-update-slice(")[0]
                   and "dynamic-update-slice(" in line]
    loops = [line for line in text.splitlines() if " while(" in line]
    if program == "decode":
        assert slab_writes == [] and loops == []
    else:
        assert slab_writes
        assert bool(loops) == (program == "verify")


def held_experts_calls(text, pairs, width):
    """``(Mosaic calls, grouped matmuls among them, unwritten buffers
    among them)`` of a compiled serving program whose expert layers
    hold a share (`moe/dropless.py:_held_moe`, ISSUE 45), having seen
    that no ``[pairs, width]`` bfloat16 buffer of sorted rows is zeroed
    or copied: the dispatch fills the live tiles of a buffer that a
    kernel with no body hands over, and the grouped matmuls are the
    calls they were, none inside a loop."""
    import re

    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert [line for line in text.splitlines() if re.search(
        r"= bf16\[%d,%d\]\S* (copy|broadcast)\(" % (pairs, width),
        line)] == []
    return (len(calls), sum("gmm" in line.split("=")[0] for line in calls),
            sum("ds_moe_unwritten_rows" in line for line in calls))


def kernel_grids(lowered_text):
    """The grid of every Mosaic kernel in a lowered (StableHLO) text, in
    order: each `tpu_custom_call` carries its serialized Mosaic module,
    whose entry function has the grid as `iteration_bounds`."""
    import base64
    import json
    import re

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    grids = []
    ctx = jax_mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True      # `stable_mosaic.*`
    with ctx:
        for config in re.findall(
                r'@tpu_custom_call\(.*?backend_config = "(\{.*?\})"',
                lowered_text, re.S):
            body = json.loads(config.replace("\\22", '"'))[
                "custom_call_config"]["body"]
            module = ir.Module.parse(base64.b64decode(body))
            for op in module.body.operations:
                if "iteration_bounds" in op.attributes:
                    grids.append(tuple(
                        ir.DenseI64ArrayAttr(
                            op.attributes["iteration_bounds"])))
    return grids


def test_flash_decode_grid(chip):
    """Steps a layer at the serve cell's shape, read from the lowered
    call itself. The kernel launches one step a row, 48; until PR 27 it
    launched rows x heads x blocks = 6,144 whatever the rows held
    (`PERF.md` section 6), and a change that brings that back fails
    here."""
    fn, args = decode_call(chip, ROWS, H, D, "float32", T // PAGE)
    assert kernel_grids(jax.jit(fn).lower(*args).as_text()) == [(ROWS,)]


@pytest.mark.parametrize("shape", [(50257, 1024), (1024, 4096), (1024,)],
                         ids=str)
def test_pallas_adam_leaf_compiles(chip, shape):
    from deepspeed_tpu.ops.pallas.fused_adam import _leaf_update

    leaf = chip(shape, jnp.float32)
    scalars = chip((8,), jnp.float32)
    text = compiled_text(
        lambda p, g, m, v, s: _leaf_update(p, g, m, v, s,
                                           interpret=False),
        leaf, leaf, leaf, leaf, scalars)
    assert "tpu_custom_call" in text


def test_block_sparse_attention_compiles(chip):
    from deepspeed_tpu.ops.sparse_attention.block_sparse_attention import (
        block_sparse_attention)

    block = 64
    nb = T // block
    # causal band: each query block sees itself and the three before it
    band = np.tril(np.ones((nb, nb), np.int64)) - \
        np.tril(np.ones((nb, nb), np.int64), -4)
    layout = np.broadcast_to(band, (H, nb, nb))

    def loss(q, k, v):
        return block_sparse_attention(
            q, k, v, layout, block, causal=True,
            implementation="pallas",
            interpret=False).astype(jnp.float32).sum()

    x = chip((B, T, H, D), jnp.bfloat16)
    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert "tpu_custom_call" in text



# --- a prompt's chunk over a per-head page pool (ISSUE 58) -------------------

# cell: query heads, key heads, head size, chunk, bucket, pool pages
CHUNK_CELLS = {"lfm2": (32, 8, 64, 1024, 9216, 3841),
               "granite": (32, 8, 64, 512, 4608, 1729),
               "qwen3-next": (16, 2, 256, 1024, 9216, 4097),
               "nemotron": (32, 2, 128, 1024, 5120, 3841)}


def chunk_kernel_calls(text):
    """``(calls, those of them under ds_attn_prefill_plain)`` of the
    chunk's kernel in a compiled program's text."""
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line
             and "ds_flash_prefill_paged" in line]
    return len(calls), sum("ds_attn_prefill_plain" in c for c in calls)


def scores_of_a_bucket(text, chunk, bucket):
    """Instructions whose float32 result is as long as the chunk times
    the bucket (a key head's, a query head's or all heads' scores of
    the dense arm), in a compiled program's text."""
    import re
    return re.findall(r"= f32\[(?:\d+,)*%d,%d\]" % (chunk, bucket), text)


@pytest.mark.parametrize("cell", sorted(CHUNK_CELLS))
def test_chunk_prefill_kernel_compiles(chip, cell):
    """The chunk's kernel (`ops/pallas/chunk_prefill.py`) at the four
    serving cells' geometries, the pool read where it lies: a grid of
    (key heads, query blocks), no copy of anything pool-shaped, no
    temporaries beside the output."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.ops.pallas.chunk_prefill import (chunk_blocks,
                                                        flash_prefill_paged)

    Hq, H, D, T, bucket, n_pages = CHUNK_CELLS[cell]
    pool = chip((n_pages, H, D, PAGE), jnp.bfloat16)

    def fn(q, k, v, table, c0):
        return flash_prefill_paged(q, k, v, table, c0, scale=D ** -0.5,
                                   interpret=False)
    lowered = jax.jit(fn).lower(
        chip((T, Hq, D), jnp.bfloat16), pool, pool,
        chip((bucket // PAGE,), jnp.int32), chip((), jnp.int32))
    bq, pages = chunk_blocks(T, Hq // H, PAGE)
    assert pages == 8 and (Hq // H) * bq in (1024, 2048)
    assert kernel_grids(lowered.as_text()) == [(H, T // bq)]
    compiled = lowered.compile()
    text = compiled.as_text()
    assert chunk_kernel_calls(text) == (1, 0)
    assert payload_shaped_copies(text, (n_pages, H, D, PAGE)) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# --- the hybrid model's serving programs (ISSUE 31) -------------------------

@pytest.mark.parametrize("cell", ["granite", "nemotron"])
def test_ssd_prefill_kernel_compiles(chip, cell):
    """The chunked scan's kernel (`ops/pallas/ssd_prefill.py`) at the two
    serving cells' calls: 512 tokens of 64 heads over one B/C group in
    scan chunks of 256, and 1,024 tokens of 128 heads over 8 groups in
    chunks of 128; sixteen heads a grid step, the chunk axis last."""
    from deepspeed_tpu.ops.pallas import ssd_prefill

    T, H, G, Q = {"granite": (512, 64, 1, 256),
                  "nemotron": (1024, 128, 8, 128)}[cell]
    maps = (T, 128) if G == 1 else (T, G, 128)
    args = (chip((T, H, 64), jnp.bfloat16), chip((T, H), jnp.float32),
            chip((H,), jnp.float32), chip(maps, jnp.bfloat16),
            chip(maps, jnp.bfloat16), chip((H, 64, 128), jnp.float32))
    assert ssd_prefill.head_block(H, G, 64, 128, Q, 2) == 16
    lowered = jax.jit(lambda *a: ssd_prefill._scan_call(
        *a, chunk=Q, interpret=False)).lower(*args)
    assert kernel_grids(lowered.as_text()) == [(H // 16, T // Q)]
    assert "ds_ssd_prefill" in lowered.compile().as_text()


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "float32", "int8"])
def test_grouped_query_flash_decode_compiles(chip, kv_dtype):
    """The decode kernel with 4 query heads to each of 8 key heads and
    the model's own score scale, at the hybrid cell's shapes: 48 rows,
    pages of 128, a pool of 48 x 36 pages."""
    fn, args = decode_call(chip, ROWS, 8, 64, kv_dtype, 36, group=4,
                           scale=1 / 64)
    lowered = jax.jit(fn).lower(*args)
    assert kernel_grids(lowered.as_text()) == [(ROWS,)]
    assert "ds_flash_decode_paged" in lowered.compile().as_text()


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_hybrid_serving_programs_compile(chip, monkeypatch, program):
    """Both programs of granite-4.0-h-micro at its published widths (one
    mixer layer and one attention layer of the 40: the layers repeat),
    cache donated, as the engine calls them: a prefill chunk of 512 in
    slot 7 and a decode step of 48 rows. The state is updated where it
    lies: no copy in the program has its shape."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.models import granite_hybrid as gh

    for name in ("deepspeed_tpu.ops.pallas.flash_decode",
                 "deepspeed_tpu.ops.pallas.ssd_prefill",
                 "deepspeed_tpu.ops.pallas.chunk_prefill"):
        _compiled_not_interpreted(monkeypatch, name)
    cfg = gh.granite_4_0_h_micro(
        num_hidden_layers=2, layer_types=(gh.MAMBA, gh.ATTENTION))
    model = gh.GraniteHybridLM(cfg)
    spec = cfg.cache_spec(ROWS, 4608, page_size=PAGE)
    abstract = lambda tree: jax.tree_util.tree_map(     # noqa: E731
        lambda a: chip(a.shape, a.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: gh.init_granite_hybrid_params(model, k),
        jax.random.PRNGKey(0)))
    cache = abstract(jax.eval_shape(lambda: init_kv_cache(spec)))
    i32 = lambda *shape: chip(shape, jnp.int32)         # noqa: E731

    if program == "prefill":
        def fn(params, cache, tokens, positions, table, slots, n_valid):
            return model.serve_apply(params, cache, tokens, positions,
                                     table, slots, n_valid,
                                     attn_impl="flash", attn_block_k=PAGE)
        args = (i32(1, 512), i32(1, 512), i32(1, 36), i32(1), i32(1))
    else:
        def fn(params, cache, tokens, positions, tables):
            live = (tables[:, 0] != 0).astype(jnp.int32)
            return model.serve_apply(
                params, cache, tokens[:, None], positions[:, None], tables,
                jnp.arange(ROWS, dtype=jnp.int32), live,
                attn_impl="flash", attn_block_k=PAGE)
        args = (i32(ROWS), i32(ROWS), i32(ROWS, 36))
    compiled = jax.jit(fn, donate_argnums=1).lower(
        params, cache, *args).compile()
    text = compiled.as_text()
    # a kernel a program: the decode attention, and since ISSUE 56 the
    # prefill's chunked scan, which reads the mixer's ``x`` and hands on
    # its ``y`` as they lie (no copy of either layout of 512 x 4096)
    # and since ISSUE 58 the attention layer's chunk, told "flash" as the
    # engine tells it: one call of the chunk's kernel under
    # ds_attn_prefill_plain, no [.., 512, 4608] float32 scores left
    # (the dense arm's program held 307.4 MB of temporaries here, this
    # one 4.5 MB), the pool read where it lies
    assert text.count("tpu_custom_call") == \
        (2 if program == "prefill" else 1)
    assert chunk_kernel_calls(text) == \
        ((1, 1) if program == "prefill" else (0, 0))
    assert scores_of_a_bucket(text, 512, 4608) == []
    if program == "prefill":
        assert compiled.memory_analysis().temp_size_in_bytes < 16e6
    assert ("ds_ssd_prefill" in text) == (program == "prefill")
    assert "ds_ssm_scan" in text and "ds_ssm_conv" in text
    for tokens in ((512, 4096), (512, 64, 64)):
        assert payload_shaped_copies(text, tokens) == []
    state = (ROWS, 64, 64, 128)
    assert payload_shaped_copies(text, state) == []
    assert payload_shaped_copies(text, (spec.n_pages, 8, 64, PAGE)) == []
    # every cache leaf goes out where it came in
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes


# --- the latent-attention model's serving programs (ISSUE 34) ---------------

# the cell's engine: 32 rows, a bucket of 17,408 (136 pages), a pool of
# 2,048 pages and the trash page, one 576-wide latent a token a layer
MLA_ROWS, MLA_BUCKET, MLA_PAGES = 32, 17408, 2049


def test_latent_flash_decode_compiles(chip):
    """The decode kernel over a pool of latents at the published sizes:
    one leaf of one 576-wide head, 64 absorbed query heads over it, the
    values the leading 512 sublanes of the block it fetched, one lane
    written. One grid step a row; nothing pool-shaped is copied."""
    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.ops.pallas.flash_decode import flash_decode_paged

    pool = {"k": chip((MLA_PAGES, 1, 576, PAGE), jnp.bfloat16)}
    new = {"k": chip((MLA_ROWS, 1, 1, 576), jnp.bfloat16)}
    q = chip((MLA_ROWS, 1, 64, 576), jnp.bfloat16)

    def fn(pool, q, new, pos, pt):
        return flash_decode_paged(q, new, pool, pos, pt, interpret=False,
                                  scale=0.1447, v_dim=512)
    lowered = jax.jit(fn, donate_argnums=0).lower(
        pool, q, new, chip((MLA_ROWS,), jnp.int32),
        chip((MLA_ROWS, MLA_BUCKET // PAGE), jnp.int32))
    assert kernel_grids(lowered.as_text()) == [(MLA_ROWS,)]
    text = lowered.compile().as_text()
    assert "ds_flash_decode_paged" in text
    assert payload_shaped_copies(text, pool["k"].shape) == []


@pytest.mark.parametrize("program", ["prefill", "prefill-flash", "decode"])
def test_mla_moe_serving_programs_compile(chip, monkeypatch, program):
    """Both programs of Kimi-K2.7-Code's share at its published widths
    (the dense layer and one expert layer of the seven: the expert
    layers repeat), cache donated, as the engine calls them: a prefill
    chunk of 1024 (its walk in XLA, and under ``"flash"`` through the
    prefill kernel, which then holds the only ``[1024, 1024]`` scores:
    no ``[64, 1024, 1024]`` array is left in the program, and the
    kernel's call lies inside the scope the benchmark's metric reads)
    and a decode step of 32 rows over a bucket of 17,408.
    **No ``[heads, chunk, bucket]`` array in either**: the dense path's
    scores would be 64 x 1024 x 17,408 x 4 B = 4.6 GB; the largest
    buffer either program holds besides its arguments is a block of the
    walk, and all its temporaries together are under a quarter of that.
    The pool is updated where it lies."""
    import re

    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.inference.cache import init_kv_cache
    from deepspeed_tpu.models import mla_moe as mm

    for name in ("deepspeed_tpu.ops.pallas.flash_decode",
                 "deepspeed_tpu.ops.pallas.latent_prefill",
                 "deepspeed_tpu.moe.dropless"):
        _compiled_not_interpreted(monkeypatch, name)
    cfg = mm.kimi_k2_share(n_layer=2)
    model = mm.MlaMoeLM(cfg)
    spec = cfg.cache_spec(MLA_ROWS, MLA_BUCKET, page_size=PAGE,
                          n_pages=MLA_PAGES)
    abstract = lambda tree: jax.tree_util.tree_map(     # noqa: E731
        lambda a: chip(a.shape, a.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: mm.init_mla_moe_params(model, k), jax.random.PRNGKey(0)))
    cache = abstract(jax.eval_shape(lambda: init_kv_cache(spec)))
    i32 = lambda *shape: chip(shape, jnp.int32)         # noqa: E731
    per_row = MLA_BUCKET // PAGE

    if program != "decode":
        impl = "flash" if program == "prefill-flash" else "dense"

        def fn(params, cache, tokens, positions, table, slots, n_valid):
            return model.serve_apply(params, cache, tokens, positions,
                                     table, slots, n_valid, attn_impl=impl)
        args = (i32(1, 1024), i32(1, 1024), i32(1, per_row), i32(1), i32(1))
    else:
        def fn(params, cache, tokens, positions, tables):
            live = (tables[:, 0] != 0).astype(jnp.int32)
            return model.serve_apply(
                params, cache, tokens[:, None], positions[:, None], tables,
                jnp.arange(MLA_ROWS, dtype=jnp.int32), live,
                attn_impl="flash", attn_block_k=PAGE)
        args = (i32(MLA_ROWS), i32(MLA_ROWS), i32(MLA_ROWS, per_row))
    compiled = jax.jit(fn, donate_argnums=1).lower(
        params, cache, *args).compile()
    text = compiled.as_text()
    # 3 grouped matmuls a expert layer; the decode kernel a layer
    assert text.count("tpu_custom_call") >= {"decode": 5, "prefill": 3,
                                             "prefill-flash": 5}[program]
    # the expert layer's, and its unwritten buffer of sorted rows (117
    # MB in prefill, of which one tile is filled: ISSUE 45)
    pairs = (MLA_ROWS if program == "decode" else 1024) * \
        cfg.num_experts_per_tok
    assert held_experts_calls(text, pairs, cfg.hidden_size)[1:] == (3, 1)
    for scope in ("ds_mla_project", "ds_moe_route", "ds_moe_experts",
                  "ds_moe_shared", "ds_flash_decode_paged" if
                  program == "decode" else "ds_mla_prefill_attn"):
        assert scope in text, scope
    dense_scores = 64 * 1024 * MLA_BUCKET * 4
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < dense_scores / 4
    # no array of the program has the dense scores' element count
    sizes = {int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text)}
    weights = max(a.size for a in jax.tree_util.tree_leaves(params))
    assert max(sizes) <= weights < dense_scores // 4
    if program != "decode":
        # a block's scores: the XLA walk's largest value, the kernel's
        # own business
        assert (64 * 1024 * 1024 in sizes) == (program == "prefill")
    if program == "prefill-flash":
        from benchmarks.suite.readers.scope_time import scopes_of
        kernel = {k: v for k, v in scopes_of(text, "ds_m").items()
                  if k.startswith("ds_flash_prefill_latent")}
        assert len(kernel) == 2     # a layer
        assert all("ds_mla_prefill_attn" in v for v in kernel.values())
    assert payload_shaped_copies(text, (MLA_PAGES, 1, 576, PAGE)) == []
    assert payload_shaped_copies(text, (MLA_PAGES, 576, PAGE)) == []
    # every cache leaf goes out where it came in
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    assert memory.alias_size_in_bytes == cache_bytes


class _AnswersTpu:
    """Stands in for `jax` inside one kernel module: the kernels ask
    `jax.devices()` whether to interpret, and here that is the CPU."""

    def __getattr__(self, name):
        return getattr(jax, name)

    @staticmethod
    def devices(*_):
        return [type("Dev", (), {"platform": "tpu"})]


def _compiled_not_interpreted(monkeypatch, module_name):
    import importlib
    monkeypatch.setattr(importlib.import_module(module_name), "jax",
                        _AnswersTpu())


@pytest.mark.parametrize("program", ["eval_batch", "train_step"])
def test_engine_loss_over_data_mesh_compiles(topo, monkeypatch, program):
    """GSPMD cannot partition a Mosaic kernel, so every program the
    engine jits over a `data=4` mesh must carry the flash kernels inside
    a `shard_map`: `DeepSpeedEngine.__init__` wraps its loss function
    once (`place_kernels_on_mesh`) and `eval_batch`, `forward`/`backward`
    and the train steps all trace that. An engine cannot be built on
    described devices (it places real arrays), so this compiles what
    those programs trace — the wrapped loss of a 1-layer GPT-2 at 350M
    width, batch rows over `data`, forward-only as `eval_batch` jits it
    and differentiated as the train step does. Interpret mode inlines
    the kernel, so no CPU test can see this fault."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.models.gpt2 import (
        GPT2Config, GPT2LMHead, make_gpt2_loss_fn)
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import place_kernels_on_mesh

    _compiled_not_interpreted(monkeypatch,
                              "deepspeed_tpu.ops.pallas.flash_attention")
    mesh = build_mesh({"data": 4}, devices=topo.devices)
    model = GPT2LMHead(GPT2Config(
        vocab_size=50257, n_positions=T, n_embd=H * D, n_layer=1,
        n_head=H, use_flash_attention=True))
    loss_fn = place_kernels_on_mesh(make_gpt2_loss_fn(model), mesh)

    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((2, T), jnp.int32))["params"])
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, P())), shapes)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (B, T), jnp.int32, sharding=NamedSharding(mesh, P("data")))}

    def eval_step(params, batch):       # `DeepSpeedEngine.eval_batch`
        cast = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)
        return loss_fn(cast, batch, None)

    fn = eval_step if program == "eval_batch" else jax.grad(eval_step)
    assert "tpu_custom_call" in compiled_text(fn, params, batch)


def test_zero2_step_gathers_the_bf16_copy_on_the_tpu(topo, monkeypatch):
    """The ZeRO-1/2 layout of 16-bit compute as the TPU's partitioner
    leaves it: the float32 masters sharded over `data=4`, the step's
    loss differentiated through the cast-then-gather of the whole tree
    (`zero/sharding.py:make_param_caster`), the gradient back in the
    masters' layout. Every parameter-sized all-gather of the compiled
    program is bf16 — none is re-widened to float32, as the CPU backend
    re-widens them — and the flash kernels are still in it. One layer
    of GPT-2 at XL's width (25 heads), as `train-gpt2-xl-zero-4chip`
    runs 48 of."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.analysis.hlo import collective_ops
    from deepspeed_tpu.models.gpt2 import (
        GPT2Config, GPT2LMHead, make_gpt2_loss_fn)
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import (
        make_grad_accumulator, place_kernels_on_mesh)
    from deepspeed_tpu.runtime.zero.sharding import (
        build_zero_shardings, constrain_tree, make_param_caster)

    _compiled_not_interpreted(monkeypatch,
                              "deepspeed_tpu.ops.pallas.flash_attention")
    mesh = build_mesh({"data": 4}, devices=topo.devices)
    model = GPT2LMHead(GPT2Config(
        vocab_size=50257, n_positions=T, n_embd=1600, n_layer=1,
        n_head=25, dtype=jnp.bfloat16, use_flash_attention=True))
    loss_fn = place_kernels_on_mesh(make_gpt2_loss_fn(model), mesh)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((2, T), jnp.int32))["params"])
    sh = build_zero_shardings(
        shapes, jax.tree_util.tree_map(lambda _: P(), shapes), mesh, 2,
        sharded_masters=True)
    cast = make_param_caster(shapes, sh["param"], mesh, jnp.bfloat16)
    assert cast.plan["replicated_leaves"] == 0
    accumulate = make_grad_accumulator(
        loss_fn, jnp.bfloat16, 1, cast_params=cast,
        constrain=lambda g: constrain_tree(g, sh["grad"]))

    def step(params, batch, rng):
        loss, grads, _ = accumulate(params, batch, rng,
                                    jnp.asarray(1.0, jnp.float32))
        return loss, constrain_tree(grads, sh["grad"])

    params = jax.tree_util.tree_map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=h),
        shapes, sh["param"])
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (1, B, T), jnp.int32, sharding=NamedSharding(mesh, P(None, "data")))}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    text = compiled_text(step, params, batch, rng)
    assert "tpu_custom_call" in text
    gathered = {}
    for op in collective_ops(text):
        if op["op"] == "all-gather":
            for dtype, nbytes in op["dtype_bytes"].items():
                gathered[dtype] = max(gathered.get(dtype, 0), nbytes)
    # the largest leaf, the embedding, rides as bf16; no float32 gather
    # is the size of even the smallest sharded leaf (a 1600-wide bias)
    assert gathered.get("bf16", 0) >= 50257 * 1600 * 2, gathered
    assert gathered.get("f32", 0) < 1600 * 4, gathered


@pytest.mark.parametrize("program", ["eval_batch", "train_step"])
def test_olmoe_loss_over_data_mesh_compiles(topo, monkeypatch, program):
    """The same for OLMoE: besides the flash kernels its step holds the
    experts' grouped matmuls (Mosaic kernels too) and a sort that must
    stay each chip's own, so `moe/dropless.py` runs routing, dispatch,
    experts and combine inside one `shard_map` over `data`. One layer
    at the published widths, 2 rows of 4096 tokens a chip as in the
    benchmark's cell."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.models.olmoe import (
        OlmoeLM, make_olmoe_loss_fn, olmoe_1b_7b)
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import place_kernels_on_mesh

    _compiled_not_interpreted(monkeypatch,
                              "deepspeed_tpu.ops.pallas.flash_attention")
    _compiled_not_interpreted(monkeypatch, "deepspeed_tpu.moe.dropless")
    mesh = build_mesh({"data": 4}, devices=topo.devices)
    model = OlmoeLM(olmoe_1b_7b(n_layer=1, use_flash_attention=True))
    loss_fn = place_kernels_on_mesh(make_olmoe_loss_fn(model), mesh)

    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, P())), shapes)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (8, 4096), jnp.int32, sharding=NamedSharding(mesh, P("data")))}

    def eval_step(params, batch):
        cast = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)
        return loss_fn(cast, batch, None)[0]

    fn = eval_step if program == "eval_batch" else jax.grad(eval_step)
    text = compiled_text(fn, params, batch)
    names = ("ds_flash_fwd", "gmm") + (
        ("ds_flash_dq", "ds_flash_dkv", "tgmm")
        if program == "train_step" else ())
    for name in names:
        assert f"%{name}" in text or f" {name}" in text, name


def test_tp_sharded_flash_decode_compiles(topo, monkeypatch):
    """The serving engine's TP path: `inference/cache.py` runs the decode
    kernel under `shard_map` over the `model` axis (4 chips, 4 heads
    each)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.analysis.hlo import payload_shaped_copies
    from deepspeed_tpu.inference import cache

    _compiled_not_interpreted(monkeypatch,
                              "deepspeed_tpu.ops.pallas.flash_decode")
    mesh = Mesh(np.array(topo.devices).reshape(4), ("model",))

    def on(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    q = on((B, 1, H, D), jnp.bfloat16, None, None, "model")
    positions = on((B, 1), jnp.int32)
    # the whole layer: the kernel, the step's write in it, runs under
    # `shard_map`, the pool in and out on its head axis
    n_pages = B * (T // PAGE) + 1
    kv = on((n_pages, H, D, PAGE), jnp.bfloat16, None, "model")
    tables = on((B, T // PAGE), jnp.int32)

    def fn(q, k, v, positions, tables):
        return cache.cached_attention(
            q, q, q, {"k": k, "v": v}, positions, jnp.bfloat16,
            impl="flash", block_k=128, mesh=mesh, page_table=tables)
    text = jax.jit(fn, donate_argnums=(1, 2)).lower(
        q, kv, kv, positions, tables).compile().as_text()
    assert "tpu_custom_call" in text
    assert payload_shaped_copies(text, (n_pages, H // 4, D, PAGE)) == []
    assert "all-gather" not in text and "all-to-all" not in text
    assert " while(" not in text and "dynamic-update-slice(" not in text


def _flash_neighbours(hlo_text):
    """For each flash custom call of a compiled program: the opcodes of
    what makes its array operands and of what takes its results, seen
    through the ops that move no data (`bitcast`, `reshape`,
    `get-tuple-element`, `tuple`). ``{kernel name: [set of producers'
    opcodes, set of users' opcodes]}`` per call."""
    import re

    ops, users = {}, {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = .*?\s([\w-]+)\((.*)", line)
        if not m:
            continue
        name, opcode, rest = m.groups()
        operands = re.findall(r"%([\w.-]+)", rest.split("), ")[0])
        ops[name] = (opcode, operands, line)
        for o in operands:
            users.setdefault(o, []).append(name)
    free = {"bitcast", "reshape", "get-tuple-element", "tuple"}

    def producers(name):
        opcode, operands, _ = ops.get(name, ("parameter", [], ""))
        if opcode in free:
            return set().union(*(producers(o) for o in operands))
        return {opcode}

    def takers(name):
        out = set()
        for u in users.get(name, []):
            opcode = ops[u][0]
            out |= takers(u) if opcode in free else {opcode}
        return out

    calls = {}
    for name, (opcode, operands, line) in ops.items():
        kernel = re.match(r"(ds_flash_\w+?)(?:\.\d+)?$", name)
        if opcode == "custom-call" and kernel:
            calls.setdefault(kernel.group(1), []).append(
                (set().union(*(producers(o) for o in operands)),
                 takers(name)))
    return calls


@pytest.mark.parametrize("model", ["gpt2-medium", "olmoe"])
def test_train_step_moves_nothing_round_the_flash_kernels(
        chip, monkeypatch, model):
    """The backward of the loss as the train steps trace it, compiled
    for one chip at the cells' widths (two of GPT-2 medium's layers,
    OLMoE's one): exactly one call of each of the three kernels a layer,
    and neither a `copy` nor a `transpose` feeds one or takes its
    result. Until PR 30 each layer paid ten such re-layouts: q, k, v,
    the output and its cotangent folded to `[B*H, T, D]` and back
    (`copy` 11.5 ms a step in the medium cell)."""
    _compiled_not_interpreted(monkeypatch,
                              "deepspeed_tpu.ops.pallas.flash_attention")
    if model == "gpt2-medium":
        from deepspeed_tpu.models.gpt2 import (
            GPT2Config, GPT2LMHead, make_gpt2_loss_fn)
        n_layer, ids = 2, (B, T)
        net = GPT2LMHead(GPT2Config(
            vocab_size=50257, n_positions=T, n_embd=H * D, n_layer=n_layer,
            n_head=H, use_flash_attention=True))
        loss_fn = make_gpt2_loss_fn(net)
    else:
        from deepspeed_tpu.models.olmoe import (
            OlmoeLM, make_olmoe_loss_fn, olmoe_1b_7b)
        _compiled_not_interpreted(monkeypatch, "deepspeed_tpu.moe.dropless")
        n_layer, ids = 1, OLMOE_SHAPE[:2]
        net = OlmoeLM(olmoe_1b_7b(n_layer=n_layer,
                                  use_flash_attention=True))
        loss_fn = make_olmoe_loss_fn(net)
    shapes = jax.eval_shape(
        lambda: net.init({"params": jax.random.PRNGKey(0)},
                         jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree_util.tree_map(lambda s: chip(s.shape, s.dtype),
                                    shapes)

    def step(params, batch):
        cast = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)
        out = loss_fn(cast, batch, None)
        return out[0] if isinstance(out, tuple) else out

    text = compiled_text(jax.grad(step), params,
                         {"input_ids": chip(ids, jnp.int32)})
    calls = _flash_neighbours(text)
    assert {k: len(v) for k, v in calls.items()} == {
        "ds_flash_fwd": n_layer, "ds_flash_dq": n_layer,
        "ds_flash_dkv": n_layer}
    for kernel, each in calls.items():
        for made_by, taken_by in each:
            assert not {"copy", "transpose"} & (made_by | taken_by), (
                kernel, made_by, taken_by)

"""Sub-``pallas_call`` static analyzer (`analysis/kernels.py`) and the
kernel rule family (`analysis/rules.py` kernel_vmem / kernel_tiling /
kernel_dma).

Two halves:

- seeded violations — four deliberately broken toy kernels, each
  surfacing as EXACTLY its expected finding (over-VMEM block, tile
  misalignment, unclamped index map failing the elision contract,
  grid-write race);
- stock kernels — the real decode and train flash-attention programs
  come back zero-findings, and the proven KV elided-DMA fraction
  equals the scenario's dead-block occupancy (the static proof that
  the decode kernel fetches no dead block).

Everything runs interpret-mode on CPU; the analyzer never executes a
kernel on hardware.
"""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from deepspeed_tpu.analysis.audit import (
    audit_decode,
    audit_flash_train,
)
from deepspeed_tpu.analysis.cost import estimate_step_cost
from deepspeed_tpu.analysis.kernels import (
    analyze_kernels,
    causal_dead_tile_fraction,
    causal_rectangle_dead_fraction,
    paged_dead_block_fraction,
)
from deepspeed_tpu.analysis.rules import (
    SEV_ERROR,
    SEV_WARNING,
    StepContext,
    run_rules,
)

KERNEL_RULES = {"kernel_vmem", "kernel_tiling", "kernel_dma"}

# The audit toys' kernel-analysis scenario: two live rows at positions
# [8, 16] over max_seq 32 at block_k 8 (see audit._kernel_analysis_for):
# the rows hold 8 // 8 + 1 = 2 and 16 // 8 + 1 = 3 of their 4 blocks.
TOY_EXPECTED_ELISION = 1.0 - (2 + 3) / (2 * 4)


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _kernel_rule_findings(ana, expected_elision=None):
    ctx = StepContext(hlo_text="", flavor="kernel_test",
                      kernel_analysis=ana,
                      kernel_expected_elision=expected_elision)
    return run_rules(ctx, KERNEL_RULES)


# ---------------------------------------------------------------------------
# seeded violations — each one yields exactly its finding
# ---------------------------------------------------------------------------

def test_seeded_vmem_violation():
    # (2048, 1024) f32 blocks: 8MB in + 8MB out, double-buffered =
    # 32MB against the 16MB v5e budget. Interpret mode runs it
    # happily — only the analyzer knows it can never compile on TPU.
    x = jnp.zeros((2048, 1024), jnp.float32)

    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            grid=(1,),
            in_specs=[pl.BlockSpec((2048, 1024), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((2048, 1024), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((2048, 1024), jnp.float32),
            interpret=True,
        )(x)

    ana = analyze_kernels(fn, (x,))
    assert len(ana.kernels) == 1
    assert ana.kernels[0].vmem_bytes > ana.vmem_budget_bytes

    findings = _kernel_rule_findings(ana)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "kernel_vmem"
    assert f.severity == SEV_ERROR
    assert "exceeds" in f.message


def test_seeded_tiling_violation():
    # Sublane block dim 12 is neither a multiple of the f32 tile (8)
    # nor the full array extent (24) — every touch pads. The output
    # block is tile-aligned (8, 128) and passes.
    x = jnp.zeros((24, 128), jnp.float32)

    def head_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[0:8, :]

    def fn(x):
        return pl.pallas_call(
            head_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((12, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            interpret=True,
        )(x)

    ana = analyze_kernels(fn, (x,))
    findings = _kernel_rule_findings(ana)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "kernel_tiling"
    assert f.severity == SEV_WARNING
    assert f.details["block_dim"] == 12
    assert f.details["tile"] == 8


def test_seeded_grid_write_race():
    # Output map i -> (i % 2, 0) over grid 4 revisits block 0 at steps
    # 0 and 2: the block is flushed when the grid moves to step 1, so
    # step 2 reads back stale data.
    x = jnp.zeros((16, 128), jnp.float32)

    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i % 2, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            interpret=True,
        )(x)

    ana = analyze_kernels(fn, (x,))
    findings = _kernel_rule_findings(ana)
    # both physical blocks are revisited non-consecutively (0 at steps
    # 0/2, 1 at steps 1/3) — one race finding each
    assert len(findings) == 2
    for f in findings:
        assert f.rule == "kernel_dma"
        assert f.severity == SEV_ERROR
        assert "stale" in f.message
    assert sorted(tuple(f.details["steps"]) for f in findings) == \
        [(0, 2), (1, 3)]


def _elision_fn(clamped):
    # A flash-decode-shaped sweep: grid 8 over a (64, 128) "cache",
    # occupancy says only the first 5 blocks are live. The clamped map
    # parks the grid on block 4 for the dead tail (consecutive
    # revisits -> elided DMAs); the unclamped map fetches every dead
    # block.
    def fn(x):
        if clamped:
            in_map = lambda i: (jnp.minimum(i, 4), 0)
        else:
            in_map = lambda i: (i, 0)
        return pl.pallas_call(
            _copy_kernel,
            grid=(8,),
            in_specs=[pl.BlockSpec((8, 128), in_map)],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((64, 128), jnp.float32),
            interpret=True,
        )(x)
    return fn


def test_seeded_unclamped_elision_shortfall():
    x = jnp.zeros((64, 128), jnp.float32)
    expected = 3.0 / 8.0  # 3 of 8 grid steps sit past the clamp

    ana = analyze_kernels(_elision_fn(clamped=False), (x,))
    findings = _kernel_rule_findings(ana, expected_elision=expected)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "kernel_dma"
    assert f.severity == SEV_WARNING
    assert "elide only" in f.message
    assert f.details["proved_elision"] == 0.0

    # The clamped twin proves exactly the contract and passes clean.
    ana = analyze_kernels(_elision_fn(clamped=True), (x,))
    (op,) = [op for k in ana.kernels for op in k.operands
             if op.kind == "input"]
    assert op.index_map_evaluated
    assert op.elided_fraction == pytest.approx(expected)
    assert _kernel_rule_findings(ana, expected_elision=expected) == []


def _rectangle_walk(n_tiles, block):
    """A flash-forward-shaped call (by name and operand order) whose
    grid is the whole (rows, q tile, kv tile) rectangle with the plain
    maps, as the training kernels had until PR 30: a dead tile's K and
    V are fetched and only the arithmetic is skipped."""
    T = n_tiles * block

    def kernel(q_ref, k_ref, v_ref, o_ref):
        o_ref[...] = q_ref[...]

    def fn(q, k, v):
        tile = lambda which: pl.BlockSpec(
            (1, block, 128), [lambda r, i, j: (r, i, 0),
                              lambda r, i, j: (r, j, 0)][which])
        return pl.pallas_call(
            kernel, name="ds_flash_fwd", grid=(2, n_tiles, n_tiles),
            in_specs=[tile(0), tile(1), tile(1)], out_specs=tile(0),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=True)(q, k, v)

    x = jnp.zeros((2, T, 128), jnp.float32)
    return analyze_kernels(fn, (x, x, x))


@pytest.mark.parametrize("n_tiles,dead", [(2, 0.25), (8, 0.4375)],
                         ids=["T1024-2x2", "T4096-8x8"])
def test_seeded_rectangle_walk_fetches_dead_tiles(n_tiles, dead):
    """The cells' tilings (T 1024 and T 4096 in blocks of 512, here in
    blocks of 8): a grid over the whole rectangle visits the tiles the
    causal mask kills, a quarter and 7/16 of them, and fails the
    contract that they are no part of the walk."""
    ana = _rectangle_walk(n_tiles, 8)
    (kernel,) = ana.kernels
    assert kernel.tile_blocks == (8, 8)
    assert kernel.tile_rectangle == 2 * n_tiles * n_tiles
    assert causal_dead_tile_fraction(kernel) == pytest.approx(dead)
    assert causal_rectangle_dead_fraction(n_tiles, n_tiles, 8, 8) == \
        pytest.approx(dead)
    (finding,) = _kernel_rule_findings(ana, expected_elision=dead)
    assert finding.rule == "kernel_dma"
    assert finding.severity == SEV_WARNING
    assert finding.details["proved_elision"] == 0.0


@pytest.mark.parametrize("seq,block", [(256, 128), (1024, 128)],
                         ids=["2x2", "8x8"])
def test_flash_train_walks_no_dead_tile(seq, block):
    """The training kernels read their tiles from a table of the live
    ones: no grid step lands on a tile the mask kills, and the tiles
    left out are exactly the rectangle's dead share (0.25, 0.4375)."""
    report = audit_flash_train(seq=seq, head_dim=64, block_q=block,
                               block_k=block)
    assert report.findings == []
    assert report.stats["dead_tile_fraction"] == {
        "ds_flash_fwd": 0.0, "ds_flash_dq": 0.0, "ds_flash_dkv": 0.0}
    n = seq // block
    assert report.stats["expected_elision"] == pytest.approx(
        1 - (n + 1) / (2 * n))
    for kd in report.stats["kernels"]["kernels"].values():
        assert kd["grid"][-1] == n * (n + 1) // 2


def test_dead_tile_fraction_is_none_without_a_tile_walk():
    x = jnp.zeros((64, 128), jnp.float32)
    ana = analyze_kernels(_elision_fn(clamped=True), (x,))
    assert [causal_dead_tile_fraction(k) for k in ana.kernels] == [None]


# ---------------------------------------------------------------------------
# stock kernels — zero findings, pinned elision
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paged_report():
    return audit_decode(kernels=True)


def _kv_elided_fractions(report):
    ks = report.stats["kernels"]
    fracs = []
    for kd in ks["kernels"].values():
        for op in kd["operands"].values():
            if op["kind"] == "input" and \
                    op["elided_fraction"] == pytest.approx(
                        TOY_EXPECTED_ELISION):
                fracs.append(op["elided_fraction"])
    return fracs


@pytest.mark.slow
def test_stock_decode_zero_findings(paged_report):
    report = paged_report
    assert report.findings == []
    ks = report.stats["kernels"]
    assert ks["kernels"], "decode program lost its Pallas kernels"
    assert ks["expected_elision"] == pytest.approx(TOY_EXPECTED_ELISION)
    for kd in ks["kernels"].values():
        assert kd["vmem_bytes"] <= ks["vmem_budget_bytes"]
        assert kd["races"] == []
        assert kd["tiling"] == []
        pools = [op for op in kd["operands"].values()
                 if op["manual_dma"] and op["kind"] == "input"]
        # the kernel leaves the pool in HBM and walks it: K and V
        # launch exactly the live blocks (q and out are one row block a
        # grid step, with nothing to elide), and only the two double
        # buffers are VMEM scratch
        assert len(pools) == 2 and kd["grid"] == [2]
        assert all(op["elided_fraction"] == pytest.approx(
            TOY_EXPECTED_ELISION) for op in pools)
        assert kd["scratch_bytes"] == sum(
            2 * op["block_bytes"] for op in pools)


@pytest.mark.slow
def test_walk_pins_dead_block_fraction(paged_report):
    # The KV operands' proven elided fraction equals the scenario's
    # dead-block occupancy — the walk fetches exactly the live cache
    # blocks, no more and no fewer.
    assert TOY_EXPECTED_ELISION == pytest.approx(0.375)
    assert len(_kv_elided_fractions(paged_report)) >= 2  # k and v


@pytest.mark.slow
def test_stock_flash_train_zero_findings():
    report = audit_flash_train()
    assert report.findings == []
    ks = report.stats["kernels"]
    assert set(ks["kernels"]) == {"ds_flash_fwd", "ds_flash_dq",
                                  "ds_flash_dkv"}
    for kd in ks["kernels"].values():
        # the backward accumulators revisit output blocks ONLY at
        # consecutive grid steps (carried-accumulator idiom) — no race
        assert kd["races"] == []
        assert kd["tiling"] == []


# ---------------------------------------------------------------------------
# cost pricing — elision-aware traffic flips the block_k ranking
# ---------------------------------------------------------------------------

def _cost_facts(report):
    ks = report.stats["kernels"]
    return [{"name": n, "dma_bytes": kd["dma_bytes"],
             "dense_bytes": kd["dense_bytes"]}
            for n, kd in ks["kernels"].items()]


@pytest.mark.slow
def test_kernel_traffic_tells_block_k_apart(paged_report):
    # Pinned scenario (ISSUE 19): at the toy occupancy, block_k=4
    # fetches FEWER live bytes (finer blocks track the ragged fill).
    # The kernel (PR 27) touches q and out once a row whatever block_k:
    # the dense rectangle is the same bytes at both, and only the
    # elision-aware DMA pricing tells them apart.
    bk4 = audit_decode(kernels=True,
                       config_overrides={"attention_block_k": 4})
    # the pool cuts [D, block_k] KV blocks, positions on the lanes, so
    # a 4-position block of an 8-position page is honestly sub-tile and
    # the lint says so; the interpret-mode toy still runs it, which is
    # all the pricing needs
    assert {f.rule for f in bk4.findings} <= {"kernel_tiling"}
    assert all(f.severity == "warning" for f in bk4.findings)
    f4 = _cost_facts(bk4)
    f8 = _cost_facts(paged_report)

    def step_s(facts, traffic):
        return estimate_step_cost("", n_devices=2, kernel_facts=facts,
                                  kernel_traffic=traffic).step_seconds

    assert step_s(f4, "dma") < step_s(f8, "dma")
    assert step_s(f8, "dense") == step_s(f4, "dense")

    with pytest.raises(ValueError, match="kernel_traffic"):
        estimate_step_cost("", n_devices=2, kernel_facts=f4,
                           kernel_traffic="bogus")


# ---------------------------------------------------------------------------
# the paged decode kernel: pool operands left in HBM, priced by its walk
# ---------------------------------------------------------------------------

# (positions, first table entries): three live rows and a dead one
WALK_POSITIONS = np.array([5, 0, 31, 16], np.int32)
WALK_TABLES = np.array([[1, 0, 0, 0], [0, 0, 0, 0], [2, 3, 4, 5],
                        [6, 7, 8, 0]], np.int32)


@pytest.mark.parametrize("block_k,live", [(8, 1 + 4 + 3), (4, 2 + 8 + 5)])
def test_paged_dead_block_fraction(block_k, live):
    """Blocks past a live row's position AND every block of a row
    without a request, out of the rows x pages x page / block_k
    rectangle."""
    dense = 4 * 4 * (8 // block_k)
    assert paged_dead_block_fraction(
        WALK_POSITIONS, WALK_TABLES, 8, block_k) == \
        pytest.approx(1.0 - live / dense)
    # all rows live: the dead row's position 0 holds one block more
    tables = np.arange(1, 17, dtype=np.int32).reshape(4, 4)
    assert paged_dead_block_fraction(
        WALK_POSITIONS, tables, 8, block_k) == pytest.approx(
            1.0 - (live + 1) / dense)


def test_paged_kernel_is_priced_by_its_walk():
    """`flash_decode_paged` leaves the pool in `ANY` memory and fetches
    by manual DMA: no block of it is pipelined (the two slots are
    scratch), and its traffic is the declared walk's — the live rows'
    live blocks against the dense rectangle on the pool as input, the
    one block a live row's position falls in, written back, on the pool
    as output (the same buffer: the call aliases them)."""
    from deepspeed_tpu.ops.pallas import flash_decode_paged

    H, D, page = 2, 8, 8
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(9, H, D, page)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(4, 1, H, D)), jnp.float32)
    ana = analyze_kernels(
        lambda q, new, pool, pos, pt: flash_decode_paged(
            q, new, pool, pos, pt, block_k=8),
        (q, {"k": q, "v": q}, {"k": pool, "v": pool},
         jnp.asarray(WALK_POSITIONS), jnp.asarray(WALK_TABLES)))
    k, = ana.kernels
    assert k.name == "ds_flash_decode_paged" and k.grid == (4,)
    ops = {op.name: op for op in k.operands}
    block = H * D * 8 * 4
    for name, traffic in (("in2", (16, 8)), ("in3", (16, 8)),   # K and V
                          ("out1", (4, 3)), ("out2", (4, 3))):  # written
        op = ops[name]
        assert op.manual_dma and op.index_map_evaluated
        assert op.block_shape == (H, D, 8) and op.block_bytes == block
        assert (op.total_fetches, op.dma_fetches) == traffic
    assert not any(ops[name].manual_dma for name in ("in0", "in1", "out0"))
    # VMEM: q, out and the step's new lanes double-buffered, plus the
    # four scratch slots (the two flags in SMEM are not VMEM)
    row, new = H * D * 4, 2 * H * D * 128 * 4
    assert k.scratch_bytes == 4 * block
    assert k.vmem_bytes == 2 * (2 * row + new) + 4 * block
    # the new lanes' one block of 128 rows is fetched once
    assert k.dma_bytes == 2 * 8 * block + 2 * 3 * block + 2 * 4 * row + new
    # the contract the audit declares for this scenario holds, and a
    # kernel that walked every block would not meet it
    expected = paged_dead_block_fraction(WALK_POSITIONS, WALK_TABLES, 8, 8)
    assert _kernel_rule_findings(ana, expected) == []
    for name in ("in2", "in3"):
        ops[name].dma_fetches = 16
    dense, = _kernel_rule_findings(ana, expected)
    assert dense.rule == "kernel_dma" and dense.severity == SEV_WARNING


def test_serving_search_space_has_block_dimension():
    from deepspeed_tpu.analysis.tune import serving_dimensions
    dims = dict(serving_dimensions({}))
    assert "block" in dims
    labels = {c.label for c in dims["block"]}
    assert {"blk2", "blk4", "blk8"} <= labels


# ---------------------------------------------------------------------------
# flash_decode geometry validation (typed errors at call time)
# ---------------------------------------------------------------------------

def test_flash_decode_geometry_errors():
    from deepspeed_tpu.ops.pallas import (
        KernelGeometryError,
        flash_decode_paged,
    )
    rng = np.random.default_rng(0)
    B, H, D = 1, 2, 8
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    pos = jnp.zeros((B,), jnp.int32)
    n_pages, page_size, ppr = 5, 16, 2
    pool = {"k": jnp.zeros((n_pages, H, D, page_size), jnp.float32),
            "v": jnp.zeros((n_pages, H, D, page_size), jnp.float32)}
    new = {"k": q, "v": q}
    tables = jnp.zeros((B, ppr), jnp.int32)

    assert issubclass(KernelGeometryError, ValueError)
    # block_k < 1 is a typed geometry error, not a ZeroDivisionError
    with pytest.raises(KernelGeometryError, match=">= 1"):
        flash_decode_paged(q, new, pool, pos, tables, block_k=0)
    # block_k must divide page_size, validated before lowering
    with pytest.raises(KernelGeometryError, match="multiple"):
        flash_decode_paged(q, new, pool, pos, tables, block_k=3)

    # the compiled-only rule (interpret=False is what a TPU build
    # checks; the engine runs the same check when it is built): every
    # block has positions on the lanes, so it is whole 128-lane rows or
    # the whole page
    from deepspeed_tpu.ops.pallas.flash_decode import _validate_block_k
    assert _validate_block_k(4, 16, True) == 4
    with pytest.raises(KernelGeometryError, match="multiple of 128"):
        _validate_block_k(4, 16, False)
    with pytest.raises(KernelGeometryError, match="multiple of 128"):
        _validate_block_k(64, 256, False)
    assert _validate_block_k(64, 64, False) == 64
    assert _validate_block_k(128, 1024, False) == 128
    assert _validate_block_k(256, 128, False) == 128     # clamps


def test_pallas_package_exports():
    import deepspeed_tpu.ops.pallas as ops
    assert "flash_decode" not in ops.__all__      # the ring kernel, PR 28
    for name in ("flash_attention", "flash_decode_paged",
                 "dense_attention", "pallas_adam_update",
                 "KernelGeometryError", "DEFAULT_BLOCK_K",
                 "DEFAULT_MASK_VALUE"):
        assert name in ops.__all__
        assert getattr(ops, name) is not None


# ---------------------------------------------------------------------------
# telemetry summary — kernel block from compile-event stats
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_metrics_summary_kernel_block(paged_report):
    from deepspeed_tpu.telemetry.cli import print_serve_summary, summarize

    events = [
        {"event": "compile", "step": 0,
         "kernels": paged_report.stats["kernels"]},
        {"event": "decode_step", "step": 1, "wall_s": 0.01,
         "new_tokens": 2},
        {"event": "decode_step", "step": 2, "wall_s": 0.01,
         "new_tokens": 2},
    ]
    s = summarize(events)
    kn = s["kernels"]
    assert kn["vmem_high_water_bytes"] == max(
        kd["vmem_bytes"]
        for kd in paged_report.stats["kernels"]["kernels"].values())
    assert kn["elided_dma_fraction"] == pytest.approx(
        1.0 - paged_report.stats["kernels"]["dma_bytes"]
        / paged_report.stats["kernels"]["dense_bytes"])
    assert kn["expected_elision"] == pytest.approx(TOY_EXPECTED_ELISION)

    out = io.StringIO()
    print_serve_summary(s, out=out)
    text = out.getvalue()
    assert "VMEM high-water" in text
    assert "elided DMA" in text
    assert "contract >= 37.5%" in text

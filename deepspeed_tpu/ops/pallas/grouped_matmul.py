"""Grouped matmul over tile-aligned groups (Pallas TPU).

``rows`` ``[R, K]`` are laid out group after group, every group padded
to whole tiles of ``tile_m`` rows, so each row tile belongs to exactly
one group and the kernel is a plain tiled matmul whose right operand is
picked per row tile: ``out[tile] = rows[tile] @ bank[tile_group[tile]]``.
No mask, no tile visited twice. ``R`` is the static worst case (every
group's last tile partly empty); the tiles past ``n_used`` belong to no
group and are skipped: their index maps stay on the last used blocks, so
they cost a grid step and no DMA, and **their rows of the result are
never written** (a caller reads only the rows it laid out).

Three programs, one per matmul of a step: ``ds_grouped_matmul`` (the
forward product), ``ds_grouped_matmul_t`` (the rows' gradient: the same
walk, the bank's block contracted the other way) and
``ds_grouped_matmul_dw`` (the bank's gradient: the row tiles of one
group follow each other, so each group's ``[K, N]`` block stays in VMEM
while its tiles accumulate into it). A group has at least one tile, so
every block of the bank's gradient is written.
"""

import functools

import jax
import jax.numpy as jnp

FWD_NAME = "ds_grouped_matmul"
DLHS_NAME = "ds_grouped_matmul_t"
DW_NAME = "ds_grouped_matmul_dw"
# A bank's whole [K, N] block (4 MB in bf16 at 2048 x 1024) is one
# tile where it fits: it then stays in VMEM over the row tiles of its
# group and is fetched once a group, and every row is read once. Double
# buffers and the float32 accumulator need up to 24 MB of VMEM.
BLOCK = 2048
VMEM_LIMIT = 64 * 1024 * 1024


def _tile(dim, want):
    """The largest tile of at most ``want`` that divides ``dim``: the
    whole of ``dim`` or a multiple of 128."""
    if dim <= want:
        return dim
    for t in range(want - want % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def _gmm(rows, bank, tile_group, n_used, tile_m, transpose_bank, interpret):
    """``rows`` ``[R, K]``; ``bank`` ``[G, K, N]`` (``[G, N, K]`` with
    ``transpose_bank``) -> ``[R, N]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, K = rows.shape
    N = bank.shape[1] if transpose_bank else bank.shape[2]
    tk, tn = _tile(K, BLOCK), _tile(N, BLOCK)
    m_tiles, n_tiles, k_tiles = R // tile_m, N // tn, K // tk
    contract = (((1,), (1,)), ((), ())) if transpose_bank else \
        (((1,), (0,)), ((), ()))

    def kernel(group_ref, used_ref, rows_ref, bank_ref, out_ref, acc_ref):
        kk = pl.program_id(2)

        @pl.when(pl.program_id(0) < used_ref[0])
        def _():
            @pl.when(kk == 0)
            def _():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            acc_ref[...] += jax.lax.dot_general(
                rows_ref[...], bank_ref[0], contract,
                preferred_element_type=jnp.float32)

            @pl.when(kk == k_tiles - 1)
            def _():
                out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def at(i, j, k, used):
        """Block indices of grid step (i, j, k). A tile past the used
        ones stays on the blocks of the last used step, whatever j and
        k: nothing is fetched for it, and the result's last block is
        written back once, as that step left it."""
        dead = i >= used[0]
        return (jnp.minimum(i, used[0] - 1),
                jnp.where(dead, n_tiles - 1, j),
                jnp.where(dead, k_tiles - 1, k))

    def rows_at(i, j, k, group, used):
        i, _, k = at(i, j, k, used)
        return i, k

    def bank_at(i, j, k, group, used):
        i, j, k = at(i, j, k, used)
        return (group[i], j, k) if transpose_bank else (group[i], k, j)

    def out_at(i, j, k, group, used):
        i, j, _ = at(i, j, k, used)
        return i, j

    call = pl.pallas_call(
        kernel,
        name=DLHS_NAME if transpose_bank else FWD_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m_tiles, n_tiles, k_tiles),
            in_specs=[
                pl.BlockSpec((tile_m, tk), rows_at),
                pl.BlockSpec((1, tn, tk) if transpose_bank else (1, tk, tn),
                             bank_at),
            ],
            out_specs=pl.BlockSpec((tile_m, tn), out_at),
            scratch_shapes=[pltpu.VMEM((tile_m, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )
    return call(tile_group, n_used, rows, bank)


def _dw(rows, grads, tile_group, n_used, n_groups, tile_m, interpret):
    """``rows`` ``[R, K]``, ``grads`` ``[R, N]`` -> ``[G, K, N]``: the
    sum over each group's tiles of ``rows[tile]^T @ grads[tile]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, K = rows.shape
    N = grads.shape[1]
    tk, tn = _tile(K, BLOCK), _tile(N, BLOCK)
    m_tiles, n_tiles, k_tiles = R // tile_m, N // tn, K // tk

    def kernel(group_ref, used_ref, rows_ref, grads_ref, out_ref, acc_ref):
        i, used = pl.program_id(2), used_ref[0]
        group = group_ref[i]
        first = jnp.logical_or(
            i == 0, group_ref[jnp.maximum(i - 1, 0)] != group)
        last = jnp.logical_or(
            i == used - 1,
            group_ref[jnp.minimum(i + 1, m_tiles - 1)] != group)

        @pl.when(i < used)
        def _():
            @pl.when(first)
            def _():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            acc_ref[...] += jax.lax.dot_general(
                rows_ref[...], grads_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

            @pl.when(last)
            def _():
                out_ref[0] = acc_ref[...].astype(out_ref.dtype)

    def live(i, used):
        return jnp.minimum(i, used[0] - 1)

    call = pl.pallas_call(
        kernel,
        name=DW_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k_tiles, n_tiles, m_tiles),
            in_specs=[
                pl.BlockSpec((tile_m, tk), lambda k, j, i, group, used:
                             (live(i, used), k)),
                pl.BlockSpec((tile_m, tn), lambda k, j, i, group, used:
                             (live(i, used), j)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda k, j, i, group, used:
                (group[live(i, used)], k, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n_groups, K, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )
    return call(tile_group, n_used, rows, grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped_matmul(rows, bank, tile_group, n_used, tile_m, interpret):
    with jax.named_scope(FWD_NAME):
        return _gmm(rows, bank, tile_group, n_used, tile_m, False,
                    interpret)


def _grouped_matmul_fwd(rows, bank, tile_group, n_used, tile_m, interpret):
    return (_grouped_matmul(rows, bank, tile_group, n_used, tile_m,
                            interpret), (rows, bank, tile_group, n_used))


def _grouped_matmul_bwd(tile_m, interpret, res, g):
    rows, bank, tile_group, n_used = res
    with jax.named_scope(DLHS_NAME):
        d_rows = _gmm(g, bank, tile_group, n_used, tile_m, True, interpret)
    with jax.named_scope(DW_NAME):
        d_bank = _dw(rows, g, tile_group, n_used, bank.shape[0], tile_m,
                     interpret)
    return d_rows, d_bank, None, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(rows, bank, tile_group, n_used, tile_m, interpret=None):
    """``out[r] = rows[r] @ bank[tile_group[r // tile_m]]`` for the rows
    of the first ``n_used[0]`` tiles; the rest of ``out`` is not written.

    ``rows`` ``[R, K]`` with ``R`` a multiple of ``tile_m`` (a multiple
    of 16: a bf16 tile's sublanes); ``bank`` ``[G, K, N]``;
    ``tile_group`` ``[R / tile_m]`` int32, non-decreasing, every group
    at least once among the used tiles; ``n_used`` ``[1]`` int32 >= 1.
    Rows that pad a group's last tile must be zero (then so are their
    results and their share of the bank's gradient); the gradient with
    respect to ``rows`` is likewise written for the used tiles only.
    Differentiable in ``rows`` and ``bank``. ``interpret``: Pallas
    interpret mode; by default wherever the first device is not a TPU."""
    if rows.shape[0] % tile_m or tile_m % 16:
        raise ValueError(
            f"grouped_matmul: {rows.shape[0]} rows do not tile by "
            f"tile_m={tile_m} (a multiple of 16)")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return _grouped_matmul(rows, bank, tile_group.astype(jnp.int32),
                           n_used.astype(jnp.int32), tile_m, interpret)

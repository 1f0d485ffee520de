"""Flash attention for TPU.

The fused-attention capability of the reference's transformer kernel
(`csrc/transformer/softmax_kernels.cu` masked scaled softmax +
strided-batch attention GEMMs, `csrc/includes/strided_batch_gemm.h`),
re-designed as an online-softmax tiled kernel so the [T, T] score matrix
never materializes in HBM.

Implementations:
- ``pallas``: TPU Pallas forward + backward kernels (online softmax over
  KV tiles, MXU-tiled, fp32 accumulators in VMEM scratch). The forward
  also emits the per-row logsumexp; the backward is the FlashAttention-2
  split — one kernel accumulating dQ over KV tiles, one accumulating
  dK/dV over Q tiles — so the [T, T] score matrix never materializes in
  either direction.
- ``xla``: blockwise lax.scan with the same online-softmax math — runs
  everywhere (CPU test meshes), differentiable, memory O(T·block).
- ``dense``: plain softmax attention (reference math for parity tests).

``flash_attention`` routes: TPU → pallas kernels; other platforms → xla
path (or pallas in interpreter mode when explicitly requested).
"""

import contextlib
import contextvars
import functools
import typing

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
# each kernel's name, as its ``pallas_call`` and the scope round it carry
# it into the HLO instruction and so into a device trace: the benchmark
# finds the three by these names
FWD_NAME, DQ_NAME, DKV_NAME = "ds_flash_fwd", "ds_flash_dq", "ds_flash_dkv"
# Additive form of a hard key mask (added to scores, so it must stay well
# inside fp32 range): exp(s - 1e9) == 0.0 exactly in fp32.
MASK_BIAS = -1e9

# Counter-based dropout: resolution of the keep threshold (top 24 bits of
# the hash compared against keep_prob * 2^24).
_DROPOUT_RESOLUTION = 1 << 24
# murmur3 fmix32 constants (as wrapping int32)
_FMIX_C1 = -2048144789      # 0x85EBCA6B
_FMIX_C2 = -1028477387      # 0xC2B2AE35
_GOLDEN = -1640531527       # 0x9E3779B9


def dropout_multiplier(seed, head, q_pos, k_pos, rate):
    """Counter-based attention-prob dropout multiplier: 0 or 1/keep_prob.

    The fused-dropout capability of the reference's transformer kernel
    (`csrc/transformer/dropout_kernels.cu`, cuRAND Philox seeded from
    `csrc/includes/context.h:177`), re-designed counter-based: the mask at
    global coordinates (head, q_pos, k_pos) is a pure integer-hash
    function (murmur3 fmix32 avalanche over a linear combination of the
    coordinates and the step seed). Because it is plain int32 arithmetic,
    it computes bitwise-identically inside the Pallas TPU kernels, the
    interpret-mode kernels, the blockwise-XLA path and the dense
    reference — which is what makes flash-with-dropout testable against
    dense-with-the-same-mask, keeps the backward's regenerated mask equal
    to the forward's without storing [T, S] bytes, and makes remat replay
    the identical mask. (``pltpu.prng_random_bits`` would be
    hardware-only: it is a zero-stub under interpret mode.)

    ``seed``/``head`` scalars (traced ok), ``q_pos``/``k_pos`` int32
    arrays that broadcast to the tile shape; ``rate`` static Python float
    in [0, 1). Returns fp32 of the broadcast shape.
    """
    keep_prob = 1.0 - rate
    h = (jnp.asarray(q_pos, jnp.int32) * jnp.int32(_GOLDEN)
         + jnp.asarray(k_pos, jnp.int32) * jnp.int32(_FMIX_C2)
         + jnp.asarray(head, jnp.int32) * jnp.int32(_FMIX_C1)
         + jnp.asarray(seed, jnp.int32))
    h = _fmix32(h)
    # Top 24 bits as a uniform value in [0, 2^24): unsigned comparison in
    # int32-safe range (both operands < 2^24).
    u24 = jax.lax.shift_right_logical(h, 8)
    thr = jnp.int32(int(round(keep_prob * _DROPOUT_RESOLUTION)))
    return (u24 < thr).astype(jnp.float32) * jnp.float32(1.0 / keep_prob)


# ---------------------------------------------------------------------------
# dense reference
# ---------------------------------------------------------------------------

def _to_key_bias(key_padding_mask, key_bias):
    """Resolve the public mask args to one additive [B, S] fp32 bias (or
    None): a bool ``key_padding_mask`` becomes 0 / MASK_BIAS; an explicit
    ``key_bias`` (soft additive penalties included) passes through."""
    assert key_padding_mask is None or key_bias is None, (
        "pass key_padding_mask OR key_bias, not both")
    if key_padding_mask is not None:
        return jnp.where(jnp.asarray(key_padding_mask, bool),
                         0.0, MASK_BIAS).astype(jnp.float32)
    if key_bias is not None:
        return key_bias.astype(jnp.float32)
    return None


def dropout_seed_from_rng(rng):
    """Derive the int32 per-step dropout seed from a JAX PRNG key — the
    one canonical way model code feeds :func:`dropout_multiplier` (every
    attention path must use this so a shared rng stream gives identical
    semantics everywhere)."""
    return jax.lax.bitcast_convert_type(
        jax.random.bits(rng, (), jnp.uint32), jnp.int32)


def _fmix32(h):
    """murmur3 finalizer: full avalanche over an int32."""
    h = h ^ jax.lax.shift_right_logical(h, 16)
    h = h * jnp.int32(_FMIX_C1)
    h = h ^ jax.lax.shift_right_logical(h, 13)
    h = h * jnp.int32(_FMIX_C2)
    h = h ^ jax.lax.shift_right_logical(h, 16)
    return h


def fold_in_seed(seed, data):
    """Mix ``data`` (a rank index, shard id, ...) into a dropout seed with
    full avalanche. A LINEAR stride (seed + data * C) is not enough: if C
    collides with one of :func:`dropout_multiplier`'s coordinate
    multipliers, the "new" seed reproduces the old mask at shifted
    coordinates (seed + r*GOLDEN ≡ the rank-0 mask at q_pos + r). The
    avalanche destroys any affine relationship to the coordinate terms."""
    h = jnp.asarray(seed, jnp.int32) ^ (
        jnp.asarray(data, jnp.int32) * jnp.int32(0x7F4A7C15))
    return _fmix32(h)


def _dropout_multiplier_full(B, H, T, S, rate, seed, head_offset=0,
                             num_heads=None):
    """The [B, H, T, S] dropout multiplier the kernels generate tile-wise,
    materialized whole (dense reference / tests). Head coordinate is the
    GLOBAL folded b*Hg + head_offset + h index — with the defaults
    (offset 0, Hg = H) that is the plain bh = b*H + h of the kernels'
    grid dim 0; under tensor parallelism the local heads are a slice and
    the globalized coordinate keeps the mask invariant to the sharding."""
    Hg = H if num_heads is None else num_heads
    bh = (jnp.arange(B)[:, None] * Hg + head_offset
          + jnp.arange(H)[None, :])                        # [B, H]
    return dropout_multiplier(
        seed, bh[:, :, None, None],
        jnp.arange(T)[None, None, :, None],
        jnp.arange(S)[None, None, None, :], rate)


def dense_attention(q, k, v, causal=True, sm_scale=None,
                    key_padding_mask=None, key_bias=None,
                    dropout_rate=0.0, dropout_seed=None,
                    dropout_head_offset=0, dropout_num_heads=None):
    """Plain attention; q,k,v: [B, T, H, D] → [B, T, H, D].
    ``key_padding_mask`` [B, S] bool (True = attend) or ``key_bias``
    [B, S] additive fp32. ``dropout_rate``/``dropout_seed``: attention-prob
    dropout with the shared counter-based mask (post-softmax, matching
    every other implementation bit-for-bit). ``dropout_head_offset`` /
    ``dropout_num_heads``: GLOBAL head coordinates when the local heads
    are a tensor-parallel shard (see :func:`flash_attention`)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    bias = _to_key_bias(key_padding_mask, key_bias)
    scores = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * sm_scale
    if causal:
        T, S = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((T, S), bool))
        scores = jnp.where(mask[None, None], scores, DEFAULT_MASK_VALUE)
    if bias is not None:
        scores = scores + bias[:, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0:
        B, T, H, _ = q.shape
        probs = probs * _dropout_multiplier_full(
            B, H, T, k.shape[1], dropout_rate, dropout_seed,
            head_offset=dropout_head_offset,
            num_heads=dropout_num_heads)
    return jnp.einsum("bhts,bshd->bthd", probs.astype(q.dtype), v)


# ---------------------------------------------------------------------------
# blockwise XLA (online softmax over KV blocks via lax.scan)
# ---------------------------------------------------------------------------

def _blockwise_attention(q, k, v, causal, sm_scale, block_k=256,
                         key_bias=None, dropout_rate=0.0, dropout_seed=None,
                         dropout_head_offset=0, dropout_num_heads=None):
    """Online-softmax attention; memory O(T * block_k) per head.
    ``key_bias`` [B, S] additive fp32 (resolved by the caller).
    Dropout uses the shared counter-based mask — bitwise-identical to the
    Pallas kernels' — applied to the normalized probs (the l normalizer
    sums the undropped probs, as softmax-then-dropout requires); head
    coordinates are globalized via ``dropout_head_offset`` /
    ``dropout_num_heads`` under tensor parallelism."""
    B, T, H, D = q.shape
    S = k.shape[1]
    if key_bias is None:
        key_bias = jnp.zeros((B, S), jnp.float32)
    kpm = key_bias
    block_k = min(block_k, S)
    n_blocks = (S + block_k - 1) // block_k
    pad = n_blocks * block_k - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpm = jnp.pad(kpm, ((0, 0), (0, pad)))

    qf = q.astype(jnp.float32) * sm_scale
    kb = k.reshape(B, n_blocks, block_k, H, D).astype(jnp.float32)
    vb = v.reshape(B, n_blocks, block_k, H, D).astype(jnp.float32)
    kb = jnp.moveaxis(kb, 1, 0)  # [n_blocks, B, block_k, H, D]
    vb = jnp.moveaxis(vb, 1, 0)
    mb = jnp.moveaxis(kpm.reshape(B, n_blocks, block_k), 1, 0)

    q_pos = jnp.arange(T)
    Hg = H if dropout_num_heads is None else dropout_num_heads
    bh_idx = (jnp.arange(B)[:, None] * Hg + dropout_head_offset
              + jnp.arange(H)[None, :])                           # [B, H]

    def body(carry, inputs):
        acc, m, l = carry
        k_blk, v_blk, m_blk, blk_idx = inputs
        s = jnp.einsum("bthd,bshd->bhts", qf, k_blk)  # [B,H,T,block_k]
        kv_pos = blk_idx * block_k + jnp.arange(block_k)
        valid = kv_pos < S
        if causal:
            valid = valid[None, :] & (kv_pos[None, :] <= q_pos[:, None])
            s = jnp.where(valid[None, None], s, DEFAULT_MASK_VALUE)
        else:
            s = jnp.where(valid[None, None, None], s, DEFAULT_MASK_VALUE)
        # additive key bias: [B, block_k] → [B, 1, 1, block_k]
        s = s + m_blk[:, None, None, :]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + p.sum(axis=-1)
        p_acc = p
        if dropout_rate > 0.0:
            p_acc = p * dropout_multiplier(
                dropout_seed, bh_idx[:, :, None, None],
                q_pos[None, None, :, None],
                kv_pos[None, None, None, :], dropout_rate)
        acc = acc * correction[..., None] + \
            jnp.einsum("bhts,bshd->bhtd", p_acc, v_blk)
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((B, H, T, D), jnp.float32)
    m0 = jnp.full((B, H, T), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (kb, vb, mb, jnp.arange(n_blocks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # [B,T,H,D]


# ---------------------------------------------------------------------------
# Pallas TPU kernels (forward + FlashAttention-2-style backward)
# ---------------------------------------------------------------------------

LANES = 128


def _to_bh(x):
    """[B, T, H, D] → [B*H, T, D]: heads fold into the leading dim. The
    flash kernels need it only for a head size that neither divides the
    128 lanes nor is a multiple of them (:func:`_lane_groups`); the
    block-sparse kernels still take this form."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _from_bh(x, B, H):
    BH, T, D = x.shape
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _lane_groups(H, D):
    """How the heads of a ``[B, T, H*D]`` activation lie on the lanes:
    ``(width, heads, groups)``: a block is ``width`` lanes wide and
    holds ``heads`` heads, and ``groups`` of them cover the row.

    - ``D`` a multiple of 128 (OLMoE's 128): one head a block.
    - ``D`` divides 128 (GPT-2's 64): ``128 // D`` heads a block, cut
      apart by lanes inside the kernel; an odd head count (GPT-2 XL's
      25) leaves the last block part full, and the kernel skips the
      heads that are not there. A row narrower than 128 lanes (a
      tensor-parallel head shard) is one block of all its heads.
    - anything else: ``None``; that shape runs folded to
      ``[B*H, T, D]``, one head a row."""
    if D % LANES == 0:
        return D, 1, H
    if LANES % D == 0:
        if H * D <= LANES:
            return H * D, H, 1
        heads = LANES // D
        return LANES, heads, -(-H // heads)
    return None


def _rows(x, fold):
    """``[B, T, H, D]`` as the kernels take it: ``[B, T, H*D]`` as it
    lies, or folded to ``[B*H, T, D]`` (:func:`_lane_groups`)."""
    if fold:
        return _to_bh(x)
    B, T, H, D = x.shape
    return x.reshape(B, T, H * D)


def _seed_and_offset(dropout_seed, dropout_head_offset):
    off = 0 if dropout_head_offset is None else dropout_head_offset
    return jnp.stack([jnp.asarray(dropout_seed, jnp.int32).reshape(()),
                      jnp.asarray(off, jnp.int32).reshape(())])


class _Layout(typing.NamedTuple):
    """How one attention call is cut up: the lane grouping of its heads
    (:func:`_lane_groups`) and its blocks."""
    fold: bool          # heads folded into the rows: [B*H, T, D]
    width: int          # lanes of a block
    heads: int          # heads in a block
    groups: int         # blocks across a row's lanes
    per_row: int        # rows a batch row makes (H folded, else 1)
    row_heads: int      # heads a row holds (1 folded, else H)
    block_q: int
    block_k: int

    @classmethod
    def of(cls, q_shape, S, block_q, block_k):
        _, T, H, D = q_shape
        block_q, block_k = min(block_q, T), min(block_k, S)
        assert T % block_q == 0 and S % block_k == 0, (
            f"seq lens ({T},{S}) must divide blocks ({block_q},{block_k})")
        groups = _lane_groups(H, D)
        fold = groups is None
        width, heads, n = (D, 1, 1) if fold else groups
        return cls(fold, width, heads, n, H if fold else 1,
                   1 if fold else H, block_q, block_k)


# a tile of 1024 x 1024 scores in float32 is 4 MiB, and a kernel holds a
# few such values at once (scores, probabilities, the mask's positions,
# dropout's hash): more than Mosaic's default 16 MiB of scoped VMEM, well
# inside the 128 MiB a v5e core has
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

# what a tile is to the causal mask (`_tile_walk`'s fifth table)
UNDER, CROSSING, ON_DIAGONAL = 0, 1, 2


def _tile_walk(n_q, n_k, block_q, block_k, causal, kv_major):
    """The tiles a kernel visits, in order, as five int32 tables it
    reads by grid step (scalar prefetch): q tile, kv tile, whether the
    step is the first / the last of its run (a q tile's kv tiles for the
    forward and dQ; a kv tile's q tiles, ``kv_major``, for dK/dV), and
    what the tile is to the causal mask: ``UNDER`` the diagonal (or not
    causal: nothing to mask), ``ON_DIAGONAL`` (square, its corner on the
    diagonal, in a walk short enough for it to matter: what lies over
    the diagonal is known dead, :func:`_pieces`), or ``CROSSING`` it in
    some other way (all of it computed, and masked). Causal: only tiles
    that hold a live score, so a dead tile is neither fetched nor
    stepped over. Built from the shapes with array operations: the
    kernel body is the same whatever the count."""
    qi, ki = np.meshgrid(np.arange(n_q), np.arange(n_k), indexing="ij")
    live = np.ones((n_q, n_k), bool)
    kind = np.full((n_q, n_k), UNDER)
    if causal:
        live = ki * block_k <= qi * block_q + block_q - 1
        kind[ki * block_k + block_k - 1 > qi * block_q] = CROSSING
        # strips pay where the diagonal's tiles are at least half of the
        # walk (T up to three tiles a side); a longer walk is mostly
        # tiles under the diagonal, and takes the few on it whole rather
        # than trace the strips' code in every process
        if block_q == block_k and 2 * min(n_q, n_k) >= live.sum():
            kind[qi == ki] = ON_DIAGONAL
        # a kv tile past the last query (S > T) still has its zero
        # gradient to write: the mask empties it
        live[-1, :] |= kv_major
    if kv_major:
        ki, qi, live, kind = ki.T, qi.T, live.T, kind.T
    major = (ki if kv_major else qi)[live]
    change = np.flatnonzero(np.diff(major)) + 1
    first = np.zeros(major.size, bool)
    first[np.r_[0, change]] = True
    last = np.zeros(major.size, bool)
    last[np.r_[change - 1, major.size - 1]] = True
    return [np.asarray(t, np.int32)
            for t in (qi[live], ki[live], first, last, kind[live])]


# strips a tile on the diagonal is cut in, by kernel (measured on the
# chip, `PERF.md` section 6, PR 30: each further strip leaves more dead
# scores out and costs a fixed piece of work; the forward, which turns
# its row statistics round for every piece, gains nothing past two)
_STRIPS = {"fwd": 2, "dq": 4, "dkv": 8}


def _strips(kernel, block):
    """Strips for ``kernel`` in a square tile of ``block``: its count,
    halved until a strip is whole (16, 128)-tiles of bf16 rows."""
    n = _STRIPS[kernel]
    while n > 1 and block % (16 * n):
        n //= 2
    return n


def _pieces(kind, lay, kernel):
    """The rectangles ``(rows, columns, masked)`` of a tile that a
    kernel computes, as static slices of its blocks. A tile under the
    diagonal is one unmasked piece, one crossing it one masked piece.
    A tile on the diagonal is a staircase of strips, each up to the
    diagonal's end in it and no further: strips of rows for the forward
    and dQ (each row's statistics and gradient see all their columns in
    one piece), strips of columns for dK/dV."""
    rows, cols = slice(0, lay.block_q), slice(0, lay.block_k)
    if kind != ON_DIAGONAL:
        return [(rows, cols, kind == CROSSING)]
    n = _strips(kernel, lay.block_q)
    step = lay.block_q // n
    if kernel == "dkv":
        return [(slice(i * step, lay.block_q),
                 slice(i * step, (i + 1) * step), True) for i in range(n)]
    return [(slice(i * step, (i + 1) * step),
             slice(0, (i + 1) * step), True) for i in range(n)]


def _for_each_piece(pl, kind, tables, lay, kernel, tile):
    """Run ``tile(rows, cols, masked)`` over the pieces of this step's
    tile, whichever kind it is: one copy of the code for each kind the
    walk holds (three at most, of `_STRIPS` pieces at most: counts the
    blocks fix, not the sequence length)."""
    present = sorted(set(tables[4].tolist()))
    for each in present:
        def run(each=each):
            for piece in _pieces(each, lay, kernel):
                tile(*piece)
        if len(present) == 1:
            run()
        else:
            pl.when(kind == each)(run)


def _positions(qi, ki, rows, cols, lay, keys_first=False):
    """Absolute (query, key) positions of a piece's scores, laid out
    ``[queries, keys]``, or ``[keys, queries]`` with ``keys_first``."""
    shape = (rows.stop - rows.start, cols.stop - cols.start)
    q_axis, k_axis = (1, 0) if keys_first else (0, 1)
    if keys_first:
        shape = shape[::-1]
    return (qi * lay.block_q + rows.start
            + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis),
            ki * lay.block_k + cols.start
            + jax.lax.broadcasted_iota(jnp.int32, shape, k_axis))


def _grid_step(pl, qi_tab, ki_tab, first_tab, last_tab, kind_tab):
    """This grid step, from the walk's tables: ``(row, lane group, q
    tile, kv tile, kind of tile, first of its run, last of its run)``.
    Read at the kernel's top: ``pl.program_id`` inside a ``pl.when`` body
    breaks the interpret-mode lowering."""
    t = pl.program_id(2)
    return (pl.program_id(0), pl.program_id(1), qi_tab[t], ki_tab[t],
            kind_tab[t], first_tab[t] == 1, last_tab[t] == 1)


def _dropout_piece(seed_ref, row, head, lay, num_heads, positions, rate):
    """A piece's dropout multiplier at the GLOBAL head coordinate
    b * Hg + offset + h (``seed_ref``: seed, offset): with the defaults
    exactly the folded b * H + h, and for a tensor-parallel head shard
    the replicated run's; the same in all three kernels, so the
    backward regenerates the forward's mask."""
    g_head = ((row // lay.per_row) * num_heads + seed_ref[1]
              + row % lay.per_row + head)
    return dropout_multiplier(seed_ref[0], g_head, *positions, rate)


def _each_head(pl, head, group, lay):
    """Run ``head(j)`` for the heads of lane group ``group``. Where the
    heads do not fill the last group (25 heads, two a group), the ones
    past the row's end are skipped: their lanes lie outside the array,
    hold whatever the copy left there, and are never written back."""
    for j in range(lay.heads):
        if lay.row_heads % lay.heads:
            pl.when(group * lay.heads + j < lay.row_heads)(
                functools.partial(head, j))
        else:
            head(j)


def _walk_specs(pl, lay, masked):
    """Block specs on the tile walk's tables (:func:`_tile_walk`), which
    every index map is handed after the grid indices (row, lane group,
    step): a q-side and a kv-side ``[rows, seq, lanes]`` block, a q-side
    and a kv-side per-head scalar block (``q_row``: the q-side one of the
    ``[rows, heads, 1, T]`` view, a row vector), and the key bias's."""
    def q_map(r, g, t, qi_tab, ki_tab, *_):
        return (r, qi_tab[t], g)

    def k_map(r, g, t, qi_tab, ki_tab, *_):
        return (r, ki_tab[t], g)

    def q_scalar_map(r, g, t, qi_tab, ki_tab, *_):
        return (r, g, qi_tab[t], 0)

    def k_scalar_map(r, g, t, qi_tab, ki_tab, *_):
        return (r, g, ki_tab[t], 0)

    def bias_map(r, g, t, qi_tab, ki_tab, *_):
        return (r // lay.per_row, ki_tab[t], 0)

    def q_row_map(r, g, t, qi_tab, ki_tab, *_):
        return (r, g, 0, qi_tab[t])

    return dict(
        q_row=pl.BlockSpec((1, lay.heads, 1, lay.block_q), q_row_map),
        q=pl.BlockSpec((1, lay.block_q, lay.width), q_map),
        k=pl.BlockSpec((1, lay.block_k, lay.width), k_map),
        q_scalar=pl.BlockSpec((1, lay.heads, lay.block_q, 1), q_scalar_map),
        k_scalar=pl.BlockSpec((1, lay.heads, lay.block_k, 1), k_scalar_map),
        bias=pl.BlockSpec((1, lay.block_k, 1), bias_map) if masked else None)




# Per-row scalars (lse, delta) live in HBM as [rows, heads, T, 1] —
# compact, not lane-broadcast. A (1, heads, block_q, 1) block DMAs block_q
# contiguous words a head and lands in VMEM as [block_q, 1] sublane
# vectors, which broadcast over the [block_q, block_k] score tile for free
# (the same m[:, None] pattern the forward's scratch uses). The official
# jax flash kernel instead broadcasts these across all 128 lanes in HBM
# ([.., T, 128] fp32) — 128x the bytes, re-streamed on every q-step of the
# dK/dV grid; at long sequence lengths that stream dwarfs the q/k/v
# traffic itself.
# jitted, so that a model's layers share one trace and one lowering of a
# kernel: lowering a `pallas_call` to Mosaic text is done anew in every
# process, before any compile cache can help, once for each equation that
# is not the same jitted call (24 layers x three kernels otherwise)
_KERNEL_STATICS = ("causal", "sm_scale", "block_q", "block_k", "interpret",
                   "dropout_rate", "dropout_num_heads")


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _pallas_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                interpret=False, key_bias=None,
                dropout_rate=0.0, dropout_seed=None,
                dropout_head_offset=None, dropout_num_heads=None):
    """Returns (out [B,T,H,D], lse [rows,heads,T,1]) — lse is the softmax
    row logsumexp residual consumed by the backward kernels.

    q, k, v are read as ``[B, T, H*D]``, as the projections wrote them:
    a grid step takes one 128-lane group of heads (:func:`_lane_groups`)
    of one q tile and one kv tile, and walks the live tiles only
    (:func:`_tile_walk`).
    ``key_bias`` [B, S] additive fp32 rides as a [B, S, 1] array indexed
    per batch row. ``dropout_rate`` (static) / ``dropout_seed``
    (int32 scalar, SMEM): in-kernel attention-prob dropout — applied to
    the accumulated probs while ``l`` keeps summing the undropped probs
    (softmax normalizes before dropout zeroes).
    ``dropout_head_offset`` (traced int32, rides in SMEM beside the
    seed) / ``dropout_num_heads`` (static): mask coordinates use the
    GLOBAL head index off + h (+ b*Hg) so a tensor-parallel head
    shard reproduces the replicated run's mask bitwise; the defaults
    reduce to the plain folded b*H + h."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    S = k.shape[1]
    Hg = H if dropout_num_heads is None else int(dropout_num_heads)
    masked = key_bias is not None
    dropping = dropout_rate > 0.0
    lay = _Layout.of(q.shape, S, block_q, block_k)
    block_q, block_k = lay.block_q, lay.block_k
    q, k, v = (_rows(x, lay.fold) for x in (q, k, v))
    R = q.shape[0]
    tables = _tile_walk(T // block_q, S // block_k, block_q, block_k,
                        causal, kv_major=False)

    def kernel(*refs):
        r, group, qi, ki, kind, first, last = _grid_step(pl, *refs[:5])
        q_ref, k_ref, v_ref, *refs = refs[5:]
        kpm_ref = refs.pop(0) if masked else None
        seed_ref = refs.pop(0) if dropping else None
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs

        def head(j):
            lanes = slice(j * D, (j + 1) * D)

            @pl.when(first)
            def _init():
                acc_ref[j] = jnp.zeros((block_q, D), jnp.float32)
                m_ref[j] = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
                l_ref[j] = jnp.zeros((block_q, 1), jnp.float32)

            def tile(rows, cols, under_mask):
                qb = q_ref[0, rows, lanes].astype(jnp.float32) * sm_scale
                kb = k_ref[0, cols, lanes].astype(jnp.float32)  # [bk, D]
                s = jax.lax.dot_general(
                    qb, kb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [bq, bk]
                if under_mask or dropping:
                    q_pos, k_pos = _positions(qi, ki, rows, cols, lay)
                if under_mask:
                    s = jnp.where(k_pos <= q_pos, s, DEFAULT_MASK_VALUE)
                if masked:
                    # [bk, 1] sublane vector → additive row bias over
                    # lanes
                    s = s + kpm_ref[0, cols, :][:, 0][None, :]
                m_prev = m_ref[j, rows, 0]
                m_new = jnp.maximum(m_prev, s.max(axis=-1))
                p = jnp.exp(s - m_new[:, None])
                corr = jnp.exp(m_prev - m_new)
                l_ref[j, rows, 0] = l_ref[j, rows, 0] * corr + p.sum(axis=-1)
                m_ref[j, rows, 0] = m_new
                pd = p
                if dropping:
                    pd = p * _dropout_piece(
                        seed_ref, r, group * lay.heads + j, lay, Hg,
                        (q_pos, k_pos), dropout_rate)
                vb = v_ref[0, cols, lanes].astype(jnp.float32)  # [bk, D]
                acc_ref[j, rows, :] = (
                    acc_ref[j, rows, :] * corr[:, None]
                    + jax.lax.dot_general(
                        pd, vb, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))

            _for_each_piece(pl, kind, tables, lay, "fwd", tile)

            @pl.when(last)
            def _finish():
                # fully-masked rows: l == 0 → guard the divide (outputs
                # for padded q positions are meaningless and masked
                # downstream)
                l_safe = jnp.maximum(l_ref[j, :, 0], 1e-30)
                o_ref[0, :, lanes] = (
                    acc_ref[j] / l_safe[:, None]).astype(o_ref.dtype)
                lse_ref[0, j] = (m_ref[j, :, 0] + jnp.log(l_safe))[:, None]

        _each_head(pl, head, group, lay)

    specs = _walk_specs(pl, lay, masked)
    in_specs = [specs["q"], specs["k"], specs["k"]]
    args = [q, k, v]
    if masked:
        in_specs.append(specs["bias"])
        args.append(key_bias.astype(jnp.float32)[..., None])   # [B, S, 1]
    if dropping:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(_seed_and_offset(dropout_seed, dropout_head_offset))
    fwd = pl.pallas_call(
        kernel,
        name=FWD_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(R, lay.groups, tables[0].size),
            in_specs=in_specs,
            out_specs=[specs["q"], specs["q_scalar"]],
            scratch_shapes=[
                pltpu.VMEM((lay.heads, block_q, D), jnp.float32),
                pltpu.VMEM((lay.heads, block_q, 1), jnp.float32),
                pltpu.VMEM((lay.heads, block_q, 1), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((R, lay.row_heads, T, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )
    with jax.named_scope(FWD_NAME):
        out, lse = fwd(*tables, *args)
    out = _from_bh(out, B, H) if lay.fold else out.reshape(B, T, H, D)
    return out, lse


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _pallas_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
                interpret=False, key_bias=None,
                dropout_rate=0.0, dropout_seed=None,
                dropout_head_offset=None, dropout_num_heads=None):
    """FlashAttention-2 backward. Two kernels, on the forward's layout
    (``[B, T, H*D]`` as it lies, a lane group of heads a grid step, live
    tiles only):

    - dQ: grid (rows, groups, tiles), q-major: accumulates dq over a q
      tile's KV tiles in VMEM.
    - dK/dV: the same tiles kv-major: accumulates dk, dv over a KV tile's
      Q tiles in VMEM.
      When a key bias is present it also emits per-head dbias partials
      (column-sums of the pre-scale ds), reduced over heads in XLA — the
      true gradient of the additive bias.

    delta = rowsum(dO ⊙ O) is computed by the dQ kernel at a q tile's
    first step and handed on to dK/dV (on ``[B, T, H*D]`` XLA would
    re-lay both factors out to reduce over a head's lanes); with
    dropout, rowsum(dP ⊙ P) still equals
    rowsum(dO ⊙ O) because the mask multiplier appears in both factors'
    chain. Dropout masks are regenerated in-kernel from the same
    counter-based hash as the forward — nothing [T, S]-shaped is stored.
    All matmuls run in fp32 on the MXU.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    S = k.shape[1]
    in_dtype = q.dtype
    Hg = H if dropout_num_heads is None else int(dropout_num_heads)
    masked = key_bias is not None
    dropping = dropout_rate > 0.0
    lay = _Layout.of(q.shape, S, block_q, block_k)
    block_q, block_k = lay.block_q, lay.block_k
    n_q, n_k = T // block_q, S // block_k

    qh, kh, vh, gh, oh = (_rows(x, lay.fold) for x in (q, k, v, g, out))
    R = qh.shape[0]
    specs = _walk_specs(pl, lay, masked)
    # what both kernels take after their own six operands
    extra_specs, extra_args = [], []
    if masked:
        extra_specs.append(specs["bias"])
        extra_args.append(key_bias.astype(jnp.float32)[..., None])
    if dropping:
        extra_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        extra_args.append(_seed_and_offset(dropout_seed,
                                           dropout_head_offset))

    def unpack(refs, n_out):
        refs = list(refs)
        ins, refs = refs[:6], refs[6:]
        kpm_ref = refs.pop(0) if masked else None
        seed_ref = refs.pop(0) if dropping else None
        return (*ins, kpm_ref, seed_ref), refs[:n_out], refs[n_out:]

    def dq_kernel(*refs):
        r, group, qi, ki, kind, first, last = _grid_step(pl, *refs[:5])
        ins, (dq_ref, delta_ref), (dq_acc,) = unpack(refs[5:], 2)
        q_ref, k_ref, v_ref, g_ref, lse_ref, o_ref, kpm_ref, seed_ref = ins

        def head(j):
            lanes = slice(j * D, (j + 1) * D)

            @pl.when(first)
            def _init():
                dq_acc[j] = jnp.zeros((block_q, D), jnp.float32)
                # delta = rowsum(dO ⊙ O), once a q tile; the block stays
                # where it is until the tile's last step, for this
                # kernel to read and the dK/dV kernel after it
                delta_ref[0, j] = jnp.sum(
                    g_ref[0, :, lanes].astype(jnp.float32)
                    * o_ref[0, :, lanes].astype(jnp.float32),
                    axis=-1, keepdims=True)

            def tile(rows, cols, under_mask):
                qb = q_ref[0, rows, lanes].astype(jnp.float32)  # [bq, D]
                kb = k_ref[0, cols, lanes].astype(jnp.float32)  # [bk, D]
                s = jax.lax.dot_general(
                    qb, kb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if under_mask or dropping:
                    q_pos, k_pos = _positions(qi, ki, rows, cols, lay)
                if under_mask:
                    s = jnp.where(k_pos <= q_pos, s, DEFAULT_MASK_VALUE)
                if masked:
                    s = s + kpm_ref[0, cols, :][:, 0][None, :]  # the bias
                p = jnp.exp(s - lse_ref[0, j, rows, :])        # [bq, bk]
                gb = g_ref[0, rows, lanes].astype(jnp.float32)  # [bq, D]
                vb = v_ref[0, cols, lanes].astype(jnp.float32)  # [bk, D]
                dp = jax.lax.dot_general(
                    gb, vb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [bq, bk]
                if dropping:
                    dp = dp * _dropout_piece(
                        seed_ref, r, group * lay.heads + j, lay, Hg,
                        (q_pos, k_pos), dropout_rate)
                ds = p * (dp - delta_ref[0, j, rows, :]) * sm_scale
                dq_acc[j, rows, :] += jax.lax.dot_general(
                    ds, kb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [bq, D]

            _for_each_piece(pl, kind, q_tables, lay, "dq", tile)

            @pl.when(last)
            def _finish():
                dq_ref[0, :, lanes] = dq_acc[j].astype(dq_ref.dtype)

        _each_head(pl, head, group, lay)

    q_tables = _tile_walk(n_q, n_k, block_q, block_k, causal,
                          kv_major=False)
    dq_call = pl.pallas_call(
        dq_kernel,
        name=DQ_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(q_tables),
            grid=(R, lay.groups, q_tables[0].size),
            in_specs=[specs["q"], specs["k"], specs["k"], specs["q"],
                      specs["q_scalar"], specs["q"]] + extra_specs,
            out_specs=[specs["q"], specs["q_scalar"]],
            scratch_shapes=[
                pltpu.VMEM((lay.heads, block_q, D), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct(qh.shape, in_dtype),
            jax.ShapeDtypeStruct(lse.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )
    with jax.named_scope(DQ_NAME):
        dq, delta = dq_call(*q_tables, qh, kh, vh, gh, lse, oh, *extra_args)

    def dkv_kernel(*refs):
        r, group, qi, ki, kind, first, last = _grid_step(pl, *refs[:5])
        ins, outs, accs = unpack(refs[5:], 3 if masked else 2)
        q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, kpm_ref, seed_ref = \
            ins
        dk_acc, dv_acc = accs[:2]

        def head(j):
            lanes = slice(j * D, (j + 1) * D)

            @pl.when(first)
            def _init():
                for acc in accs:
                    acc[j] = jnp.zeros(acc.shape[1:], jnp.float32)

            def tile(rows, cols, under_mask):
                # the tile keys-first, [bk, bq]: both gradients are then
                # plain products of it, with nothing score-sized to turn
                # round; lse and delta come as row vectors for it
                qb = q_ref[0, rows, lanes].astype(jnp.float32)  # [bq, D]
                kb = k_ref[0, cols, lanes].astype(jnp.float32)  # [bk, D]
                s = jax.lax.dot_general(
                    kb, qb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if under_mask or dropping:
                    q_pos, k_pos = _positions(qi, ki, rows, cols, lay,
                                              keys_first=True)
                if under_mask:
                    s = jnp.where(k_pos <= q_pos, s, DEFAULT_MASK_VALUE)
                if masked:
                    s = s + kpm_ref[0, cols, :]            # additive bias
                p = jnp.exp(s - lse_ref[0, j, :, rows])        # [bk, bq]
                gb = g_ref[0, rows, lanes].astype(jnp.float32)  # [bq, D]
                if dropping:
                    mult = _dropout_piece(
                        seed_ref, r, group * lay.heads + j, lay, Hg,
                        (q_pos, k_pos), dropout_rate)
                    pd = p * mult
                else:
                    pd = p
                dv_acc[j, cols, :] += jax.lax.dot_general(
                    pd, gb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [bk, D]
                vb = v_ref[0, cols, lanes].astype(jnp.float32)
                dp = jax.lax.dot_general(
                    vb, gb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [bk, bq]
                if dropping:
                    dp = dp * mult
                ds0 = p * (dp - delta_ref[0, j, :, rows])      # pre-scale
                ds = ds0 * sm_scale
                dk_acc[j, cols, :] += jax.lax.dot_general(
                    ds, qb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [bk, D]
                if masked:
                    # d(bias_j) = Σ_t ds0[j, t] (bias is added after
                    # sm_scale)
                    accs[2][j, cols, :] += ds0.sum(axis=1, keepdims=True)

            _for_each_piece(pl, kind, k_tables, lay, "dkv", tile)

            @pl.when(last)
            def _finish():
                outs[0][0, :, lanes] = dk_acc[j].astype(in_dtype)
                outs[1][0, :, lanes] = dv_acc[j].astype(in_dtype)
                if masked:
                    outs[2][0, j] = accs[2][j]

        _each_head(pl, head, group, lay)

    dkv_out_specs = [specs["k"], specs["k"]]
    dkv_out_shapes = [
        jax.ShapeDtypeStruct(kh.shape, in_dtype),
        jax.ShapeDtypeStruct(vh.shape, in_dtype),
    ]
    dkv_scratch = [
        pltpu.VMEM((lay.heads, block_k, D), jnp.float32),
        pltpu.VMEM((lay.heads, block_k, D), jnp.float32),
    ]
    if masked:
        # Per-head dbias partials [rows, heads, S, 1]: each (head, ki)
        # block is owned by one contiguous qi sweep, so no cross-head
        # accumulation races; the cheap head reduction happens in XLA
        # below.
        dkv_out_specs.append(specs["k_scalar"])
        dkv_out_shapes.append(
            jax.ShapeDtypeStruct((R, lay.row_heads, S, 1), jnp.float32))
        dkv_scratch.append(pltpu.VMEM((lay.heads, block_k, 1), jnp.float32))
    k_tables = _tile_walk(n_q, n_k, block_q, block_k, causal,
                          kv_major=True)
    dkv_call = pl.pallas_call(
        dkv_kernel,
        name=DKV_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(k_tables),
            grid=(R, lay.groups, k_tables[0].size),
            in_specs=[specs["q"], specs["k"], specs["k"], specs["q"],
                      specs["q_row"], specs["q_row"]] + extra_specs,
            out_specs=dkv_out_specs,
            scratch_shapes=dkv_scratch),
        out_shape=dkv_out_shapes,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )
    # the same bytes seen as row vectors: [rows, heads, 1, T]
    as_rows = [x.reshape(x.shape[:2] + (1, T)) for x in (lse, delta)]
    with jax.named_scope(DKV_NAME):
        outs = dkv_call(*k_tables, qh, kh, vh, gh, *as_rows, *extra_args)
    if masked:
        dk, dv, dbias_part = outs
        dbias = dbias_part.reshape(B, H, S).sum(axis=1)       # [B, S]
    else:
        dk, dv = outs
        dbias = None

    def back(x):
        return (_from_bh(x, B, H) if lay.fold
                else x.reshape(x.shape[:2] + (H, D)))

    return back(dq), back(dk), back(dv), dbias


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash_pallas(q, k, v, key_bias, dropout_seed, dropout_head_offset,
                  causal, sm_scale, block_q, block_k, dropout_rate,
                  dropout_num_heads, interpret=False):
    out, _ = _pallas_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                         interpret, key_bias=key_bias,
                         dropout_rate=dropout_rate,
                         dropout_seed=dropout_seed,
                         dropout_head_offset=dropout_head_offset,
                         dropout_num_heads=dropout_num_heads)
    return out


def _flash_pallas_fwd(q, k, v, key_bias, dropout_seed, dropout_head_offset,
                      causal, sm_scale, block_q, block_k, dropout_rate,
                      dropout_num_heads, interpret):
    out, lse = _pallas_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                           interpret, key_bias=key_bias,
                           dropout_rate=dropout_rate,
                           dropout_seed=dropout_seed,
                           dropout_head_offset=dropout_head_offset,
                           dropout_num_heads=dropout_num_heads)
    return out, (q, k, v, key_bias, dropout_seed, dropout_head_offset,
                 out, lse)


def _flash_pallas_bwd(causal, sm_scale, block_q, block_k, dropout_rate,
                      dropout_num_heads, interpret, res, g):
    (q, k, v, key_bias, dropout_seed, dropout_head_offset,
     out, lse) = res
    dq, dk, dv, dbias = _pallas_bwd(q, k, v, out, lse, g, causal, sm_scale,
                                    block_q, block_k, interpret,
                                    key_bias=key_bias,
                                    dropout_rate=dropout_rate,
                                    dropout_seed=dropout_seed,
                                    dropout_head_offset=dropout_head_offset,
                                    dropout_num_heads=dropout_num_heads)
    dkb = None if key_bias is None else dbias.astype(key_bias.dtype)
    # int32 seed/offset: cotangent type is float0
    f0 = lambda x: (None if x is None
                    else np.zeros(jnp.shape(x), jax.dtypes.float0))
    return dq, dk, dv, dkb, f0(dropout_seed), f0(dropout_head_offset)


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


# How q/k/v lie on the mesh of the program being traced, as whoever traces
# it said through :func:`placed_on_mesh`: (mesh, rows axis, heads axis).
_PLACEMENT = contextvars.ContextVar("flash_attention_placement",
                                    default=None)


@contextlib.contextmanager
def placed_on_mesh(mesh, rows, heads):
    """Trace-time scope: the Pallas attention calls traced inside run
    once per shard of ``mesh``, batch rows split over the axis named
    ``rows`` and heads over the axis named ``heads``.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so a program jitted over a multi-device
    mesh has to say where the kernel's operands lie; the kernel then
    wraps itself in ``shard_map`` over that mesh. The caller that owns
    the mesh (the training engine, around its loss function) names the
    axes — this module knows none."""
    token = _PLACEMENT.set((mesh, rows, heads))
    try:
        yield
    finally:
        _PLACEMENT.reset(token)


def placement():
    """``(mesh, rows axis, heads axis)`` as `placed_on_mesh` said, for a
    kernel's caller to wrap it by; ``None`` with no placement, or
    inside a ``shard_map`` that already made the mesh's axes manual."""
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return _PLACEMENT.get()


def _flash_pallas_on_mesh(q, k, v, key_bias, dropout_seed,
                          dropout_head_offset, causal, sm_scale, block_q,
                          block_k, dropout_rate, dropout_num_heads,
                          interpret):
    """:func:`_flash_pallas`, placed as :func:`placed_on_mesh` says.

    Attention is independent per (row, head), so each shard runs the
    plain kernel on its block; the dropout mask hashes GLOBAL (row,
    head) coordinates (``dropout_head_offset``), so its bits match the
    unsharded call. With no placement, or inside a ``shard_map`` that
    already made the mesh's axes manual, this is the bare kernel call.
    """
    def kernel(q, k, v, key_bias, seed, offset, num_heads):
        return _flash_pallas(q, k, v, key_bias, seed, offset, causal,
                             sm_scale, block_q, block_k, dropout_rate,
                             num_heads, interpret)

    placed = placement()
    if placed is None:
        return kernel(q, k, v, key_bias, dropout_seed,
                      dropout_head_offset, dropout_num_heads)

    mesh, rows, heads = placed
    B, _, H, _ = q.shape
    for axis, extent, what in ((rows, B, "batch rows"), (heads, H, "heads")):
        if extent % mesh.shape[axis]:
            raise ValueError(
                f"flash_attention: {extent} {what} do not divide over "
                f"the {mesh.shape[axis]} devices of mesh axis {axis!r}")
    qkv = PartitionSpec(rows, None, heads, None)
    # the dropout mask hashes the GLOBAL folded index b * Hg + h: a
    # shard adds where its rows and heads start (both terms are linear)
    Hg = H if dropout_num_heads is None else dropout_num_heads

    def local(q, k, v, key_bias, seed, offset):
        offset += jax.lax.axis_index(rows) * (q.shape[0] * Hg)
        offset += jax.lax.axis_index(heads) * q.shape[2]
        return kernel(q, k, v, key_bias, seed, offset, Hg)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(qkv, qkv, qkv,
                  None if key_bias is None else PartitionSpec(rows, None),
                  None if dropout_seed is None else PartitionSpec(),
                  PartitionSpec()),
        out_specs=qkv, check_vma=False,
    )(q, k, v, key_bias, dropout_seed,
      jnp.asarray(dropout_head_offset, jnp.int32))


def _fit_block(block, length):
    """The tile a sequence of ``length`` is cut in under the bound
    ``block``: the whole sequence where it is shorter, else ``block``
    halved until it divides the length (1536 under 1024: 512) or is down
    to a lane group's 128."""
    block = min(block, length)
    while length % block and block % 2 == 0 and block > LANES:
        block //= 2
    return block


def flash_attention(q, k, v, causal=True, sm_scale=None,
                    block_q=1024, block_k=1024, implementation="auto",
                    key_padding_mask=None, key_bias=None,
                    dropout_rate=0.0, dropout_seed=None,
                    dropout_head_offset=0, dropout_num_heads=None):
    """Memory-efficient attention; q,k,v: [B, T, H, D] → [B, T, H, D].

    ``implementation``: "auto" (pallas on TPU, xla elsewhere), "pallas"
    (interpreter mode off-TPU — slow, for parity tests), "xla", or "dense".
    ``block_q`` / ``block_k``: the pallas kernels' tile of queries and of
    keys (a sequence they do not divide is cut in the next halving that
    does). The kernels read q, k, v as ``[B, T, H*D]``, as the
    projections wrote them, a 128-lane group of heads a grid step: no
    transpose is made for them, whatever the head size and count.
    ``key_padding_mask`` [B, S] bool (True = attend) or ``key_bias``
    [B, S] additive fp32 (soft penalties honored exactly, with true
    gradients on every implementation): applied to scores everywhere;
    outputs at fully-masked *query* positions are unspecified (mask them
    downstream, as the loss does).

    ``dropout_rate`` (static float) / ``dropout_seed`` (int32 scalar,
    traced ok — e.g. derived per step from a PRNG key): attention-prob
    dropout computed inside the kernels from a counter-based hash of the
    global (head, query, key) coordinates (see :func:`dropout_multiplier`)
    — the in-kernel-dropout capability of the reference's fused
    transformer (`csrc/transformer/dropout_kernels.cu`), with the same
    mask bits on every implementation.

    ``dropout_head_offset`` (traced int32 ok) / ``dropout_num_heads``
    (static int): when the local heads are a tensor-parallel SHARD of a
    larger attention (Megatron head partition), pass this rank's first
    global head and the global head count — the mask then hashes global
    coordinates, so the sharded run reproduces the replicated run's
    dropout bitwise (round 5; previously TP blocks had to fall back to
    dense attention under dropout).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if dropout_rate:
        if not isinstance(dropout_rate, (int, float)):
            raise TypeError("dropout_rate must be a static Python float")
        if not 0.0 <= dropout_rate < 1.0:
            raise ValueError(f"dropout_rate {dropout_rate} not in [0, 1)")
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        if dropout_num_heads is not None:
            import numbers
            if not isinstance(dropout_num_heads, numbers.Integral):
                raise TypeError("dropout_num_heads must be a static int")
            dropout_num_heads = int(dropout_num_heads)
            if dropout_num_heads < q.shape[2]:
                raise ValueError(
                    f"dropout_num_heads {dropout_num_heads} < local heads "
                    f"{q.shape[2]}")
        dropout_seed = jnp.asarray(dropout_seed, jnp.int32)
    bias = _to_key_bias(key_padding_mask, key_bias)
    on_tpu = jax.devices()[0].platform == "tpu"
    if implementation == "auto":
        implementation = "pallas" if on_tpu else "xla"
    drop_kw = dict(dropout_rate=dropout_rate, dropout_seed=dropout_seed,
                   dropout_head_offset=dropout_head_offset,
                   dropout_num_heads=dropout_num_heads)
    if implementation == "dense":
        return dense_attention(q, k, v, causal, sm_scale, key_bias=bias,
                               **drop_kw)
    if implementation == "xla":
        return _blockwise_attention(q, k, v, causal, sm_scale,
                                    key_bias=bias, **drop_kw)
    if implementation == "pallas":
        T = q.shape[1]
        bq = _fit_block(block_q, T)
        bk = _fit_block(block_k, k.shape[1])
        if T % bq != 0 or k.shape[1] % bk != 0:
            if on_tpu:
                # the caller asked for the kernel (or "auto" chose it):
                # never hand back another implementation in its name
                raise ValueError(
                    f"flash_attention: q length {T} / kv length "
                    f"{k.shape[1]} do not tile by blocks ({bq}, {bk}); "
                    f"pick block_q/block_k that divide them, or ask for "
                    f"implementation='xla'")
            # off-TPU "pallas" is the interpret-mode parity path of the
            # CPU tests, where odd toy shapes use the blockwise oracle
            return _blockwise_attention(q, k, v, causal, sm_scale,
                                        key_bias=bias, **drop_kw)
        if on_tpu and bq % LANES and bq != T:
            # dK/dV reads lse and delta as row vectors: a q tile is
            # whole lane groups or the whole sequence
            raise ValueError(
                f"flash_attention: block_q {bq} is neither a multiple of "
                f"{LANES} nor the q length {T}")
        return _flash_pallas_on_mesh(
            q, k, v, bias, dropout_seed, dropout_head_offset, causal,
            sm_scale, bq, bk, float(dropout_rate), dropout_num_heads,
            not on_tpu)
    raise ValueError(f"unknown implementation {implementation!r}")

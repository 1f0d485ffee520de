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

def _to_bh(x):
    """[B, T, H, D] → [B*H, T, D]: heads fold into the grid's leading dim so
    block shapes end in (seq_tile, D) — the TPU-tileable layout."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _from_bh(x, B, H):
    BH, T, D = x.shape
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


# Per-row scalars (lse, delta) live in HBM as [B*H, T, 1] — compact, not
# lane-broadcast. A (1, block_q, 1) block DMAs block_q contiguous words and
# lands in VMEM as a [block_q, 1] sublane vector, which broadcasts over the
# [block_q, block_k] score tile for free (the same m[:, None] pattern the
# forward's scratch uses). The official jax flash kernel instead broadcasts
# these across all 128 lanes in HBM ([.., T, 128] fp32) — 128x the bytes,
# re-streamed on every q-step of the dK/dV grid; at long sequence lengths
# that stream dwarfs the q/k/v traffic itself.
def _pallas_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                interpret=False, key_bias=None,
                dropout_rate=0.0, dropout_seed=None,
                dropout_head_offset=None, dropout_num_heads=None):
    """Returns (out [B,T,H,D], lse [B*H,T,1]) — lse is the softmax row
    logsumexp residual consumed by the backward kernels.
    ``key_bias`` [B, S] additive fp32 rides as a [B, S, 1] array indexed
    per batch (bh // H). ``dropout_rate`` (static) / ``dropout_seed``
    (int32 scalar, SMEM): in-kernel attention-prob dropout — applied to
    the accumulated probs while ``l`` keeps summing the undropped probs
    (softmax normalizes before dropout zeroes).
    ``dropout_head_offset`` (traced int32, rides in SMEM beside the
    seed) / ``dropout_num_heads`` (static): mask coordinates use the
    GLOBAL head index off + bh%H (+ b*Hg) so a tensor-parallel head
    shard reproduces the replicated run's mask bitwise; the defaults
    reduce to the plain folded bh."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    S = k.shape[1]
    Hg = H if dropout_num_heads is None else int(dropout_num_heads)
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    assert T % block_q == 0 and S % block_k == 0, (
        f"seq lens ({T},{S}) must divide blocks ({block_q},{block_k})")
    n_q = T // block_q
    n_k = S // block_k
    masked = key_bias is not None
    dropping = dropout_rate > 0.0

    q, k, v = _to_bh(q), _to_bh(k), _to_bh(v)
    kpm = None
    if masked:
        kpm = key_bias.astype(jnp.float32)[..., None]        # [B, S, 1]

    def kernel(q_ref, k_ref, v_ref, *refs):
        refs = list(refs)
        kpm_ref = refs.pop(0) if masked else None
        seed_ref = refs.pop(0) if dropping else None
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        bh = pl.program_id(0)
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[:] = jnp.zeros_like(l_ref)

        run = True
        if causal:
            # Skip fully-masked tiles above the diagonal.
            run = (ki * block_k) <= (qi * block_q + block_q - 1)

        @pl.when(run if causal else True)
        def _compute():
            qb = q_ref[0].astype(jnp.float32) * sm_scale   # [bq, D]
            kb = k_ref[0].astype(jnp.float32)              # [bk, D]
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [bq, bk]
            if causal or dropping:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = ki * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
            if causal:
                s = jnp.where(k_pos <= q_pos, s, DEFAULT_MASK_VALUE)
            if masked:
                # [bk, 1] sublane vector → additive row bias over lanes
                s = s + kpm_ref[0][:, 0][None, :]
            m_prev = m_ref[:, 0]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
            m_ref[:, 0] = m_new
            pd = p
            if dropping:
                # Global head coordinate: bh%H local head + SMEM offset
                # (+ batch stride Hg). Defaults make this exactly bh.
                g_head = bh + (bh // H) * (Hg - H) + seed_ref[1]
                pd = p * dropout_multiplier(
                    seed_ref[0], g_head, q_pos, k_pos, dropout_rate)
            vb = v_ref[0].astype(jnp.float32)              # [bk, D]
            acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
                pd, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(ki == n_k - 1)
        def _finish():
            # fully-masked rows: l == 0 → guard the divide (outputs for
            # padded q positions are meaningless and masked downstream)
            l_safe = jnp.maximum(l_ref[:, 0], 1e-30)
            o_ref[0] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
            lse_ref[0] = (m_ref[:, 0] + jnp.log(l_safe))[:, None]

    grid = (B * H, n_q, n_k)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
    ]
    args = [q, k, v]
    if masked:
        in_specs.append(pl.BlockSpec(
            (1, block_k, 1), lambda bh, qi, ki: (bh // H, ki, 0)))
        args.append(kpm)
    if dropping:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        off = 0 if dropout_head_offset is None else dropout_head_offset
        args.append(jnp.stack(
            [jnp.asarray(dropout_seed, jnp.int32).reshape(()),
             jnp.asarray(off, jnp.int32).reshape(())]))
    fwd = pl.pallas_call(
        kernel,
        name=FWD_NAME,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )
    with jax.named_scope(FWD_NAME):
        out, lse = fwd(*args)
    return _from_bh(out, B, H), lse


def _pallas_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
                interpret=False, key_bias=None,
                dropout_rate=0.0, dropout_seed=None,
                dropout_head_offset=None, dropout_num_heads=None):
    """FlashAttention-2 backward. Two kernels:

    - dQ: grid (BH, n_q, n_k), accumulates dq over KV tiles in VMEM.
    - dK/dV: grid (BH, n_k, n_q), accumulates dk, dv over Q tiles in VMEM.
      When a key bias is present it also emits per-head dbias partials
      (column-sums of the pre-scale ds), reduced over heads in XLA — the
      true gradient of the additive bias.

    delta = rowsum(dO ⊙ O) is precomputed in XLA (it is a cheap fused
    elementwise+reduce); with dropout, rowsum(dP ⊙ P) still equals
    rowsum(dO ⊙ O) because the mask multiplier appears in both factors'
    chain. Dropout masks are regenerated in-kernel from the same
    counter-based hash as the forward — nothing [T, S]-shaped is stored.
    All matmuls run in fp32 on the MXU.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    S = k.shape[1]
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    n_q = T // block_q
    n_k = S // block_k

    in_dtype = q.dtype
    H = q.shape[2]
    Hg = H if dropout_num_heads is None else int(dropout_num_heads)
    masked = key_bias is not None
    dropping = dropout_rate > 0.0
    kpm = key_bias.astype(jnp.float32)[..., None] if masked else None
    seed_arr = None
    if dropping:
        off = 0 if dropout_head_offset is None else dropout_head_offset
        seed_arr = jnp.stack(
            [jnp.asarray(dropout_seed, jnp.int32).reshape(()),
             jnp.asarray(off, jnp.int32).reshape(())])
    qh, kh, vh = _to_bh(q), _to_bh(k), _to_bh(v)
    oh, gh = _to_bh(out), _to_bh(g)
    delta = jnp.sum(gh.astype(jnp.float32) * oh.astype(jnp.float32),
                    axis=-1, keepdims=True)                # [BH, T, 1]

    def positions(qi, ki):
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        return q_pos, k_pos

    def scores(q_ref, k_ref, qi, ki, kpm_ref=None):
        qb = q_ref[0].astype(jnp.float32)                  # [bq, D]
        kb = k_ref[0].astype(jnp.float32)                  # [bk, D]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        if causal:
            q_pos, k_pos = positions(qi, ki)
            s = jnp.where(k_pos <= q_pos, s, DEFAULT_MASK_VALUE)
        if kpm_ref is not None:
            s = s + kpm_ref[0][:, 0][None, :]              # additive bias
        return s

    def drop_tile(seed_ref, bh, qi, ki):
        # NB: bh is bound at kernel top — pl.program_id inside a pl.when
        # body breaks the interpret-mode lowering. Head coordinate is
        # globalized (TP head shard: off + bh%H, batch stride Hg) —
        # identical to the forward's, so the regenerated mask matches.
        q_pos, k_pos = positions(qi, ki)
        g_head = bh + (bh // H) * (Hg - H) + seed_ref[1]
        return dropout_multiplier(seed_ref[0], g_head, q_pos, k_pos,
                                  dropout_rate)

    def dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                  *refs):
        refs = list(refs)
        kpm_ref = refs.pop(0) if masked else None
        seed_ref = refs.pop(0) if dropping else None
        dq_ref, dq_acc = refs
        bh = pl.program_id(0)
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            dq_acc[:] = jnp.zeros_like(dq_acc)

        run = True
        if causal:
            run = (ki * block_k) <= (qi * block_q + block_q - 1)

        @pl.when(run if causal else True)
        def _compute():
            s = scores(q_ref, k_ref, qi, ki, kpm_ref)
            lse = lse_ref[0][:, :1]                        # [bq, 1]
            p = jnp.exp(s - lse)                           # [bq, bk]
            gb = g_ref[0].astype(jnp.float32)              # [bq, D]
            vb = v_ref[0].astype(jnp.float32)              # [bk, D]
            dp = jax.lax.dot_general(
                gb, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [bq, bk]
            if dropping:
                dp = dp * drop_tile(seed_ref, bh, qi, ki)
            ds = p * (dp - delta_ref[0][:, :1]) * sm_scale
            kb = k_ref[0].astype(jnp.float32)
            dq_acc[:] += jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [bq, D]

        @pl.when(ki == n_k - 1)
        def _finish():
            dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    dq_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
    ]
    dq_args = [qh, kh, vh, gh, lse, delta]
    if masked:
        dq_in_specs.append(pl.BlockSpec(
            (1, block_k, 1), lambda bh, qi, ki: (bh // H, ki, 0)))
        dq_args.append(kpm)
    if dropping:
        dq_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dq_args.append(seed_arr)
    dq_call = pl.pallas_call(
        dq_kernel,
        name=DQ_NAME,
        grid=(B * H, n_q, n_k),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qh.shape, in_dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )
    with jax.named_scope(DQ_NAME):
        dq = dq_call(*dq_args)

    def dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                   *refs):
        refs = list(refs)
        kpm_ref = refs.pop(0) if masked else None
        seed_ref = refs.pop(0) if dropping else None
        if masked:
            dk_ref, dv_ref, dbias_ref, dk_acc, dv_acc, dbias_acc = refs
        else:
            dk_ref, dv_ref, dk_acc, dv_acc = refs
            dbias_ref = dbias_acc = None
        bh = pl.program_id(0)
        ki = pl.program_id(1)
        qi = pl.program_id(2)

        @pl.when(qi == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)
            if masked:
                dbias_acc[:] = jnp.zeros_like(dbias_acc)

        run = True
        if causal:
            # Q tiles strictly above the diagonal see nothing of this KV tile.
            run = (ki * block_k) <= (qi * block_q + block_q - 1)

        @pl.when(run if causal else True)
        def _compute():
            s = scores(q_ref, k_ref, qi, ki, kpm_ref)
            p = jnp.exp(s - lse_ref[0][:, :1])             # [bq, bk]
            gb = g_ref[0].astype(jnp.float32)              # [bq, D]
            if dropping:
                mult = drop_tile(seed_ref, bh, qi, ki)
                pd = p * mult
            else:
                pd = p
            dv_acc[:] += jax.lax.dot_general(
                pd, gb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [bk, D]
            vb = v_ref[0].astype(jnp.float32)
            dp = jax.lax.dot_general(
                gb, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [bq, bk]
            if dropping:
                dp = dp * mult
            ds0 = p * (dp - delta_ref[0][:, :1])           # pre-scale ds
            ds = ds0 * sm_scale
            qb = q_ref[0].astype(jnp.float32)
            dk_acc[:] += jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [bk, D]
            if masked:
                # d(bias_j) = Σ_t ds0[t, j] (bias is added after sm_scale)
                dbias_acc[:, 0] += ds0.sum(axis=0)

        @pl.when(qi == n_q - 1)
        def _finish():
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
            if masked:
                dbias_ref[0] = dbias_acc[:]

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
        pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
    ]
    dkv_args = [qh, kh, vh, gh, lse, delta]
    if masked:
        dkv_in_specs.append(pl.BlockSpec(
            (1, block_k, 1), lambda bh, ki, qi: (bh // H, ki, 0)))
        dkv_args.append(kpm)
    if dropping:
        dkv_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dkv_args.append(seed_arr)
    dkv_out_specs = [
        pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
    ]
    dkv_out_shapes = [
        jax.ShapeDtypeStruct(kh.shape, in_dtype),
        jax.ShapeDtypeStruct(vh.shape, in_dtype),
    ]
    dkv_scratch = [
        pltpu.VMEM((block_k, D), jnp.float32),
        pltpu.VMEM((block_k, D), jnp.float32),
    ]
    if masked:
        # Per-head dbias partials [BH, S, 1]: each (bh, ki) block is owned
        # by one contiguous qi sweep, so no cross-head accumulation races;
        # the cheap head reduction happens in XLA below.
        dkv_out_specs.append(pl.BlockSpec(
            (1, block_k, 1), lambda bh, ki, qi: (bh, ki, 0)))
        dkv_out_shapes.append(
            jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32))
        dkv_scratch.append(pltpu.VMEM((block_k, 1), jnp.float32))
    dkv_call = pl.pallas_call(
        dkv_kernel,
        name=DKV_NAME,
        grid=(B * H, n_k, n_q),
        in_specs=dkv_in_specs,
        out_specs=dkv_out_specs,
        out_shape=dkv_out_shapes,
        scratch_shapes=dkv_scratch,
        interpret=interpret,
    )
    with jax.named_scope(DKV_NAME):
        outs = dkv_call(*dkv_args)
    if masked:
        dk, dv, dbias_part = outs
        dbias = dbias_part[:, :, 0].reshape(B, H, S).sum(axis=1)  # [B, S]
    else:
        dk, dv = outs
        dbias = None

    return (_from_bh(dq, B, H), _from_bh(dk, B, H), _from_bh(dv, B, H),
            dbias)


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


def flash_attention(q, k, v, causal=True, sm_scale=None,
                    block_q=512, block_k=512, implementation="auto",
                    key_padding_mask=None, key_bias=None,
                    dropout_rate=0.0, dropout_seed=None,
                    dropout_head_offset=0, dropout_num_heads=None):
    """Memory-efficient attention; q,k,v: [B, T, H, D] → [B, T, H, D].

    ``implementation``: "auto" (pallas on TPU, xla elsewhere), "pallas"
    (interpreter mode off-TPU — slow, for parity tests), "xla", or "dense".
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
        bq = min(block_q, T)
        bk = min(block_k, k.shape[1])
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
        return _flash_pallas_on_mesh(
            q, k, v, bias, dropout_seed, dropout_head_offset, causal,
            sm_scale, bq, bk, float(dropout_rate), dropout_num_heads,
            not on_tpu)
    raise ValueError(f"unknown implementation {implementation!r}")

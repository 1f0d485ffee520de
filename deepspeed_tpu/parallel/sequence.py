"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has **no** sequence-dim sharding (SURVEY.md §5.7 — its
long-context story is block-sparse attention only). These are the
TPU-idiomatic long-context mechanisms this framework adds on top of parity:

- **Ring attention** (Liu et al., arXiv:2310.01889): q stays put, k/v chunks
  rotate around the ``seq`` mesh axis via ``ppermute`` (ICI-neighbor
  traffic), with online-softmax accumulation so each device only ever holds
  one remote chunk. Memory per device: O(T/n); comm: n-1 neighbor hops that
  XLA overlaps with the chunk matmuls.
- **Ulysses** (DeepSpeed-Ulysses, arXiv:2309.14509): two ``all_to_all``
  collectives re-shard [seq-sharded, all heads] ⟷ [all seq, head-sharded]
  so any full-sequence attention kernel (flash, block-sparse) runs
  unchanged on H/n heads.

Both come as a ``*_local`` form for use inside an existing ``shard_map``
(how the engine composes them) and a standalone wrapper that builds the
``shard_map`` over a mesh.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from deepspeed_tpu.ops.pallas.flash_attention import (
    DEFAULT_MASK_VALUE,
    dropout_multiplier,
    flash_attention,
    fold_in_seed,
)
from deepspeed_tpu.parallel.collectives import (all_to_all_overlap,
                                                barrier_after,
                                                overlap_plan)


def _check_dropout_args(dropout_rate, dropout_seed):
    if dropout_rate:
        if not 0.0 <= dropout_rate < 1.0:
            raise ValueError(f"dropout_rate {dropout_rate} not in [0, 1)")
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")


def ring_attention_local(q, k, v, axis_name, causal=True, sm_scale=None,
                         dropout_rate=0.0, dropout_seed=None,
                         data_axis_name=None):
    """Ring attention over ``axis_name``; call inside ``shard_map``.

    q, k, v: [B, T_local, H, D] — this device's sequence shard. Returns the
    local [B, T_local, H, D] attention output, exactly equal to the
    corresponding slice of full attention over the global sequence.

    ``dropout_rate``/``dropout_seed``: in-kernel attention-prob dropout
    with the shared counter-based mask at GLOBAL sequence coordinates —
    every seq rank derives the same bits for the same (b, h, q, k)
    element, so the sharded result equals dense-with-the-same-mask. The
    batch coordinate is the shard-local row index; pass
    ``data_axis_name`` when a data axis is also bound so each data shard
    mixes its rank into the seed (otherwise all data shards would reuse
    one mask pattern across the batch).
    """
    _check_dropout_args(dropout_rate, dropout_seed)
    if dropout_rate and data_axis_name is not None:
        dropout_seed = fold_in_seed(dropout_seed,
                                    jax.lax.axis_index(data_axis_name))
    B, Tloc, H, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)

    qf = q.astype(jnp.float32) * sm_scale
    q_pos = idx * Tloc + jnp.arange(Tloc)            # global q positions
    perm = [(j, (j + 1) % n) for j in range(n)]
    bh_idx = jnp.arange(B)[:, None] * H + jnp.arange(H)[None, :]  # [B, H]

    def compute_chunk(acc, m, l, kc, vc, src):
        k_pos = src * Tloc + jnp.arange(Tloc)
        s = jnp.einsum("bthd,bshd->bhts", qf, kc.astype(jnp.float32))
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]  # [Tloc, Tloc] global
            s = jnp.where(mask[None, None], s, DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pd = p
        if dropout_rate > 0.0:
            pd = p * dropout_multiplier(
                dropout_seed, bh_idx[:, :, None, None],
                q_pos[None, None, :, None],
                k_pos[None, None, None, :], dropout_rate)
        acc = acc * corr[..., None] + \
            jnp.einsum("bhts,bshd->bhtd", pd, vc.astype(jnp.float32))
        return acc, m_new, l_new

    def step(carry, t):
        acc, m, l, kc, vc = carry
        # rotation first: t=0 (own chunk) is handled outside the scan, so
        # only n-1 ppermutes ever ship data
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        # after t rotations this device holds the chunk of owner (idx - t)
        src = jnp.mod(idx - t, n)
        if causal:
            # chunks entirely in the future are all-masked: skip their
            # matmuls (predicate varies per device; branch is local math)
            acc, m, l = jax.lax.cond(
                src <= idx,
                lambda a, mm, ll: compute_chunk(a, mm, ll, kc, vc, src),
                lambda a, mm, ll: (a, mm, ll),
                acc, m, l)
        else:
            acc, m, l = compute_chunk(acc, m, l, kc, vc, src)
        return (acc, m, l, kc, vc), None

    acc0 = jnp.zeros((B, H, Tloc, D), jnp.float32)
    m0 = jnp.full((B, H, Tloc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Tloc), jnp.float32)
    acc, m, l = compute_chunk(acc0, m0, l0, k, v, idx)   # own (diagonal) chunk
    (acc, m, l, _, _), _ = jax.lax.scan(
        step, (acc, m, l, k, v), jnp.arange(1, n))
    # causal rows always see the diagonal chunk (t=0), so l > 0 everywhere
    out = acc / l[..., None]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)


def ulysses_attention_local(q, k, v, axis_name, causal=True, sm_scale=None,
                            attn_fn=None, dropout_rate=0.0,
                            dropout_seed=None, data_axis_name=None):
    """Ulysses sequence parallelism; call inside ``shard_map``.

    q, k, v: [B, T_local, H, D] seq shards with H divisible by the axis
    size. all_to_all → [B, T, H/n, D], run ``attn_fn`` (default
    :func:`flash_attention`) on the full sequence, all_to_all back.

    Dropout is delegated to ``attn_fn`` with this rank's axis index MIXED
    into the seed (full avalanche, :func:`fold_in_seed` — a linear stride
    would alias the hash's coordinate multipliers): each rank attends a
    DIFFERENT head group but sees the same local head indices, so an
    unfolded seed would repeat the identical mask pattern across head
    groups (correlated dropout). ``data_axis_name``: as in
    :func:`ring_attention_local`.

    Under an active ``ulysses`` overlap plan the heads are split into
    chunk groups: group *j+1*'s decomposed ``all_to_all`` (shift
    ``ppermute``s, :func:`all_to_all_overlap`) can overlap group *j*'s
    attention. The un-chunked result is identical (the inverse
    ``all_to_all`` restores the original head order) except under
    dropout, where each group additionally folds its index into the seed
    (decorrelated but not bit-matching the monolithic mask).
    """
    _check_dropout_args(dropout_rate, dropout_seed)
    n = jax.lax.psum(1, axis_name)
    H = q.shape[2]
    assert H % n == 0, f"heads {H} must divide seq-parallel degree {n}"
    if attn_fn is None:
        attn_fn = flash_attention   # "auto": Pallas on TPU, XLA elsewhere
    kwargs = {}
    if dropout_rate > 0.0:
        seed = fold_in_seed(dropout_seed, jax.lax.axis_index(axis_name))
        if data_axis_name is not None:
            seed = fold_in_seed(seed, jax.lax.axis_index(data_axis_name))
        kwargs = {"dropout_rate": dropout_rate, "dropout_seed": seed}

    plan = overlap_plan("ulysses")
    c = 0
    if plan is not None and plan.chunks > 1 and n > 1:
        # groups must keep the per-group head dim divisible by n:
        # largest divisor of H/n that is <= plan.chunks
        h_loc = H // n
        c = min(plan.chunks, h_loc)
        while c > 1 and h_loc % c:
            c -= 1
    if c > 1:
        h_grp = H // c
        outs = []
        dep = None   # serialize the decomposed exchanges (barrier_after)
        for j in range(c):
            gkw = dict(kwargs)
            if gkw:
                gkw["dropout_seed"] = fold_in_seed(gkw["dropout_seed"], j)
            start = j * h_grp
            grp = []
            for t in (q, k, v):
                t = jax.lax.slice_in_dim(t, start, start + h_grp, axis=2)
                t = all_to_all_overlap(barrier_after(t, dep), axis_name,
                                       2, 1, chunks=c)
                dep = t
                grp.append(t)
            og = attn_fn(*grp, causal=causal, sm_scale=sm_scale, **gkw)
            back = all_to_all_overlap(og, axis_name, 1, 2, chunks=c)
            dep = back
            outs.append(back)
        return jnp.concatenate(outs, axis=2)

    def scatter_heads(x):   # [B, Tloc, H, D] → [B, T, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    out = attn_fn(scatter_heads(q), scatter_heads(k), scatter_heads(v),
                  causal=causal, sm_scale=sm_scale, **kwargs)
    # [B, T, H/n, D] → [B, Tloc, H, D]
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def _seq_sharded_call(local_fn, mesh, q, k, v, seq_axis, data_axis):
    specs = P(data_axis, seq_axis, None, None)
    fn = shard_map(local_fn, mesh=mesh, in_specs=(specs, specs, specs),
                       out_specs=specs, check_vma=False)
    return fn(q, k, v)


def ring_attention(q, k, v, mesh, causal=True, sm_scale=None,
                   seq_axis="seq", data_axis="data",
                   dropout_rate=0.0, dropout_seed=None):
    """Standalone ring attention: q,k,v [B, T, H, D] global arrays sharded
    [data, seq] over ``mesh``."""
    # fold the data rank into the seed only when there IS data sharding —
    # at data=1 the fold would be a pure (parity-breaking) seed rewrite
    dax = data_axis if mesh.shape[data_axis] > 1 else None
    local = functools.partial(ring_attention_local, axis_name=seq_axis,
                              causal=causal, sm_scale=sm_scale,
                              dropout_rate=dropout_rate,
                              dropout_seed=dropout_seed,
                              data_axis_name=dax)
    return _seq_sharded_call(local, mesh, q, k, v, seq_axis, data_axis)


def ulysses_attention(q, k, v, mesh, causal=True, sm_scale=None,
                      seq_axis="seq", data_axis="data", attn_fn=None,
                      dropout_rate=0.0, dropout_seed=None):
    """Standalone Ulysses attention: q,k,v [B, T, H, D] sharded [data, seq]."""
    dax = data_axis if mesh.shape[data_axis] > 1 else None
    local = functools.partial(ulysses_attention_local, axis_name=seq_axis,
                              causal=causal, sm_scale=sm_scale,
                              attn_fn=attn_fn, dropout_rate=dropout_rate,
                              dropout_seed=dropout_seed,
                              data_axis_name=dax)
    return _seq_sharded_call(local, mesh, q, k, v, seq_axis, data_axis)

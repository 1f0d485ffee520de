"""Dropless top-k routing over a bank of gated experts.

`moe/layer.py` routes GShard-style: one-hot ``[B, S, E, C]`` dispatch and
combine tensors and a fixed capacity, so tokens over capacity are
dropped. The sparse-expert models deployed since (OLMoE, DeepSeek-MoE,
Qwen-MoE) drop nothing, and at their shapes those tensors cost as much
as the model (64 experts, top-8, 8192 tokens: 335 M elements each).
Here every token-expert pair is kept: the pairs are laid out expert
after expert, each expert's rows padded to whole tiles
(`group_layout`), the tokens' rows are gathered into that layout, one
grouped matmul per weight bank runs over it
(`ops/pallas/grouped_matmul.py`), and each token gathers its rows back
and sums them by its router probabilities. Shapes are static throughout
(the worst-case number of rows always; only the group sizes vary), so
the step compiles once. Forward and backward move rows by gathers
alone: the two permutations carry hand-written cotangents that gather
by the inverse map where jax's own would scatter-add.

The four phases carry ``jax.named_scope`` names (``ds_moe_route``,
``ds_moe_dispatch``, ``ds_moe_experts``, ``ds_moe_combine``) that reach
the compiled program's op metadata, by which a trace's ops are laid to
them (`benchmarks/suite/readers/scope_time.py`).
"""

import functools
import math

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import grouped_matmul


def tile_rows(n_pairs, n_experts):
    """Rows of a tile of the experts' grouped matmuls: half an expert's
    mean load as a power of two, between 16 (a bf16 tile's sublanes) and
    128. Every expert's last tile is half empty on average, and the
    padding is gathered, multiplied and gathered back with the rest: at
    65,536 pairs over 64 experts on a v5e a layer forward and backward
    took 34.2 ms with tiles of 128 rows, 34.8 with 256, 36.0 with 512
    (the kernels alone 17.5, 17.1, 16.4)."""
    mean = max(1.0, n_pairs / n_experts)
    return int(min(128, max(16, 2 ** round(math.log2(mean / 2)))))


def group_layout(pair_expert, n_experts, tile_m):
    """Where the token-expert pairs lie once sorted by expert with every
    expert's rows padded to whole tiles of ``tile_m`` (at least one):
    the layout `ops/pallas/grouped_matmul.py` multiplies. Static shapes:
    ``R = (ceil(pairs / tile_m) + n_experts) * tile_m`` rows, the worst
    case. Returns a dict: ``group_sizes`` ``[E]``; ``tile_group``
    ``[R / tile_m]`` and ``n_used`` ``[1]`` for the kernel; ``row_pair``
    ``[R]`` (the pair a row holds) with ``row_valid`` ``[R]`` (false on
    padding); ``pair_row`` ``[pairs]`` (the row a pair lies in)."""
    n_pairs = pair_expert.size
    m_tiles = -(-n_pairs // tile_m) + n_experts
    group_sizes = jnp.zeros((n_experts,), jnp.int32).at[pair_expert].add(1)
    order = jnp.argsort(pair_expert, stable=True).astype(jnp.int32)
    position = jnp.zeros_like(order).at[order].set(
        jnp.arange(n_pairs, dtype=jnp.int32), unique_indices=True)
    tiles = jnp.maximum(-(-group_sizes // tile_m), 1)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile_m     # a group's first row
    sorted_start = jnp.cumsum(group_sizes) - group_sizes
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(m_tiles, dtype=jnp.int32),
                         side="right"), n_experts - 1).astype(jnp.int32)
    row_group = jnp.repeat(tile_group, tile_m)
    rank = jnp.arange(m_tiles * tile_m, dtype=jnp.int32) - \
        row_start[row_group]
    row_valid = rank < group_sizes[row_group]
    row_pair = order[jnp.clip(sorted_start[row_group] + rank, 0,
                              n_pairs - 1)]
    pair_row = row_start[pair_expert] + position - sorted_start[pair_expert]
    return {"group_sizes": group_sizes, "tile_group": tile_group,
            "n_used": tile_end[-1:].astype(jnp.int32),
            "row_pair": row_pair, "row_valid": row_valid,
            "pair_row": pair_row}


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gather_tokens(x, row_pair, row_valid, pair_row, top_k):
    """Each row of the layout gets its pair's token (pair p belongs to
    token ``p // top_k``), padding gets zeros. The cotangent is gathered
    too, not scattered: a token's ``top_k`` rows are fetched by
    ``pair_row`` and summed."""
    return jnp.where(row_valid[:, None], x[row_pair // top_k], 0)


def _gather_tokens_fwd(x, row_pair, row_valid, pair_row, top_k):
    return jnp.where(row_valid[:, None], x[row_pair // top_k], 0), pair_row


def _gather_tokens_bwd(top_k, pair_row, g):
    back = g[pair_row].reshape(-1, top_k, g.shape[-1])
    return back.astype(jnp.float32).sum(1).astype(g.dtype), None, None, None


_gather_tokens.defvjp(_gather_tokens_fwd, _gather_tokens_bwd)


@jax.custom_vjp
def _gather_pairs(rows, row_pair, row_valid, pair_row):
    """``rows[pair_row]``: each pair's row of the layout, in pair
    order; the cotangent goes the other way by ``row_pair``, zeros into
    the padding."""
    return rows[pair_row]


def _gather_pairs_fwd(rows, row_pair, row_valid, pair_row):
    return rows[pair_row], (row_pair, row_valid)


def _gather_pairs_bwd(res, g):
    row_pair, row_valid = res
    return (jnp.where(row_valid[:, None], g[row_pair], 0), None, None, None)


_gather_pairs.defvjp(_gather_pairs_fwd, _gather_pairs_bwd)


def router_logits(x, router):
    """``x router`` in float32 at the highest precision, whatever type
    the tokens come in: near-ties between experts are decided here."""
    return jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def dropless_moe(x, router, w_gate, w_up, w_down, top_k):
    """``y[t] = sum over the top_k experts e of token t of
    p[t, e] * w_down[e] (silu(w_gate[e] x[t]) * w_up[e] x[t])`` with
    ``p = softmax(x router)`` in float32 over all experts, the chosen
    probabilities used as they are (not renormalised).

    ``x`` ``[N, M]`` tokens; ``router`` ``[M, E]``; ``w_gate``, ``w_up``
    ``[E, M, I]``; ``w_down`` ``[E, I, M]``. The expert products run in
    ``x``'s dtype, the router and the weighted sum in float32.

    Returns ``(y [N, M], stats)``; ``stats`` holds, for the losses and
    the step's counters: ``chosen`` ``[N, top_k]`` (each token's
    experts), ``tokens_per_expert`` ``[E]`` (pairs sent to each expert),
    ``prob_sum`` ``[E]`` (router probability summed over tokens;
    differentiable), ``z_sum`` (sum over tokens of
    ``logsumexp(logits)^2``; differentiable) and ``dropped`` (pairs
    that reached no expert: ``N * top_k`` less the group sizes' sum, 0
    by construction)."""
    n_tokens, n_experts = x.shape[0], router.shape[1]
    tile_m = tile_rows(n_tokens * top_k, n_experts)
    with jax.named_scope("ds_moe_route"):
        logits = router_logits(x, router)
        lse = jax.nn.logsumexp(logits, axis=-1)
        probs = jnp.exp(logits - lse[:, None])
        weights, experts = jax.lax.top_k(probs, top_k)      # [N, k]
        lay = group_layout(experts.reshape(-1), n_experts, tile_m)
        group_sizes = lay["group_sizes"]
        where = (lay["row_pair"], lay["row_valid"], lay["pair_row"])
        tiles = (lay["tile_group"], lay["n_used"], tile_m)
    with jax.named_scope("ds_moe_dispatch"):
        rows = _gather_tokens(x, *where, top_k)             # [R, M]
    with jax.named_scope("ds_moe_experts"):
        dt = x.dtype
        hidden = jax.nn.silu(
            grouped_matmul(rows, w_gate.astype(dt), *tiles)) * \
            grouped_matmul(rows, w_up.astype(dt), *tiles)
        out = grouped_matmul(hidden, w_down.astype(dt), *tiles)
    with jax.named_scope("ds_moe_combine"):
        out = _gather_pairs(out, *where).reshape(n_tokens, top_k, -1)
        y = jnp.einsum("nk,nkm->nm", weights, out.astype(jnp.float32))
    stats = {
        "chosen": experts,
        "tokens_per_expert": group_sizes,
        "prob_sum": probs.sum(0),
        "z_sum": jnp.sum(lse * lse),
        "dropped": n_tokens * top_k - group_sizes.sum(),
    }
    return y.astype(x.dtype), stats

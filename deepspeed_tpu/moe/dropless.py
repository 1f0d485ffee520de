"""Dropless top-k routing over banks of experts.

`moe/layer.py` routes GShard-style: one-hot ``[B, S, E, C]`` dispatch and
combine tensors and a fixed capacity, so tokens over capacity are
dropped. The sparse-expert models deployed since (OLMoE, DeepSeek-MoE,
Qwen-MoE) drop nothing, and at their shapes those tensors cost as much
as the model (64 experts, top-8, 8192 tokens: 335 M elements each).
Here every token-expert pair is kept: the pairs are sorted by expert,
the tokens' rows are gathered into that order, one grouped matmul per
weight bank runs over the ragged groups (`grouped_matmul`: jax's
`megablox.gmm` Pallas kernel), and each token gathers its rows back and
sums them by its router probabilities. Shapes are static throughout
(tokens x top_k rows always; only the group sizes vary), so the step
compiles once. Forward and backward move rows by gathers alone: the two
permutations carry hand-written cotangents that gather by the inverse
permutation where jax's own would scatter-add.

GSPMD cannot partition a Mosaic kernel, and a sort over all chips'
tokens is not what data parallelism means: where the tracing engine has
said how batch rows lie on its mesh
(`ops/pallas/flash_attention.py:placed_on_mesh`), the whole of
`dropless_moe` runs inside a ``shard_map`` over that axis, each chip
routing its own tokens through its copy of the experts, the counters
and the losses' sums added up over the chips.

**Routing is a function** (`softmax_top_k`, OLMoE's;
`softmax_top_k_renorm`, Qwen3-MoE's: the chosen probabilities over
their sum; `softmax_top_k_scaled`, Laguna's: those times a factor;
`sigmoid_top_k`,
DeepSeek-V3's and Kimi-K2's: sigmoid scores, a bias that moves the
choice and no weight, the chosen scores renormalised and scaled;
`sigmoid_group_top_k`, that with ``noaux_tc``'s group stage before the
choice, Ling-3.0's), and
**an expert layer can hold a share** (ISSUE 34): given ``first_expert``
the banks are the ``bank.shape[0]`` experts from there on of the
router's ``router.shape[1]``, as one chip of an expert-parallel
deployment holds them. Routing still runs over all experts; the pairs
whose expert is held are sorted to the front by expert and run through
the same grouped matmuls, whose grid follows the group sizes, so the
rows behind them (pairs of experts held elsewhere, pairs of masked
tokens) cost no tile. That holds for the gathers and the combine too
(ISSUE 45, `_held_moe`): dispatch, the activation between the grouped
matmuls and the combine are loops over the row tiles that hold held
pairs, to a bound that is a value on the device; the dispatch fills
those tiles of a buffer that is otherwise never written, and the
combine adds each tile's rows to their tokens, so a row behind the
held pairs is neither fetched nor multiplied nor summed, and adds zero
by its index and not by what a kernel left there. Shapes stay static
and nothing is dropped at any held count. A share is served and never
differentiated, so this path is apart from the whole layer's
(`_dropless_moe`), whose rows move by gathers with hand-written
cotangents. On one chip the layer runs without its exchange: what the
other chips would add is not computed and nothing stands in for it.

**An expert has one of two forms**, told by the banks given: three
banks, ``w_down (silu(w_gate r) * w_up r)`` (OLMoE's, DeepSeek-V3's), or
two (``w_gate`` None), ``w_down relu(w_up r)^2`` (Nemotron-H's: no gate
matrix, so two grouped matmuls). And **what the router reads need not
be what the experts take** (ISSUE 41): given ``rows``, routing runs on
the tokens ``x`` while the rows that are sorted, gathered, multiplied
and summed are ``rows`` (Nemotron-H's experts work on a 1024-wide
projection of the 4096-wide tokens the router scores); the output is
then as wide as ``rows``.

The four phases carry ``jax.named_scope`` names (``ds_moe_route``,
``ds_moe_dispatch``, ``ds_moe_experts``, ``ds_moe_combine``) that reach
the compiled program's op metadata, by which a trace's ops are laid to
them (`benchmarks/suite/readers/scope_time.py`).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.pallas.flash_attention import placement


def grouped_matmul(rows, bank, group_sizes):
    """``out[r] = rows[r] @ bank[g]`` for the rows of group ``g``:
    ``rows`` ``[R, K]`` lie group after group, ``group_sizes`` ``[G]``
    int32 summing to ``R``; ``bank`` ``[G, K, N]``. Differentiable in
    ``rows`` and ``bank``.

    `jax.experimental.pallas.ops.tpu.megablox.gmm`, in tiles of 256
    rows (the largest power of two up to it that divides ``R``) by 1024
    x 1024 of the bank. On a v5e, one OLMoE layer forward and backward
    at 65,536 rows over 64 experts took 33.8 ms so, 34.0 with 512 rows,
    37.2 with 512-wide blocks, 278 with the kernel's default tiles of
    128 cubed; `jax.lax.ragged_dot` took 43.7, and a kernel of this
    repo's over groups padded to whole tiles 34.2 (PERF.md, PR 26).
    Interpret mode wherever the first device is not a TPU."""
    from jax.experimental.pallas.ops.tpu import megablox

    # a tile of rows is whole sublanes (Mosaic refuses a block of 4 rows:
    # 2 decode rows x 22 pairs): rows are padded up to eight; the padding
    # lies behind every group, so no tile of it is visited
    n_rows = rows.shape[0]
    if n_rows % 8:
        rows = jnp.pad(rows, ((0, -n_rows % 8), (0, 0)))
    tiling = (math.gcd(rows.shape[0], 256), min(rows.shape[1], 1024),
              min(bank.shape[2], 1024))
    out = megablox.gmm(rows, bank, group_sizes, rows.dtype, tiling,
                       interpret=jax.devices()[0].platform != "tpu")
    return out[:n_rows]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_tokens(x, order, inverse, top_k):
    """Row ``r`` of the sorted pairs gets its pair's token (pair ``p``
    belongs to token ``p // top_k``). The cotangent is gathered too,
    not scattered: a token's ``top_k`` rows are fetched by ``inverse``
    and summed."""
    return x[order // top_k]


def _gather_tokens_fwd(x, order, inverse, top_k):
    return x[order // top_k], inverse


def _gather_tokens_bwd(top_k, inverse, g):
    back = g[inverse].reshape(-1, top_k, g.shape[-1])
    return back.astype(jnp.float32).sum(1).astype(g.dtype), None, None


_gather_tokens.defvjp(_gather_tokens_fwd, _gather_tokens_bwd)


@jax.custom_vjp
def _gather_pairs(rows, order, inverse):
    """``rows[inverse]``: each pair's row, in pair order; the cotangent
    goes the other way by ``order``."""
    return rows[inverse]


def _gather_pairs_fwd(rows, order, inverse):
    return rows[inverse], order


def _gather_pairs_bwd(order, g):
    return g[order], None, None


_gather_pairs.defvjp(_gather_pairs_fwd, _gather_pairs_bwd)


def router_logits(x, router):
    """``x router`` in float32 at the highest precision, whatever type
    the tokens come in: near-ties between experts are decided here."""
    return jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def softmax_top_k(x, router, top_k):
    """OLMoE's routing: ``p = softmax(x router)`` over all experts, the
    ``top_k`` most probable, their probabilities as they are (not
    renormalised). Returns ``(weights [N, k] float32, experts [N, k],
    aux)``; ``aux`` holds what the router losses sum (``prob_sum``
    ``[E]``, ``z_sum``)."""
    logits = router_logits(x, router)
    # not exp(logits - lse): a v5e's float32 log leaves lse off by
    # 1e-4, and with it every probability of the token by 6e-5
    probs = jax.nn.softmax(logits, axis=-1)
    lse = jax.nn.logsumexp(logits, axis=-1)     # the z-loss's
    weights, experts = jax.lax.top_k(probs, top_k)      # [N, k]
    return weights, experts, {"prob_sum": probs.sum(0),
                              "z_sum": jnp.sum(lse * lse)}


def softmax_top_k_renorm(x, router, top_k):
    """Qwen3-MoE's routing (``norm_topk_prob``): ``p = softmax(x
    router)`` over all experts, the ``top_k`` most probable, their
    probabilities over their sum, so a token's weights add up to 1
    over all its chosen experts, held here or not. All float32. A
    function beside `softmax_top_k` and not a flag inside it: OLMoE's
    training step runs that one bit for bit."""
    probs = jax.nn.softmax(router_logits(x, router), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)      # [N, k]
    return weights / weights.sum(-1, keepdims=True), experts, {}


def softmax_top_k_scaled(scaling, renormalise=True):
    """Laguna's routing (``moe_routed_scaling_factor``): the weights of
    `softmax_top_k_renorm` (of `softmax_top_k` if not ``renormalise``)
    times ``scaling``. Returns a routing function for `dropless_moe`; a
    sibling and not an argument of those two, whose lowered text other
    models' tests pin."""
    plain = softmax_top_k_renorm if renormalise else softmax_top_k

    def route(x, router, top_k):
        weights, experts, aux = plain(x, router, top_k)
        return weights * scaling, experts, aux
    return route


def sigmoid_top_k(bias, scaling, renormalise=True, eps=1e-20):
    """DeepSeek-V3's routing without groups (``noaux_tc`` with one
    group, Kimi-K2's): ``s = sigmoid(x router)``; the ``top_k`` largest
    of ``s + bias`` are chosen (``bias`` ``[E]`` moves the choice and
    nothing else); their weights are ``s``, over their sum plus ``eps``
    if ``renormalise`` (LFM2's ``eps`` is 1e-6), times ``scaling``. All
    float32. Returns a routing function for `dropless_moe`."""
    def route(x, router, top_k):
        scores = jax.nn.sigmoid(router_logits(x, router))
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if renormalise:
            weights = weights / (weights.sum(-1, keepdims=True) + eps)
        return weights * scaling, experts, {}
    return route


def sigmoid_group_top_k(bias, scaling, n_group, topk_group,
                        renormalise=True):
    """DeepSeek-V3's routing with its group stage (``noaux_tc`` with
    ``n_group`` > 1; Ling-3.0's): ``s = sigmoid(x router)``, ``c = s +
    bias``; the experts lie in ``n_group`` groups of consecutive
    experts, a group's score is the sum of its two largest ``c``, the
    ``topk_group`` best groups are kept, and the ``top_k`` largest ``c``
    inside them are chosen; their weights are ``s``, over their sum if
    ``renormalise``, times ``scaling``. All float32. Returns a routing
    function for `dropless_moe` whose ``aux`` holds ``kept_groups``
    ``[N, topk_group]``. A sibling of `sigmoid_top_k` and not an
    argument of it: other models' lowered text is pinned."""
    def route(x, router, top_k):
        scores = jax.nn.sigmoid(router_logits(x, router))
        choice = scores + bias.astype(jnp.float32)
        n, e = choice.shape
        if e % n_group:
            raise ValueError(f"{e} experts do not lie in {n_group} groups")
        per = e // n_group
        group_score = jax.lax.top_k(choice.reshape(n, n_group, per),
                                    min(2, per))[0].sum(-1)
        _, kept = jax.lax.top_k(group_score, topk_group)
        keep = (kept[:, :, None] == jnp.arange(n_group)).any(1)
        _, experts = jax.lax.top_k(
            jnp.where(jnp.repeat(keep, per, axis=1), choice, -jnp.inf),
            top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if renormalise:
            weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return weights * scaling, experts, {"kept_groups": kept}
    return route


def _sort_pairs(keys, n_groups):
    """``(group_sizes [n_groups], order, inverse)`` of the pairs sorted
    by ``keys`` (a pair's group; ``n_groups`` for a pair that belongs to
    none, which sorts behind them all). order: the pair in each sorted
    row; inverse: each pair's row."""
    # a key of n_groups is out of bounds, and a scatter drops it
    group_sizes = jnp.zeros((n_groups,), jnp.int32).at[keys].add(1)
    order = jnp.argsort(keys, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.size, dtype=jnp.int32), unique_indices=True)
    return group_sizes, order, inverse


def _dropless_moe(x, router, w_gate, w_up, w_down, top_k,
                  route=softmax_top_k, rows=None):
    """`dropless_moe` on the tokens of one chip, every expert held."""
    n_tokens, n_experts = x.shape[0], router.shape[1]
    taken = x if rows is None else rows
    with jax.named_scope("ds_moe_route"):
        weights, experts, aux = route(x, router, top_k)
        group_sizes, order, inverse = _sort_pairs(experts.reshape(-1),
                                                  n_experts)
    with jax.named_scope("ds_moe_dispatch"):
        rows = _gather_tokens(taken, order, inverse, top_k)  # [N k, M]
    with jax.named_scope("ds_moe_experts"):
        dt = taken.dtype
        if w_gate is None:
            hidden = jnp.square(jax.nn.relu(
                grouped_matmul(rows, w_up.astype(dt), group_sizes)))
        else:
            hidden = jax.nn.silu(
                grouped_matmul(rows, w_gate.astype(dt), group_sizes)) * \
                grouped_matmul(rows, w_up.astype(dt), group_sizes)
        out = grouped_matmul(hidden, w_down.astype(dt), group_sizes)
    with jax.named_scope("ds_moe_combine"):
        out = _gather_pairs(out, order, inverse).reshape(
            n_tokens, top_k, -1)
        y = jnp.einsum("nk,nkm->nm", weights, out.astype(jnp.float32))
    stats = {
        "chosen": experts,
        "weights": weights,
        "tokens_per_expert": group_sizes,
        "dropped": n_tokens * top_k - group_sizes.sum(),
        **aux,
    }
    return y.astype(taken.dtype), stats


def _unwritten_rows(like, n_rows):
    """``[n_rows, like.shape[1]]`` of ``like``'s dtype that nothing has
    written: a kernel with no body, its output left in the device's
    memory. `_held_moe` fills the tiles it uses; zeros would be a pass
    over every row (117 MB a Kimi layer, 185 us: PERF.md, PR 45).
    ``like`` is handed in, unread, so that two layers' calls are not
    one call to the compiler, which would then copy the buffer for the
    second."""
    from jax.experimental import pallas as pl

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        lambda like, out: None, in_specs=[anywhere], out_specs=anywhere,
        out_shape=jax.ShapeDtypeStruct((n_rows, like.shape[1]), like.dtype),
        name="ds_moe_unwritten_rows",
        interpret=jax.devices()[0].platform != "tpu")(like)


def _held_moe(x, router, w_gate, w_up, w_down, top_k, route, first_expert,
              token_mask=None, rows=None):
    """`dropless_moe` on a share of the experts (``first_expert``):
    served, never differentiated. The ``H`` held pairs (held expert,
    live token) sort to rows ``[0, H)``; everything outside the grouped
    matmuls is a loop over the ``ceil(H / tile)`` row tiles that hold
    them (``tile`` the grouped matmul's row tile), ``H`` a value on the
    device, so the rows behind cost neither a fetch nor a sum. The
    grouped matmuls run once a bank on the whole ``[N top_k, .]`` buffer
    and visit the same tiles."""
    n_tokens, n_held = x.shape[0], w_up.shape[0]
    taken = x if rows is None else rows
    dt = taken.dtype
    n_pairs = n_tokens * top_k
    tile = math.gcd(n_pairs, 256)
    with jax.named_scope("ds_moe_route"):
        weights, experts, aux = route(x, router, top_k)
        local = experts.reshape(-1) - first_expert
        held = (local >= 0) & (local < n_held)
        if token_mask is not None:
            held &= jnp.repeat(token_mask, top_k)
        # not `_sort_pairs`: no row is fetched back by an inverse here,
        # and its scatter-add counts a pair at a time (197 us at 22,528
        # pairs, a compare and a sum 14: PERF.md, PR 45)
        keys = jnp.where(held, local, n_held)
        group_sizes = (keys[:, None] == jnp.arange(n_held)).sum(
            0, dtype=jnp.int32)
        order = jnp.argsort(keys, stable=True).astype(jnp.int32)
        n_live = group_sizes.sum()
        tiles = (n_live + tile - 1) // tile

    def pairs_of(i):
        return jax.lax.dynamic_slice(order, (i * tile,), (tile,))

    def rows_of(buf, i):
        return jax.lax.dynamic_slice(buf, (i * tile, 0),
                                     (tile, buf.shape[1]))

    def over_tiles(body, init):
        return jax.lax.fori_loop(0, tiles, body, init)

    with jax.named_scope("ds_moe_dispatch"):
        def fill(i, buf):
            return jax.lax.dynamic_update_slice(
                buf, taken[pairs_of(i) // top_k], (i * tile, 0))
        rows = over_tiles(fill, _unwritten_rows(taken, n_pairs))
    with jax.named_scope("ds_moe_experts"):
        gate = None if w_gate is None else \
            grouped_matmul(rows, w_gate.astype(dt), group_sizes)
        up = grouped_matmul(rows, w_up.astype(dt), group_sizes)

        # behind the live tiles the grouped matmuls left what was in
        # memory, and the next one reads none of it
        def activate(i, hidden):
            u = rows_of(hidden, i)
            h = jnp.square(jax.nn.relu(u)) if gate is None else \
                jax.nn.silu(rows_of(gate, i)) * u
            return jax.lax.dynamic_update_slice(hidden, h, (i * tile, 0))
        out = grouped_matmul(over_tiles(activate, up), w_down.astype(dt),
                             group_sizes)
    with jax.named_scope("ds_moe_combine"):
        pair_weight = weights.reshape(-1)
        token = jnp.arange(n_tokens)[:, None]

        # a tile's rows, scaled, are added to their tokens by a 0/1
        # selector at the highest precision (exact: one term a product);
        # a row past the held pairs adds zero by its index, not by what
        # the kernel left there
        def add(i, y):
            pairs = pairs_of(i)
            live = i * tile + jnp.arange(tile) < n_live
            part = pair_weight[pairs][:, None] * \
                rows_of(out, i).astype(jnp.float32)
            part = jnp.where(live[:, None], part, 0.0)
            pick = (token == pairs // top_k).astype(jnp.float32)
            return y + jnp.dot(pick, part,
                               precision=jax.lax.Precision.HIGHEST)
        y = over_tiles(add, jnp.zeros((n_tokens, out.shape[1]),
                                      jnp.float32))
    stats = {
        "chosen": experts,
        "weights": weights,
        "tokens_per_expert": group_sizes,
        "dropped": n_pairs - n_live,
        "rows_visited": tiles * tile,
        **aux,
    }
    return y.astype(dt), stats


class ExpertExchangeUnsupported(NotImplementedError):
    """A layer that holds a share of the experts was traced under a
    mesh placement: the exchange of tokens between the chips that hold
    the other shares is not written yet (ROADMAP Reach A2)."""


def dropless_moe(x, router, w_gate, w_up, w_down, top_k,
                 route=softmax_top_k, first_expert=None, token_mask=None,
                 rows=None):
    """``y[t] = sum over the top_k experts e of token t of
    p[t, e] * w_down[e] (silu(w_gate[e] x[t]) * w_up[e] x[t])`` with
    ``p = softmax(x router)`` in float32 over all experts, the chosen
    probabilities used as they are (not renormalised); another
    ``route`` (`sigmoid_top_k`) gives other experts and weights.

    ``w_gate`` None: an expert is two banks, ``w_down[e]
    relu(w_up[e] r)^2``. ``rows`` ``[N, L]`` (or None: the tokens
    themselves): what the experts take of each token, where that is not
    what the router reads; the banks are then ``[E, L, I]`` and ``[E,
    I, L]`` and ``y`` is ``[N, L]``.

    ``first_expert`` (an int, or None: the banks hold every expert):
    the banks hold the ``w_gate.shape[0]`` experts from ``first_expert``
    on of the router's ``router.shape[1]``. Pairs of other experts, and
    every pair of a token where ``token_mask`` ``[N]`` is False (a row
    without a request, a chunk's padding), add exactly zero, cost no
    tile of the grouped matmuls, and are counted in ``dropped``
    (``tokens_per_expert`` is then ``[held]``: the pairs each held
    expert took). One chip only (:class:`ExpertExchangeUnsupported`).

    ``x`` ``[N, M]`` tokens; ``router`` ``[M, E]``; ``w_gate``, ``w_up``
    ``[E, M, I]``; ``w_down`` ``[E, I, M]``. The expert products run in
    ``x``'s dtype, the router and the weighted sum in float32. Traced
    under `placed_on_mesh`, ``x`` is taken as split over the mesh's
    rows axis and the weights as whole on every chip.

    Returns ``(y [N, M], stats)``; ``stats`` holds, for the losses and
    the step's counters: ``chosen`` ``[N, top_k]`` (each token's
    experts) and ``weights`` ``[N, top_k]`` (their probabilities,
    float32), ``tokens_per_expert`` ``[E]`` (pairs sent to each
    expert), ``prob_sum`` ``[E]`` (router probability summed over
    tokens; differentiable), ``z_sum`` (sum over tokens of
    ``logsumexp(logits)^2``; differentiable) and ``dropped`` (pairs
    that reached no expert: ``N * top_k`` less the group sizes' sum, 0
    by construction); the last four summed over all chips' tokens."""
    placed = placement()
    if placed is None or placed[0].shape[placed[1]] == 1:
        if first_expert is None:
            return _dropless_moe(x, router, w_gate, w_up, w_down, top_k,
                                 route, rows)
        return _held_moe(x, router, w_gate, w_up, w_down, top_k, route,
                         first_expert, token_mask, rows)
    if first_expert is not None:
        raise ExpertExchangeUnsupported(
            "dropless_moe with a share of the experts runs on one chip: "
            "no exchange of tokens between shares exists yet")
    mesh, axis, _ = placed
    if x.shape[0] % mesh.shape[axis]:
        raise ValueError(
            f"dropless_moe: {x.shape[0]} tokens do not divide over the "
            f"{mesh.shape[axis]} devices of mesh axis {axis!r}")

    def local(x, rows, router, w_gate, w_up, w_down):
        y, stats = _dropless_moe(x, router, w_gate, w_up, w_down, top_k,
                                 route, rows=rows)
        for key in ("tokens_per_expert", "prob_sum", "z_sum", "dropped"):
            stats[key] = jax.lax.psum(stats[key], axis)
        return y, stats

    tokens = P(axis, None)
    return jax.shard_map(
        local, mesh=mesh,
        # None (no rows apart from the tokens, no gate bank) is an
        # empty tree: its spec is never read
        in_specs=(tokens, tokens, P(), P(), P(), P()),
        out_specs=(tokens, {
            "chosen": tokens, "weights": tokens, "tokens_per_expert": P(),
            "prob_sum": P(), "z_sum": P(), "dropped": P()}),
        check_vma=False)(x, rows, router, w_gate, w_up, w_down)

"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change
what a token or a kernel call is worth. ``cfg`` is a configuration file's
dict (the published ``config.json`` keys: ``n_layer``, ``n_embd``,
``n_head``, ``vocab_size``).
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def train_flops_per_token(cfg, seq_len):
    """Matmul FLOPs per token of a decoder-only LM, forward + backward
    (6 x weights): the blocks, the tied LM head, and the attention
    score/value matmuls counted dense (12 * L * C * T; a causal kernel
    that skips the masked half does less work than is counted here, as
    in every published MFU). Embedding lookups are gathers and do not
    count; recomputed operations do not count. Copied from
    ``bench.py:model_flops_per_token`` (checked there against XLA's cost
    analysis: 742M analytic vs 743M counted at 125M)."""
    n_layer, n_embd = cfg["n_layer"], cfg["n_embd"]
    block_params = n_layer * (12 * n_embd ** 2 + 13 * n_embd)
    lm_head = cfg["vocab_size"] * n_embd
    attention = 12 * n_layer * n_embd * seq_len
    return 6 * (block_params + 2 * n_embd + lm_head) + attention


def param_count(cfg):
    """Parameters of the GPT-2 LM (tied head)."""
    n_layer, n_embd = cfg["n_layer"], cfg["n_embd"]
    return (n_layer * (12 * n_embd ** 2 + 13 * n_embd) + 2 * n_embd +
            (cfg["vocab_size"] + cfg["n_positions"]) * n_embd)


def peaks_for(device_kind):
    """The published peaks of ``device_kind``. A device that is not in
    ``peaks.json`` is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"peaks.json (known: {sorted(table)}); add a sourced row")
    return table[device_kind]


# --- what one kernel call needs: (operations, bytes), from shapes ---------

def flash_attention_train_step(ctx, result):
    """The flash-attention kernels of one training step on one chip:
    forward 4 * B*H*T^2*D operations (scores and values), backward 10
    (the standard FlashAttention count: the scores are recomputed, then
    dV, dP, dQ, dK), halved for the causal mask, times the layers. Bytes:
    q, k, v, o read or written once forward and, with their gradients,
    twice more backward (16 passes over a [rows, T, C] tensor in bf16).
    At T = 1024 and a head size of 64 the two bounds are close (7.3 ms of
    operations against 7.9 ms of bytes for GPT-2 medium at 8 rows)."""
    cfg, t = ctx.config, ctx.workload["traffic"]
    rows = t["rows"] / len(ctx.devices)
    bht2d = rows * t["seq"] ** 2 * cfg["n_embd"]
    ops = 0.5 * 14 * bht2d * cfg["n_layer"]
    moved = 16 * rows * t["seq"] * cfg["n_embd"] * 2 * cfg["n_layer"]
    return ops, moved


def flash_decode_step(ctx, result):
    """The decode-attention kernel of one decode step: each live row
    reads the keys and values of the positions it holds, once a layer;
    operations are 4 per cached element (score and value product).
    ``kv_tokens_per_step`` is the mean over the window's steps of the
    positions held by live rows. Bound by bytes."""
    cfg = ctx.config
    tokens = result.facts.get("kv_tokens_per_step")
    if not tokens:
        return None
    elems = tokens * cfg["n_embd"] * 2 * cfg["n_layer"]
    return 2 * elems, elems * result.facts["kv_bytes_per_element"]

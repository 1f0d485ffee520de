"""Operations and bytes the OLMoE configurations need, from shapes
alone, counted as ``flops.py`` counts them. ``cfg`` is a configuration
file's dict: the published ``config.json`` keys, and ``n_layer`` for the
layers that are run."""


def active_matmul_params(cfg):
    """Weights a token is multiplied by: per layer the four attention
    projections, its ``num_experts_per_tok`` experts' three matrices and
    the router; the untied head. The embedding is a lookup and does not
    count; the norms are not matmuls."""
    c, i = cfg["hidden_size"], cfg["intermediate_size"]
    layer = 4 * c * c + cfg["num_experts_per_tok"] * 3 * c * i + \
        c * cfg["num_experts"]
    return cfg["n_layer"] * layer + cfg["vocab_size"] * c


def train_flops_per_token(cfg, seq_len):
    """Matmul FLOPs per token, forward + backward: 6 x the weights a
    token meets, and the attention score/value matmuls counted dense
    (12 * L * C * T), as ``flops.train_flops_per_token`` counts them.
    Recomputed operations do not count. 1.122 GFLOP at one layer and
    4096 tokens: the head 0.618, the experts 0.302, attention with its
    projections 0.201, the router 0.0008."""
    return 6 * active_matmul_params(cfg) + \
        12 * cfg["n_layer"] * cfg["hidden_size"] * seq_len


def param_count(cfg):
    """All parameters as run (every expert, embedding and head)."""
    c, i, e = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_experts"]
    layer = 4 * c * c + 3 * e * c * i + c * e + 4 * c
    return cfg["n_layer"] * layer + 2 * cfg["vocab_size"] * c + c


# --- what one kernel call needs: (operations, bytes), from shapes ---------

def expert_matmuls_train_step(ctx, result):
    """The grouped matmuls of one training step on one chip. Every
    token-expert pair is a row (tokens x experts per token, none
    dropped, whatever the router does). Forward: gate, up and down, each
    2 * rows * C * I operations; backward twice that (the rows' and the
    banks' gradients): 18 * rows * C * I a layer, 2.47 TFLOP at 65,536
    rows. Bytes: each of the nine calls reads or writes one [rows, C]
    and one [rows, I] array and one [experts, C, I] bank, in bf16."""
    cfg, t = ctx.config, ctx.workload["traffic"]
    c, i = cfg["hidden_size"], cfg["intermediate_size"]
    rows = t["rows"] * t["seq"] * cfg["num_experts_per_tok"] / \
        len(ctx.devices)
    ops = 18 * rows * c * i * cfg["n_layer"]
    moved = 9 * (rows * (c + i) + cfg["num_experts"] * c * i) * 2 * \
        cfg["n_layer"]
    return ops, moved

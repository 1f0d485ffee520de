"""Parameters, operations and bytes of a ``qwen3_next`` configuration
held as a share, from shapes and from the profiled segment's own
counters, counted as ``flops.py`` counts them. ``cfg`` is a
configuration file's dict (the published ``config.json`` keys,
``n_layer``, ``vocab_size`` as held and ``assumed.experts_held``).

Each work function gives ``(operations, bytes)`` of what the ALGORITHM
needs for one ``per`` of its metric, whatever implements it (plain XLA
or a kernel), so a share of the roofline cannot pass 100 %: work the
program does beyond it (dead rows' state read and written back, the
masked halves of a chunk's products, rows of the grouped matmuls' tiles
that hold no pair, the rest of a block past a row's last token) is not
counted. The counts come from the profiled segment's own decode steps
and prefills (`drivers/serve_qwen3_next.py:ring_facts`), not from the
window's means (`PERF.md` section 7 (e), (k)).
"""

DELTA, ATTENTION = "linear_attention", "full_attention"


def layer_types(cfg, n_layer=None):
    n = cfg["n_layer"] if n_layer is None else n_layer
    return [ATTENTION if (i + 1) % cfg["full_attention_interval"] == 0
            else DELTA for i in range(n)]


def _count(cfg, kind):
    return layer_types(cfg).count(kind)


def delta_params(cfg):
    """One Gated DeltaNet mixer: the two in-projections, the
    convolution's taps, ``dt_bias``, ``A_log``, the gated norm's weight
    and the out-projection (33.72 M as published)."""
    c = cfg["hidden_size"]
    d_k = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    hv = cfg["linear_num_value_heads"]
    d_v = hv * cfg["linear_value_head_dim"]
    return c * (2 * d_k + 2 * d_v) + c * 2 * hv + \
        cfg["linear_conv_kernel_dim"] * (2 * d_k + d_v) + 2 * hv + \
        cfg["linear_value_head_dim"] + d_v * c


def attention_params(cfg):
    """One gated attention: q (query and gate), k, v, o and the two
    head norms (27.26 M)."""
    c, d = cfg["hidden_size"], cfg["head_dim"]
    return 3 * c * cfg["num_attention_heads"] * d + \
        2 * c * cfg["num_key_value_heads"] * d + 2 * d


def expert_params(cfg):
    """One routed expert: three matrices (3.146 M)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_layer_params(cfg):
    """An expert layer without its routed experts: the router, the
    shared expert and its gate (4.20 M)."""
    c = cfg["hidden_size"]
    return c * cfg["num_experts"] + \
        3 * c * cfg["shared_expert_intermediate_size"] + c


def param_count(cfg, held=None, n_layer=None, vocab_size=None):
    """All parameters as this chip holds them (3,667.3 M for the cell's
    share). ``held`` / ``n_layer`` / ``vocab_size``: another count of
    held experts, blocks and rows (the published 512, 48 and 151,936
    give the model's 79.67 G)."""
    held = cfg["assumed"]["experts_held"][1] if held is None else held
    vocab = cfg["vocab_size"] if vocab_size is None else vocab_size
    c = cfg["hidden_size"]
    mixer = {DELTA: delta_params(cfg), ATTENTION: attention_params(cfg)}
    block = expert_layer_params(cfg) + held * expert_params(cfg) + 2 * c
    return sum(mixer[k] + block for k in layer_types(cfg, n_layer)) + \
        2 * vocab * c + c


def state_elements(cfg):
    """Elements of one row's state in one Gated DeltaNet layer."""
    return cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] * \
        cfg["linear_value_head_dim"]


def state_bytes_per_row(cfg):
    """Float32 state and the convolution window (two bytes a number)
    one row owns over all Gated DeltaNet layers (12.88 MB for the
    cell's six)."""
    d_k = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    d_v = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    window = (cfg["linear_conv_kernel_dim"] - 1) * (2 * d_k + d_v) * 2
    return (4 * state_elements(cfg) + window) * _count(cfg, DELTA)


def kv_bytes_per_token(cfg, itemsize=2):
    """What the pool keeps of a token over all attention layers (4,096
    B for the cell's two)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize * \
        _count(cfg, ATTENTION)


# --- what one call needs: (operations, bytes) ------------------------------

def gdn_decode_step(ctx, result):
    """The delta rule's update of one decode step, all Gated DeltaNet
    layers: each LIVE row's state is read and written once a layer
    (float32), and meets 7 operations an element (the decay, the read
    ``S^T k``'s multiply and add, the outer product's multiply and add,
    the output ``S^T q``'s multiply and add). ``gdn_rows_live_profiled``
    is the mean of the program's own counter over the profiled
    segment's decode steps. Bound by bytes."""
    rows = result.facts.get("gdn_rows_live_profiled")
    if not rows:
        return None
    elems = rows * state_elements(ctx.config) * _count(ctx.config, DELTA)
    return 7 * elems, 2 * 4 * elems


def gdn_prefill_call(ctx, result):
    """The chunked delta rule of one prompt's prefill, all Gated
    DeltaNet layers: ``prefill_chunks_profiled`` calls (the mean of the
    profiled segment's prefills) of ``prefill_chunk`` tokens in chunks
    of Q = ``assumed.delta_chunk_size``. A value head's chunk, keys K
    and values V wide: the two masked score products ``k k^T`` and ``q
    k^T`` (Q^2 K each, the causal half of 2 Q^2 K), the forward
    substitution for K + V right-hand columns (Q^2 (K + V)), the three
    products with the state ``W S``, ``q S`` and ``k^T D`` (2 Q K V
    each) and the masked scores times the deltas (Q^2 V): a token's Q
    (3 K + 2 V) + 6 K V. Bytes: q and k of the key heads and v in
    (bfloat16), g and beta (float32), o out (float32), and a call's
    state read and written."""
    calls = result.facts.get("prefill_chunks_profiled")
    if not calls:
        return None
    cfg = ctx.config
    t = result.facts["prefill_chunk"]
    q = min(cfg["assumed"]["delta_chunk_size"], t)
    hv, k, v = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"], \
        cfg["linear_value_head_dim"]
    d_k = cfg["linear_num_key_heads"] * k
    ops = t * hv * (q * (3 * k + 2 * v) + 6 * k * v)
    moved = t * (2 * 2 * d_k + 2 * hv * v + 4 * hv * v + 2 * 4 * hv) + \
        2 * 4 * state_elements(cfg)
    layers = _count(cfg, DELTA)
    return calls * layers * ops, calls * layers * moved


def gqa_decode_step(ctx, result):
    """The decode-attention kernel of one decode step, all attention
    layers: each live row reads the keys and values of the positions it
    holds once a layer (``num_key_value_heads`` heads of ``head_dim``)
    and writes back the one block pair that holds its new position
    (`PERF.md` section 7 (k): ``flops_ssm.gqa_decode_step`` counts no
    written bytes); every cached element meets its group's queries (2
    operations each). ``kv_tokens_per_step_profiled`` is the mean of
    the positions the live rows held over the profiled segment's own
    steps, ``kv_rows_written_profiled`` the rows whose block went back.
    Bound by bytes."""
    cfg, facts = ctx.config, result.facts
    tokens = facts.get("kv_tokens_per_step_profiled")
    rows = facts.get("kv_rows_written_profiled")
    if not tokens or rows is None:
        return None
    per_position = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        _count(cfg, ATTENTION)
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    moved = (tokens + rows * facts["attention_block_k"]) * per_position * \
        facts["kv_bytes_per_element"]
    return 2 * group * tokens * per_position, moved


def expert_matmuls_decode_step(ctx, result):
    """The grouped matmuls (gate, up, down) of one decode step, all
    expert layers: each expert a step TOUCHES (a pair of a live row fell
    on it) has its three matrices read once, and each held pair is a
    hidden-wide row in, two ``moe_intermediate_size`` rows out, one in
    and a hidden-wide row out. ``moe_experts_touched_profiled`` and
    ``moe_pairs_held_profiled`` are the program's counters on
    ``serve/step/decode``, summed over the layers, their means over the
    profiled segment's own steps. Bound by bytes."""
    cfg, facts = ctx.config, result.facts
    touched = facts.get("moe_experts_touched_profiled")
    pairs = facts.get("moe_pairs_held_profiled")
    if not touched or pairs is None:
        return None
    c, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    b = facts["kv_bytes_per_element"]
    ops = 2 * pairs * expert_params(cfg)
    moved = (touched * expert_params(cfg) + pairs * (2 * c + 3 * i)) * b
    return ops, moved

"""Parameters, operations and bytes of a ``bailing_hybrid`` (Ling-3.0)
configuration held as a share, from shapes and from the profiled
segment's own counters, counted as ``flops.py`` counts them. ``cfg`` is a
configuration file's dict (the published ``config.json`` keys,
``n_layer``, ``vocab_size`` as held and ``assumed.experts_held``).

Each work function gives ``(operations, bytes)`` of what the ALGORITHM
needs for one ``per`` of its metric, whatever implements it, so a share
of the roofline cannot pass 100 %: work the program does beyond it (the
masked halves of a chunk's products, the exponentials of the decays, a
block past a row's last token) is not counted. The counts come from the
profiled segment's own decode steps and prefills
(`drivers/serve_ling.py:ring_facts`). The expert layers' grouped matmuls
are counted by ``flops_qwen3_next.expert_matmuls_decode_step``, which
reads the two keys this configuration shares with that one.
"""

KDA, MLA = "kda", "mla"


def layer_types(cfg, n_layer=None):
    n = cfg["n_layer"] if n_layer is None else n_layer
    return [MLA if (i + 1) % cfg["layer_group_size"] == 0 else KDA
            for i in range(n)]


def _count(cfg, kind):
    return layer_types(cfg).count(kind)


def key_dim(cfg):
    return cfg["num_attention_heads"] * cfg["head_dim"]


def kda_params(cfg):
    """One KDA mixer: q, k, v, the two full-rank gate projections and
    the output projection (six of hidden x 4096), beta's projection, the
    three convolutions' taps, ``dt_bias``, ``A_log`` and the output
    norm's weight (63.05 M as published)."""
    c, d, h = cfg["hidden_size"], key_dim(cfg), cfg["num_attention_heads"]
    return 6 * c * d + c * h + 3 * cfg["short_conv_kernel_size"] * d + \
        d + h + cfg["head_dim"]


def mla_params(cfg):
    """The latent attention without a query latent: q, the down
    projection and its norm, the up projection, the gate a head and the
    output projection (31.97 M)."""
    c, h, r = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return c * h * (dn + dr) + c * (r + dr) + r + r * h * (dn + dv) + \
        c * h + h * dv * c


def dense_mlp_params(cfg):
    """A leading dense layer's SwiGLU (47.19 M)."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """One routed expert: three matrices (5.898 M)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_layer_params(cfg):
    """An expert layer without its routed experts: the router with its
    bias and the shared expert (7.21 M)."""
    c = cfg["hidden_size"]
    return (c + 1) * cfg["num_experts"] + cfg["num_shared_experts"] * \
        3 * c * cfg["moe_shared_expert_intermediate_size"]


def param_count(cfg, held=None, n_layer=None, vocab_size=None,
                active=False):
    """All parameters as this chip holds them (5,342 M for the cell's
    share). ``held`` / ``n_layer`` / ``vocab_size``: another count of
    held experts, layers and rows (the published 512, 42 and 157,184
    give the model's 124.4 G); ``active``: a token's own experts only
    (``num_experts_per_tok`` of them: 5.5 G as published)."""
    held = cfg["assumed"]["experts_held"][1] if held is None else held
    if active:
        held = cfg["num_experts_per_tok"]
    vocab = cfg["vocab_size"] if vocab_size is None else vocab_size
    c = cfg["hidden_size"]
    mixer = {KDA: kda_params(cfg), MLA: mla_params(cfg)}
    total = 2 * vocab * c + c
    for i, kind in enumerate(layer_types(cfg, n_layer)):
        ffn = dense_mlp_params(cfg) if i < cfg["first_k_dense_replace"] \
            else expert_layer_params(cfg) + held * expert_params(cfg)
        total += mixer[kind] + ffn + 2 * c
    return total


def state_elements(cfg):
    """Elements of one row's state in one KDA layer."""
    return cfg["num_attention_heads"] * cfg["head_dim"] ** 2


def state_bytes_per_row(cfg):
    """Float32 state and the convolutions' window (two bytes a number)
    one row owns over all KDA layers (15.20 MB for the cell's seven)."""
    window = (cfg["short_conv_kernel_size"] - 1) * 3 * key_dim(cfg) * 2
    return (4 * state_elements(cfg) + window) * _count(cfg, KDA)


def latent_bytes_per_token(cfg, itemsize=2):
    """What the pool keeps of a token over all latent layers (1,152 B
    for the cell's one)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize * \
        _count(cfg, MLA)


# --- what one call needs: (operations, bytes) ------------------------------

def kda_decode_step(ctx, result):
    """The delta rule's update of one decode step, all KDA layers, as
    ``flops_qwen3_next.gdn_decode_step`` counts it: each LIVE row's
    state read and written once a layer (float32), 7 operations an
    element; plus the row's decay vector ``g`` ``[heads, head_dim]``
    float32 read once a layer. ``kda_rows_live_profiled`` is the mean of
    the program's own counter over the profiled segment's decode steps.
    Bound by bytes."""
    rows = result.facts.get("kda_rows_live_profiled")
    if not rows:
        return None
    cfg = ctx.config
    layers = _count(cfg, KDA)
    elems = rows * state_elements(cfg) * layers
    return 7 * elems, 2 * 4 * elems + rows * layers * 4 * key_dim(cfg)


def kda_prefill_call(ctx, result):
    """The chunked delta rule of one prompt's prefill, all KDA layers:
    ``prefill_chunks_profiled`` calls of ``prefill_chunk`` tokens in
    chunks of Q = ``assumed.kda_chunk_size``, as
    ``flops_qwen3_next.gdn_prefill_call`` counts a head's chunk (the
    two masked pair products, the substitution, the three products with
    the state, the masked scores times the deltas: a token's Q (3 K + 2
    V) + 6 K V) with one more multiply a pair term a channel for its
    decay (the causal half of 2 Q^2 K a chunk: Q K a token more). Bytes:
    q, k and v in (bfloat16), ``g`` ``[T, H, K]`` and beta in (float32),
    o out (float32), and a call's state read and written."""
    calls = result.facts.get("prefill_chunks_profiled")
    if not calls:
        return None
    cfg = ctx.config
    t = result.facts["prefill_chunk"]
    q = min(cfg["assumed"]["kda_chunk_size"], t)
    h, k = cfg["num_attention_heads"], cfg["head_dim"]
    v, d = k, key_dim(cfg)
    ops = t * h * (q * (4 * k + 2 * v) + 6 * k * v)
    moved = t * (3 * 2 * d + 4 * d + 4 * h + 4 * d) + \
        2 * 4 * state_elements(cfg)
    layers = _count(cfg, KDA)
    return calls * layers * ops, calls * layers * moved


def mla_decode_step(ctx, result):
    """``flops_mla.mla_decode_step`` for a model whose latent layers are
    ``layer_group_size``-th layers only (one of the cell's eight): each
    live row reads the latents of the positions it holds once a latent
    layer and writes back the block that holds its new position; every
    position meets ``num_attention_heads`` queries over the latent for
    the scores and over ``kv_lora_rank`` for the values."""
    cfg, facts = ctx.config, result.facts
    tokens = facts.get("kv_tokens_per_step_profiled")
    rows = facts.get("kv_rows_written_profiled")
    if not tokens or rows is None:
        return None
    d = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    layers = _count(cfg, MLA)
    ops = 2 * tokens * cfg["num_attention_heads"] * \
        (d + cfg["kv_lora_rank"]) * layers
    moved = (tokens + rows * facts["attention_block_k"]) * d * \
        facts["kv_bytes_per_element"] * layers
    return ops, moved


def mla_prefill_attention_prompt(ctx, result):
    """``flops_mla_prefill.mla_prefill_attention_prompt`` over the latent
    layers alone: a prompt of ``n`` calls of ``t`` tokens visits ``n (n +
    1) / 2`` blocks, each expanded to every head's keys and values, and
    its queries meet ``(n (n - 1) / 2 + n / 2) t^2`` pairs. ``n`` is the
    MEAN calls a prompt of the profiled segment's prefills: the count is
    convex in ``n``, so the share can only under-read (it does here: one
    prompt in ten has 8 to 32 calls)."""
    cfg, facts = ctx.config, result.facts
    n, t = facts.get("prefill_chunks_profiled"), facts.get("prefill_chunk")
    if not n or not t:
        return None
    h, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    b = facts["kv_bytes_per_element"]
    visits = n * (n + 1) / 2
    pairs = (n * (n - 1) / 2 + n / 2) * t * t
    ops = visits * 2 * t * rkv * h * (dn + dv) + \
        pairs * h * 2 * (dn + dr + dv)
    moved = (visits * t * (rkv + dr) +
             n * t * (h * (dn + dr) + (rkv + dr) + h * dv)) * b
    layers = _count(cfg, MLA)
    return layers * ops, layers * moved

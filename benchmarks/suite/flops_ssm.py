"""Operations and bytes the state-space (Mamba-2) layers and the
grouped-query decode attention of a hybrid configuration need, from
shapes alone, counted as ``flops.py`` counts them. ``cfg`` is a
configuration file's dict (the published ``config.json`` keys:
``layer_types``, ``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``mamba_chunk_size``, ``num_key_value_heads``, ...).

Each function gives ``(operations, bytes)`` of what the ALGORITHM needs
for one ``per`` of its metric, so a share of the roofline cannot pass
100 %: work the program does beyond it (dead rows read and written
back, padding, a masked half computed) is not counted.
"""


def _count(cfg, kind):
    return sum(t == kind for t in cfg["layer_types"])


def state_elements(cfg):
    """Elements of one row's state in one mixer."""
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def state_bytes_per_row(cfg):
    """Float32 state one row owns over all mixers: 75.5 MB at the
    published sizes (36 x 64 x 64 x 128 x 4)."""
    return 4 * state_elements(cfg) * _count(cfg, "mamba")


def param_count(cfg):
    """All parameters as run (tied head counted once)."""
    c, i = cfg["hidden_size"], cfg["shared_intermediate_size"]
    d_in = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv = d_in + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    head = c // cfg["num_attention_heads"]
    mlp = 3 * c * i + 2 * c
    mamba = c * (d_in + conv + cfg["mamba_n_heads"]) + \
        (cfg["mamba_d_conv"] + 1) * conv + 3 * cfg["mamba_n_heads"] + \
        d_in + d_in * c
    att = 2 * c * c + 2 * c * cfg["num_key_value_heads"] * head
    return _count(cfg, "mamba") * (mamba + mlp) + \
        _count(cfg, "attention") * (att + mlp) + cfg["vocab_size"] * c + c


# --- what one call needs: (operations, bytes), from shapes ----------------

def ssm_decode_step(ctx, result):
    """The state update of one decode step, all mixers: each LIVE row's
    state is read and written once a layer (float32), and meets 5
    operations an element (decay, the outer product's multiply and add,
    the contraction with C). ``ssm_rows_live_profiled`` is the mean
    live rows of the profiled segment's own decode steps (the program's
    counter on ``serve/step/decode``), not the window's mean. Bound by
    bytes."""
    rows = result.facts.get("ssm_rows_live_profiled")
    if not rows:
        return None
    cfg = ctx.config
    elems = rows * state_elements(cfg) * _count(cfg, "mamba")
    return 5 * elems, 2 * 4 * elems


def ssd_prefill_call(ctx, result):
    """The chunked scans of one prompt's prefill, all mixers:
    ``prefill_chunks_profiled`` calls (the mean of the profiled
    segment's prefills) of ``prefill_chunk`` tokens. A call of T tokens
    in scan chunks of Q, per mixer: the scores C.B^T and the masked
    product with x inside a chunk (2 T Q (N + H P), halved for the
    causal mask), each chunk's state and the carried state's
    contribution (2 T H P N each). Bytes: x and y once (bf16 in,
    float32 out), B, C, dt, and the state read and written."""
    calls = result.facts.get("prefill_chunks_profiled")
    if not calls:
        return None
    cfg = ctx.config
    t = result.facts["prefill_chunk"]
    q = min(cfg["mamba_chunk_size"], t)
    h, n = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    hp = h * cfg["mamba_d_head"]
    ops = t * q * (n + hp) + 4 * t * hp * n
    moved = t * (2 * hp + 4 * hp + 2 * 2 * n + 4 * h) + \
        2 * 4 * state_elements(cfg)
    layers = _count(cfg, "mamba")
    return calls * layers * ops, calls * layers * moved


def gqa_decode_step(ctx, result):
    """The decode-attention kernel of one decode step of a model with
    grouped queries: each live row reads the keys and values of the
    positions it holds once an attention layer (``num_key_value_heads``
    heads), and every cached element meets its group's queries (2
    operations each). ``kv_tokens_per_step_profiled`` is the mean of the
    positions the live rows held over the profiled segment's own steps,
    the steps whose kernel time the share divides by (the window's mean,
    which ``flops.flash_decode_step`` reads, is another load: PERF.md
    section 7 (e)). Bound by bytes."""
    cfg = ctx.config
    tokens = result.facts.get("kv_tokens_per_step_profiled")
    if not tokens:
        return None
    head = cfg["hidden_size"] // cfg["num_attention_heads"]
    elems = tokens * cfg["num_key_value_heads"] * head * 2 * \
        _count(cfg, "attention")
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return 2 * group * elems, elems * result.facts["kv_bytes_per_element"]

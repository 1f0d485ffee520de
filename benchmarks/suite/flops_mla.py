"""Operations and bytes the latent-attention decode kernel and the held
experts' grouped matmuls of a ``kimi_k2`` / DeepSeek-V3 configuration
need, from shapes and from the profiled segment's own counters, counted
as ``flops.py`` counts them. ``cfg`` is a configuration file's dict
(the published ``config.json`` keys, ``n_layer``, ``vocab_size`` as
held and ``assumed.experts_held``).

Each function gives ``(operations, bytes)`` of what the ALGORITHM needs
for one ``per`` of its metric, so a share of the roofline cannot pass
100 %: work the program does beyond it (the rest of a 128-position
block past a row's last token, rows of the grouped matmuls' tiles that
hold no pair) is not counted. The counts come from the profiled
segment's own decode steps (`drivers/serve_mla.py:ring_facts` and
``kv_tokens_per_step_profiled``), not from the window's means
(`PERF.md` section 7 (e), (k)).
"""


def latent_dim(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def expert_layers(cfg):
    return sum(i >= cfg["first_k_dense_replace"] and
               i % cfg["moe_layer_freq"] == 0 for i in range(cfg["n_layer"]))


def attention_params(cfg):
    """One layer's attention: the two down- and two up-projections and
    the output projection (101.1 M as published)."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return c * rq + rq * h * (dn + dr) + c * (rkv + dr) + \
        rkv * h * (dn + dv) + h * dv * c


def expert_params(cfg):
    """One routed expert (and one shared expert): 44.04 M."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def param_count(cfg):
    """All matrices as this chip holds them (norm weights and the
    router's bias, 0.1 M, left out): 4,849.5 M for the cell's share."""
    c = cfg["hidden_size"]
    moe = expert_layers(cfg)
    held = cfg["assumed"]["experts_held"][1]
    dense = (cfg["n_layer"] - moe) * 3 * c * cfg["intermediate_size"]
    experts = moe * ((held + cfg["n_shared_experts"]) * expert_params(cfg) +
                     c * cfg["n_routed_experts"])
    return cfg["n_layer"] * attention_params(cfg) + dense + experts + \
        2 * cfg["vocab_size"] * c


# --- what one call needs: (operations, bytes) ------------------------------

def mla_decode_step(ctx, result):
    """The decode-attention kernel of one decode step, all layers: each
    live row reads the latents of the positions it holds once a layer
    (``latent_dim`` numbers a position: they are keys and values both),
    and writes back the one block that holds its new position; every
    position meets ``num_attention_heads`` queries over ``latent_dim``
    entries for the scores and over ``kv_lora_rank`` for the values (2
    operations each). ``kv_tokens_per_step_profiled`` is the mean of the
    positions the live rows held over the profiled segment's own steps,
    ``kv_rows_written_profiled`` the rows whose block went back."""
    cfg, facts = ctx.config, result.facts
    tokens = facts.get("kv_tokens_per_step_profiled")
    rows = facts.get("kv_rows_written_profiled")
    if not tokens or rows is None:
        return None
    d, b = latent_dim(cfg), facts["kv_bytes_per_element"]
    ops = 2 * tokens * cfg["num_attention_heads"] * \
        (d + cfg["kv_lora_rank"]) * cfg["n_layer"]
    moved = (tokens + rows * facts["attention_block_k"]) * d * b * \
        cfg["n_layer"]
    return ops, moved


def expert_matmuls_decode_step(ctx, result):
    """The grouped matmuls (gate, up, down) of one decode step, all
    expert layers: each expert a step TOUCHES (a pair of a live row fell
    on it) has its three matrices read once, and each held pair is a
    row through them. ``moe_experts_touched_profiled`` and
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
    moved = (touched * expert_params(cfg) + pairs * 3 * (c + i)) * b
    return ops, moved

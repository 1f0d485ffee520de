"""Parameters, bytes a token by group, and the operations and bytes of
each attention program of a ``laguna`` configuration held as a share,
from shapes and from the profiled segment's own counters, counted as
``flops.py`` counts them. ``cfg`` is a configuration file's dict (the
published ``config.json`` keys, ``n_layer``, ``vocab_size`` as held and
``assumed.experts_held``).

Each work function gives ``(operations, bytes)`` of what the
MATHEMATICS needs for one ``per`` of its metric, whatever implements it
(plain XLA or a kernel), so a share of the roofline cannot pass 100 %:
the positions the live rows hold, each read once; the one position a
row adds, written once; the pairs the mask admits. Work a program does
beyond that (the rest of a block past a row's last token or before its
window's first, the whole block a kernel writes back for one new
position, the masked half of a chunk's diagonal block, a prefix read
again by every call of a prompt, rows that hold no request) is not
counted. The counts come from the profiled segment's own decode steps
and prefills (`drivers/serve_mimo_v2.py:ring_facts`, which
`drivers/serve_laguna.py` runs), not from the window's means (`PERF.md`
section 7 (e), (k)). The experts' grouped matmuls are
`flops_qwen3_next.expert_matmuls_decode_step`'s (three banks under
SiLU at ``hidden_size`` x ``moe_intermediate_size``: it fits).
"""

FULL, WINDOW = "full", "window"
PUBLISHED = {FULL: "full_attention", WINDOW: "sliding_attention"}


def layer_kinds(cfg, n_layer=None):
    n = cfg["n_layer"] if n_layer is None else n_layer
    return [FULL if t == PUBLISHED[FULL] else WINDOW
            for t in cfg["layer_types"][:n]]


def query_heads(cfg, which):
    """A kind's query heads (48 full, 72 window)."""
    return next(h for h, t in zip(cfg["num_attention_heads_per_layer"],
                                  cfg["layer_types"])
                if t == PUBLISHED[which])


def _count(cfg, which):
    return layer_kinds(cfg).count(which)


def attention_params(cfg, which):
    """One attention layer: q, k, v, o and a gate a head (full 44.19 M,
    window 63.14 M)."""
    c, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = query_heads(cfg, which), cfg["num_key_value_heads"]
    return 2 * c * hq * d + 2 * c * hkv * d + c * hq


def expert_params(cfg):
    """One routed expert: three matrices (9.437 M)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    """The shared expert: three matrices (9.437 M)."""
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def router_params(cfg):
    """The router (0.786 M)."""
    return cfg["hidden_size"] * cfg["num_experts"]


def param_count(cfg, held=None, n_layer=None, vocab_size=None, active=False):
    """All parameters as this chip holds them (2,843.1 M for the cell's
    share; ISSUE 51's 2,843 M left the norms' 0.05 M out). ``held`` /
    ``n_layer`` / ``vocab_size``: another count of held experts, layers
    and rows (the published 256, 48 and 100,352 give the model's 117.6
    G). ``active``: what one token meets (``num_experts_per_tok``
    experts and the shared one a layer, one row of the embedding, the
    head: 8.1 G of the published model)."""
    held = cfg["assumed"]["experts_held"][1] if held is None else held
    vocab = cfg["vocab_size"] if vocab_size is None else vocab_size
    n = cfg["n_layer"] if n_layer is None else n_layer
    c = cfg["hidden_size"]
    if active:
        held = cfg["num_experts_per_tok"]
    total = 0
    for i, which in enumerate(layer_kinds(cfg, n)):
        total += attention_params(cfg, which) + 2 * c
        if cfg["mlp_layer_types"][i] == "dense":
            total += 3 * c * cfg["intermediate_size"]
        else:
            total += router_params(cfg) + held * expert_params(cfg) + \
                shared_params(cfg)
    return total + (1 if active else vocab) * c + vocab * c + c


def kv_bytes_per_token(cfg, which, itemsize=2):
    """What a group's pool keeps of a token over its layers (4,096 B a
    layer: full 8,192 B, window 24,576 B for the cell's 2 and 6)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize * \
        _count(cfg, which)


def ring_bytes_per_row(cfg, page_size, itemsize=2):
    """A row's ring over the window layers, whatever its length (15.73
    MB for the cell's five pages of 128)."""
    return (cfg["sliding_window"] // page_size + 1) * page_size * \
        kv_bytes_per_token(cfg, WINDOW, itemsize)


# --- what one call needs: (operations, bytes) ------------------------------

def _decode_step(ctx, result, which, tokens):
    """A group's decode attention over ``tokens`` cached positions a
    layer (summed over the live rows): each is read once a layer (keys
    and values), each live row writes the one position it adds and
    reads its queries and writes its output, and every cached element
    meets its group's queries (2 operations each). Bound by bytes."""
    cfg, facts = ctx.config, result.facts
    rows = facts.get("kv_rows_written_profiled")
    if not tokens or rows is None:
        return None
    hq, hkv, d = query_heads(cfg, which), cfg["num_key_value_heads"], \
        cfg["head_dim"]
    layers = _count(cfg, which)
    per_position = 2 * hkv * d
    moved = ((tokens + rows) * per_position + rows * 2 * hq * d) * layers * \
        facts["kv_bytes_per_element"]
    return 2 * (hq // hkv) * tokens * per_position * layers, moved


def full_decode_step(ctx, result):
    """The full layers' decode attention of one step (6 queries a key
    head): ``kv_tokens_per_step_profiled`` is the mean over the profiled
    segment's own steps of the positions the live rows held."""
    return _decode_step(ctx, result, FULL,
                        result.facts.get("kv_tokens_per_step_profiled"))


def window_decode_step(ctx, result):
    """The window layers' decode attention of one step (9 queries a key
    head): a live row past its first window holds ``sliding_window``
    positions whatever its length (the cell's prompts are no shorter),
    so the step reads ``kv_rows_written_profiled x sliding_window``
    positions a layer."""
    rows = result.facts.get("kv_rows_written_profiled")
    if not rows:
        return None
    return _decode_step(ctx, result, WINDOW,
                        rows * result.facts["sliding_window"])


def full_prefill_call(ctx, result):
    """The full layers' attention of one prompt's prefill: query ``t``
    meets keys ``0..t`` (``n (n + 1) / 2`` pairs for a prompt of ``n``
    tokens: ``prefill_pairs_profiled`` is the mean over the profiled
    segment's prompts), ``4 head_dim`` operations a pair and query head;
    the prompt's queries are read and its output written once, and its
    keys and values written and read once (``prefill_tokens_profiled``).
    Bound by operations."""
    cfg, facts = ctx.config, result.facts
    pairs = facts.get("prefill_pairs_profiled")
    tokens = facts.get("prefill_tokens_profiled")
    if not pairs or not tokens:
        return None
    hq, hkv, d = query_heads(cfg, FULL), cfg["num_key_value_heads"], \
        cfg["head_dim"]
    layers = _count(cfg, FULL)
    ops = 2 * pairs * hq * 2 * d * layers
    moved = tokens * (2 * hq * d + 2 * 2 * hkv * d) * layers * \
        facts["kv_bytes_per_element"]
    return ops, moved


def gate_decode_step(ctx, result):
    """The gates of one decode step, all layers: a live row's normed
    input ``[hidden]`` meets ``W_g`` ``[hidden, heads]`` (read once a
    layer), and its ``heads x head_dim`` attention output is read and
    written once. Bound by bytes (`attn_gate_ms.serve`'s yardstick)."""
    cfg, facts = ctx.config, result.facts
    rows = facts.get("kv_rows_written_profiled")
    if not rows:
        return None
    c, d = cfg["hidden_size"], cfg["head_dim"]
    ops = moved = 0
    for which in (FULL, WINDOW):
        hq, layers = query_heads(cfg, which), _count(cfg, which)
        ops += layers * rows * (2 * c * hq + 2 * hq * d)
        moved += layers * (c * hq + rows * (c + 2 * hq * d))
    return ops, moved * facts["kv_bytes_per_element"]

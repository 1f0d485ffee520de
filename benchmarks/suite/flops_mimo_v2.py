"""Parameters, bytes a token by group, and the operations and bytes of
each attention program of a ``mimo_v2`` configuration held as a share,
from shapes and from the profiled segment's own counters, counted as
``flops.py`` counts them. ``cfg`` is a configuration file's dict (the
published ``config.json`` keys, ``n_layer``, ``vocab_size`` as held and
``assumed.experts_held``).

Each work function gives ``(operations, bytes)`` of what the ALGORITHM
needs for one ``per`` of its metric, whatever implements it (plain XLA
or a kernel), so a share of the roofline cannot pass 100 %: work the
program does beyond it (the masked half of a chunk's diagonal block,
the rest of a block past a row's last token or before its window's
first, rows that hold no request) is not counted. The counts come from
the profiled segment's own decode steps and prefills
(`drivers/serve_mimo_v2.py:ring_facts`), not from the window's means
(`PERF.md` section 7 (e), (k)).
"""

FULL, WINDOW = "full", "window"


def layer_kinds(cfg, n_layer=None):
    n = cfg["n_layer"] if n_layer is None else n_layer
    return [WINDOW if p else FULL for p in cfg["hybrid_layer_pattern"][:n]]


def kind(cfg, which):
    """``(heads, key heads, head_dim, v_head_dim)`` of a kind."""
    pre = "swa_" if which == WINDOW else ""
    return tuple(cfg[pre + k] for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "v_head_dim"))


def _count(cfg, which):
    return layer_kinds(cfg).count(which)


def attention_params(cfg, which):
    """One attention layer: q, k, v, o and, in a window layer, a sink a
    head (full 89.13 M, window 94.37 M)."""
    c = cfg["hidden_size"]
    hq, hkv, d, dv = kind(cfg, which)
    sink = cfg["add_swa_attention_sink_bias" if which == WINDOW
               else "add_full_attention_sink_bias"]
    return c * hq * d + c * hkv * (d + dv) + hq * dv * c + hq * bool(sink)


def expert_params(cfg):
    """One routed expert: three matrices (25.17 M)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    """The router and its bias (1.05 M)."""
    return (cfg["hidden_size"] + 1) * cfg["n_routed_experts"]


def param_count(cfg, held=None, n_layer=None, vocab_size=None, active=False):
    """All parameters as this chip holds them (3,429.9 M for the cell's
    share). ``held`` / ``n_layer`` / ``vocab_size``: another count of
    held experts, layers and rows (the published 256, 48 and 152,576
    give the model's 308.8 G). ``active``: what one token meets
    (``num_experts_per_tok`` experts a layer, one row of the embedding:
    14.8 G of the published model)."""
    held = cfg["assumed"]["experts_held"][1] if held is None else held
    vocab = cfg["vocab_size"] if vocab_size is None else vocab_size
    n = cfg["n_layer"] if n_layer is None else n_layer
    c = cfg["hidden_size"]
    if active:
        held = cfg["num_experts_per_tok"]
    total = 0
    for i, which in enumerate(layer_kinds(cfg, n)):
        total += attention_params(cfg, which) + 2 * c
        if cfg["moe_layer_freq"][i]:
            total += router_params(cfg) + held * expert_params(cfg)
        else:
            total += 3 * c * cfg["intermediate_size"]
    return total + (1 if active else vocab) * c + vocab * c + c


def kv_bytes_per_token(cfg, which, itemsize=2):
    """What a group's pool keeps of a token over its layers (full 5,120
    B, window 25,600 B for the cell's 2 and 5)."""
    _, hkv, d, dv = kind(cfg, which)
    return hkv * (d + dv) * itemsize * _count(cfg, which)


def ring_bytes_per_row(cfg, page_size, itemsize=2):
    """A row's ring over the window layers, whatever its length (6.55 MB
    for the cell's two pages of 128)."""
    return (cfg["sliding_window"] // page_size + 1) * page_size * \
        kv_bytes_per_token(cfg, WINDOW, itemsize)


# --- what one call needs: (operations, bytes) ------------------------------

def _decode_step(ctx, result, which, tokens):
    """A group's decode kernel over ``tokens`` cached positions a layer
    (summed over the live rows): each is read once a layer (keys and
    values), each live row writes back the one block pair that holds its
    new position, and every cached element meets its group's queries (2
    operations each). Bound by bytes."""
    cfg, facts = ctx.config, result.facts
    rows = facts.get("kv_rows_written_profiled")
    if not tokens or rows is None:
        return None
    hq, hkv, d, dv = kind(cfg, which)
    layers = _count(cfg, which)
    per_position = hkv * (d + dv) * layers
    moved = (tokens + rows * facts["attention_block_k"]) * per_position * \
        facts["kv_bytes_per_element"]
    return 2 * (hq // hkv) * tokens * per_position, moved


def full_decode_step(ctx, result):
    """The full layers' decode attention of one step:
    ``kv_tokens_per_step_profiled`` is the mean over the profiled
    segment's own steps of the positions the live rows held."""
    return _decode_step(ctx, result, FULL,
                        result.facts.get("kv_tokens_per_step_profiled"))


def window_decode_step(ctx, result):
    """The window layers' decode attention of one step: a live row past
    its first window holds ``sliding_window`` positions whatever its
    length (the cell's prompts are no shorter), so the step reads
    ``kv_rows_written_profiled x sliding_window`` positions a layer."""
    rows = result.facts.get("kv_rows_written_profiled")
    if not rows:
        return None
    return _decode_step(ctx, result, WINDOW,
                        rows * result.facts["sliding_window"])


def full_prefill_call(ctx, result):
    """The full layers' attention of one prompt's prefill: query ``t``
    meets keys ``0..t`` (``n (n + 1) / 2`` pairs for a prompt of ``n``
    tokens: ``prefill_pairs_profiled`` is the mean over the profiled
    segment's prompts), ``2 (head_dim + v_head_dim)`` operations a pair
    and query head; a call reads the queries and writes the output once
    and reads the keys and values of its prefix once
    (``prefill_prefix_tokens_profiled``: the positions the prompt's
    calls walked, whole blocks not counted beyond the call's last
    position). Bound by operations."""
    cfg, facts = ctx.config, result.facts
    pairs = facts.get("prefill_pairs_profiled")
    walked = facts.get("prefill_prefix_tokens_profiled")
    tokens = facts.get("prefill_tokens_profiled")
    if not pairs or not walked or not tokens:
        return None
    hq, hkv, d, dv = kind(cfg, FULL)
    layers = _count(cfg, FULL)
    b = facts["kv_bytes_per_element"]
    ops = 2 * pairs * hq * (d + dv) * layers
    moved = (tokens * hq * (d + dv) + walked * hkv * (d + dv)) * b * layers
    return ops, moved

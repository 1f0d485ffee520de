"""Parameters, operations and bytes of an ``lfm2_moe`` (LFM2 with
experts) configuration held as a share, from shapes and from the
profiled segment's own counters, counted as ``flops.py`` counts them.
``cfg`` is a configuration file's dict (the published ``config.json``
keys, ``n_layer`` and ``assumed.experts_held``).

Each work function gives ``(operations, bytes)`` of what the ALGORITHM
needs for one ``per`` of its metric, whatever implements it, so a share
of the roofline cannot pass 100 %: work the program does beyond it (a
chunk's padded tail, a dead row's window read and written back, the
gates' float32 temporaries) is not counted. The counts come from the
profiled segment's own decode steps and prefills
(`drivers/serve_lfm2.py:ring_facts`). The expert layers' grouped matmuls
are counted by ``flops_qwen3_next.expert_matmuls_decode_step``, which
reads the two keys this configuration shares with that one.
"""

CONV, ATTENTION = "conv", "full_attention"


def layer_types(cfg):
    return list(cfg["layer_types"][:cfg["n_layer"]])


def _count(cfg, kind):
    return layer_types(cfg).count(kind)


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def sconv_params(cfg):
    """One short-convolution mixer: the input projection to ``b | c |
    x``, the output projection and the taps (12.58 + 4.19 + 0.006 =
    16.78 M as published)."""
    c = cfg["hidden_size"]
    return 3 * c * c + c * c + cfg["conv_L_cache"] * c


def attention_params(cfg):
    """One attention mixer: q and o of hidden x hidden, k and v of
    hidden x (key heads x head), the two head norms (10.49 M)."""
    c, d = cfg["hidden_size"], head_dim(cfg)
    return 2 * c * c + 2 * c * cfg["num_key_value_heads"] * d + 2 * d


def dense_mlp_params(cfg):
    """A leading dense layer's SwiGLU (44.04 M)."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """One routed expert: three matrices (11.01 M)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    """An expert layer without its experts: the router with its bias
    (0.066 M)."""
    return (cfg["hidden_size"] + 1) * cfg["num_experts"]


def param_count(cfg, held=None, active=False):
    """All parameters as this chip holds them, the tied head counted
    once (2,526.6 M for the cell's share). ``held``: another count of
    held experts (the published 32 gives the model's 8,339.9 M);
    ``active``: a token's own experts only (``num_experts_per_tok`` of
    them: 1,557.7 M as published)."""
    held = cfg["assumed"]["experts_held"][1] if held is None else held
    if active:
        held = cfg["num_experts_per_tok"]
    c = cfg["hidden_size"]
    mixer = {CONV: sconv_params(cfg), ATTENTION: attention_params(cfg)}
    total = cfg["vocab_size"] * c + c
    for i, kind in enumerate(layer_types(cfg)):
        ffn = dense_mlp_params(cfg) if i < cfg["num_dense_layers"] \
            else router_params(cfg) + held * expert_params(cfg)
        total += mixer[kind] + ffn + 2 * c
    return total


def state_bytes_per_row(cfg, itemsize=2):
    """The windows one row owns over all convolution layers: ``L - 1``
    rows of ``hidden_size`` each (147,456 B for the cell's eighteen)."""
    return (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * itemsize * \
        _count(cfg, CONV)


def kv_bytes_per_token(cfg, itemsize=2):
    """What the pools keep of a token over all attention layers: keys
    and values of every key head (12,288 B for the cell's six)."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize * \
        _count(cfg, ATTENTION)


# --- what one call needs: (operations, bytes) ------------------------------

def _sconv(cfg, tokens, windows, calls):
    """``tokens`` tokens through every convolution layer in ``calls``
    calls that move ``windows`` rows' windows: 2 operations a parameter
    a token; both projections' weights and the taps read once a call;
    a window read and written once; the normed input read and the
    mixer's output written once a token (two bytes a number)."""
    c, layers = cfg["hidden_size"], _count(cfg, CONV)
    ops = 2 * sconv_params(cfg) * tokens
    moved = 2 * (calls * sconv_params(cfg) +
                 2 * windows * (cfg["conv_L_cache"] - 1) * c +
                 2 * tokens * c)
    return layers * ops, layers * moved


def sconv_decode_step(ctx, result):
    """The short-convolution mixers of one decode step, all eighteen
    layers, for the LIVE rows: ``sconv_rows_live_profiled`` is the mean
    of the program's own counter over the profiled segment's decode
    steps. The weights' 33.6 MB a layer dwarf a row's 24 KB: bound by
    bytes."""
    rows = result.facts.get("sconv_rows_live_profiled")
    if not rows:
        return None
    return _sconv(ctx.config, rows, rows, 1)


def sconv_prefill_call(ctx, result):
    """The short-convolution mixers of one prompt's prefill, all
    eighteen layers: ``prefill_chunks_profiled`` calls of
    ``prefill_chunk`` tokens less the prompt's padded tail
    (``prefill_pad_tokens_profiled``: the mean over the profiled
    segment's prefills), each call reading the weights and moving one
    row's window. At 1,024 tokens a call the operations bound it."""
    calls = result.facts.get("prefill_chunks_profiled")
    if not calls:
        return None
    tokens = calls * result.facts["prefill_chunk"] - \
        (result.facts.get("prefill_pad_tokens_profiled") or 0)
    return _sconv(ctx.config, tokens, calls, calls)

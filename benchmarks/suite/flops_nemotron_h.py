"""Parameters, operations and bytes of a ``nemotron_h`` configuration
held as a share, from shapes and from the profiled segment's own
counters, counted as ``flops.py`` counts them. ``cfg`` is a
configuration file's dict (the published ``config.json`` keys,
``n_layer``, ``vocab_size`` as held and ``assumed.experts_held``).

Each work function gives ``(operations, bytes)`` of what the ALGORITHM
needs for one ``per`` of its metric, so a share of the roofline cannot
pass 100 %: work the program does beyond it (rows of the grouped
matmuls' tiles that hold no pair, dead rows, padding) is not counted.
The state update of a decode step and the decode kernel are
``flops_ssm.py``'s (`ssm_decode_step`, `gqa_decode_step`), which read
this configuration through the aliases its file carries
(``layer_types``, ``mamba_*``); the chunked scan's is here because
``flops_ssm.ssd_prefill_call`` counts one B/C group.
"""

MIXER, ATTENTION, EXPERTS = "M", "*", "E"


def pattern(cfg):
    return cfg["hybrid_override_pattern"][:cfg["n_layer"]]


def mixer_params(cfg):
    """One ``M`` block: in- and out-projection, the convolution's taps
    and bias, ``dt_bias``, ``A_log``, ``D``, the gated norm's weight and
    the block's norm (109.64 M as published)."""
    c = cfg["hidden_size"]
    h = cfg["mamba_num_heads"]
    d_in = h * cfg["mamba_head_dim"]
    conv = d_in + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return c * (d_in + conv + h) + d_in * c + \
        (cfg["conv_kernel"] + 1) * conv + 3 * h + d_in + c


def attention_params(cfg):
    """One ``*`` block: q, k, v, o and the block's norm (35.66 M)."""
    c, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * c * cfg["num_attention_heads"] * d + \
        2 * c * cfg["num_key_value_heads"] * d + c


def expert_params(cfg):
    """One routed expert: two matrices in the latent (5.505 M)."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def expert_layer_params(cfg):
    """One ``E`` block without its routed experts: router and its bias,
    the two latent projections, the shared expert, the block's norm
    (54.53 M)."""
    c = cfg["hidden_size"]
    shared = cfg["n_shared_experts"] * \
        cfg["moe_shared_expert_intermediate_size"]
    return c * cfg["n_routed_experts"] + cfg["n_routed_experts"] + \
        2 * c * cfg["moe_latent_size"] + 2 * c * shared + c


def param_count(cfg, held=None, layers=None):
    """All parameters as this chip holds them (4,648.2 M for the cell's
    share). ``held`` / ``layers``: another count of held experts,
    another pattern (the published 512 and 88 blocks give the model's
    120.67 G with ``vocab_size`` 131072)."""
    held = cfg["assumed"]["experts_held"][1] if held is None else held
    layers = pattern(cfg) if layers is None else layers
    per = {MIXER: mixer_params(cfg), ATTENTION: attention_params(cfg),
           EXPERTS: expert_layer_params(cfg) + held * expert_params(cfg)}
    c = cfg["hidden_size"]
    return sum(per[k] for k in layers) + 2 * cfg["vocab_size"] * c + c


def state_bytes_per_row(cfg):
    """Float32 state one row owns over all mixers (20.97 MB for the
    cell's five)."""
    return 4 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * \
        cfg["ssm_state_size"] * pattern(cfg).count(MIXER)


# --- what one call needs: (operations, bytes) ------------------------------

def latent_expert_matmuls_decode_step(ctx, result):
    """The two grouped matmuls (up, down) of one decode step, all expert
    layers: each expert a step TOUCHES (a pair of a live row fell on it)
    has its two matrices read once, and each held pair is a latent row
    in, a hidden row out and in again, a latent row out.
    ``moe_experts_touched_profiled`` and ``moe_pairs_held_profiled``
    are the program's counters on ``serve/step/decode``, summed over
    the layers, their means over the profiled segment's own steps.
    Bound by bytes."""
    cfg, facts = ctx.config, result.facts
    touched = facts.get("moe_experts_touched_profiled")
    pairs = facts.get("moe_pairs_held_profiled")
    if not touched or pairs is None:
        return None
    lat, i = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    b = facts["kv_bytes_per_element"]
    ops = 2 * pairs * expert_params(cfg)
    moved = (touched * expert_params(cfg) + pairs * 2 * (lat + i)) * b
    return ops, moved


def ssd_prefill_call(ctx, result):
    """The chunked scans of one prompt's prefill, all mixers, as
    ``flops_ssm.ssd_prefill_call`` counts them but with ``n_groups``
    B/C groups: the scores C.B^T are one ``[Q, Q]`` product a GROUP
    (2 T Q G N, halved for the causal mask), the masked product with x
    one a head (2 T Q H P, halved), each chunk's state and the carried
    state's contribution 2 T H P N each; bytes: x in (bf16) and y out
    (float32), B and C of every group, dt, and the state read and
    written."""
    calls = result.facts.get("prefill_chunks_profiled")
    if not calls:
        return None
    cfg = ctx.config
    t = result.facts["prefill_chunk"]
    q = min(cfg["chunk_size"], t)
    h, n, g = cfg["mamba_num_heads"], cfg["ssm_state_size"], cfg["n_groups"]
    hp = h * cfg["mamba_head_dim"]
    ops = t * q * (g * n + hp) + 4 * t * hp * n
    moved = t * (2 * hp + 4 * hp + 2 * 2 * g * n + 4 * h) + 2 * 4 * hp * n
    layers = pattern(cfg).count(MIXER)
    return calls * layers * ops, calls * layers * moved

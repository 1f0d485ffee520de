"""Operations and bytes that a prompt's prefill attention over a latent
pool needs (a ``kimi_k2`` / DeepSeek-V3 configuration, the expanded
form), counted as ``flops_mla.py`` counts: what the ALGORITHM needs for
one ``per`` of its metric, whatever implements it (an XLA walk, a
kernel), from facts the driver gives. ``cfg`` is a configuration file's
dict.
"""


def mla_prefill_attention_prompt(ctx, result):
    """The attention of one prompt's prefill, all layers, under the
    scope ``ds_mla_prefill_attn``. A prompt of ``n`` calls of ``t`` =
    ``prefill_chunk`` tokens walks its prefix in blocks of ``t``
    positions: call ``c`` (from 1) visits ``c`` blocks, ``n (n + 1) /
    2`` in all, and each visit expands the block's latents to every
    head's keys and values (``2 t kv_lora_rank heads (nope + v)``
    operations); a call's queries meet every key before their chunk
    (``n (n - 1) / 2`` blocks of ``t x t`` pairs) and half of their own
    block's (``n / 2``: the pairs the mask admits), each pair ``2 (nope
    + rope + v)`` operations a head. Bytes: a visit reads the block's
    latents, a call reads its queries and writes its latents and its
    output. ``n`` is ``prefill_chunks_profiled``, the MEAN calls a
    prompt of the profiled segment's prefills, whose count the scope's
    time is divided by: ``n (n + 1) / 2`` is convex, so the mean ``n``
    under-counts the mean work and the share can only under-read. Bound
    by operations."""
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
    return cfg["n_layer"] * ops, cfg["n_layer"] * moved

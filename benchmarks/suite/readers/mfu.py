"""Model FLOP/s utilisation of a training cell: the window's tokens per
second per chip, times the operations the forward and backward passes
need per token (``flops.py``; recomputed operations do not count), over
the chip's published bf16 peak (``peaks.json``)."""


def read(ctx, result):
    rate = result.facts.get("tokens_per_s_per_chip")
    if rate is None:
        return None
    return 100.0 * rate * result.facts["flops_per_token"] / \
        ctx.peaks["bf16_flops_per_s"]

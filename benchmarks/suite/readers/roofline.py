"""A kernel's share of its roofline: the least time the chip could take
for what the call needs (the larger of operations / peak FLOP/s and
bytes / peak bytes/s, ``flops.py`` and ``peaks.json``) over the kernel's
device time from the trace. ``work`` names the function in ``flops.py``
that gives (operations, bytes) for one ``per`` (see ``op_time``). The
bf16 peak stands for operations whatever the kernel's type, so a float32
kernel's share is if anything understated; the detail line says which
of the two bounds it."""

from benchmarks.suite import flops
from benchmarks.suite.readers import op_time


def read(ctx, result, pattern, per, work):
    ms = op_time.read(ctx, result, pattern=pattern, per=per)
    need = getattr(flops, work)(ctx, result) if ms else None
    if not need:
        return None
    ops, moved = need
    least = max(ops / ctx.peaks["bf16_flops_per_s"],
                moved / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * ms)

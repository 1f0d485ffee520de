"""``roofline``, for a kernel whose (operations, bytes) function lives
in another file than ``flops.py``: ``module`` names it
(``benchmarks/suite/<module>.py``). Everything else is ``roofline``'s:
the least time by ``ctx.peaks`` over the kernel's device time."""

import importlib

from benchmarks.suite.readers import op_time


def read(ctx, result, pattern, per, work, module):
    ms = op_time.read(ctx, result, pattern=pattern, per=per)
    if not ms:
        return None
    need = getattr(importlib.import_module("benchmarks.suite." + module),
                   work)(ctx, result)
    if not need:
        return None
    ops, moved = need
    least = max(ops / ctx.peaks["bf16_flops_per_s"],
                moved / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * ms)

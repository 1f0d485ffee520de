"""``roofline_in`` for a kernel found by ``program_op_time`` (one
program's calls of a kernel that another program of the run calls too):
the least time by ``ctx.peaks`` for what ``module.work`` says one
``per`` needs, over the kernel's device time in that program."""

import importlib

from benchmarks.suite.readers import program_op_time


def read(ctx, result, program, pattern, per, work, module):
    ms = program_op_time.read(ctx, result, program=program,
                              pattern=pattern, per=per)
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

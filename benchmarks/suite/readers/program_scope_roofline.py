"""``roofline_in`` for phases found by ``program_scope_time`` (plain XLA
under a named scope, which no pattern on the trace's op names finds):
the least time by ``ctx.peaks`` for what ``module.work`` says one
``per`` needs, over the phases' device time."""

import importlib

from benchmarks.suite.readers import program_scope_time


def read(ctx, result, program, scopes, per, work, module):
    ms = program_scope_time.read(ctx, result, program=program,
                                 scopes=scopes, per=per)
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

"""How long the rows in decode stood still for another request's
prompt (PR 36): over the window's ``serve/step`` spans that decoded a
live row, a statistic of that step's time in ``serve/step/admit/
prefill`` spans whose ``rows_waiting`` (the rows that held a request in
decode when the prefill began) is above 0; 0 for a step without one. A
prompt's chunks all run inside one ``step()``, so this is the tail of
the gaps between tokens that the p95 does not reach. ms. ``None``
where no prefill span of the run carries ``rows_waiting``."""

import bisect

from benchmarks.suite import program_records, program_ring

PREFILL = program_ring.STEP + "/admit/prefill"


def read(ctx, result, stat):
    run = program_records.run_of(ctx, result, per_step=True)
    if run is None:
        return None
    carried = [r for r in run.records
               if r[0] == PREFILL and r[2] >= run.ramp0 and r[3]
               and r[3].get("rows_waiting") is not None]
    if not carried:
        return None
    stalling = sorted((r[1], r[2] - r[1]) for r in carried
                      if r[3]["rows_waiting"] > 0)
    values = []
    for path, t0, t1, attrs in run.closed_in(run.w0, run.quiet1):
        if path != program_ring.STEP or not (attrs or {}).get("batch"):
            continue
        i = bisect.bisect_left(stalling, (t0, 0.0))
        held = 0.0
        while i < len(stalling) and stalling[i][0] < t1:
            held += stalling[i][1]
            i += 1
        values.append(held)
    if not values:
        return None
    return 1e3 * program_ring.statistic(values, stat)

#!/usr/bin/env python3
"""Look at one trace by hand before trusting a reader: runs a cell traced
(the same arguments as run.py), keeps the raw trace, and writes its
planes, lines, event counts and the device ops by self time to
``chiprun_out/trace_<cell>.json``."""

import json
import os
import sys

SUITE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(SUITE))
sys.path.insert(0, ROOT)


def main(argv):
    from benchmarks.suite import run, xplane

    cell = argv[argv.index("--workload") + 1]
    out = os.path.join(ROOT, "chiprun_out")
    keep = os.path.join(out, "trace_" + cell)
    os.makedirs(out, exist_ok=True)
    code = run.main(argv, keep_trace=keep)
    if code:
        return code
    trace = xplane.load(keep)
    ops = {}
    for name, t in xplane.self_times(trace.devices[min(trace.devices)]):
        ops[name] = ops.get(name, 0.0) + t
    spans = {}
    for name, s, e in trace.spans:
        n, t = spans.get(name, (0, 0.0))
        spans[name] = (n + 1, t + e - s)
    with open(os.path.join(out, f"trace_{cell}.json"), "w") as f:
        json.dump({"lines": xplane.describe(keep),
                   "window": trace.window(), "spans": spans,
                   "ops_by_self_time": sorted(
                       ops.items(), key=lambda kv: -kv[1])[:150]},
                  f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

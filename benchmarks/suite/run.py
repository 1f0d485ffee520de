#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmarks/suite/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A new process, one cell, one run. Everything that belongs to a cell, a
configuration or a per-layer metric is found by the name
``BENCHMARK.json`` gives it: ``workloads/<cell>.json``, the
configuration's ``file``, ``metrics/<metric>.json``, and through them
``drivers/<kind>.py``, ``traffic/<generator>.py`` and
``readers/<reader>.py``. This file holds no cell's, configuration's or
metric's name, and reads no environment variable.

Progress goes to stderr. The last line of stdout is the result:
``correct``, ``attempted``, ``failed``, ``metrics`` (with ``--trace 0``
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics),
``device`` and, traced, ``breakdown``. The line before it (``detail``)
holds medians, sample counts and the checks behind ``correct``.

Exit codes: 0 a result was printed; 2 bad arguments or names; 3 no TPU,
or not the number of chips the cell asks for; 4 the program under test
is not there. No result is printed unless the code is 0.
"""

import time

T_PROCESS = time.perf_counter()

import argparse     # noqa: E402
import importlib    # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

SUITE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(SUITE))
EXIT_NAMES, EXIT_DEVICE, EXIT_NO_PROGRAM = 2, 3, 4


def log(msg):
    print(f"[bench {time.perf_counter() - T_PROCESS:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def metrics_of(manifest, section, cell):
    """The section's metrics this cell reports: those without a
    ``workloads`` key, and those that list the cell."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def prepare(workload, seed, seconds, trace, keep_trace=None):
    """Resolve the cell's files, refuse a wrong device, turn the compile
    cache on. Returns ``(exit code, context, manifest)``; the code is 0
    only where a run can start."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        manifest = load_json(ROOT, "BENCHMARK.json")
        cell = named(manifest["workloads"], workload, "workload")
        config_entry = named(manifest["configs"], cell["config"], "config")
        workload = load_json(SUITE, "workloads", cell["name"] + ".json")
        config = load_json(ROOT, config_entry["file"])
    except (OSError, KeyError, ValueError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return EXIT_NAMES, None, None
    try:
        import deepspeed_tpu  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program under test is not importable from "
              f"{ROOT}: {e}", file=sys.stderr)
        return EXIT_NO_PROGRAM, None, None

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell["chips"]:
        print(f"run.py: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX reports {len(devices)} x "
              f"{devices[0].platform!r}. Nothing was run.",
              file=sys.stderr)
        return EXIT_DEVICE, None, None

    from benchmarks.suite import flops, harness
    from deepspeed_tpu.telemetry import compile_cache

    cache_dir = compile_cache.configure(os.path.join(ROOT, ".jax_cache"))
    log(f"{len(devices)} x {devices[0].device_kind}; compile cache "
        f"{cache_dir}")
    ctx = harness.Context(
        cell=cell, workload=workload, config=config, seed=seed,
        seconds=seconds, trace=bool(trace), t_process=T_PROCESS,
        devices=devices, peaks=flops.peaks_for(devices[0].device_kind),
        log=log, compiles=harness.CompileCounter(), keep_trace=keep_trace)
    return 0, ctx, manifest


def main(argv=None, keep_trace=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    code, ctx, manifest = prepare(args.workload, args.seed, args.seconds,
                                  args.trace, keep_trace)
    if code:
        return code
    from benchmarks.suite import harness
    from deepspeed_tpu.telemetry import compile_cache

    cell, workload, devices = ctx.cell, ctx.workload, ctx.devices
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    driver = importlib.import_module(
        "benchmarks.suite.drivers." + workload["driver"])
    result = driver.run(ctx)

    values = dict(result.end_to_end, setup_s=result.setup_s)
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_of(manifest, section, cell["name"]):
        if args.trace:
            spec = load_json(SUITE, "metrics", m["name"] + ".json")
            reader = importlib.import_module(
                "benchmarks.suite.readers." + spec["reader"])
            value = reader.read(ctx, result, **spec["args"])
        else:
            value = values.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = harness.memory_peak_bytes(devices)
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed, "metrics": metrics, "device": device}
    detail = {"workload": cell["name"], "seed": args.seed,
              "seconds": args.seconds,
              "compile_cache": compile_cache.counts(),
              "end_to_end": values, **result.detail}
    if args.trace and result.trace is not None:
        w0, w1 = result.trace.window()
        device["busy_s"] = result.trace.busy_seconds()
        device["window_s"] = w1 - w0
        line["breakdown"] = {"device_ops": result.trace.top_ops(10),
                             "idle_gaps": result.trace.top_gaps(10)}
    print(json.dumps({"detail": detail}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

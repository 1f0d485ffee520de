"""Environment/op diagnostic — the ``ds_report`` analog
(reference `deepspeed/env_report.py:23-109`): native-op build/compat
matrix, framework versions, device inventory."""

import importlib
import sys

GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
OKAY = f"{GREEN}[OKAY]{END}"
NO = f"{RED}[NO]{END}"


def op_report(out=sys.stdout):
    from deepspeed_tpu.ops.op_builder import ALL_OPS
    print("-" * 64, file=out)
    print("deepspeed_tpu native op report", file=out)
    print("-" * 64, file=out)
    print(f"{'op name':<20} {'compatible':<14} {'built':<10}", file=out)
    print("-" * 64, file=out)
    rows = []
    for name, builder_cls in sorted(ALL_OPS.items()):
        b = builder_cls()
        compatible = b.is_compatible()
        built = b.lib_path().exists() if compatible else False
        print(f"{name:<20} {(OKAY if compatible else NO):<23} "
              f"{(OKAY if built else NO):<10}", file=out)
        rows.append((name, compatible, built))
    return rows


def debug_report(out=sys.stdout):
    import jax
    import jaxlib
    import deepspeed_tpu
    print("-" * 64, file=out)
    print("environment", file=out)
    print("-" * 64, file=out)
    rows = [
        ("deepspeed_tpu version", deepspeed_tpu.__version__),
        ("jax version", jax.__version__),
        ("jaxlib version", getattr(jaxlib, "__version__", "?")),
        ("python version", sys.version.split()[0]),
    ]
    for mod in ("flax", "optax", "orbax.checkpoint"):
        try:
            m = importlib.import_module(mod)
            rows.append((f"{mod} version", getattr(m, "__version__", "?")))
        except ImportError:
            rows.append((f"{mod} version", "not installed"))
    try:
        devs = jax.devices()
        rows.append(("default backend", jax.default_backend()))
        rows.append(("device count", str(len(devs))))
        rows.append(("devices", ", ".join(str(d) for d in devs[:8])))
    except Exception as e:  # device init can fail off-TPU
        rows.append(("devices", f"unavailable ({e})"))
    for name, val in rows:
        print(f"{name:.<30} {val}", file=out)
    return rows


def main(out=sys.stdout, argv=None):
    import argparse
    parser = argparse.ArgumentParser(
        description="deepspeed_tpu environment report (reference bin/"
                    "ds_report)")
    parser.add_argument(
        "--perf", action="store_true",
        help="also run the CPU Adam micro-benchmark at reference scale "
             "(~1e8 elements; reference tests/perf/adam_test.py)")
    args = parser.parse_args(argv)
    op_report(out=out)
    debug_report(out=out)
    from deepspeed_tpu.telemetry.profiler import device_report
    device_report(out=out)
    if args.perf:
        import json
        from deepspeed_tpu.ops.adam.perf import benchmark_cpu_adam
        print("cpu_adam micro-bench (1e8 elems, best of 5):", file=out)
        print(json.dumps(benchmark_cpu_adam()), file=out)


if __name__ == "__main__":
    main()

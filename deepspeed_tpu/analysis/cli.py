"""`ds_tpu_audit`: audit compiled train steps from the command line.

Three modes:

- ``ds_tpu_audit --flavors dense,zero1`` (default: all seven stock
  flavors) — build toy engines per flavor and audit each compiled step.
- ``ds_tpu_audit --config my_config.json`` — build an engine from a
  user DeepSpeed-style config (with a toy GPT-2 model supplying the
  loss) and audit the step that config actually compiles to.
- ``ds_tpu_audit --hlo dump.txt`` — run the HLO-text rule subset over a
  saved HLO dump (no engine, no trace; the jaxpr-level rules don't run).

``--memory`` appends the static peak-memory table per audited step
(liveness peak, temp peak, parameter/output/donated bytes from
``analysis.hlo.estimate_peak_memory``).

Reports findings as text (default) or JSON (``--json``); exits non-zero
when findings at or above ``--fail-on`` severity (default ``error``)
exist. Runs on CPU by default (``JAX_PLATFORMS=cpu`` unless the caller
overrides) — the audit reads compile-time artifacts, so no TPU needed.
"""

import argparse
import json
import os
import sys


def _build_config_engine(config_path, compilation_cache_dir=None):
    """Engine for a user config: toy GPT-2 supplies model/loss (pipeline
    configs need a PipelineModule and aren't supported here — use
    ``--flavors pipeline`` for the stock pipeline audit)."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (GPT2LMHead, gpt2_tiny,
                                           init_gpt2_params,
                                           make_gpt2_loss_fn)
    import numpy as np

    with open(config_path) as f:
        cfg = json.load(f)
    if compilation_cache_dir:
        cfg["compilation_cache_dir"] = compilation_cache_dir
    model = GPT2LMHead(gpt2_tiny())
    params = init_gpt2_params(model, jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=cfg, loss_fn=make_gpt2_loss_fn(model), params=params)
    rows = int(cfg.get("train_batch_size", 8))
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 255, (rows, 32)).astype(np.int32)}
    return engine, batch


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="ds_tpu_audit",
        description="Static audit of compiled train steps: donation/"
                    "aliasing, ZeRO byte budgets, dtype hygiene, host "
                    "transfers, trip-count-aware collective accounting, "
                    "recompile detection.")
    parser.add_argument("--config", default=None,
                        help="DeepSpeed-style JSON config to audit "
                             "(engine built with a toy GPT-2 model)")
    parser.add_argument("--hlo", default=None, metavar="FILE",
                        help="audit a saved HLO text dump instead of "
                             "building an engine (HLO-text rules only)")
    parser.add_argument("--memory", action="store_true",
                        help="print the static peak-memory table per "
                             "audited step (text mode; JSON always "
                             "carries it in stats.peak_memory)")
    parser.add_argument("--flavors", default=None,
                        help="comma-separated stock flavors to audit "
                             "(default: all seven); extra flavors like "
                             "pipeline_tp (TP overlap) must be named "
                             "explicitly; ignored with --config")
    parser.add_argument("--kernels", action="store_true",
                        help="run the sub-pallas_call kernel analyzer "
                             "sweep (analysis/kernels.py) instead of "
                             "the train-step flavors: VMEM budgets, "
                             "tile-alignment lint, DMA-elision proofs "
                             "and grid-write races over flash_train, "
                             "decode_paged, speculative; "
                             "--flavors selects a subset of those")
    parser.add_argument("--rules", default=None,
                        help="comma-separated rule ids to run "
                             "(default: full catalog)")
    parser.add_argument("--steps", type=int, default=0,
                        help="extra train steps to run for the recompile "
                             "detector (default 0)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="emit a JSON report instead of text")
    parser.add_argument("--fail-on", default="error",
                        choices=("error", "warning"),
                        help="exit non-zero on findings at/above this "
                             "severity (default: error)")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalog and exit")
    parser.add_argument("--compilation-cache-dir", default=None,
                        metavar="DIR",
                        help="persistent XLA compile cache for the "
                             "audited engines (repeat audits become "
                             "cache hits)")
    args = parser.parse_args(argv)

    # Audits read compile-time artifacts; default to the CPU backend
    # (and an 8-device virtual mesh for the sharded flavors) so this
    # runs anywhere. Must happen before jax import.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if "cpu" in os.environ.get("JAX_PLATFORMS", "") \
            and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()

    if args.compilation_cache_dir:
        # toy audits compile in under jax's default persistence
        # threshold (1s); cache them anyway so reruns are hits
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)

    from deepspeed_tpu.analysis.rules import RULE_IDS, SEV_ERROR
    if args.list_rules:
        from deepspeed_tpu.analysis import rules as rules_mod
        for rule_id in RULE_IDS:
            fn = rules_mod.RULES.get(rule_id)
            doc = (fn.__doc__ or "recompile detector (orchestrator-level)"
                   ).strip().splitlines()[0]
            print(f"{rule_id:16s} {doc}")
        return 0

    rules = None
    if args.rules:
        rules = [r.strip() for r in args.rules.split(",") if r.strip()]
        unknown = sorted(set(rules) - set(RULE_IDS))
        if unknown:
            parser.error(f"unknown rule id(s) {unknown}; "
                         f"known: {list(RULE_IDS)}")

    from deepspeed_tpu.analysis.audit import (EXTRA_FLAVORS, STEP_FLAVORS,
                                              audit_decode, audit_engine,
                                              audit_flash_train,
                                              audit_flavors, audit_hlo,
                                              audit_kernel_flavors,
                                              audit_speculative)
    if args.hlo and args.config:
        parser.error("--hlo and --config are mutually exclusive")
    if args.kernels and (args.hlo or args.config):
        parser.error("--kernels audits the stock kernel flavors; it "
                     "does not combine with --hlo/--config")
    if args.kernels:
        kernel_sweep = {
            "flash_train": lambda: audit_flash_train(rules=rules),
            "decode_paged": lambda: audit_decode(
                rules=rules, kernels=True),
            "speculative": lambda: audit_speculative(
                rules=rules, kernels=True),
        }
        if args.flavors:
            names = [f.strip() for f in args.flavors.split(",")
                     if f.strip()]
            unknown = sorted(set(names) - set(kernel_sweep))
            if unknown:
                parser.error(f"unknown kernel flavor(s) {unknown}; "
                             f"known: {list(kernel_sweep)}")
            reports = {name: kernel_sweep[name]() for name in names}
            for name, rep in reports.items():
                rep.flavor = name
        else:
            reports = audit_kernel_flavors(rules=rules)
    elif args.hlo:
        try:
            with open(args.hlo) as f:
                hlo_text = f.read()
        except OSError as exc:
            parser.error(f"cannot read --hlo file: {exc}")
        reports = {"hlo": audit_hlo(hlo_text, rules=rules)}
    elif args.config:
        engine, batch = _build_config_engine(
            args.config,
            compilation_cache_dir=args.compilation_cache_dir)
        reports = {"config": audit_engine(engine, batch, rules=rules,
                                          steps=args.steps)}
    else:
        flavors = STEP_FLAVORS
        if args.flavors:
            flavors = [f.strip() for f in args.flavors.split(",")
                       if f.strip()]
            known = STEP_FLAVORS + EXTRA_FLAVORS
            unknown = sorted(set(flavors) - set(known))
            if unknown:
                parser.error(f"unknown flavor(s) {unknown}; "
                             f"known: {list(known)}")
        overrides = None
        if args.compilation_cache_dir:
            overrides = {
                "compilation_cache_dir": args.compilation_cache_dir}
        reports = audit_flavors(flavors, rules=rules, steps=args.steps,
                                config_overrides=overrides)

    fail_severities = {"error": (SEV_ERROR,),
                       "warning": (SEV_ERROR, "warning")}[args.fail_on]
    n_failing = sum(1 for rep in reports.values() for f in rep.findings
                    if f.severity in fail_severities)
    n_findings = sum(len(rep.findings) for rep in reports.values())

    if args.as_json:
        # Same schema tag as the telemetry event log so downstream
        # tooling can join audit output with run telemetry by version.
        from deepspeed_tpu.telemetry.events import SCHEMA_VERSION
        print(json.dumps(
            {"schema": SCHEMA_VERSION,
             "reports": {k: rep.to_dict() for k, rep in reports.items()},
             "findings_total": n_findings,
             "failing_findings": n_failing,
             "fail_on": args.fail_on,
             "ok": n_failing == 0},
            indent=2, sort_keys=True))
    else:
        for rep in reports.values():
            print(rep.to_text())
        if args.memory:
            print("\nstatic peak memory (analysis.hlo.estimate_peak_"
                  "memory):")
            cols = ("peak_bytes", "temp_peak_bytes", "parameter_bytes",
                    "output_bytes", "donated_output_bytes")
            head = "step".ljust(12) + "".join(
                c.replace("donated_output", "donated")
                 .replace("_bytes", "").rjust(12) for c in cols)
            print(head)
            for name, rep in reports.items():
                pm = (rep.stats or {}).get("peak_memory") or {}
                row = name.ljust(12) + "".join(
                    f"{pm.get(c, 0) / (1 << 20):11.2f}M" for c in cols)
                print(row)
        print(f"\n{len(reports)} step(s) audited, {n_findings} "
              f"finding(s), {n_failing} at/above --fail-on="
              f"{args.fail_on}")
    return 1 if n_failing else 0


if __name__ == "__main__":
    sys.exit(main())

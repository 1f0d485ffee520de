"""`ds_tpu_serve`: drive the serving engine against a request stream.

    ds_tpu_serve --synthetic 8                # scripted open-loop stream
    ds_tpu_serve --requests stream.jsonl      # one request per line
    ds_tpu_serve --config ds_config.json      # inference block from config
    ds_tpu_serve --scan-layers --kv-cache-dtype int8
    ds_tpu_serve --expect-compiles 2 --json
    ds_tpu_serve --synthetic 8 --shared-prefix 12 \
                 --expect-prefix-hits 1   # radix prefix-cache smoke
    ds_tpu_serve --synthetic 8 --replicas 2 \
                 --kill-replica 0 --kill-at-step 3 \
                 --expect-redispatch 1    # fleet resilience smoke
    ds_tpu_serve --synthetic 8 --disaggregate \
                 --prefill-workers 1 --decode-workers 1 \
                 --expect-compiles 2      # tiered prefill/decode smoke
    ds_tpu_serve --synthetic 8 --speculative --spec-k 4 \
                 --draft-layers 1 --block-scale 0.1 \
                 --expect-compiles 3 --expect-min-accepted 1.0
    ds_tpu_serve --synthetic 4 --checkpoint /ckpts/run1 --n-head 4

The model is the test-size GPT-2 with seeded random params — this CLI
exists to exercise and measure the serving engine (CI smoke, bench
rows, audits), not to ship checkpoints. A request line is
``{"rid": "r0", "prompt": [1, 2, 3], "max_new_tokens": 8,
"eos_id": null, "arrival_step": 0}`` (only ``prompt`` required; also
``deadline_s``/``queue_timeout_s`` per ISSUE 17).

``--expect-compiles N`` makes the exit code enforce the recompile
contract: after the stream drains, prefill + decode (+ draft + verify
with ``--speculative``) jit-cache entries must total exactly N (2 for
any single-engine serve — one prefill, one decode — and exactly 3
speculative: prefill, draft, verify, with the plain decode program
never entered). With ``--replicas`` the gate applies PER SURVIVING
REPLICA. With ``--disaggregate`` it counts DISTINCT compiled programs
across the whole fleet (2: the prefill tier's one program plus the
decode tier's), not per-worker jit entries — each worker holds its own
cache entry for its tier's single program, so entries scale with
worker count while the program count must not.
``--jsonl`` writes telemetry events for ``ds_tpu_metrics summary``
serve mode (``decode_step`` single-engine; fleet events with
``--replicas``).

``--replicas N`` (N >= 2) serves through the fleet router
(`inference/fleet.py` + `router.py`): N replica workers behind one
admission queue with drain/redispatch on replica death.
``--kill-replica I --kill-at-step S`` arms a real SIGKILL inside
replica I's decode loop (``DS_TPU_SERVE_INJECT``), and
``--expect-redispatch N`` gates the exit code on the fleet actually
recovering.

Exit codes: 0 ok, 1 contract violation or unfinished requests,
2 usage errors.
"""

import argparse
import json
import sys

import numpy as np


def _build_requests(args, vocab_size, max_seq):
    from deepspeed_tpu.inference.scheduler import Request
    if args.requests:
        reqs = []
        with open(args.requests) as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                reqs.append(Request(
                    rid=str(d.get("rid", f"r{i}")),
                    prompt=[int(t) for t in d["prompt"]],
                    max_new_tokens=int(
                        d.get("max_new_tokens", args.max_new)),
                    eos_id=d.get("eos_id"),
                    arrival_step=int(d.get("arrival_step", 0)),
                    session_id=d.get("session_id"),
                    deadline_s=d.get("deadline_s", args.deadline_s),
                    queue_timeout_s=d.get("queue_timeout_s",
                                          args.queue_timeout_s)))
        return reqs
    # synthetic open-loop stream: varied prompt lengths spanning the
    # buckets, staggered arrivals, deterministic under --seed. With
    # --shared-prefix N every prompt opens with the same N tokens (a
    # common system prompt) so a paged engine's radix cache gets hits.
    rng = np.random.default_rng(args.seed)
    shared = rng.integers(
        0, vocab_size, args.shared_prefix).tolist() \
        if args.shared_prefix else []
    reqs = []
    for i in range(args.synthetic):
        plen = int(rng.integers(2, max(3, args.synthetic_max_prompt)))
        tail = rng.integers(0, vocab_size, plen).tolist()
        prompt = (shared + tail)[:max_seq - 1]
        reqs.append(Request(
            rid=f"s{i}",
            prompt=prompt,
            max_new_tokens=args.max_new,
            arrival_step=int(i * args.arrival_every),
            deadline_s=args.deadline_s,
            queue_timeout_s=args.queue_timeout_s))
    return reqs


# gpt2_tiny's fixed test vocab — the synthetic stream only needs the
# token range, so fleet mode doesn't build a model in the parent
_TINY_VOCAB = 256


def _scale_blocks(params, scale):
    """Damp every block's residual-branch output projections
    (attn/mlp ``c_proj`` kernels) by ``scale``.

    Seeded-random weights give each block a ~unit-RMS output riding on
    a 0.02-RMS embedding stream, so a truncated-depth draft diverges
    from the full model immediately and speculative acceptance sits at
    chance (~1/vocab). Trained transformers converge through depth;
    ``--block-scale 0.1`` emulates that residual-stream convergence so
    the CI mean-accepted gate measures the accept machinery, not the
    entropy of random init."""
    def walk(tree, path):
        if hasattr(tree, "items"):
            return {k: walk(v, path + (str(k),))
                    for k, v in tree.items()}
        if "c_proj" in path and path[-1] == "kernel":
            return tree * scale
        return tree

    return walk(params, ())


def _gpt2_tiny_model(args):
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny
    model = GPT2LMHead(gpt2_tiny(n_embd=32, dtype=jnp.float32,
                                 scan_layers=args.scan_layers))
    return model, lambda rng: model.init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"]


def _hybrid_model(preset):
    def build(args, dtype=None):
        from deepspeed_tpu.models import granite_hybrid as gh
        kw = {} if dtype is None else {"dtype": dtype,
                                       "param_dtype": dtype}
        model = gh.GraniteHybridLM(getattr(gh, preset)(**kw))
        return model, lambda rng: gh.init_granite_hybrid_params(
            model, rng)
    return build


# --model: preset name -> builder of (model, weights from a key), in
# the preset's own dtype (float32 for the test-size GPT-2, bfloat16 for
# a hybrid of state-space and attention layers) or in a checkpoint's.
# The engine serves every preset through the same protocol.
MODELS = {"gpt2-tiny": _gpt2_tiny_model,
          "granite-hybrid-tiny": _hybrid_model("granite_hybrid_tiny"),
          "granite-4.0-h-micro": _hybrid_model("granite_4_0_h_micro")}


def _load_checkpoint_model(args, jax, jnp):
    """Serve a real trained checkpoint: resolve + load a
    `runtime/resilience/checkpoint.py` manifest, take its fp32 master
    params, infer the GPT-2 geometry from leaf shapes, and convert the
    layer layout (the elastic ``param_layout`` metadata: ``stacked``
    scan_layers vs ``per_layer`` unrolled) to the requested serving
    variant — training→serving handoff in one command. Checkpoints
    saved under a different tensor-parallel topology need a
    ``ds_tpu_reshard`` relayout first (single-host serving reads
    replicated host leaves)."""
    import re

    from deepspeed_tpu.models.gpt2 import (
        GPT2Config,
        GPT2LMHead,
        stack_gpt2_layer_params,
        unstack_gpt2_layer_params,
    )
    from deepspeed_tpu.runtime.resilience.checkpoint import (
        CheckpointManager)

    mgr = CheckpointManager()
    tag = mgr.resolve_tag(args.checkpoint, args.ckpt_tag)
    if tag is None:
        raise SystemExit(
            f"ds_tpu_serve: no valid checkpoint under {args.checkpoint}")
    state, meta, path = mgr.load(args.checkpoint, tag)
    if "params" not in state:
        raise SystemExit(
            f"ds_tpu_serve: checkpoint {path} carries no 'params' tree")
    params = state["params"]
    if "embed" in params and "wte" not in params:
        # a hybrid checkpoint (`models/granite_hybrid.py`): the preset
        # --model names, held to the leaves' shapes, served in the dtype
        # the leaves were saved in (no float32 copy)
        params = jax.tree_util.tree_map(jnp.asarray, params)
        if args.model == "gpt2-tiny":
            raise SystemExit(
                f"ds_tpu_serve: checkpoint {path} holds a hybrid model; "
                f"name its preset with --model")
        model, _ = MODELS[args.model](args, params["embed"].dtype)
        cfg = model.config
        if params["embed"].shape != (cfg.vocab_size, cfg.hidden_size):
            raise SystemExit(
                f"ds_tpu_serve: checkpoint {path}'s embedding "
                f"{params['embed'].shape} is not {args.model}'s")
        return model, params, {
            "tag": tag, "path": path, "n_layer": cfg.num_hidden_layers,
            "n_embd": cfg.hidden_size, "vocab_size": cfg.vocab_size,
            "param_layout": "per_layer"}
    topo = (meta or {}).get("topology") or {}
    saved_tp = int((topo.get("mesh_shape") or {}).get("model", 1) or 1)
    if saved_tp > 1:
        print(f"note: checkpoint {tag} was saved on a model-parallel "
              f"mesh (model axis {saved_tp}); if its leaves were "
              f"persisted sharded, relayout with ds_tpu_reshard before "
              f"serving", file=sys.stderr)
    # layer-layout conversion: the round trip is bit-exact, so a
    # per-layer training checkpoint serves as scan_layers and back
    if args.scan_layers and "h" not in params:
        params = stack_gpt2_layer_params(params)
    elif not args.scan_layers and "h" in params:
        params = unstack_gpt2_layer_params(params)
    wte, wpe = params["wte"], params["wpe"]
    if "h" in params:
        n_layer = int(jax.tree_util.tree_leaves(params["h"])[0].shape[0])
    else:
        n_layer = len([k for k in params
                       if re.match(r"^h_\d+$", str(k))])
    n_embd = int(wte.shape[1])
    if n_embd % args.n_head:
        raise SystemExit(
            f"ds_tpu_serve: --n-head {args.n_head} does not divide the "
            f"checkpoint's n_embd {n_embd}")
    cfg = GPT2Config(
        vocab_size=int(wte.shape[0]), n_positions=int(wpe.shape[0]),
        n_embd=n_embd, n_layer=n_layer, n_head=args.n_head,
        dropout=0.0, dtype=jnp.float32, param_dtype=jnp.float32,
        scan_layers=args.scan_layers)
    params = jax.tree_util.tree_map(jnp.asarray, params)
    return GPT2LMHead(cfg), params, {"tag": tag, "path": path,
                                     "n_layer": n_layer,
                                     "n_embd": n_embd,
                                     "vocab_size": cfg.vocab_size,
                                     "param_layout": topo.get(
                                         "param_layout")}


def _run_fleet(args, inf_cfg, session):
    """Serve through the N-replica fleet router (ISSUE 17)."""
    import os
    import tempfile

    from deepspeed_tpu.inference import fleet as fleet_mod
    from deepspeed_tpu.inference.router import FleetRouter

    workdir = os.path.abspath(
        args.workdir or tempfile.mkdtemp(prefix="ds-tpu-fleet-"))
    max_seq = max(inf_cfg.get("seq_buckets", (16, 32)))
    requests = _build_requests(args, _TINY_VOCAB, max_seq)

    inject = None
    if args.kill_replica is not None:
        inject = {"kill": {"op": "decode_step",
                           "at_step": args.kill_at_step}}
    spec = {"inf_cfg": {k: (list(v) if isinstance(v, tuple) else v)
                        for k, v in inf_cfg.items()},
            "seed": args.seed, "scan_layers": args.scan_layers}

    if args.replica_backend == "process":
        replicas = []
        for i in range(args.replicas):
            rspec = dict(spec, jsonl=os.path.join(
                workdir, f"replica{i}.jsonl"))
            replicas.append(fleet_mod.ProcessReplica(
                i, rspec, workdir, num_replicas=args.replicas,
                inject=inject if i == args.kill_replica else None,
                hang_timeout_s=args.hang_timeout_s,
                heartbeat_stale_s=args.heartbeat_stale_s).start())
        for r in replicas:
            r.wait_ready()
    else:
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny

        def factory():
            cfg = gpt2_tiny(n_embd=32, dtype=jnp.float32,
                            scan_layers=args.scan_layers)
            model = GPT2LMHead(cfg)
            params = model.init(jax.random.PRNGKey(args.seed),
                                jnp.zeros((1, 8), jnp.int32))["params"]
            return InferenceEngine(model, params, config=inf_cfg)

        replicas = [fleet_mod.ThreadReplica(i, factory).start()
                    for i in range(args.replicas)]

    router = FleetRouter(
        replicas, session=session,
        max_redispatch=(args.max_redispatch if args.max_redispatch
                        is not None
                        else int(inf_cfg.get("max_redispatch", 2))),
        max_queue_depth=(args.max_queue_depth if args.max_queue_depth
                         is not None
                         else int(inf_cfg.get("max_queue_depth", 8))),
        max_pending=args.max_pending)
    fr = router.run(requests, timeout_s=args.fleet_timeout)

    ok = fr.ok
    compiles_bad = []
    if args.expect_compiles is not None:
        for st in fr.stats:
            total = sum(n for n in st["compile_counts"].values()
                        if n is not None)
            if total != args.expect_compiles:
                compiles_bad.append((st["replica"], total))
        ok = ok and not compiles_bad
    redisp_ok = True
    if args.expect_redispatch is not None:
        redisp_ok = fr.redispatched_total >= args.expect_redispatch
        ok = ok and redisp_ok

    result = {
        "requests": len(requests),
        "completions": fr.completions,
        "fleet": {
            "replicas": fr.replicas,
            "backend": args.replica_backend,
            "replicas_dead": fr.replicas_dead,
            "dead_causes": dict(router.dead),
            "redispatched_total": fr.redispatched_total,
            "aborted": fr.aborted, "shed": fr.shed,
            "defers": fr.defers, "timeouts": fr.timeouts,
            "latency_s": fr.latency_s,
            "stats": fr.stats,
            "workdir": workdir,
        },
        "ok": ok,
    }
    if args.expect_compiles is not None:
        result["expect_compiles"] = args.expect_compiles
    if args.expect_redispatch is not None:
        result["expect_redispatch"] = args.expect_redispatch

    if args.as_json:
        print(json.dumps(result, indent=2, sort_keys=True))
    else:
        for c in fr.completions:
            extra = ""
            if c["redispatched"]:
                extra = f", redispatched x{c['redispatched']}"
            print(f"{c['rid']}: prompt {c['prompt_len']} tokens -> "
                  f"{len(c['tokens'])} generated "
                  f"({c['finish_reason']}, replica {c['replica']}"
                  f"{extra})")
        fl = result["fleet"]
        print(f"{len(fr.completions)}/{len(requests)} requests "
              f"completed on {fl['replicas']} replica(s) "
              f"({fl['replicas_dead']} died: {fl['dead_causes']}); "
              f"redispatched={fl['redispatched_total']} "
              f"aborted={fl['aborted']} shed={fl['shed']} "
              f"timeouts={fl['timeouts']}")
        for st in fr.stats:
            cc = st["compile_counts"]
            print(f"replica {st['replica']}: {st['completed']} "
                  f"completed in {st['steps']} step(s); compiles: "
                  f"prefill={cc.get('prefill')} "
                  f"decode={cc.get('decode')}")
        if not ok:
            if compiles_bad:
                why = (f"replica compile counts {compiles_bad} != "
                       f"expected {args.expect_compiles}")
            elif not redisp_ok:
                why = (f"redispatched {fr.redispatched_total} < "
                       f"expected {args.expect_redispatch}")
            else:
                why = ("unfinished/aborted/shed/timed-out requests "
                       "in the fleet result")
            print(f"FAIL: {why}", file=sys.stderr)
    return 0 if ok else 1


def _run_disagg(args, inf_cfg, session):
    """Serve through disaggregated prefill/decode tiers (ISSUE 20).

    Each tier pins exactly ONE compiled program warmup-to-drain — the
    prefill tier never enters the decode jit and vice versa — so the
    fleet-wide compile total is 2 regardless of worker counts. The
    process backend hands KV off through a durable
    ``FileHandoffStore`` under ``workdir/handoff`` (CRC-verified, park/
    resume survives a dead decode worker); the thread backend uses the
    consume-once device-to-device ``DeviceHandoffStore``."""
    import os
    import tempfile

    from deepspeed_tpu.inference import fleet as fleet_mod
    from deepspeed_tpu.inference.router import DisaggRouter

    workdir = os.path.abspath(
        args.workdir or tempfile.mkdtemp(prefix="ds-tpu-disagg-"))
    max_seq = max(inf_cfg.get("seq_buckets", (16, 32)))
    requests = _build_requests(args, _TINY_VOCAB, max_seq)

    n_pre, n_dec = args.prefill_workers, args.decode_workers
    total = n_pre + n_dec

    def tier_inf(tier):
        # per-tier engine config: tiers scale max_batch independently
        # (0 / unset falls back to the shared max_batch)
        cfg = {k: v for k, v in inf_cfg.items()
               if k not in ("disaggregated", "prefill_workers",
                            "decode_workers", "prefill_max_batch",
                            "decode_max_batch")}
        mb = (args.prefill_max_batch if tier == "prefill"
              else args.decode_max_batch)
        if mb is None:
            mb = int(inf_cfg.get(f"{tier}_max_batch", 0) or 0)
        if mb:
            cfg["max_batch"] = mb
        return cfg

    inject = None
    if args.kill_prefill_worker is not None:
        inject = {"kill": {"op": "prefill_chunk",
                           "at_step": args.kill_at_step}}

    if args.replica_backend == "process":
        from deepspeed_tpu.inference.disagg import FileHandoffStore
        handoff_dir = os.path.join(workdir, "handoff")
        # the router shares the workers' durable store: parked()/drop()
        # are plain file probes, so tier-aware recovery works from the
        # parent without touching any device state
        store = FileHandoffStore(handoff_dir)

        def spawn(i, tier, tag, inj):
            cfg = {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in tier_inf(tier).items()}
            rspec = {"inf_cfg": cfg, "seed": args.seed,
                     "scan_layers": args.scan_layers, "tier": tier,
                     "handoff_dir": handoff_dir,
                     "jsonl": os.path.join(workdir, f"{tag}.jsonl")}
            return fleet_mod.TierProcessReplica(
                i, rspec, workdir, num_replicas=total, inject=inj,
                hang_timeout_s=args.hang_timeout_s,
                heartbeat_stale_s=args.heartbeat_stale_s).start()

        # globally-unique indices across tiers: prefill 0..N-1,
        # decode N..N+M-1 (heartbeats/done markers share the workdir)
        prefill = [spawn(i, "prefill", f"prefill{i}",
                         inject if i == args.kill_prefill_worker
                         else None)
                   for i in range(n_pre)]
        decode = [spawn(n_pre + j, "decode", f"decode{j}", None)
                  for j in range(n_dec)]
        for r in prefill + decode:
            r.wait_ready()
    else:
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.inference.disagg import (
            DecodeWorker, DeviceHandoffStore, PrefillWorker)
        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.models.gpt2 import GPT2LMHead, gpt2_tiny

        store = DeviceHandoffStore()

        def make_factory(tier):
            cfg_t = dict(tier_inf(tier), tier=tier)

            def factory():
                cfg = gpt2_tiny(n_embd=32, dtype=jnp.float32,
                                scan_layers=args.scan_layers)
                model = GPT2LMHead(cfg)
                params = model.init(
                    jax.random.PRNGKey(args.seed),
                    jnp.zeros((1, 8), jnp.int32))["params"]
                engine = InferenceEngine(model, params, config=cfg_t)
                cls = (PrefillWorker if tier == "prefill"
                       else DecodeWorker)
                return cls(engine, store)
            return factory

        prefill = [fleet_mod.TierThreadReplica(
            i, make_factory("prefill")).start() for i in range(n_pre)]
        decode = [fleet_mod.TierThreadReplica(
            n_pre + j, make_factory("decode")).start()
            for j in range(n_dec)]

    router = DisaggRouter(
        prefill, decode, store, session=session,
        max_redispatch=(args.max_redispatch if args.max_redispatch
                        is not None
                        else int(inf_cfg.get("max_redispatch", 2))),
        max_queue_depth=(args.max_queue_depth if args.max_queue_depth
                         is not None
                         else int(inf_cfg.get("max_queue_depth", 8))),
        max_pending=args.max_pending)
    fr = router.run(requests, timeout_s=args.fleet_timeout)

    # the one-program-per-tier pin is intrinsic to disaggregation:
    # every surviving worker must hold exactly its own tier's program
    # and never have entered the other one
    pins = {"prefill": {"prefill": 1, "decode": 0},
            "decode": {"prefill": 0, "decode": 1}}
    tier_bad = []
    programs = set()
    for st in fr.stats:
        cc = st.get("compile_counts") or {}
        got = {"prefill": cc.get("prefill") or 0,
               "decode": cc.get("decode") or 0}
        programs.update(k for k, v in got.items() if v)
        if got != pins[st["tier"]]:
            tier_bad.append((st["replica"], st["tier"], got))
    # the fleet census counts DISTINCT programs, not jit entries:
    # every worker necessarily holds its own cache entry for its
    # tier's one program, so entries scale with worker count while the
    # program count stays 2 — and the pin check above already fails
    # any worker holding more than its single program
    total_compiles = len(programs)
    ok = fr.ok and not tier_bad
    compiles_ok = True
    if args.expect_compiles is not None:
        compiles_ok = total_compiles == args.expect_compiles
        ok = ok and compiles_ok
    redisp_ok = True
    if args.expect_redispatch is not None:
        redisp_ok = fr.redispatched_total >= args.expect_redispatch
        ok = ok and redisp_ok

    result = {
        "requests": len(requests),
        "completions": fr.completions,
        "disagg": {
            "backend": args.replica_backend,
            "prefill_workers": fr.prefill_replicas,
            "decode_workers": fr.decode_replicas,
            "replicas_dead": fr.replicas_dead,
            "dead_by_tier": fr.dead_by_tier,
            "dead_causes": dict(router.dead),
            "redispatched_total": fr.redispatched_total,
            "aborted": fr.aborted, "shed": fr.shed,
            "defers": fr.defers, "timeouts": fr.timeouts,
            "handoffs": fr.handoffs,
            "handoff_bytes": fr.handoff_bytes,
            "handoff_bytes_per_session": (
                fr.handoff_bytes / fr.handoffs if fr.handoffs else 0.0),
            "handoff_corrupt": fr.handoff_corrupt,
            "resumed_from_park": fr.resumed_from_park,
            "latency_s": fr.latency_s,
            "ttft_s": fr.ttft_s,
            "total_compiles": total_compiles,
            "stats": fr.stats,
            "workdir": workdir,
        },
        "ok": ok,
    }
    if args.expect_compiles is not None:
        result["expect_compiles"] = args.expect_compiles
    if args.expect_redispatch is not None:
        result["expect_redispatch"] = args.expect_redispatch

    if args.as_json:
        print(json.dumps(result, indent=2, sort_keys=True))
    else:
        for c in fr.completions:
            extra = ""
            if c.get("redispatched"):
                extra += f", redispatched x{c['redispatched']}"
            if c.get("restarts"):
                extra += f", re-prefilled x{c['restarts']}"
            print(f"{c['rid']}: prompt {c['prompt_len']} tokens -> "
                  f"{len(c['tokens'])} generated "
                  f"({c['finish_reason']}, replica {c['replica']}"
                  f"{extra})")
        dg = result["disagg"]
        print(f"{len(fr.completions)}/{len(requests)} requests "
              f"completed across {dg['prefill_workers']}+"
              f"{dg['decode_workers']} tiered worker(s) "
              f"({dg['replicas_dead']} died: {dg['dead_causes']}); "
              f"redispatched={dg['redispatched_total']} "
              f"aborted={dg['aborted']} timeouts={dg['timeouts']}")
        for tier in ("prefill", "decode"):
            sts = [s for s in fr.stats if s["tier"] == tier]
            # distinct programs the tier's workers hold (1 each when
            # the pins are honored, whatever the worker count)
            tp = {"prefill": 0, "decode": 0}
            for s in sts:
                for k, v in (s.get("compile_counts") or {}).items():
                    if v:
                        tp[k] = 1
            done = sum(int(s.get("completed", 0)) for s in sts)
            print(f"{tier} tier: {len(sts)} surviving worker(s), "
                  f"{done} completion(s); compiles: "
                  f"prefill={tp['prefill']} decode={tp['decode']}")
        def _ms(v):
            return "n/a" if v is None else f"{v * 1e3:.1f}ms"
        tt, lat = fr.ttft_s, fr.latency_s
        print(f"handoff: {dg['handoffs']} session(s), "
              f"{dg['handoff_bytes']} byte(s) "
              f"({dg['handoff_bytes_per_session']:.0f}/session), "
              f"corrupt={dg['handoff_corrupt']} "
              f"resumed_from_park={dg['resumed_from_park']}; "
              f"ttft p50={_ms(tt['p50'])} p95={_ms(tt['p95'])} "
              f"p99={_ms(tt['p99'])}; latency p99={_ms(lat['p99'])}")
        if not ok:
            if tier_bad:
                why = (f"per-tier compile pins violated: {tier_bad} "
                       f"(each worker must hold exactly one program, "
                       f"its own tier's)")
            elif not compiles_ok:
                why = (f"fleet compile total {total_compiles} != "
                       f"expected {args.expect_compiles}")
            elif not redisp_ok:
                why = (f"redispatched {fr.redispatched_total} < "
                       f"expected {args.expect_redispatch}")
            else:
                why = ("unfinished/aborted/shed/timed-out requests "
                       "in the disaggregated result")
            print(f"FAIL: {why}", file=sys.stderr)
    return 0 if ok else 1


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="ds_tpu_serve",
        description="run the jitted serving engine over a request "
                    "stream (continuous batching, bucketed KV cache)")
    parser.add_argument("--config", default=None,
                        help="DeepSpeed-style JSON config; its "
                             "'inference' block configures the engine")
    parser.add_argument("--scan-layers", action="store_true",
                        help="serve the scan_layers model variant")
    parser.add_argument("--kv-cache-dtype", default=None,
                        help="override cache storage: bf16, f32, or a "
                             "codec name (int8, f8e4m3fn, f8e5m2)")
    parser.add_argument("--max-batch", type=int, default=None,
                        help="override inference.max_batch")
    parser.add_argument("--seq-buckets", default=None,
                        help="override inference.seq_buckets, e.g. 16,32")
    parser.add_argument("--prefill-chunk", type=int, default=None,
                        help="override inference.prefill_chunk")
    parser.add_argument("--attention", default=None,
                        choices=("dense", "flash"),
                        help="decode attention impl: dense softmax or "
                             "the Pallas flash-decode kernel")
    parser.add_argument("--block-k", type=int, default=None,
                        help="flash-decode KV block size (must divide "
                             "max(seq_buckets))")
    parser.add_argument("--page-size", type=int, default=None,
                        help="tokens per KV page (0 = "
                             "auto; must be a multiple of "
                             "prefill_chunk and divide max seq bucket)")
    parser.add_argument("--n-pages", type=int, default=None,
                        help="physical pool pages "
                             "(0 = auto; page 0 is the trash page)")
    parser.add_argument("--prefix-cache", dest="prefix_cache",
                        action="store_true", default=None,
                        help="intern finished prompts in "
                             "the radix prefix cache (default on)")
    parser.add_argument("--no-prefix-cache", dest="prefix_cache",
                        action="store_false",
                        help="disable prefix sharing")
    parser.add_argument("--park-threshold", type=float, default=None,
                        help="evacuate parked sessions "
                             "to host RAM when the free-page fraction "
                             "drops below this (0 disables)")
    parser.add_argument("--shared-prefix", type=int, default=0,
                        help="synthetic stream: open every prompt with "
                             "the same N tokens (a shared system "
                             "prompt) to exercise the prefix cache")
    parser.add_argument("--expect-prefix-hits", type=int, default=None,
                        help="exit 1 unless the prefix cache "
                             "recorded at least this many hits")
    parser.add_argument("--temperature", type=float, default=None,
                        help="sampling temperature (0 = greedy argmax, "
                             "the default)")
    parser.add_argument("--top-k", type=int, default=None,
                        help="keep only the k most likely tokens "
                             "(0 = disabled)")
    parser.add_argument("--top-p", type=float, default=None,
                        help="nucleus sampling mass (1.0 = disabled)")
    # -- speculative decoding (ISSUE 18) --------------------------------
    parser.add_argument("--speculative", action="store_true",
                        help="self-speculative decoding: draft k "
                             "tokens through the first draft_layers "
                             "blocks, verify all of them in one "
                             "full-depth forward")
    parser.add_argument("--spec-k", type=int, default=4,
                        help="draft window: tokens drafted per verify "
                             "round (>= 1)")
    parser.add_argument("--draft-layers", type=int, default=0,
                        help="transformer blocks the draft pass runs "
                             "(0 = auto n_layer // 2)")
    parser.add_argument("--min-accept-to-grow", type=float, default=0.0,
                        help="adaptive draft length: grow the window "
                             "when mean accepted drafts/round clears "
                             "this, shrink when it doesn't (0 = fixed "
                             "window)")
    parser.add_argument("--block-scale", type=float, default=None,
                        help="damp every block's c_proj kernels by "
                             "this factor; emulates trained residual "
                             "convergence so seeded-random weights "
                             "give measurable draft acceptance")
    parser.add_argument("--expect-min-accepted", type=float,
                        default=None,
                        help="exit 1 unless mean accepted tokens per "
                             "speculative round clears this")
    # -- checkpoint serving (ISSUE 18) ----------------------------------
    parser.add_argument("--checkpoint", default=None,
                        help="serve params from this training "
                             "checkpoint dir (runtime/resilience "
                             "manifest layout) instead of seeded "
                             "random weights")
    parser.add_argument("--ckpt-tag", default=None,
                        help="checkpoint tag to load (default: the "
                             "newest valid one)")
    parser.add_argument("--model", default="gpt2-tiny", choices=MODELS,
                        help="the seeded model to serve, in its preset's "
                             "dtype (or the preset a hybrid checkpoint "
                             "holds, served in the dtype it was saved "
                             "in)")
    parser.add_argument("--n-head", type=int, default=4,
                        help="attention heads for --checkpoint serving "
                             "(not recoverable from param shapes)")
    parser.add_argument("--requests", default=None,
                        help="JSONL request stream (one request/line)")
    parser.add_argument("--synthetic", type=int, default=0,
                        help="generate N synthetic open-loop requests "
                             "instead of --requests")
    parser.add_argument("--synthetic-max-prompt", type=int, default=24,
                        help="synthetic prompt length upper bound")
    parser.add_argument("--arrival-every", type=float, default=1.0,
                        help="synthetic arrival spacing in decode steps")
    parser.add_argument("--max-new", type=int, default=8,
                        help="default max_new_tokens per request")
    parser.add_argument("--seed", type=int, default=0,
                        help="params + synthetic stream seed")
    parser.add_argument("--expect-compiles", type=int, default=None,
                        help="exit 1 unless total jit cache entries "
                             "(prefill + decode) equal exactly this")
    parser.add_argument("--jsonl", default=None,
                        help="write decode_step telemetry events here "
                             "(ds_tpu_metrics summary serve mode)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="print the result dict as JSON")
    # -- fleet mode (ISSUE 17) ------------------------------------------
    parser.add_argument("--replicas", type=int, default=1,
                        help="serve through an N-replica fleet behind "
                             "the admission router (N >= 2)")
    parser.add_argument("--replica-backend", default="process",
                        choices=("process", "thread"),
                        help="fleet replicas: real subprocess workers "
                             "(SIGKILL-able) or in-process threads")
    parser.add_argument("--workdir", default=None,
                        help="fleet workdir (heartbeats, done markers, "
                             "replica logs); default: a temp dir")
    parser.add_argument("--deadline-s", type=float, default=None,
                        help="per-request total wall-clock deadline "
                             "(typed 'timeout' finish reason)")
    parser.add_argument("--queue-timeout-s", type=float, default=None,
                        help="per-request bound on queue wait before "
                             "admission (typed 'timeout')")
    parser.add_argument("--max-redispatch", type=int, default=None,
                        help="redispatches before a request aborts "
                             "(typed RequestAbortedError path)")
    parser.add_argument("--max-queue-depth", type=int, default=None,
                        help="per-replica in-flight bound (router "
                             "defers past it, emitting fleet_defer)")
    parser.add_argument("--max-pending", type=int, default=None,
                        help="global admission bound (router sheds "
                             "past it, emitting fleet_shed)")
    parser.add_argument("--fleet-timeout", type=float, default=300.0,
                        help="whole-fleet drive-loop wall bound")
    parser.add_argument("--hang-timeout-s", type=float, default=None,
                        help="replica heartbeat stuck-in-step bound")
    parser.add_argument("--heartbeat-stale-s", type=float, default=None,
                        help="replica heartbeat staleness bound")
    parser.add_argument("--kill-replica", type=int, default=None,
                        help="arm a SIGKILL fault in this replica index")
    parser.add_argument("--kill-at-step", type=int, default=3,
                        help="decode step the armed kill fires at")
    parser.add_argument("--expect-redispatch", type=int, default=None,
                        help="exit 1 unless the fleet redispatched at "
                             "least this many requests")
    # -- disaggregated prefill/decode tiers (ISSUE 20) -------------------
    parser.add_argument("--disaggregate", action="store_true",
                        help="split serving into a prefill tier and a "
                             "decode tier (one compiled program each; "
                             "KV pages hand off through the paged "
                             "store between tiers)")
    parser.add_argument("--prefill-workers", type=int, default=None,
                        help="disaggregated: prefill-tier worker count "
                             "(default from config, else 1)")
    parser.add_argument("--decode-workers", type=int, default=None,
                        help="disaggregated: decode-tier worker count "
                             "(default from config, else 1)")
    parser.add_argument("--prefill-max-batch", type=int, default=None,
                        help="disaggregated: prefill-tier max_batch "
                             "override (0/unset = shared max_batch)")
    parser.add_argument("--decode-max-batch", type=int, default=None,
                        help="disaggregated: decode-tier max_batch "
                             "override (0/unset = shared max_batch)")
    parser.add_argument("--kill-prefill-worker", type=int, default=None,
                        help="arm a SIGKILL mid-prefill-chunk in this "
                             "prefill-tier worker index (process "
                             "backend)")
    args = parser.parse_args(argv)

    if not args.requests and not args.synthetic:
        parser.error("one of --requests or --synthetic N is required")
    if args.requests and args.synthetic:
        parser.error("--requests and --synthetic are mutually exclusive")
    if args.replicas < 1:
        parser.error("--replicas must be >= 1")
    if args.replicas == 1 and args.kill_replica is not None:
        parser.error("--kill-replica requires --replicas >= 2 (use "
                     "--kill-prefill-worker with --disaggregate)")
    if args.replicas == 1 and args.expect_redispatch is not None \
            and not args.disaggregate:
        parser.error("--expect-redispatch requires --replicas >= 2 "
                     "or --disaggregate")
    if args.disaggregate:
        if args.speculative:
            parser.error("--disaggregate excludes --speculative (the "
                         "draft/verify pair would break the one-"
                         "program-per-tier contract)")
        if args.replicas > 1:
            parser.error("--disaggregate and --replicas are mutually "
                         "exclusive; tiers scale via "
                         "--prefill-workers/--decode-workers")
        if args.checkpoint:
            parser.error("--disaggregate serves the seeded test model "
                         "only (no --checkpoint)")
    if args.kill_prefill_worker is not None:
        if not args.disaggregate:
            parser.error("--kill-prefill-worker requires --disaggregate")
        if args.replica_backend != "process":
            parser.error("--kill-prefill-worker needs --replica-backend "
                         "process (a thread cannot be SIGKILLed in "
                         "isolation)")
    for name in ("prefill_workers", "decode_workers"):
        v = getattr(args, name)
        if v is not None and v < 1:
            parser.error(f"--{name.replace('_', '-')} must be >= 1")
    if args.kill_replica is not None and \
            not 0 <= args.kill_replica < args.replicas:
        parser.error(f"--kill-replica {args.kill_replica} outside "
                     f"0..{args.replicas - 1}")
    if args.kill_replica is not None and \
            args.replica_backend != "process":
        parser.error("--kill-replica needs --replica-backend process "
                     "(a thread cannot be SIGKILLed in isolation)")
    if args.speculative and args.replicas > 1:
        parser.error("--speculative is single-replica only (the fleet "
                     "router has no variable-tokens-per-step protocol "
                     "yet)")
    if args.expect_min_accepted is not None and not args.speculative:
        parser.error("--expect-min-accepted requires --speculative")
    if args.checkpoint and args.replicas > 1:
        parser.error("--checkpoint serving is single-replica only")
    if args.spec_k < 1:
        parser.error("--spec-k must be >= 1")
    if args.draft_layers < 0:
        parser.error("--draft-layers must be >= 0 (0 = auto)")
    if args.n_head < 1:
        parser.error("--n-head must be >= 1")

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.telemetry.session import TelemetrySession

    inf_cfg = {"max_batch": 2, "seq_buckets": (16, 32),
               "prefill_chunk": 4}
    if args.config:
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        with open(args.config) as f:
            raw = json.load(f)
        # a serving config needn't carry training batch sizes; give the
        # validator trivial ones (world_size pinned to 1 — serving does
        # no data parallelism) so only the inference block matters
        raw.setdefault("train_batch_size", 1)
        raw.setdefault("train_micro_batch_size_per_gpu", 1)
        ds = DeepSpeedConfig(raw, world_size=1)
        inf = ds.inference
        inf_cfg = {"max_batch": inf.max_batch,
                   "seq_buckets": inf.seq_buckets,
                   "prefill_chunk": inf.prefill_chunk,
                   "kv_cache_dtype": inf.kv_cache_dtype,
                   "max_new_tokens": inf.max_new_tokens,
                   "attention_impl": inf.attention_impl,
                   "attention_block_k": inf.attention_block_k,
                   "temperature": inf.temperature,
                   "top_k": inf.top_k,
                   "top_p": inf.top_p,
                   "sampling_seed": inf.sampling_seed,
                   "page_size": inf.page_size,
                   "n_pages": inf.n_pages,
                   "prefix_cache": inf.prefix_cache,
                   "host_park_threshold": inf.host_park_threshold,
                   "replicas": inf.replicas,
                   "max_redispatch": inf.max_redispatch,
                   "max_queue_depth": inf.max_queue_depth,
                   "deadline_s": inf.deadline_s,
                   "queue_timeout_s": inf.queue_timeout_s,
                   "speculative": inf.speculative,
                   "disaggregated": inf.disaggregated,
                   "prefill_workers": inf.prefill_workers,
                   "decode_workers": inf.decode_workers,
                   "prefill_max_batch": inf.prefill_max_batch,
                   "decode_max_batch": inf.decode_max_batch}
    if args.max_batch is not None:
        inf_cfg["max_batch"] = args.max_batch
    if args.seq_buckets is not None:
        inf_cfg["seq_buckets"] = tuple(
            int(b) for b in args.seq_buckets.split(",") if b.strip())
    if args.prefill_chunk is not None:
        inf_cfg["prefill_chunk"] = args.prefill_chunk
    if args.kv_cache_dtype is not None:
        inf_cfg["kv_cache_dtype"] = args.kv_cache_dtype
    if args.attention is not None:
        inf_cfg["attention_impl"] = args.attention
    if args.block_k is not None:
        inf_cfg["attention_block_k"] = args.block_k
    if args.temperature is not None:
        inf_cfg["temperature"] = args.temperature
    if args.top_k is not None:
        inf_cfg["top_k"] = args.top_k
    if args.top_p is not None:
        inf_cfg["top_p"] = args.top_p
    if args.page_size is not None:
        inf_cfg["page_size"] = args.page_size
    if args.n_pages is not None:
        inf_cfg["n_pages"] = args.n_pages
    if args.prefix_cache is not None:
        inf_cfg["prefix_cache"] = args.prefix_cache
    if args.park_threshold is not None:
        inf_cfg["host_park_threshold"] = args.park_threshold
    if args.speculative:
        inf_cfg["speculative"] = {
            "enabled": True, "k": args.spec_k,
            "draft_layers": args.draft_layers,
            "min_accept_to_grow": args.min_accept_to_grow}
    # --seed doubles as the sampling seed: one knob pins params, the
    # synthetic stream, AND the in-program sampler, so a serve is
    # reproducible end to end (a non-default --seed beats the config).
    if args.seed != 0 or "sampling_seed" not in inf_cfg:
        inf_cfg["sampling_seed"] = args.seed

    session = None
    if args.jsonl:
        from deepspeed_tpu.telemetry.exporters import JsonlExporter
        session = TelemetrySession(exporters=[JsonlExporter(args.jsonl)])

    # config-file fleet/deadline knobs apply when the flags stay at
    # their defaults (0 in the config block means disabled)
    args.replicas = max(args.replicas, int(inf_cfg.get("replicas", 1)
                                           or 1))
    if args.deadline_s is None:
        args.deadline_s = inf_cfg.get("deadline_s") or None
    if args.queue_timeout_s is None:
        args.queue_timeout_s = inf_cfg.get("queue_timeout_s") or None
    args.disaggregate = args.disaggregate or bool(
        inf_cfg.get("disaggregated"))
    if args.disaggregate:
        if inf_cfg.get("speculative"):
            parser.error("config enables speculative decoding but the "
                         "serve is disaggregated; the tiers pin one "
                         "program each")
        if args.prefill_workers is None:
            args.prefill_workers = int(
                inf_cfg.get("prefill_workers", 1) or 1)
        if args.decode_workers is None:
            args.decode_workers = int(
                inf_cfg.get("decode_workers", 1) or 1)
        if args.kill_prefill_worker is not None and not \
                0 <= args.kill_prefill_worker < args.prefill_workers:
            parser.error(f"--kill-prefill-worker "
                         f"{args.kill_prefill_worker} outside "
                         f"0..{args.prefill_workers - 1}")
        return _run_disagg(args, inf_cfg, session)
    if args.replicas > 1:
        if inf_cfg.get("speculative"):
            parser.error("config enables speculative decoding but the "
                         "serve is fleet-mode; run single-replica")
        return _run_fleet(args, inf_cfg, session)

    ckpt_info = None
    if args.checkpoint:
        model, params, ckpt_info = _load_checkpoint_model(args, jax, jnp)
        cfg = model.config
    else:
        model, init = MODELS[args.model](args)
        params = init(jax.random.PRNGKey(args.seed))
        cfg = model.config
    if args.block_scale is not None:
        params = _scale_blocks(params, args.block_scale)
    engine = InferenceEngine(model, params, config=inf_cfg,
                             session=session)
    sched = ContinuousBatchingScheduler(engine)

    requests = _build_requests(args, cfg.vocab_size, engine.max_seq)
    completions = sched.run(requests)

    counts = engine.compile_counts()
    total_compiles = sum(n for n in counts.values() if n is not None)
    result = {
        "requests": len(requests),
        "completions": [
            {"rid": c.rid, "prompt_len": c.prompt_len,
             "tokens": c.tokens, "finish_reason": c.finish_reason,
             "bucket": c.bucket, "slot": c.slot, "steps": c.steps,
             "prefix_hit": c.prefix_hit, "resumed": c.resumed,
             "prefill_chunks": c.prefill_chunks,
             "prefill_chunks_skipped": c.prefill_chunks_skipped,
             # from the scheduler's own stamps: arrival -> the step
             # that made the first token returns; arrival -> finish
             "ttft_s": c.ttft_s, "latency_s": c.latency_s}
            for c in completions],
        "decode_steps": sched.step_count,
        "compile_counts": counts,
        "cache": engine.cache_facts(),
        "attention": {"impl": engine.attention_impl,
                      "block_k": engine.attention_block_k},
        "sampling": {"temperature": engine.temperature,
                     "top_k": engine.top_k, "top_p": engine.top_p,
                     "seed": engine.sampling_seed},
    }
    result["paging"] = sched.paging.facts()
    if engine.speculative is not None:
        result["speculative"] = engine.speculative.facts()
    if ckpt_info is not None:
        result["checkpoint"] = ckpt_info
    ok = len(completions) == len(requests)
    if args.expect_compiles is not None:
        result["expect_compiles"] = args.expect_compiles
        ok = ok and total_compiles == args.expect_compiles
    prefix_hits_ok = True
    if args.expect_prefix_hits is not None:
        hits = result["paging"]["prefix_hits"]
        result["expect_prefix_hits"] = args.expect_prefix_hits
        prefix_hits_ok = hits >= args.expect_prefix_hits
        ok = ok and prefix_hits_ok
    accepted_ok = True
    if args.expect_min_accepted is not None:
        mean_acc = result["speculative"]["mean_accepted"]
        result["expect_min_accepted"] = args.expect_min_accepted
        accepted_ok = mean_acc >= args.expect_min_accepted
        ok = ok and accepted_ok
    result["ok"] = ok

    if args.as_json:
        print(json.dumps(result, indent=2, sort_keys=True))
    else:
        for c in completions:
            extra = ""
            if c.prefix_hit or c.resumed:
                kind = "resumed" if c.resumed else "prefix hit"
                extra = (f", {kind}: skipped "
                         f"{c.prefill_chunks_skipped} prefill chunk(s)")
            print(f"{c.rid}: prompt {c.prompt_len} tokens -> "
                  f"{len(c.tokens)} generated ({c.finish_reason}, "
                  f"bucket {c.bucket}, slot {c.slot}{extra})")
        compiles = (f"prefill={counts['prefill']} "
                    f"decode={counts['decode']}")
        if engine.speculative is not None:
            compiles += (f" draft={counts['draft']} "
                         f"verify={counts['verify']}")
        print(f"{len(completions)}/{len(requests)} requests completed "
              f"in {sched.step_count} decode step(s); compiles: "
              f"{compiles}")
        if ckpt_info is not None:
            print(f"checkpoint: tag {ckpt_info['tag']} "
                  f"({ckpt_info['n_layer']}L/{ckpt_info['n_embd']}d, "
                  f"vocab {ckpt_info['vocab_size']}, saved layout "
                  f"{ckpt_info['param_layout']})")
        if engine.speculative is not None:
            sp = result["speculative"]
            print(f"speculative: k={sp['k']} "
                  f"draft_layers={sp['draft_layers']}/{sp['n_layer']}, "
                  f"mean accepted {sp['mean_accepted']:.3f} "
                  f"tokens/round over {sp['row_rounds']} row-round(s), "
                  f"draft efficiency {sp['draft_efficiency']:.3f}")
        pg = result["paging"]
        print(f"paged KV: {pg['pages_resident']}/{pg['n_pages']} "
              f"pages resident, prefix hits {pg['prefix_hits']}/"
              f"misses {pg['prefix_misses']}, host-parked "
              f"{pg['sessions_parked_host']} session(s)")
        if not ok:
            if len(completions) != len(requests):
                why = "unfinished requests"
            elif not prefix_hits_ok:
                why = (f"prefix hits "
                       f"{result['paging']['prefix_hits']} < expected "
                       f"{args.expect_prefix_hits}")
            elif not accepted_ok:
                why = (f"mean accepted "
                       f"{result['speculative']['mean_accepted']:.3f} "
                       f"< expected {args.expect_min_accepted}")
            else:
                why = (f"compile count {total_compiles} != expected "
                       f"{args.expect_compiles}")
            print(f"FAIL: {why}", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

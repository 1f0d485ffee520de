"""Jitted autoregressive serving engine.

The training side of this framework compiles ONE step and reuses it;
serving gets the same discipline: one chunked-prefill program, one
decode program, and a host-side continuous-batching scheduler that
admits/evicts requests between decode steps without ever changing a
compiled shape (the PR 4 recompile detector is the enforcement
mechanism — see :func:`engine.InferenceEngine.compile_counts`).

Pieces:

- :mod:`.cache` — paged KV cache (one page pool a layer, addressed
  through per-row page tables), optionally stored int8/fp8 through the
  shared codec registry (`runtime/comm/codecs.py`).
- :mod:`.engine` — the two compiled programs over the GPT-2 family
  (unrolled and ``scan_layers``), TP-shardable via the model's
  Megatron PartitionSpecs.
- :mod:`.scheduler` — continuous batching: admit/evict/pad loop over an
  open-loop request queue, emitting ``decode_step`` telemetry events.
- :mod:`.router` / :mod:`.fleet` — multi-replica serving: an admission
  router owning the global queue in front of N replicas (subprocess
  workers under the ``ds_tpu_run`` env contract, or in-process threads
  for tests), with heartbeat health checks, dead-replica drain and
  redispatch, deadlines, and backpressure (docs/inference.md).
- :mod:`.serve` — the ``ds_tpu_serve`` CLI (``--replicas N`` for fleet
  mode).
"""

from deepspeed_tpu.inference.cache import (
    KVCacheSpec,
    cache_dtype_census,
    init_kv_cache,
    kv_cache_nbytes,
    spec_for_model,
)
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.fleet import (
    ProcessReplica,
    ThreadReplica,
    build_process_fleet,
)
from deepspeed_tpu.inference.paging import HostPageCorruptError
from deepspeed_tpu.inference.router import (
    FleetResult,
    FleetRouter,
    RequestAbortedError,
)
from deepspeed_tpu.inference.scheduler import (
    Completion,
    ContinuousBatchingScheduler,
    Request,
)

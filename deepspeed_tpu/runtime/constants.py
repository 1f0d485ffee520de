"""Config keys and defaults.

Mirrors the key/default surface of the reference's
``deepspeed/runtime/constants.py`` (306 LoC of CONSTANT/CONSTANT_DEFAULT
pairs) so that an existing DeepSpeed JSON config is accepted unchanged, with
TPU-specific additions (precision policy, mesh shape) at the bottom.
"""

#############################################
# Routes
#############################################
ROUTE_TRAIN = "train"
ROUTE_EVAL = "eval"
ROUTE_PREDICT = "predict"
ROUTE_ENCODE = "encode"

#############################################
# Batch size
#############################################
TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_BATCH_SIZE_DEFAULT = None

TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT = None

GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"
GRADIENT_ACCUMULATION_STEPS_DEFAULT = None

#############################################
# Optimizer and lr scheduler
#############################################
OPTIMIZER = "optimizer"
OPTIMIZER_TYPE_DEFAULT = None
OPTIMIZER_PARAMS = "params"
TYPE = "type"
LEGACY_FUSION = "legacy_fusion"
LEGACY_FUSION_DEFAULT = False
SCHEDULER = "scheduler"
SCHEDULER_TYPE_DEFAULT = None
SCHEDULER_PARAMS = "params"
MAX_GRAD_NORM = "max_grad_norm"

ZERO_ALLOW_UNTESTED_OPTIMIZER = "zero_allow_untested_optimizer"
ZERO_ALLOW_UNTESTED_OPTIMIZER_DEFAULT = False

# Steps
STEPS_PER_PRINT = "steps_per_print"
STEPS_PER_PRINT_DEFAULT = 10

# Sparse gradients (embedding-style CSR reduction)
SPARSE_GRADIENTS = "sparse_gradients"
SPARSE_GRADIENTS_DEFAULT = False

#############################################
# FP16 (on TPU: low-precision policy; bf16 needs no loss scaling)
#############################################
FP16 = "fp16"
FP16_ENABLED = "enabled"
FP16_ENABLED_DEFAULT = False
FP16_LOSS_SCALE = "loss_scale"
FP16_LOSS_SCALE_DEFAULT = 0
FP16_INITIAL_SCALE_POWER = "initial_scale_power"
FP16_INITIAL_SCALE_POWER_DEFAULT = 32
FP16_LOSS_SCALE_WINDOW = "loss_scale_window"
FP16_LOSS_SCALE_WINDOW_DEFAULT = 1000
FP16_HYSTERESIS = "hysteresis"
FP16_HYSTERESIS_DEFAULT = 2
FP16_MIN_LOSS_SCALE = "min_loss_scale"
FP16_MIN_LOSS_SCALE_DEFAULT = 1

#############################################
# BF16 (TPU-native low precision; extension over the reference)
#############################################
BF16 = "bf16"
BF16_ENABLED = "enabled"
BF16_ENABLED_DEFAULT = False

#############################################
# AMP (accepted for config compat; maps onto the bf16 policy on TPU)
#############################################
AMP = "amp"
AMP_ENABLED = "enabled"
AMP_ENABLED_DEFAULT = False

#############################################
# Gradient clipping / allreduce knobs
#############################################
GRADIENT_CLIPPING = "gradient_clipping"
GRADIENT_CLIPPING_DEFAULT = 0.0

FP32_ALLREDUCE = "fp32_allreduce"
FP32_ALLREDUCE_DEFAULT = False

PRESCALE_GRADIENTS = "prescale_gradients"
PRESCALE_GRADIENTS_DEFAULT = False

GRADIENT_PREDIVIDE_FACTOR = "gradient_predivide_factor"
GRADIENT_PREDIVIDE_FACTOR_DEFAULT = 1.0

DISABLE_ALLGATHER = "disable_allgather"
DISABLE_ALLGATHER_DEFAULT = False

DUMP_STATE = "dump_state"
DUMP_STATE_DEFAULT = False

VOCABULARY_SIZE = "vocabulary_size"
VOCABULARY_SIZE_DEFAULT = None

WALL_CLOCK_BREAKDOWN = "wall_clock_breakdown"
WALL_CLOCK_BREAKDOWN_DEFAULT = False

MEMORY_BREAKDOWN = "memory_breakdown"
MEMORY_BREAKDOWN_DEFAULT = False

#############################################
# Tensorboard
#############################################
TENSORBOARD = "tensorboard"
TENSORBOARD_ENABLED = "enabled"
TENSORBOARD_ENABLED_DEFAULT = False
TENSORBOARD_OUTPUT_PATH = "output_path"
TENSORBOARD_OUTPUT_PATH_DEFAULT = ""
TENSORBOARD_JOB_NAME = "job_name"
TENSORBOARD_JOB_NAME_DEFAULT = "DeepSpeedTpuJobName"

#############################################
# Progressive Layer Drop (PLD)
#############################################
PROGRESSIVE_LAYER_DROP = "progressive_layer_drop"
PLD_ENABLED = "enabled"
PLD_ENABLED_DEFAULT = False
PLD_THETA = "theta"
PLD_THETA_DEFAULT = 1.0
PLD_GAMMA = "gamma"
PLD_GAMMA_DEFAULT = 0.001

#############################################
# Sparse attention
#############################################
SPARSE_ATTENTION = "sparse_attention"
SPARSE_DENSE_MODE = "dense"
SPARSE_FIXED_MODE = "fixed"
SPARSE_VARIABLE_MODE = "variable"
SPARSE_BIGBIRD_MODE = "bigbird"
SPARSE_BSLONGFORMER_MODE = "bslongformer"
SPARSE_MODE = "mode"
SPARSE_MODE_DEFAULT = SPARSE_FIXED_MODE
SPARSE_BLOCK = "block"
SPARSE_BLOCK_DEFAULT = 16
SPARSE_DIFFERENT_LAYOUT_PER_HEAD = "different_layout_per_head"
SPARSE_DIFFERENT_LAYOUT_PER_HEAD_DEFAULT = False
SPARSE_NUM_LOCAL_BLOCKS = "num_local_blocks"
SPARSE_NUM_LOCAL_BLOCKS_DEFAULT = 4
SPARSE_NUM_GLOBAL_BLOCKS = "num_global_blocks"
SPARSE_NUM_GLOBAL_BLOCKS_DEFAULT = 1
SPARSE_ATTENTION_TYPE = "attention"
SPARSE_ATTENTION_TYPE_DEFAULT = "bidirectional"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION = "horizontal_global_attention"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION_DEFAULT = False
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS = "num_different_global_patterns"
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS_DEFAULT = 1
SPARSE_NUM_RANDOM_BLOCKS = "num_random_blocks"
SPARSE_NUM_RANDOM_BLOCKS_DEFAULT = 0
SPARSE_LOCAL_WINDOW_BLOCKS = "local_window_blocks"
SPARSE_LOCAL_WINDOW_BLOCKS_DEFAULT = [4]
SPARSE_GLOBAL_BLOCK_INDICES = "global_block_indices"
SPARSE_GLOBAL_BLOCK_INDICES_DEFAULT = [0]
SPARSE_GLOBAL_BLOCK_END_INDICES = "global_block_end_indices"
SPARSE_GLOBAL_BLOCK_END_INDICES_DEFAULT = None
SPARSE_NUM_SLIDING_WINDOW_BLOCKS = "num_sliding_window_blocks"
SPARSE_NUM_SLIDING_WINDOW_BLOCKS_DEFAULT = 3

#############################################
# Pipeline parallelism
#############################################
PIPELINE = "pipeline"
PIPELINE_STAGES = "stages"
PIPELINE_STAGES_DEFAULT = None
PIPELINE_PARTITION = "partition"
PIPELINE_PARTITION_DEFAULT = "best"
PIPELINE_SEED_LAYERS = "seed_layers"
PIPELINE_SEED_LAYERS_DEFAULT = False
PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL = "activation_checkpoint_interval"
PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL_DEFAULT = 0

#############################################
# TPU-specific additions (not in the reference)
#############################################
# Mesh shape: named axes -> sizes, e.g. {"data": 8, "model": 1}.
# Axes: data / model / pipe / seq / expert. Unspecified axes default to 1;
# a data axis of None absorbs the remaining devices.
MESH = "mesh"
MESH_DEFAULT = None

# 1-bit Adam comm compression (reference: runtime/fp16/onebit_adam.py)
ONEBIT_ADAM_FREEZE_STEP = "freeze_step"
ONEBIT_ADAM_FREEZE_STEP_DEFAULT = 100000

# Int8 quantized gradient all-reduce (EQuARX-style; runtime/comm/quantized.py).
# Chunk-wise absmax-scaled int8 reduce-scatter + all-gather for the dense-DP /
# ZeRO-1/2 gradient sync, with optional error-feedback residuals and
# fixed-byte bucketing for backward overlap.
COMM_QUANTIZATION = "comm_quantization"
COMM_QUANTIZATION_ENABLED = "enabled"
COMM_QUANTIZATION_ENABLED_DEFAULT = False
COMM_QUANTIZATION_BITS = "bits"
COMM_QUANTIZATION_BITS_DEFAULT = 8
COMM_QUANTIZATION_CHUNK_SIZE = "chunk_size"
COMM_QUANTIZATION_CHUNK_SIZE_DEFAULT = 512
COMM_QUANTIZATION_BUCKET_MB = "bucket_mb"
COMM_QUANTIZATION_BUCKET_MB_DEFAULT = 4
COMM_QUANTIZATION_ERROR_FEEDBACK = "error_feedback"
COMM_QUANTIZATION_ERROR_FEEDBACK_DEFAULT = False

# Resilience subsystem (runtime/resilience/): preemption-safe checkpointing,
# auto-resume, step health guards, fault injection. See docs/resilience.md.
RESILIENCE = "resilience"
RESILIENCE_AUTO_RESUME = "auto_resume"
RESILIENCE_AUTO_RESUME_DEFAULT = False
RESILIENCE_SAVE_DIR = "save_dir"
RESILIENCE_SAVE_DIR_DEFAULT = None
RESILIENCE_SAVE_INTERVAL_STEPS = "save_interval_steps"
RESILIENCE_SAVE_INTERVAL_STEPS_DEFAULT = 0  # 0 = no periodic saves

RESILIENCE_CHECKPOINT = "checkpoint"
RESILIENCE_CKPT_ASYNC_SAVE = "async_save"
RESILIENCE_CKPT_ASYNC_SAVE_DEFAULT = False
RESILIENCE_CKPT_KEEP_LAST_N = "keep_last_n"
RESILIENCE_CKPT_KEEP_LAST_N_DEFAULT = 0  # 0 = keep everything
RESILIENCE_CKPT_IO_RETRIES = "io_retries"
RESILIENCE_CKPT_IO_RETRIES_DEFAULT = 3
RESILIENCE_CKPT_IO_RETRY_BASE_S = "io_retry_base_s"
RESILIENCE_CKPT_IO_RETRY_BASE_S_DEFAULT = 0.05
RESILIENCE_CKPT_IO_TIMEOUT_S = "io_timeout_s"
RESILIENCE_CKPT_IO_TIMEOUT_S_DEFAULT = None  # None = no deadline

RESILIENCE_GUARDS = "guards"
RESILIENCE_GUARD_ACTION = "action"
RESILIENCE_GUARD_NAN = "nan_grads"
RESILIENCE_GUARD_NAN_ACTION_DEFAULT = None  # disabled
RESILIENCE_GUARD_LOSS_SPIKE = "loss_spike"
RESILIENCE_GUARD_LOSS_SPIKE_ACTION_DEFAULT = None  # disabled
RESILIENCE_GUARD_LOSS_SPIKE_WINDOW = "window"
RESILIENCE_GUARD_LOSS_SPIKE_WINDOW_DEFAULT = 20
RESILIENCE_GUARD_LOSS_SPIKE_FACTOR = "factor"
RESILIENCE_GUARD_LOSS_SPIKE_FACTOR_DEFAULT = 10.0
RESILIENCE_GUARD_LOSS_SPIKE_MIN_HISTORY = "min_history"
RESILIENCE_GUARD_LOSS_SPIKE_MIN_HISTORY_DEFAULT = 5
RESILIENCE_GUARD_SCALE_COLLAPSE = "scale_collapse"
RESILIENCE_GUARD_SCALE_COLLAPSE_ACTION_DEFAULT = None  # disabled
RESILIENCE_GUARD_SCALE_COLLAPSE_PATIENCE = "patience"
RESILIENCE_GUARD_SCALE_COLLAPSE_PATIENCE_DEFAULT = 10

RESILIENCE_PREEMPTION = "preemption"
RESILIENCE_PREEMPTION_SAVE_ON_SIGTERM = "save_on_sigterm"
RESILIENCE_PREEMPTION_SAVE_ON_SIGTERM_DEFAULT = False

RESILIENCE_FAULT_INJECTION = "fault_injection"
RESILIENCE_FAULT_INJECTION_ENABLED = "enabled"
RESILIENCE_FAULT_INJECTION_ENABLED_DEFAULT = False

# In-memory hot-checkpoint tier (runtime/resilience/hotckpt.py):
# frequent CRC-stamped device->host snapshots the restore ladder tries
# before any disk checkpoint. interval_steps = 0 disables the tier.
RESILIENCE_HOT_CHECKPOINT = "hot_checkpoint"
RESILIENCE_HOT_ENABLED = "enabled"
RESILIENCE_HOT_ENABLED_DEFAULT = False
RESILIENCE_HOT_INTERVAL_STEPS = "interval_steps"
RESILIENCE_HOT_INTERVAL_STEPS_DEFAULT = 1
RESILIENCE_HOT_CAPACITY = "capacity"
RESILIENCE_HOT_CAPACITY_DEFAULT = 1
RESILIENCE_HOT_MIRROR_DIR = "mirror_dir"
RESILIENCE_HOT_MIRROR_DIR_DEFAULT = None  # None = RAM-only tier
RESILIENCE_HOT_MIRROR_KEEP = "mirror_keep"
RESILIENCE_HOT_MIRROR_KEEP_DEFAULT = 1

RESILIENCE_HOST_ADAM_RETRIES = "host_adam_retries"
RESILIENCE_HOST_ADAM_RETRIES_DEFAULT = 2

# Elasticity (runtime/elastic/): topology-agnostic checkpoints,
# reshard-on-resume across data-parallel world sizes, and the elastic
# batch solver that re-derives micro x grad_accum to preserve the
# effective batch. See docs/elasticity.md.
ELASTICITY = "elasticity"
ELASTICITY_ENABLED = "enabled"
ELASTICITY_ENABLED_DEFAULT = False
ELASTICITY_TARGET_GLOBAL_BATCH = "target_global_batch"
ELASTICITY_TARGET_GLOBAL_BATCH_DEFAULT = None  # None = train_batch_size
ELASTICITY_MAX_WORLD_SIZE = "max_world_size"
ELASTICITY_MAX_WORLD_SIZE_DEFAULT = 0  # 0 = unbounded
ELASTICITY_STRICT = "strict"
ELASTICITY_STRICT_DEFAULT = False
ELASTICITY_LR_SCALING = "lr_scaling"
ELASTICITY_LR_SCALING_DEFAULT = "linear"  # linear | sqrt | none

# Compiled-program analysis (deepspeed_tpu/analysis): opt-in audits of
# the compiled train step's HLO at compile time — donation/aliasing,
# ZeRO byte budgets, dtype hygiene, host transfers, trip-count
# accounting — plus a per-step recompile detector. See docs/analysis.md.
ANALYSIS = "analysis"
ANALYSIS_ENABLED = "enabled"
ANALYSIS_ENABLED_DEFAULT = False
ANALYSIS_FAIL_ON_FINDINGS = "fail_on_findings"
ANALYSIS_FAIL_ON_FINDINGS_DEFAULT = False
ANALYSIS_RULES = "rules"
ANALYSIS_RULES_DEFAULT = None  # None = the full rule catalog
ANALYSIS_CHECK_RECOMPILE = "check_recompile"
ANALYSIS_CHECK_RECOMPILE_DEFAULT = True
# Explicit per-device peak-memory budget (MB) for the `peak_memory`
# rule; 0 derives a generous per-ZeRO-stage default from the model's
# fp32 master footprint (see analysis/rules.py:rule_peak_memory).
ANALYSIS_PEAK_MEMORY_BUDGET_MB = "peak_memory_budget_mb"
ANALYSIS_PEAK_MEMORY_BUDGET_MB_DEFAULT = 0
# Cost-model constants table for the autotuner (`ds_tpu_tune`) and any
# roofline estimate derived from this config; must name a row of
# analysis.cost.PLATFORMS.
ANALYSIS_PLATFORM = "platform"
ANALYSIS_PLATFORM_DEFAULT = "tpu_v5e"

# Manual tensor-parallel tuning (parallel/pipe_tp.py, parallel/sequence.py,
# moe/expert_pipe.py). The `overlap` block enables the latency-hiding
# collective matmul: row-parallel combines / Ulysses all_to_all brackets
# are split into `chunks` pieces whose ppermute rings software-pipeline
# against the adjacent matmuls (parallel/collectives.py). Per-site
# overrides under `sites` keyed by parallel.collectives.OVERLAP_SITES.
# See docs/tensor-parallel.md.
TENSOR_PARALLEL = "tensor_parallel"
TP_OVERLAP = "overlap"
TP_OVERLAP_ENABLED = "enabled"
TP_OVERLAP_ENABLED_DEFAULT = False
TP_OVERLAP_CHUNKS = "chunks"
TP_OVERLAP_CHUNKS_DEFAULT = 4
TP_OVERLAP_BIDIRECTIONAL = "bidirectional"
TP_OVERLAP_BIDIRECTIONAL_DEFAULT = False
TP_OVERLAP_SITES = "sites"
TP_OVERLAP_SITES_DEFAULT = None  # None = no per-site overrides
# Quantized-wire codec for the overlap rings ("int8" / "f8e4m3fn" /
# "f8e5m2"; None = full-precision wire). Chunk payloads + per-chunk f32
# scales ride the same ppermute; chunks=1 routes through the bracketed
# quantize→monolithic-collective reference. See docs/fp8.md.
TP_OVERLAP_WIRE_DTYPE = "wire_dtype"
TP_OVERLAP_WIRE_DTYPE_DEFAULT = None
TP_OVERLAP_WIRE_CHUNK = "wire_chunk"
TP_OVERLAP_WIRE_CHUNK_DEFAULT = 512

# fp8 end-to-end training (ops/fp8.py + the quantized collective wire;
# docs/fp8.md). `enabled` turns the GPT-2 Dense matmuls into delayed-
# scaling fp8 GEMMs (f8e4m3fn forward operands, f8e5m2 backward
# cotangents, amax histories carried as engine state); the `wire` block
# quantizes the ring collectives' payloads through the codec registry
# (runtime/comm/codecs.py) — including ZeRO-3 gathers.
FP8 = "fp8"
FP8_ENABLED = "enabled"
FP8_ENABLED_DEFAULT = False
FP8_MARGIN = "margin"
FP8_MARGIN_DEFAULT = 0
FP8_AMAX_HISTORY_LEN = "amax_history_len"
FP8_AMAX_HISTORY_LEN_DEFAULT = 16
FP8_SITES = "sites"
FP8_SITES_DEFAULT = None         # None = no per-site overrides
FP8_WIRE = "wire"
FP8_WIRE_ENABLED = "enabled"
FP8_WIRE_ENABLED_DEFAULT = False
FP8_WIRE_DTYPE = "dtype"
FP8_WIRE_DTYPE_DEFAULT = "f8e4m3fn"
FP8_WIRE_CHUNK_SIZE = "chunk_size"
FP8_WIRE_CHUNK_SIZE_DEFAULT = 512

# Runtime telemetry (deepspeed_tpu/telemetry): structured metrics
# registry, step-phase spans, and the schema-versioned JSONL event log
# the ds_tpu_metrics CLI reads. Disabled by default — the engine's hot
# path then pays one no-op check per phase. See docs/observability.md.
TELEMETRY = "telemetry"
TELEMETRY_ENABLED = "enabled"
TELEMETRY_ENABLED_DEFAULT = False
TELEMETRY_JSONL_PATH = "jsonl_path"
TELEMETRY_JSONL_PATH_DEFAULT = None  # None = in-memory ring only
TELEMETRY_CONSOLE = "console"
TELEMETRY_CONSOLE_DEFAULT = False
TELEMETRY_PROMETHEUS_TEXTFILE = "prometheus_textfile"
TELEMETRY_PROMETHEUS_TEXTFILE_DEFAULT = None
TELEMETRY_PROMETHEUS_WRITE_EVERY = "prometheus_write_every"
TELEMETRY_PROMETHEUS_WRITE_EVERY_DEFAULT = 20
# Bounded event ring (engine.metrics_history): last N step events kept
# in memory so tests/health guards can assert without file I/O.
TELEMETRY_HISTORY = "history"
TELEMETRY_HISTORY_DEFAULT = 256
# Stamp compile-time static facts (collective bytes/counts, static peak
# memory) into one `compile` event. Free when the analysis block already
# audited the step; otherwise costs one extra lowering at first compile.
TELEMETRY_STAMP_STATIC_FACTS = "stamp_static_facts"
TELEMETRY_STAMP_STATIC_FACTS_DEFAULT = True
# Model flops per token for the MFU estimate (0 = unknown; the
# ds_tpu_metrics CLI can also supply it at read time).
TELEMETRY_FLOPS_PER_TOKEN = "flops_per_token"
TELEMETRY_FLOPS_PER_TOKEN_DEFAULT = 0

# Runtime forensics (telemetry/flight.py, telemetry/watchdog.py):
# setting crash_dump_dir turns on the flight recorder — a bounded
# black-box ring of events / span transitions / collective confessions
# dumped atomically there (plus all-thread stacks) on unhandled
# exception, SIGTERM/SIGQUIT, guard-trip abort, or watchdog firing.
# It also holds per-process heartbeat files and watchdog dumps, so the
# nested watchdog block requires it. See docs/observability.md.
TELEMETRY_CRASH_DUMP_DIR = "crash_dump_dir"
TELEMETRY_CRASH_DUMP_DIR_DEFAULT = None
TELEMETRY_FLIGHT_HISTORY = "flight_history"
TELEMETRY_FLIGHT_HISTORY_DEFAULT = 512
# Hang watchdog: daemon thread fed per-phase heartbeats from the span
# stack; fires when a step's elapsed wall exceeds
# max(min_deadline_s, deadline_factor * rolling-median step wall).
TELEMETRY_WATCHDOG = "watchdog"
TELEMETRY_WATCHDOG_ENABLED = "enabled"
TELEMETRY_WATCHDOG_ENABLED_DEFAULT = False
TELEMETRY_WATCHDOG_DEADLINE_FACTOR = "deadline_factor"
TELEMETRY_WATCHDOG_DEADLINE_FACTOR_DEFAULT = 3.0
TELEMETRY_WATCHDOG_MIN_DEADLINE_S = "min_deadline_s"
TELEMETRY_WATCHDOG_MIN_DEADLINE_S_DEFAULT = 60.0
# "dump" = flight dump once per hung step, run continues if the step
# ever completes; "abort" = dump + thread stacks + SIGABRT so a cluster
# supervisor restarts the process.
TELEMETRY_WATCHDOG_ACTION = "action"
TELEMETRY_WATCHDOG_ACTION_DEFAULT = "dump"
# Anomaly-triggered trace capture: a step-wall regression past factor x
# rolling median (or a recompile / guard trip) arms the profiling
# block's TraceProfiler to capture the next capture_steps steps.
TELEMETRY_ANOMALY_TRACE = "anomaly_trace"
TELEMETRY_ANOMALY_TRACE_ENABLED = "enabled"
TELEMETRY_ANOMALY_TRACE_ENABLED_DEFAULT = False
TELEMETRY_ANOMALY_TRACE_FACTOR = "factor"
TELEMETRY_ANOMALY_TRACE_FACTOR_DEFAULT = 2.0
TELEMETRY_ANOMALY_TRACE_WINDOW = "window"
TELEMETRY_ANOMALY_TRACE_WINDOW_DEFAULT = 32
TELEMETRY_ANOMALY_TRACE_CAPTURE_STEPS = "capture_steps"
TELEMETRY_ANOMALY_TRACE_CAPTURE_STEPS_DEFAULT = 3

#############################################
# Inference / serving (deepspeed_tpu/inference/)
#############################################
# The jitted autoregressive serving engine: one chunked-prefill
# program + one decode program over a bucketed ring-buffer KV cache,
# driven by a host-side continuous-batching scheduler. See
# docs/inference.md.
INFERENCE = "inference"

# Rows in the KV cache = the compiled decode batch. Every decode step
# runs all rows; inactive rows are padding.
INFERENCE_MAX_BATCH = "max_batch"
INFERENCE_MAX_BATCH_DEFAULT = 8

# Per-request sequence-length budgets (host-side admission control,
# NOT compiled shapes): a request is assigned the smallest bucket that
# fits prompt + max_new_tokens and is evicted at the bucket edge. The
# cache buffer is sized to max(seq_buckets). Every bucket must be a
# multiple of prefill_chunk.
INFERENCE_SEQ_BUCKETS = "seq_buckets"
INFERENCE_SEQ_BUCKETS_DEFAULT = (128, 512)

# Prompts prefill in fixed [1, prefill_chunk] chunks so prompt length
# never reaches a jit boundary.
INFERENCE_PREFILL_CHUNK = "prefill_chunk"
INFERENCE_PREFILL_CHUNK_DEFAULT = 32

# KV cache storage: None = model compute dtype; "bf16"/"f32" = plain
# storage; a codec name from runtime/comm/codecs.py ("int8",
# "f8e4m3fn", "f8e5m2") = quantized storage with per-(row, position,
# head) f32 absmax scales.
INFERENCE_KV_CACHE_DTYPE = "kv_cache_dtype"
INFERENCE_KV_CACHE_DTYPE_DEFAULT = None

# Default generation budget for requests that don't specify one.
INFERENCE_MAX_NEW_TOKENS = "max_new_tokens"
INFERENCE_MAX_NEW_TOKENS_DEFAULT = 64

# Decode attention implementation: "dense" = full-cache softmax (the
# parity oracle), "flash" = the Pallas split-K flash-decode kernel
# (ops/pallas/flash_decode.py) with active-length block skipping and
# in-kernel KV dequantization. Prefill always runs dense.
INFERENCE_ATTENTION_IMPL = "attention_impl"
INFERENCE_ATTENTION_IMPL_DEFAULT = "dense"

# Flash-decode KV block size: the kernel streams the cache row in
# [block_k, head_dim] blocks. Clamped to max(seq_buckets), which it
# must divide.
INFERENCE_ATTENTION_BLOCK_K = "attention_block_k"
INFERENCE_ATTENTION_BLOCK_K_DEFAULT = 128

# In-program sampling knobs (static: they select the traced decode
# graph). temperature 0.0 = greedy argmax (consumes no randomness);
# top_k 0 and top_p 1.0 disable those filters.
INFERENCE_TEMPERATURE = "temperature"
INFERENCE_TEMPERATURE_DEFAULT = 0.0
INFERENCE_TOP_K = "top_k"
INFERENCE_TOP_K_DEFAULT = 0
INFERENCE_TOP_P = "top_p"
INFERENCE_TOP_P_DEFAULT = 1.0
INFERENCE_SAMPLING_SEED = "sampling_seed"
INFERENCE_SAMPLING_SEED_DEFAULT = 0

# The KV cache is one [n_pages, page_size] pool per layer addressed
# through per-row page tables (host-side allocator + radix prefix
# cache + host-RAM tier for parked sessions, inference/paging.py).
# The key chose between it and a ring layout until PR 28; it is still
# accepted, "paged" only.
INFERENCE_KV_LAYOUT = "kv_layout"

# Tokens per page. 0 = auto (two prefill chunks).
# Must be a multiple of prefill_chunk (or divide it) and divide
# max(seq_buckets);
# flash block_k clamps to it.
INFERENCE_PAGE_SIZE = "page_size"
INFERENCE_PAGE_SIZE_DEFAULT = 0

# Physical pages in the pool. 0 = auto: every row at full length
# (max_batch * max_seq / page_size) + the reserved trash page.
# Smaller pools trade admission headroom for HBM — the bench A/B and
# the tuner explore this.
INFERENCE_N_PAGES = "n_pages"
INFERENCE_N_PAGES_DEFAULT = 0

# Radix-tree prefix cache: admissions whose prompt
# prefix matches interned pages map them copy-on-write and skip the
# shared span's prefill chunks.
INFERENCE_PREFIX_CACHE = "prefix_cache"
# None: on for a model whose cache is pages only, off for one with a
# recurrent state beside them (an explicit true refuses such a model)
INFERENCE_PREFIX_CACHE_DEFAULT = None

# Host-RAM tier pressure threshold: while free pages /
# n_pages sits below this fraction, parked sessions' pages are
# evacuated to host RAM (LRU first). 0.0 disables proactive
# evacuation (pressure-driven eviction still runs on exhaustion).
INFERENCE_HOST_PARK_THRESHOLD = "host_park_threshold"
INFERENCE_HOST_PARK_THRESHOLD_DEFAULT = 0.25

# Serving fleet (ISSUE 17): N replica workers behind one admission
# router with drain/redispatch on replica death. replicas=1 keeps the
# single-engine path.
INFERENCE_REPLICAS = "replicas"
INFERENCE_REPLICAS_DEFAULT = 1

# Redispatches a request survives before the router aborts it with the
# typed RequestAbortedError / "aborted" finish reason.
INFERENCE_MAX_REDISPATCH = "max_redispatch"
INFERENCE_MAX_REDISPATCH_DEFAULT = 2

# Per-replica in-flight bound: the router defers dispatch (fleet_defer)
# while every healthy replica is at it.
INFERENCE_MAX_QUEUE_DEPTH = "max_queue_depth"
INFERENCE_MAX_QUEUE_DEPTH_DEFAULT = 8

# Per-request wall-clock bounds (seconds; 0 disables): total budget
# from submit to completion, and queue wait before admission. Either
# expiry finishes the request with the typed "timeout" reason.
INFERENCE_DEADLINE_S = "deadline_s"
INFERENCE_DEADLINE_S_DEFAULT = 0.0
INFERENCE_QUEUE_TIMEOUT_S = "queue_timeout_s"
INFERENCE_QUEUE_TIMEOUT_S_DEFAULT = 0.0

# Disaggregated prefill/decode serving (inference.disaggregated): the
# admission router splits the fleet into a PREFILL tier (workers that
# only run the prefill program, writing paged KV) and a DECODE tier
# (workers that only run the decode step), moving finished prompts
# between them through an explicit KV-page handoff. Each tier pins
# exactly one compiled program; tiers scale independently
# (prefill_workers x decode_workers, each with its own max_batch —
# 0 falls back to the shared max_batch).
INFERENCE_DISAGGREGATED = "disaggregated"
INFERENCE_DISAGGREGATED_DEFAULT = False
INFERENCE_PREFILL_WORKERS = "prefill_workers"
INFERENCE_PREFILL_WORKERS_DEFAULT = 1
INFERENCE_DECODE_WORKERS = "decode_workers"
INFERENCE_DECODE_WORKERS_DEFAULT = 1
INFERENCE_PREFILL_MAX_BATCH = "prefill_max_batch"
INFERENCE_PREFILL_MAX_BATCH_DEFAULT = 0
INFERENCE_DECODE_MAX_BATCH = "decode_max_batch"
INFERENCE_DECODE_MAX_BATCH_DEFAULT = 0

# Speculative decoding (inference.speculative sub-block): a
# self-speculative draft of `k` tokens through the first `draft_layers`
# blocks of the SAME model (truncated scan — no second weight set),
# verified in one full-depth teacher-forced program. The serving
# compile contract becomes 3 pinned programs (prefill, draft, verify).
# draft_layers=0 auto-selects n_layer // 2; min_accept_to_grow > 0
# turns on the adaptive draft-length controller (grow toward k while
# mean acceptance clears the threshold, shrink otherwise).
INFERENCE_SPECULATIVE = "speculative"
INFERENCE_SPECULATIVE_ENABLED = "enabled"
INFERENCE_SPECULATIVE_ENABLED_DEFAULT = False
INFERENCE_SPECULATIVE_K = "k"
INFERENCE_SPECULATIVE_K_DEFAULT = 4
INFERENCE_SPECULATIVE_DRAFT_LAYERS = "draft_layers"
INFERENCE_SPECULATIVE_DRAFT_LAYERS_DEFAULT = 0
INFERENCE_SPECULATIVE_MIN_ACCEPT_TO_GROW = "min_accept_to_grow"
INFERENCE_SPECULATIVE_MIN_ACCEPT_TO_GROW_DEFAULT = 0.0

"""PipelineEngine: train a PipelineModule over the ``pipe`` mesh axis.

Analog of the reference's ``PipelineEngine`` (`runtime/pipe/engine.py:152` —
``train_batch``:229, ``eval_batch``:305, ``_exec_schedule``:1144). The
reference interprets instruction lists per rank; here the whole train
batch compiles into one XLA program (see `runtime/pipe/pipeline.py`).
Training executes the hand-scheduled **1F1B** interleave
(``make_pipeline_value_and_grad_fn``: forward and backward ticks in one
``lax.scan``, O(num_stages) activation memory independent of the
microbatch count — the buffer bound of reference `schedule.py:243-247`,
proven by ``test_pipe.py::test_1f1b_memory_independent_of_microbatches``);
eval runs the forward-only GPipe wavefront. The instruction schedules in
`runtime/pipe/schedule.py` remain the introspectable specification of the
executed order.

Everything else — optimizer, ZeRO shardings of the per-stage params, mixed
precision, dynamic loss scale, checkpointing — is inherited from
:class:`DeepSpeedEngine`; the pipeline is "just" a loss function whose
internals shard compute over ``pipe``.
"""

import dataclasses

import jax
import jax.numpy as jnp

from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.pipe.module import PipelineModule
from deepspeed_tpu.runtime.pipe.pipeline import (
    build_pipeline_parts,
    make_pipeline_loss_fn,
    make_pipeline_value_and_grad_fn,
)
from deepspeed_tpu.runtime.pipe.schedule import TrainSchedule, InferenceSchedule
from deepspeed_tpu.utils.logging import log_dist


class PipelineEngine(DeepSpeedEngine):
    """Training engine for :class:`PipelineModule` models."""

    def __init__(self,
                 args=None,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 dist_init_required=None,
                 collate_fn=None,
                 config=None,
                 config_params=None,
                 mesh=None,
                 seed=0):
        assert isinstance(model, PipelineModule), (
            "PipelineEngine requires a PipelineModule")

        if config is None and config_params is not None:
            config = config_params
        if config is None and args is not None and \
                getattr(args, "deepspeed_config", None):
            config = args.deepspeed_config
        assert config is not None, "config (dict or json path) required"

        # Join the multi-host cluster BEFORE the first backend-touching
        # call (build_mesh below) — same contract as the base engine.
        from deepspeed_tpu.parallel.mesh import initialize_distributed
        initialize_distributed()

        mesh_cfg = config.get("mesh") if isinstance(config, dict) else None
        mesh = mesh if mesh is not None else build_mesh(mesh_cfg)
        num_stages = mesh.shape["pipe"]
        if model.num_stages is not None and model.num_stages != num_stages:
            raise ValueError(
                f"PipelineModule(num_stages={model.num_stages}) does not "
                f"match the mesh pipe axis ({num_stages})")
        if num_stages < 2:
            log_dist("pipe axis is 1: pipeline degenerates to sequential "
                     "execution (DataParallelSchedule)", ranks=[0])

        # micro-batches per train batch = gradient accumulation steps
        # (reference pipe/engine.py:229: micro_batches == grad accum).
        probe = DeepSpeedConfig(config, world_size=mesh.shape["data"])
        if probe.pld_enabled:
            raise ValueError(
                "progressive_layer_drop is not supported with "
                "PipelineModule: the hand-scheduled 1F1B program takes no "
                "pld_theta (stage bodies are homogeneous scans)")
        self.micro_batches = probe.gradient_accumulation_steps
        self.num_stages = num_stages

        example = model.example_input
        assert example is not None, (
            "PipelineModule(example_input=...) is required for parameter "
            "shape inference (a microbatch-shaped pytree; row count free)")

        if model.partition_method not in ("uniform", "parameters"):
            log_dist(
                f"partition_method={model.partition_method!r}: the compiled "
                f"pipeline stacks the homogeneous body uniformly (for equal "
                f"layers this equals the parameter-balanced split); the "
                f"requested policy is recorded but not load-bearing",
                ranks=[0])

        self.pipeline_parts = build_pipeline_parts(
            model, num_stages, jax.random.PRNGKey(seed), example)
        if model_parameters is not None:
            # Pretrained weights: must match the built structure
            # (prologue/body/epilogue/tied with the stacked body layout).
            expected = jax.tree_util.tree_structure(self.pipeline_parts.params)
            got = jax.tree_util.tree_structure(model_parameters)
            assert got == expected, (
                f"model_parameters do not match the built pipeline param "
                f"structure:\n  expected {expected}\n  got      {got}")
            self.pipeline_parts.params = model_parameters
        # reference semantics: interval 0 disables rematerialization
        auto_axes = tuple(getattr(model, "auto_axes", ()) or ())
        if auto_axes:
            # The vag-level capability works and is parity-tested
            # (test_pipe_auto.py), but composing it with the engine's
            # compiled train step deadlocks XLA's in-process CPU
            # collective rendezvous when body params are PLACED sharded
            # over the auto axis (devices split 4/4 across the fwd/bwd
            # ppermute rendezvous; repro in the test file's docstring).
            # Real-TPU behavior is untested (different collective
            # runtime) — gate rather than abort the process.
            raise NotImplementedError(
                f"PipelineModule(auto_axes={auto_axes!r}) through the "
                "engine is experimental and currently disabled: the "
                "in-process CPU runtime deadlocks on the pipeline's "
                "ppermutes when params are placed sharded over an auto "
                "axis. Use make_pipeline_value_and_grad_fn(...) directly "
                "(works, see tests/unit/test_pipe_auto.py) or the "
                "manual-collective TP blocks (parallel/pipe_tp.py)")
        # tensor_parallel.overlap: the latency-hiding collective-matmul
        # plan for manual-mode TP/SP/MoE layers, threaded to the trace-
        # time overlap_scope inside the pipeline's shard_map.
        overlap = probe.tensor_parallel.overlap_plan()
        # fp8: route the TP blocks' local matmuls through current-scaling
        # qdq (per-site amax threading isn't available through the
        # hand-written 1F1B backward), and — when fp8.wire is on — carry
        # the ring exchanges quantized by composing the wire codec into
        # the overlap plan the TP blocks already consume.
        fp8_plan = probe.fp8.plan()
        if probe.fp8.wire_enabled and overlap is not None:
            overlap = dataclasses.replace(
                overlap, wire_dtype=probe.fp8.active_wire_dtype(),
                wire_chunk=int(probe.fp8.wire_chunk_size))
        loss_fn = make_pipeline_loss_fn(
            self.pipeline_parts, mesh, self.micro_batches,
            remat=model.activation_checkpoint_interval > 0,
            auto_axes=auto_axes, overlap=overlap, fp8=fp8_plan)
        # Training runs the hand-scheduled 1F1B (loss, grads) program —
        # O(num_stages) activation memory independent of micro_batches;
        # the GPipe loss above remains the eval/forward-only path.
        compute_dtype = jnp.bfloat16 if probe.bf16_enabled else (
            jnp.float16 if probe.fp16_enabled else None)
        loss_fn.direct_value_and_grad = make_pipeline_value_and_grad_fn(
            self.pipeline_parts, mesh, self.micro_batches,
            compute_dtype=compute_dtype, auto_axes=auto_axes,
            overlap=overlap, fp8=fp8_plan)
        # 1-bit Adam composition: same 1F1B program, but gradients come
        # back data-LOCAL (stacked data axis) for the compressed
        # collective to average (engine._pipeline_onebit_step).
        loss_fn.direct_value_and_grad_local = make_pipeline_value_and_grad_fn(
            self.pipeline_parts, mesh, self.micro_batches,
            compute_dtype=compute_dtype, data_local=True,
            auto_axes=auto_axes, overlap=overlap, fp8=fp8_plan)

        super().__init__(args=args,
                         model=model,
                         optimizer=optimizer,
                         lr_scheduler=lr_scheduler,
                         mpu=mpu,
                         dist_init_required=dist_init_required,
                         training_data=training_data,
                         collate_fn=collate_fn,
                         config=config,
                         config_params=None,
                         loss_fn=loss_fn,
                         params=self.pipeline_parts.params,
                         param_specs=self.pipeline_parts.param_specs,
                         mesh=mesh,
                         seed=seed)
        tied_keys = list(self.pipeline_parts.params["tied"])
        # The engine copied+placed the params; drop the stale init copy.
        self.pipeline_parts.params = None

        log_dist(
            f"PipelineEngine: stages={num_stages}, "
            f"micro_batches={self.micro_batches}, "
            f"layers_per_stage={self.pipeline_parts.layers_per_stage}, "
            f"tied={tied_keys}", ranks=[0])

    # The pipeline consumes the whole train batch in one program; the
    # engine-level accumulation scan collapses to a single iteration.
    def _engine_accum_steps(self):
        return 1

    def _forensics_extra(self):
        """Pipeline topology on run_start events and flight-dump meta —
        a postmortem of a hung 1F1B ring needs stages/micro-batches to
        read the stage-transfer confessions."""
        return {"num_stages": self.num_stages,
                "micro_batches": self.micro_batches}

    # --- reference-parity introspection -------------------------------
    def train_schedule(self, stage_id=0):
        """The 1F1B instruction stream the compiled program implements."""
        return TrainSchedule(micro_batches=self.micro_batches,
                             stages=self.num_stages,
                             stage_id=stage_id)

    def inference_schedule(self, stage_id=0):
        return InferenceSchedule(micro_batches=self.micro_batches,
                                 stages=self.num_stages,
                                 stage_id=stage_id)

    def is_gradient_accumulation_boundary(self):
        """The compiled train batch always ends on the boundary."""
        return True

    def forward(self, *args, **kwargs):
        raise RuntimeError(
            "PipelineEngine executes whole train batches: use "
            "train_batch(batch) / eval_batch(batch) (reference "
            "pipe/engine.py raises the same)")

    def backward(self, *args, **kwargs):
        raise RuntimeError(
            "PipelineEngine executes whole train batches: use "
            "train_batch(batch) (reference pipe/engine.py raises the same)")

    def step(self, *args, **kwargs):
        raise RuntimeError(
            "PipelineEngine executes whole train batches: use "
            "train_batch(batch) (reference pipe/engine.py raises the same)")

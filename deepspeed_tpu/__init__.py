"""deepspeed_tpu: a TPU-native training framework with the capabilities of
DeepSpeed (v0.3.2) — ZeRO, pipeline parallelism, mixed precision, fused ops —
re-designed for JAX/XLA/Pallas over named device meshes.

Public surface mirrors the reference `deepspeed/__init__.py`:
``initialize()`` (:47), ``add_config_arguments()`` (:190), plus the engine,
pipeline, ops and checkpointing exports.
"""

from deepspeed_tpu.version import version as __version__, git_hash, git_branch
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.lr_schedules import add_tuning_arguments
from deepspeed_tpu.runtime.activation_checkpointing import (
    checkpointing)
from deepspeed_tpu.utils.logging import logger, log_dist
from deepspeed_tpu.telemetry import compile_cache as _compile_cache

# the compile ledger and the collector's record go on the span ring from
# here on: a harness's weights and a reference's jits are caught too
_compile_cache.install()


def _parse_version(version_str):
    parts = version_str.split(".")
    return int(parts[0]), int(parts[1]), parts[2] if len(parts) > 2 else "0"


__version_major__, __version_minor__, __version_patch__ = \
    _parse_version(__version__)


def initialize(args=None,
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
               loss_fn=None,
               params=None,
               param_specs=None,
               mesh=None,
               seed=0):
    """Initialize the engine — analog of ``deepspeed.initialize``
    (`deepspeed/__init__.py:47`).

    Model contract (TPU-native): a pure ``loss_fn(params, batch, rng)`` plus
    an initial ``params`` pytree (or a model object exposing ``.loss_fn`` /
    ``.params``); a :class:`deepspeed_tpu.pipe.PipelineModule` routes to the
    pipeline engine, mirroring the reference's engine dispatch
    (`deepspeed/__init__.py:106-128`).

    Returns the tuple ``(engine, optimizer, training_dataloader,
    lr_scheduler)`` for drop-in familiarity.
    """
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    from deepspeed_tpu.telemetry.spans import Span

    log_dist(f"deepspeed_tpu info: version={__version__}, "
             f"git-hash={git_hash}, git-branch={git_branch}", ranks=[0])

    # the engine's construction on the span ring, kept past any window:
    # ``setup/engine`` with the placing of the parameters and the
    # optimizer state inside it (and every trace and compile they cost)
    with Span("setup/engine"):
        if isinstance(model, PipelineModule):
            from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
            engine = PipelineEngine(args=args,
                                    model=model,
                                    optimizer=optimizer,
                                    model_parameters=model_parameters,
                                    training_data=training_data,
                                    lr_scheduler=lr_scheduler,
                                    mpu=mpu,
                                    dist_init_required=dist_init_required,
                                    collate_fn=collate_fn,
                                    config=config,
                                    config_params=config_params,
                                    mesh=mesh,
                                    seed=seed)
        else:
            engine = DeepSpeedEngine(args=args,
                                     model=model,
                                     optimizer=optimizer,
                                     model_parameters=model_parameters,
                                     training_data=training_data,
                                     lr_scheduler=lr_scheduler,
                                     mpu=mpu,
                                     dist_init_required=dist_init_required,
                                     collate_fn=collate_fn,
                                     config=config,
                                     config_params=config_params,
                                     loss_fn=loss_fn,
                                     params=params,
                                     param_specs=param_specs,
                                     mesh=mesh,
                                     seed=seed)

    return_items = [
        engine,
        getattr(engine, "client_optimizer", None),
        engine.training_dataloader,
        getattr(engine, "lr_scheduler", None),
    ]
    return tuple(return_items)


def add_config_arguments(parser):
    """Add ``--deepspeed``/``--deepspeed_config`` CLI flags
    (reference `deepspeed/__init__.py:139-187`)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed",
                       default=False,
                       action="store_true",
                       help="Enable DeepSpeed (helper flag for user code, no "
                            "impact on engine behavior)")
    group.add_argument("--deepspeed_config",
                       default=None,
                       type=str,
                       help="DeepSpeed json configuration file.")
    group.add_argument("--deepspeed_mpi",
                       default=False,
                       action="store_true",
                       help="Run via MPI; rank/world size discovered from the "
                            "MPI environment.")
    return parser

"""deepspeed_tpu — a TPU-native large-scale training framework.

Capability parity with DeepSpeed v0.3.11 (reference: `/root/reference`),
re-designed for JAX/XLA/Pallas on TPU: SPMD over a named device mesh instead
of per-process NCCL collectives, bf16-first precision, jit-compiled train
steps, Pallas kernels for the fused ops.

Public surface parity with the reference ``deepspeed/__init__.py``:
``initialize()``, ``add_config_arguments()``, ``init_distributed()``, plus
the pipeline module, ops, and checkpointing re-exports.
"""
import time as _time
# The start-up ledger's clock (monitor/startup.py), taken before anything
# else of the package runs.
_IMPORT_CLOCK = _time.perf_counter()

from .version import __version__

from .runtime.config import DeepSpeedConfig
from .runtime import lr_schedules
from .utils.logging import logger, log_dist


def initialize(args=None, model=None, optimizer=None, model_params=None,
               training_data=None, lr_scheduler=None, mpu=None,
               dist_init_required=None, collate_fn=None, config=None,
               config_params=None, rng=None, param_shardings=None, mesh=None,
               zero3_scan=None):
    """Initialize the engine. Parity with reference ``__init__.py:50``.

    Returns a tuple of ``(engine, optimizer, dataloader, lr_scheduler)``.
    """
    # (the engines' modules load at the first call: the start-up ledger
    # files it with the package's import)
    with _startup.span("package_import", part="runtime"):
        from .runtime.engine import DeepSpeedEngine
        from .runtime.pipe.module import PipelineModule
        from .runtime.pipe.engine import PipelineEngine
        from .models.gpt2_pipe import PipeSpec

    cfg = config if config is not None else config_params
    if cfg is None and args is not None:
        cfg = getattr(args, "deepspeed_config", None)
    if cfg is None:
        raise ValueError("DeepSpeed requires a config via `config=`, "
                         "`config_params=`, or args.deepspeed_config")

    if isinstance(model, (PipelineModule, PipeSpec)):
        pipe_mpu = mpu
        if pipe_mpu is None and isinstance(model, PipelineModule):
            pipe_mpu = model.mpu()
        engine = PipelineEngine(args=args, model=model, optimizer=optimizer,
                                model_params=model_params, training_data=training_data,
                                lr_scheduler=lr_scheduler, mpu=pipe_mpu,
                                dist_init_required=dist_init_required,
                                collate_fn=collate_fn, config=cfg, rng=rng,
                                mesh=mesh)
    else:
        engine = DeepSpeedEngine(args=args, model=model, optimizer=optimizer,
                                 model_params=model_params, training_data=training_data,
                                 lr_scheduler=lr_scheduler, mpu=mpu,
                                 dist_init_required=dist_init_required,
                                 collate_fn=collate_fn, config=cfg, rng=rng,
                                 param_shardings=param_shardings, mesh=mesh,
                                 zero3_scan=zero3_scan)

    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def add_config_arguments(parser):
    """Add --deepspeed / --deepspeed_config CLI flags (reference __init__.py:193)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag for user scripts).")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed json configuration.")
    group.add_argument("--deepspeed_mpi", default=False, action="store_true",
                       help="Run via MPI; deprecated on TPU (topology is discovered).")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated alias for --deepspeed.")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated alias for --deepspeed_config.")
    return parser


def init_distributed(dist_backend: str = "xla", auto_mpi_discovery: bool = True,
                     distributed_port: int = 29500, verbose: bool = True,
                     timeout=None, init_method=None):
    """Initialize the multi-host runtime (reference utils/distributed.py:12).

    On TPU this wraps ``jax.distributed.initialize`` using environment
    variables set by the launcher; a no-op for single-process runs.
    """
    from .parallel.comm import init_distributed as _init
    return _init(dist_backend=dist_backend, distributed_port=distributed_port,
                 verbose=verbose, init_method=init_method)


from .monitor import startup as _startup
_startup.package_imported()     # closes the ledger's ``package_import`` row

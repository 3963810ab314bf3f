"""The DeepSpeed-style JSON config system, TPU edition.

Parity with reference ``runtime/config.py`` (DeepSpeedConfig, config.py:515):
- accepts a path to a JSON file or an already-parsed dict
- rejects duplicate JSON keys (config_utils)
- elasticity pre-pass rewrites the batch keys before the solver runs
  (config.py:537-588)
- batch triple inference: train_batch_size =
  micro_batch_per_device * gradient_accumulation_steps * dp_world_size, with
  any one/two of the three inferable from the others (config.py:655-725)
- ~50 typed getters with defaults (config.py:48-491)
- error checks for missing/conflicting batch info (config.py:746-782)

TPU deltas: ``bf16`` section is first-class; ``world_size`` is the number of
*data-parallel replicas* (mesh dp-axis size), not processes, since one JAX
process drives many chips.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Union

from . import config_utils
from .. import constants as C
from .zero.config import ZeroConfig
from .activation_checkpointing.config import ActivationCheckpointingConfig
from ..utils.logging import logger


class DeepSpeedConfigError(Exception):
    pass


class FlopsProfilerConfig:
    def __init__(self, param_dict: Optional[Dict[str, Any]] = None):
        d = (param_dict or {}).get(C.FLOPS_PROFILER, {})
        get = config_utils.get_scalar_param
        self.enabled = get(d, C.FLOPS_PROFILER_ENABLED, C.FLOPS_PROFILER_ENABLED_DEFAULT)
        self.profile_step = get(d, C.FLOPS_PROFILER_PROFILE_STEP,
                                C.FLOPS_PROFILER_PROFILE_STEP_DEFAULT)
        self.module_depth = get(d, C.FLOPS_PROFILER_MODULE_DEPTH,
                                C.FLOPS_PROFILER_MODULE_DEPTH_DEFAULT)
        self.top_modules = get(d, C.FLOPS_PROFILER_TOP_MODULES,
                               C.FLOPS_PROFILER_TOP_MODULES_DEFAULT)
        self.detailed = get(d, C.FLOPS_PROFILER_DETAILED, C.FLOPS_PROFILER_DETAILED_DEFAULT)


class ProgressiveLayerDropConfig:
    def __init__(self, param_dict: Optional[Dict[str, Any]] = None):
        d = (param_dict or {}).get(C.PROGRESSIVE_LAYER_DROP, {})
        get = config_utils.get_scalar_param
        self.enabled = get(d, C.PLD_ENABLED, C.PLD_ENABLED_DEFAULT)
        self.theta = get(d, C.PLD_THETA, C.PLD_THETA_DEFAULT)
        self.gamma = get(d, C.PLD_GAMMA, C.PLD_GAMMA_DEFAULT)


class PipelineConfig:
    def __init__(self, param_dict: Optional[Dict[str, Any]] = None):
        d = (param_dict or {}).get(C.PIPELINE, {})
        get = config_utils.get_scalar_param
        self.stages = get(d, C.PIPELINE_STAGES, C.PIPELINE_STAGES_DEFAULT)
        self.partition = get(d, C.PIPELINE_PARTITION, C.PIPELINE_PARTITION_DEFAULT)
        self.seed_layers = get(d, C.PIPELINE_SEED_LAYERS, C.PIPELINE_SEED_LAYERS_DEFAULT)
        self.activation_checkpoint_interval = get(
            d, C.PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL,
            C.PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL_DEFAULT)
        self.schedule = get(d, C.PIPELINE_SCHEDULE,
                            C.PIPELINE_SCHEDULE_DEFAULT)


class TensorboardConfig:
    def __init__(self, param_dict: Optional[Dict[str, Any]] = None):
        d = (param_dict or {}).get(C.TENSORBOARD, {})
        get = config_utils.get_scalar_param
        self.enabled = get(d, C.TENSORBOARD_ENABLED, C.TENSORBOARD_ENABLED_DEFAULT)
        self.output_path = get(d, C.TENSORBOARD_OUTPUT_PATH, C.TENSORBOARD_OUTPUT_PATH_DEFAULT)
        self.job_name = get(d, C.TENSORBOARD_JOB_NAME, C.TENSORBOARD_JOB_NAME_DEFAULT)


class TelemetryHealthConfig:
    """The ``telemetry.health`` block (monitor/health.py + flight.py):
    anomaly detection with NaN/Inf provenance, the hang watchdog, and
    the crash flight recorder. Enabled by default whenever telemetry is
    on — detection is drain-time host work; the watchdog (a daemon
    thread) is the one opt-in."""

    def __init__(self, d: Optional[Dict[str, Any]] = None):
        d = d or {}
        get = config_utils.get_scalar_param
        self.enabled = get(d, C.TELEMETRY_HEALTH_ENABLED,
                           C.TELEMETRY_HEALTH_ENABLED_DEFAULT)
        self.grad_taps = get(d, C.TELEMETRY_HEALTH_GRAD_TAPS,
                             C.TELEMETRY_HEALTH_GRAD_TAPS_DEFAULT)
        self.z_threshold = get(d, C.TELEMETRY_HEALTH_Z_THRESHOLD,
                               C.TELEMETRY_HEALTH_Z_THRESHOLD_DEFAULT)
        self.ewma_alpha = get(d, C.TELEMETRY_HEALTH_EWMA_ALPHA,
                              C.TELEMETRY_HEALTH_EWMA_ALPHA_DEFAULT)
        self.warmup_steps = get(d, C.TELEMETRY_HEALTH_WARMUP_STEPS,
                                C.TELEMETRY_HEALTH_WARMUP_STEPS_DEFAULT)
        self.watchdog = get(d, C.TELEMETRY_HEALTH_WATCHDOG,
                            C.TELEMETRY_HEALTH_WATCHDOG_DEFAULT)
        self.watchdog_factor = get(
            d, C.TELEMETRY_HEALTH_WATCHDOG_FACTOR,
            C.TELEMETRY_HEALTH_WATCHDOG_FACTOR_DEFAULT)
        self.watchdog_min_s = get(
            d, C.TELEMETRY_HEALTH_WATCHDOG_MIN_S,
            C.TELEMETRY_HEALTH_WATCHDOG_MIN_S_DEFAULT)
        self.flight_recorder = get(d, C.TELEMETRY_HEALTH_FLIGHT,
                                   C.TELEMETRY_HEALTH_FLIGHT_DEFAULT)
        self.flight_path = get(d, C.TELEMETRY_HEALTH_FLIGHT_PATH,
                               C.TELEMETRY_HEALTH_FLIGHT_PATH_DEFAULT)
        self.flight_window = get(d, C.TELEMETRY_HEALTH_FLIGHT_WINDOW,
                                 C.TELEMETRY_HEALTH_FLIGHT_WINDOW_DEFAULT)
        self._validate()

    def _validate(self) -> None:
        blk = f"{C.TELEMETRY}.{C.TELEMETRY_HEALTH}"
        for name, v in ((C.TELEMETRY_HEALTH_ENABLED, self.enabled),
                        (C.TELEMETRY_HEALTH_GRAD_TAPS, self.grad_taps),
                        (C.TELEMETRY_HEALTH_WATCHDOG, self.watchdog),
                        (C.TELEMETRY_HEALTH_FLIGHT, self.flight_recorder)):
            if not isinstance(v, bool):
                raise DeepSpeedConfigError(
                    f"{blk}.{name} must be a bool, got {v!r}")
        if not isinstance(self.z_threshold, (int, float)) or \
                isinstance(self.z_threshold, bool) or self.z_threshold <= 0:
            raise DeepSpeedConfigError(
                f"{blk}.{C.TELEMETRY_HEALTH_Z_THRESHOLD} must be a "
                f"positive number, got {self.z_threshold!r}")
        if not isinstance(self.ewma_alpha, (int, float)) or \
                isinstance(self.ewma_alpha, bool) or \
                not (0.0 < float(self.ewma_alpha) <= 1.0):
            raise DeepSpeedConfigError(
                f"{blk}.{C.TELEMETRY_HEALTH_EWMA_ALPHA} must be in "
                f"(0, 1], got {self.ewma_alpha!r}")
        if not isinstance(self.warmup_steps, int) or self.warmup_steps < 0:
            raise DeepSpeedConfigError(
                f"{blk}.{C.TELEMETRY_HEALTH_WARMUP_STEPS} must be a "
                f"non-negative int, got {self.warmup_steps!r}")
        for name, v in ((C.TELEMETRY_HEALTH_WATCHDOG_FACTOR,
                         self.watchdog_factor),
                        (C.TELEMETRY_HEALTH_WATCHDOG_MIN_S,
                         self.watchdog_min_s)):
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v <= 0:
                raise DeepSpeedConfigError(
                    f"{blk}.{name} must be a positive number, got {v!r}")
        if not isinstance(self.flight_window, int) or \
                self.flight_window <= 0:
            raise DeepSpeedConfigError(
                f"{blk}.{C.TELEMETRY_HEALTH_FLIGHT_WINDOW} must be a "
                f"positive int, got {self.flight_window!r}")


class CheckpointConfig:
    """The ``checkpoint`` block (runtime/async_ckpt.py + the engine's
    save/load paths): async snapshot-to-host saving, the auto-save
    cadence, and the preemption (SIGTERM) final-save handler. Tag
    validation stays on the DeepSpeedConfig top level for
    compatibility."""

    def __init__(self, param_dict: Optional[Dict[str, Any]] = None):
        d = (param_dict or {}).get(C.CHECKPOINT, {})
        get = config_utils.get_scalar_param
        self.async_save = get(d, C.CHECKPOINT_ASYNC,
                              C.CHECKPOINT_ASYNC_DEFAULT)
        self.snapshot_every = get(d, C.CHECKPOINT_SNAPSHOT_EVERY,
                                  C.CHECKPOINT_SNAPSHOT_EVERY_DEFAULT)
        self.save_dir = get(d, C.CHECKPOINT_SAVE_DIR,
                            C.CHECKPOINT_SAVE_DIR_DEFAULT)
        self.preempt_save = get(d, C.CHECKPOINT_PREEMPT_SAVE,
                                C.CHECKPOINT_PREEMPT_SAVE_DEFAULT)
        self.max_pending_snapshots = get(d, C.CHECKPOINT_MAX_PENDING,
                                         C.CHECKPOINT_MAX_PENDING_DEFAULT)
        self.writer_timeout_s = get(d, C.CHECKPOINT_WRITER_TIMEOUT_S,
                                    C.CHECKPOINT_WRITER_TIMEOUT_S_DEFAULT)
        self.fsync = get(d, C.CHECKPOINT_FSYNC, C.CHECKPOINT_FSYNC_DEFAULT)
        self._validate()

    def _validate(self) -> None:
        blk = C.CHECKPOINT
        for name, v in ((C.CHECKPOINT_ASYNC, self.async_save),
                        (C.CHECKPOINT_PREEMPT_SAVE, self.preempt_save),
                        (C.CHECKPOINT_FSYNC, self.fsync)):
            if not isinstance(v, bool):
                raise DeepSpeedConfigError(
                    f"{blk}.{name} must be a bool, got {v!r}")
        if not isinstance(self.snapshot_every, int) or \
                isinstance(self.snapshot_every, bool) or \
                self.snapshot_every < 0:
            raise DeepSpeedConfigError(
                f"{blk}.{C.CHECKPOINT_SNAPSHOT_EVERY} must be a "
                f"non-negative int (0 = no auto-save), got "
                f"{self.snapshot_every!r}")
        if not isinstance(self.save_dir, str):
            raise DeepSpeedConfigError(
                f"{blk}.{C.CHECKPOINT_SAVE_DIR} must be a string path, "
                f"got {self.save_dir!r}")
        if self.snapshot_every > 0 and not self.save_dir:
            raise DeepSpeedConfigError(
                f"{blk}.{C.CHECKPOINT_SNAPSHOT_EVERY} > 0 needs "
                f"{blk}.{C.CHECKPOINT_SAVE_DIR}: auto-saves have to land "
                "somewhere")
        if not isinstance(self.max_pending_snapshots, int) or \
                isinstance(self.max_pending_snapshots, bool) or \
                self.max_pending_snapshots < 1:
            raise DeepSpeedConfigError(
                f"{blk}.{C.CHECKPOINT_MAX_PENDING} must be an int >= 1 "
                f"(each pending snapshot is a full host state copy), got "
                f"{self.max_pending_snapshots!r}")
        if not isinstance(self.writer_timeout_s, (int, float)) or \
                isinstance(self.writer_timeout_s, bool) or \
                self.writer_timeout_s <= 0:
            raise DeepSpeedConfigError(
                f"{blk}.{C.CHECKPOINT_WRITER_TIMEOUT_S} must be a "
                f"positive number, got {self.writer_timeout_s!r}")


class TelemetryProfileConfig:
    """The ``telemetry.profile`` block (monitor/profile_ingest.py +
    reconcile.py): the jax.profiler capture window, trace ingestion, and
    measured-vs-floor reconciliation thresholds. The legacy flat
    ``telemetry.profile_start_step``/``profile_num_steps``/``profile_dir``
    keys remain as aliases; an explicit nested block wins."""

    def __init__(self, d: Optional[Dict[str, Any]] = None,
                 legacy_start: int = C.TELEMETRY_PROFILE_START_STEP_DEFAULT,
                 legacy_steps: int = C.TELEMETRY_PROFILE_NUM_STEPS_DEFAULT,
                 legacy_dir: str = C.TELEMETRY_PROFILE_DIR_DEFAULT):
        d = d or {}
        get = config_utils.get_scalar_param
        self.start_step = get(d, C.TELEMETRY_PROFILE_BLOCK_START,
                              legacy_start)
        legacy_armed = isinstance(legacy_start, int) and \
            not isinstance(legacy_start, bool) and legacy_start >= 0
        self.window_steps = get(
            d, C.TELEMETRY_PROFILE_BLOCK_STEPS,
            legacy_steps if legacy_armed
            else C.TELEMETRY_PROFILE_BLOCK_STEPS_DEFAULT)
        self.out_dir = get(d, C.TELEMETRY_PROFILE_BLOCK_DIR, legacy_dir)
        self.divergence_threshold = get(
            d, C.TELEMETRY_PROFILE_THRESHOLD,
            C.TELEMETRY_PROFILE_THRESHOLD_DEFAULT)
        self.host_frac = get(d, C.TELEMETRY_PROFILE_HOST_FRAC,
                             C.TELEMETRY_PROFILE_HOST_FRAC_DEFAULT)
        self._validate()

    def _validate(self) -> None:
        blk = f"{C.TELEMETRY}.{C.TELEMETRY_PROFILE}"
        if not isinstance(self.start_step, int) or \
                isinstance(self.start_step, bool):
            raise DeepSpeedConfigError(
                f"{blk}.{C.TELEMETRY_PROFILE_BLOCK_START} must be an int "
                f"(-1 = off), got {self.start_step!r}")
        if not isinstance(self.window_steps, int) or \
                isinstance(self.window_steps, bool) or \
                self.window_steps <= 0:
            raise DeepSpeedConfigError(
                f"{blk}.{C.TELEMETRY_PROFILE_BLOCK_STEPS} must be a "
                f"positive int, got {self.window_steps!r}")
        for name, v in ((C.TELEMETRY_PROFILE_THRESHOLD,
                         self.divergence_threshold),
                        (C.TELEMETRY_PROFILE_HOST_FRAC, self.host_frac)):
            if not isinstance(v, (int, float)) or isinstance(v, bool) or \
                    v <= 0:
                raise DeepSpeedConfigError(
                    f"{blk}.{name} must be a positive number, got {v!r}")
        if not isinstance(self.out_dir, str):
            raise DeepSpeedConfigError(
                f"{blk}.{C.TELEMETRY_PROFILE_BLOCK_DIR} must be a string, "
                f"got {self.out_dir!r}")


class TelemetryConfig:
    """The ``telemetry`` block (monitor/ subsystem).

    Subsumes the ``tensorboard`` block, which stays as an alias: a config
    with only ``tensorboard.enabled`` gets an enabled telemetry sink with
    the tensorboard block's output_path/job_name (and the tensorboard
    writer itself, when importable). An explicit ``telemetry`` key always
    wins over the alias.
    """

    def __init__(self, param_dict: Optional[Dict[str, Any]] = None,
                 tensorboard: Optional[TensorboardConfig] = None):
        d = (param_dict or {}).get(C.TELEMETRY, {})
        tb = tensorboard or TensorboardConfig(param_dict)
        get = config_utils.get_scalar_param
        self.enabled = get(d, C.TELEMETRY_ENABLED, bool(tb.enabled))
        self.output_path = get(d, C.TELEMETRY_OUTPUT_PATH,
                               tb.output_path or
                               C.TELEMETRY_OUTPUT_PATH_DEFAULT)
        self.job_name = get(d, C.TELEMETRY_JOB_NAME,
                            tb.job_name if tb.enabled
                            else C.TELEMETRY_JOB_NAME_DEFAULT)
        self.tensorboard = bool(tb.enabled)
        self.buffer_size = get(d, C.TELEMETRY_BUFFER_SIZE,
                               C.TELEMETRY_BUFFER_SIZE_DEFAULT)
        self.report_steps = get(d, C.TELEMETRY_REPORT_STEPS,
                                C.TELEMETRY_REPORT_STEPS_DEFAULT)
        self.trace_path = get(d, C.TELEMETRY_TRACE_PATH,
                              C.TELEMETRY_TRACE_PATH_DEFAULT)
        self.fail_on_recompile = get(d, C.TELEMETRY_FAIL_ON_RECOMPILE,
                                     C.TELEMETRY_FAIL_ON_RECOMPILE_DEFAULT)
        self.recompile_warmup_calls = get(d, C.TELEMETRY_RECOMPILE_WARMUP,
                                          C.TELEMETRY_RECOMPILE_WARMUP_DEFAULT)
        self.memory_watermarks = get(d, C.TELEMETRY_MEMORY_WATERMARKS,
                                     C.TELEMETRY_MEMORY_WATERMARKS_DEFAULT)
        self.watermark_ratio = get(d, C.TELEMETRY_WATERMARK_RATIO,
                                   C.TELEMETRY_WATERMARK_RATIO_DEFAULT)
        self.watermark_slack_bytes = get(
            d, C.TELEMETRY_WATERMARK_SLACK_BYTES,
            C.TELEMETRY_WATERMARK_SLACK_BYTES_DEFAULT)
        legacy_start = get(d, C.TELEMETRY_PROFILE_START_STEP,
                           C.TELEMETRY_PROFILE_START_STEP_DEFAULT)
        legacy_steps = get(d, C.TELEMETRY_PROFILE_NUM_STEPS,
                           C.TELEMETRY_PROFILE_NUM_STEPS_DEFAULT)
        legacy_dir = get(d, C.TELEMETRY_PROFILE_DIR,
                         C.TELEMETRY_PROFILE_DIR_DEFAULT)
        self.profile = TelemetryProfileConfig(
            d.get(C.TELEMETRY_PROFILE), legacy_start=legacy_start,
            legacy_steps=legacy_steps, legacy_dir=legacy_dir)
        # Flat aliases kept in sync with the resolved block (telemetry.py
        # and older callers read these).
        self.profile_start_step = self.profile.start_step
        self.profile_num_steps = self.profile.window_steps
        self.profile_dir = self.profile.out_dir
        self.cost_model = get(d, C.TELEMETRY_COST_MODEL,
                              C.TELEMETRY_COST_MODEL_DEFAULT)
        self.per_host_shards = get(d, C.TELEMETRY_PER_HOST,
                                   C.TELEMETRY_PER_HOST_DEFAULT)
        self.health = TelemetryHealthConfig(d.get(C.TELEMETRY_HEALTH))
        self._validate()

    def _validate(self) -> None:
        if not isinstance(self.buffer_size, int) or self.buffer_size <= 0:
            raise DeepSpeedConfigError(
                f"{C.TELEMETRY}.{C.TELEMETRY_BUFFER_SIZE} must be a "
                f"positive int, got {self.buffer_size!r}")
        if not isinstance(self.report_steps, int) or self.report_steps < 0:
            raise DeepSpeedConfigError(
                f"{C.TELEMETRY}.{C.TELEMETRY_REPORT_STEPS} must be a "
                f"non-negative int (0 = follow steps_per_print), got "
                f"{self.report_steps!r}")
        if not isinstance(self.recompile_warmup_calls, int) or \
                self.recompile_warmup_calls < 0:
            raise DeepSpeedConfigError(
                f"{C.TELEMETRY}.{C.TELEMETRY_RECOMPILE_WARMUP} must be a "
                f"non-negative int, got {self.recompile_warmup_calls!r}")
        if not isinstance(self.watermark_ratio, (int, float)) or \
                self.watermark_ratio <= 0:
            raise DeepSpeedConfigError(
                f"{C.TELEMETRY}.{C.TELEMETRY_WATERMARK_RATIO} must be a "
                f"positive number, got {self.watermark_ratio!r}")
        if not isinstance(self.cost_model, bool):
            raise DeepSpeedConfigError(
                f"{C.TELEMETRY}.{C.TELEMETRY_COST_MODEL} must be a bool, "
                f"got {self.cost_model!r}")
        if not isinstance(self.per_host_shards, bool):
            raise DeepSpeedConfigError(
                f"{C.TELEMETRY}.{C.TELEMETRY_PER_HOST} must be a bool, "
                f"got {self.per_host_shards!r}")


class InferenceSloConfig:
    """The ``inference.slo`` block (monitor/serving_slo.py): TTFT/TPOT
    targets, availability target, and the trailing attainment window.
    Both latency targets unset (0) leaves the tracker off — snapshots
    then omit the ``slo`` section entirely."""

    def __init__(self, d: Optional[Dict[str, Any]] = None):
        d = d or {}
        get = config_utils.get_scalar_param
        self.ttft_ms = get(d, C.INFERENCE_SLO_TTFT_MS,
                           C.INFERENCE_SLO_TTFT_MS_DEFAULT)
        self.tpot_ms = get(d, C.INFERENCE_SLO_TPOT_MS,
                           C.INFERENCE_SLO_TPOT_MS_DEFAULT)
        self.availability = get(d, C.INFERENCE_SLO_AVAILABILITY,
                                C.INFERENCE_SLO_AVAILABILITY_DEFAULT)
        self.window_s = get(d, C.INFERENCE_SLO_WINDOW_S,
                            C.INFERENCE_SLO_WINDOW_S_DEFAULT)
        self._validate()

    @property
    def enabled(self) -> bool:
        return self.ttft_ms > 0 or self.tpot_ms > 0

    def _validate(self) -> None:
        blk = f"{C.INFERENCE}.{C.INFERENCE_SLO}"
        for name, v in ((C.INFERENCE_SLO_TTFT_MS, self.ttft_ms),
                        (C.INFERENCE_SLO_TPOT_MS, self.tpot_ms)):
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v < 0:
                raise DeepSpeedConfigError(
                    f"{blk}.{name} must be a non-negative number "
                    f"(0 = target unset), got {v!r}")
        if not isinstance(self.availability, (int, float)) \
                or isinstance(self.availability, bool) \
                or not (0.0 < self.availability < 1.0):
            raise DeepSpeedConfigError(
                f"{blk}.{C.INFERENCE_SLO_AVAILABILITY} must be a number "
                f"in (0, 1), got {self.availability!r}")
        if not isinstance(self.window_s, (int, float)) \
                or isinstance(self.window_s, bool) or self.window_s <= 0:
            raise DeepSpeedConfigError(
                f"{blk}.{C.INFERENCE_SLO_WINDOW_S} must be a positive "
                f"number of seconds, got {self.window_s!r}")


class InferenceConfig:
    """The ``inference`` block (inference/ serving subsystem).

    Every knob here is STATIC compiled-program shape: slot count, cache
    sequence capacity, weight quantization mode, prefill chunk length.
    The continuous-batching scheduler varies the ACTIVE request set at
    run time without touching any of them — that is what keeps the
    decode step at one compilation for the whole serve.
    """

    def __init__(self, param_dict: Optional[Dict[str, Any]] = None):
        d = (param_dict or {}).get(C.INFERENCE, {})
        get = config_utils.get_scalar_param
        self.max_slots = get(d, C.INFERENCE_MAX_SLOTS,
                             C.INFERENCE_MAX_SLOTS_DEFAULT)
        self.max_seq_len = get(d, C.INFERENCE_MAX_SEQ_LEN,
                               C.INFERENCE_MAX_SEQ_LEN_DEFAULT)
        self.quantize = get(d, C.INFERENCE_QUANTIZE,
                            C.INFERENCE_QUANTIZE_DEFAULT)
        self.prefill_chunk = get(d, C.INFERENCE_PREFILL_CHUNK,
                                 C.INFERENCE_PREFILL_CHUNK_DEFAULT)
        self.block_size = get(d, C.INFERENCE_BLOCK_SIZE,
                              C.INFERENCE_BLOCK_SIZE_DEFAULT)
        self.num_blocks = get(d, C.INFERENCE_NUM_BLOCKS,
                              C.INFERENCE_NUM_BLOCKS_DEFAULT)
        self.spec_k = get(d, C.INFERENCE_SPEC_K, C.INFERENCE_SPEC_K_DEFAULT)
        self.spec_ngram = get(d, C.INFERENCE_SPEC_NGRAM,
                              C.INFERENCE_SPEC_NGRAM_DEFAULT)
        self.kv_cache_dtype = get(d, C.INFERENCE_KV_DTYPE,
                                  C.INFERENCE_KV_DTYPE_DEFAULT)
        self.replica = get(d, C.INFERENCE_REPLICA,
                           C.INFERENCE_REPLICA_DEFAULT)
        self.paged_kernel = get(d, C.INFERENCE_PAGED_KERNEL,
                                C.INFERENCE_PAGED_KERNEL_DEFAULT)
        slo_d = d.get(C.INFERENCE_SLO)
        if slo_d is not None and not isinstance(slo_d, dict):
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_SLO} must be a dict block, "
                f"got {slo_d!r}")
        self.slo = InferenceSloConfig(slo_d)
        self._validate()

    def _validate(self) -> None:
        if not isinstance(self.max_slots, int) or self.max_slots <= 0:
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_MAX_SLOTS} must be a positive "
                f"int, got {self.max_slots!r}")
        if not isinstance(self.max_seq_len, int) or self.max_seq_len < 0:
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_MAX_SEQ_LEN} must be a "
                f"non-negative int (0 = model max), got "
                f"{self.max_seq_len!r}")
        if self.quantize not in C.INFERENCE_QUANTIZE_MODES:
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_QUANTIZE} must be one of "
                f"{C.INFERENCE_QUANTIZE_MODES}, got {self.quantize!r}")
        if not isinstance(self.prefill_chunk, int) or self.prefill_chunk <= 0:
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_PREFILL_CHUNK} must be a "
                f"positive int (chunked prefill is the only admission "
                f"path), got {self.prefill_chunk!r}")
        if not isinstance(self.block_size, int) or self.block_size <= 0:
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_BLOCK_SIZE} must be a "
                f"positive int (the paged block pool is the only KV "
                f"layout), got {self.block_size!r}")
        sizes = self.num_blocks if isinstance(self.num_blocks, dict) \
            else {"": self.num_blocks}
        if not all(isinstance(k, str) and isinstance(n, int) and n >= 0
                   for k, n in sizes.items()):
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_NUM_BLOCKS} must be a "
                f"non-negative int (0 = full provisioning), or for a model "
                f"with classes of cache layers {{class name: such an int}}, "
                f"got {self.num_blocks!r}")
        if not isinstance(self.spec_k, int) or self.spec_k < 0:
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_SPEC_K} must be a "
                f"non-negative int (0 = speculative decoding off), got "
                f"{self.spec_k!r}")
        if not isinstance(self.spec_ngram, int) or self.spec_ngram < 1:
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_SPEC_NGRAM} must be a "
                f"positive int, got {self.spec_ngram!r}")
        if self.kv_cache_dtype not in C.INFERENCE_KV_DTYPE_MODES:
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_KV_DTYPE} must be one of "
                f"{C.INFERENCE_KV_DTYPE_MODES}, got "
                f"{self.kv_cache_dtype!r}")
        if not isinstance(self.replica, str):
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_REPLICA} must be a string "
                f"label, got {self.replica!r}")
        if self.paged_kernel not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"{C.INFERENCE}.{C.INFERENCE_PAGED_KERNEL} must be true, "
                f"false, or \"auto\", got {self.paged_kernel!r}")


class MoeConfig:
    """The ``moe`` block (deepspeed_tpu/moe/ expert parallelism).

    ``num_experts == 0`` (the default) leaves the block inert. The
    engine reads it for the `expert` mesh axis, the MoE metrics schema,
    and the all-to-all wire model; build the model's
    ``TransformerConfig.moe`` from it via ``MoEConfig.from_ds_config``.
    """

    def __init__(self, param_dict: Optional[Dict[str, Any]] = None):
        d = (param_dict or {}).get(C.MOE, {})
        get = config_utils.get_scalar_param
        self.num_experts = get(d, C.MOE_NUM_EXPERTS,
                               C.MOE_NUM_EXPERTS_DEFAULT)
        self.top_k = get(d, C.MOE_TOP_K, C.MOE_TOP_K_DEFAULT)
        self.capacity_factor = get(d, C.MOE_CAPACITY_FACTOR,
                                   C.MOE_CAPACITY_FACTOR_DEFAULT)
        self.aux_loss_weight = get(d, C.MOE_AUX_LOSS_WEIGHT,
                                   C.MOE_AUX_LOSS_WEIGHT_DEFAULT)
        self.z_loss_weight = get(d, C.MOE_Z_LOSS_WEIGHT,
                                 C.MOE_Z_LOSS_WEIGHT_DEFAULT)
        self.expert_parallel_size = get(d, C.MOE_EXPERT_PARALLEL_SIZE,
                                        C.MOE_EXPERT_PARALLEL_SIZE_DEFAULT)
        self.grouped_gemm = get(d, C.MOE_GROUPED_GEMM,
                                C.MOE_GROUPED_GEMM_DEFAULT)
        self._validate()

    def _validate(self) -> None:
        blk = C.MOE
        if not isinstance(self.num_experts, int) or self.num_experts < 0:
            raise DeepSpeedConfigError(
                f"{blk}.{C.MOE_NUM_EXPERTS} must be a non-negative int "
                f"(0 = disabled), got {self.num_experts!r}")
        if not isinstance(self.expert_parallel_size, int) or \
                self.expert_parallel_size < 1:
            raise DeepSpeedConfigError(
                f"{blk}.{C.MOE_EXPERT_PARALLEL_SIZE} must be a positive "
                f"int, got {self.expert_parallel_size!r}")
        if self.grouped_gemm not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"{blk}.{C.MOE_GROUPED_GEMM} must be true/false/"
                f"\"auto\", got {self.grouped_gemm!r}")
        if self.num_experts == 0:
            if self.expert_parallel_size > 1:
                raise DeepSpeedConfigError(
                    f"{blk}.{C.MOE_EXPERT_PARALLEL_SIZE} > 1 needs "
                    f"{C.MOE_NUM_EXPERTS} > 0")
            return
        if self.top_k not in (1, 2):
            raise DeepSpeedConfigError(
                f"{blk}.{C.MOE_TOP_K} must be 1 or 2, got {self.top_k!r}")
        if self.top_k > self.num_experts:
            raise DeepSpeedConfigError(
                f"{blk}.{C.MOE_TOP_K}={self.top_k} exceeds "
                f"{C.MOE_NUM_EXPERTS}={self.num_experts}")
        cf = self.capacity_factor
        if isinstance(cf, bool) or not isinstance(cf, (int, float)) or \
                not cf > 0:
            raise DeepSpeedConfigError(
                f"{blk}.{C.MOE_CAPACITY_FACTOR} must be a positive "
                f"number (inf = never drop), got {cf!r}")
        for name, v in ((C.MOE_AUX_LOSS_WEIGHT, self.aux_loss_weight),
                        (C.MOE_Z_LOSS_WEIGHT, self.z_loss_weight)):
            if isinstance(v, bool) or not isinstance(v, (int, float)) \
                    or v < 0:
                raise DeepSpeedConfigError(
                    f"{blk}.{name} must be a non-negative number, "
                    f"got {v!r}")
        if self.num_experts % self.expert_parallel_size != 0:
            raise DeepSpeedConfigError(
                f"{blk}.{C.MOE_NUM_EXPERTS}={self.num_experts} not "
                f"divisible by {C.MOE_EXPERT_PARALLEL_SIZE}="
                f"{self.expert_parallel_size}")


class MeshConfig:
    """TPU-native extension: requested logical mesh axis sizes.

    Sizes of -1 / None are inferred (dp absorbs the remainder of the device
    count after mp/pp/sp are fixed).
    """

    def __init__(self, param_dict: Optional[Dict[str, Any]] = None):
        d = (param_dict or {}).get(C.MESH, {})
        get = config_utils.get_scalar_param
        self.data_parallel_size = get(d, C.MESH_DATA_PARALLEL_SIZE, None)
        self.model_parallel_size = get(d, C.MESH_MODEL_PARALLEL_SIZE, 1)
        self.pipe_parallel_size = get(d, C.MESH_PIPE_PARALLEL_SIZE, 1)
        self.sequence_parallel_size = get(d, C.MESH_SEQUENCE_PARALLEL_SIZE, 1)
        # Multi-slice scale-out: ICI domains joined by DCN; the `slice`
        # mesh axis is OUTERMOST and dp factors within a slice.
        self.num_slices = get(d, C.MESH_NUM_SLICES, 1)
        if not isinstance(self.num_slices, int) or self.num_slices < 1:
            raise DeepSpeedConfigError(
                f"{C.MESH}.{C.MESH_NUM_SLICES} must be a positive int "
                f"(ICI domains the mesh spans), got {self.num_slices!r}")


class DeepSpeedConfig:
    def __init__(self, config: Union[str, Dict[str, Any]], mpu=None,
                 param_dict: Optional[Dict[str, Any]] = None,
                 world_size: Optional[int] = None):
        if param_dict is not None:
            self._param_dict = param_dict
        elif isinstance(config, dict):
            self._param_dict = config
        else:
            self._param_dict = config_utils.load_config_json(config)

        # Data-parallel world size for the batch solver: the mesh dp-axis
        # size. Resolution order mirrors the reference's mpu override
        # (config.py:523-535).
        if world_size is not None:
            self.world_size = world_size
        elif mpu is not None:
            self.world_size = mpu.get_data_parallel_world_size()
        else:
            self.world_size = self._infer_default_world_size()

        # Elasticity pre-pass (reference config.py:537-588).
        self.elasticity_enabled = False
        self._configure_elasticity()

        self._initialize_params(self._param_dict)
        self._configure_train_batch_size()
        self._do_sanity_check()

    # ------------------------------------------------------------------ #
    def _infer_default_world_size(self) -> int:
        import os
        if "WORLD_SIZE" in os.environ:
            return int(os.environ["WORLD_SIZE"])
        try:
            import jax
            mesh = self._param_dict.get(C.MESH, {})
            mp = mesh.get(C.MESH_MODEL_PARALLEL_SIZE, 1) or 1
            pp = mesh.get(C.MESH_PIPE_PARALLEL_SIZE, 1) or 1
            sp = mesh.get(C.MESH_SEQUENCE_PARALLEL_SIZE, 1) or 1
            return max(1, jax.device_count() // (mp * pp * sp))
        except Exception:
            return 1

    def _configure_elasticity(self) -> None:
        from ..elasticity import elasticity_enabled, compute_elastic_config
        if not elasticity_enabled(self._param_dict):
            return
        from ..elasticity.config import ElasticityConfigError
        elastic_dict = self._param_dict[C.ELASTICITY]
        ignore_non_elastic = elastic_dict.get(
            C.IGNORE_NON_ELASTIC_BATCH_INFO, C.IGNORE_NON_ELASTIC_BATCH_INFO_DEFAULT)
        if not ignore_non_elastic:
            batch_params = (C.TRAIN_BATCH_SIZE, C.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
                            C.GRADIENT_ACCUMULATION_STEPS)
            if any(self._param_dict.get(k) is not None for k in batch_params):
                raise ElasticityConfigError(
                    "One or more batch related parameters were found in your ds_config "
                    f"({', '.join(batch_params)}). These parameters *will not be used* since "
                    "elastic training is enabled, which takes control of these parameters. "
                    f"If you want to supress this error set '{C.IGNORE_NON_ELASTIC_BATCH_INFO}':true "
                    "in your elasticity config.")
        final_batch_size, valid_gpus, micro_batch_size = compute_elastic_config(
            ds_config=self._param_dict, target_deepspeed_version="0.1.0",
            world_size=self.world_size)
        self.elastic_train_batch_size = final_batch_size
        self.elastic_valid_gpus = valid_gpus
        self.elasticity_enabled = True
        self._param_dict[C.TRAIN_BATCH_SIZE] = final_batch_size
        self._param_dict[C.TRAIN_MICRO_BATCH_SIZE_PER_GPU] = micro_batch_size
        self._param_dict[C.GRADIENT_ACCUMULATION_STEPS] = None

    # ------------------------------------------------------------------ #
    def _initialize_params(self, d: Dict[str, Any]) -> None:
        get = config_utils.get_scalar_param

        self.train_batch_size = get(d, C.TRAIN_BATCH_SIZE, C.TRAIN_BATCH_SIZE_DEFAULT)
        self.train_micro_batch_size_per_gpu = get(
            d, C.TRAIN_MICRO_BATCH_SIZE_PER_GPU, C.TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT)
        self.gradient_accumulation_steps = get(
            d, C.GRADIENT_ACCUMULATION_STEPS, C.GRADIENT_ACCUMULATION_STEPS_DEFAULT)
        self.steps_per_print = get(d, C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT)
        self.dump_state = get(d, C.DUMP_STATE, C.DUMP_STATE_DEFAULT)
        self.disable_allgather = get(d, C.DISABLE_ALLGATHER, C.DISABLE_ALLGATHER_DEFAULT)

        self.prescale_gradients = get(d, C.PRESCALE_GRADIENTS, C.PRESCALE_GRADIENTS_DEFAULT)
        self.gradient_predivide_factor = get(
            d, C.GRADIENT_PREDIVIDE_FACTOR, C.GRADIENT_PREDIVIDE_FACTOR_DEFAULT)
        self.sparse_gradients_enabled = get(d, C.SPARSE_GRADIENTS, C.SPARSE_GRADIENTS_DEFAULT)
        self.allreduce_always_fp32 = get(d, C.ALLREDUCE_ALWAYS_FP32,
                                         C.ALLREDUCE_ALWAYS_FP32_DEFAULT)

        self.zero_config = ZeroConfig(d)
        self.zero_optimization_stage = self.zero_config.stage
        self.zero_enabled = self.zero_optimization_stage > 0

        self.activation_checkpointing_config = ActivationCheckpointingConfig(d)
        self.flops_profiler_config = FlopsProfilerConfig(d)
        self.pld_config = ProgressiveLayerDropConfig(d)
        self.pipeline_config = PipelineConfig(d)
        self.tensorboard_config = TensorboardConfig(d)
        self.telemetry_config = TelemetryConfig(
            d, tensorboard=self.tensorboard_config)
        self.inference_config = InferenceConfig(d)
        self.mesh_config = MeshConfig(d)
        self.moe_config = MoeConfig(d)

        fp16 = d.get(C.FP16, {})
        self.fp16_enabled = get(fp16, C.FP16_ENABLED, C.FP16_ENABLED_DEFAULT)
        self.fp16_loss_scale = get(fp16, C.FP16_LOSS_SCALE, C.FP16_LOSS_SCALE_DEFAULT)
        self.fp16_initial_scale_power = get(fp16, C.FP16_INITIAL_SCALE_POWER,
                                            C.FP16_INITIAL_SCALE_POWER_DEFAULT)
        self.fp16_loss_scale_window = get(fp16, C.FP16_LOSS_SCALE_WINDOW,
                                          C.FP16_LOSS_SCALE_WINDOW_DEFAULT)
        self.fp16_hysteresis = get(fp16, C.FP16_HYSTERESIS, C.FP16_HYSTERESIS_DEFAULT)
        self.fp16_min_loss_scale = get(fp16, C.FP16_MIN_LOSS_SCALE,
                                       C.FP16_MIN_LOSS_SCALE_DEFAULT)

        bf16 = d.get(C.BF16, {})
        self.bf16_enabled = get(bf16, C.BF16_ENABLED, C.BF16_ENABLED_DEFAULT)
        self.bf16_stochastic_rounding = get(
            bf16, C.BF16_STOCHASTIC_ROUNDING,
            C.BF16_STOCHASTIC_ROUNDING_DEFAULT)

        amp = d.get(C.AMP, {})
        self.amp_enabled = get(amp, C.AMP_ENABLED, C.AMP_ENABLED_DEFAULT)
        self.amp_params = {k: v for k, v in amp.items() if k != C.AMP_ENABLED}
        # amp acts or raises — silent-ignore is the one unacceptable state
        # (reference engine.py:630-668 wraps apex amp). On TPU the amp
        # semantic (mixed-precision compute, fp32 masters) IS the bf16
        # path, so "amp": {"enabled": true} maps onto it with a notice;
        # combined with fp16 it raises instead of guessing.
        if self.amp_enabled:
            if self.fp16_enabled:
                raise DeepSpeedConfigError(
                    "amp and fp16 cannot both be enabled: on TPU amp maps "
                    "to the bf16 mixed-precision path — pick `bf16` (or "
                    "`amp` alone) or `fp16`")
            if not self.bf16_enabled:
                self.bf16_enabled = True
                logger.info(
                    "amp: enabled -> mapped to the bf16 mixed-precision "
                    "path (TPU has no apex; bf16 is the amp-equivalent "
                    "O1 mode). Set bf16.enabled directly to silence this.")

        self.gradient_clipping = get(d, C.GRADIENT_CLIPPING, C.GRADIENT_CLIPPING_DEFAULT)

        optimizer = d.get(C.OPTIMIZER)
        if optimizer is not None:
            self.optimizer_name = optimizer.get(C.TYPE, C.OPTIMIZER_TYPE_DEFAULT)
            if self.optimizer_name is not None:
                self.optimizer_name = self.optimizer_name.lower()
            self.optimizer_params = optimizer.get(C.OPTIMIZER_PARAMS, {})
            self.optimizer_legacy_fusion = optimizer.get(C.LEGACY_FUSION,
                                                         C.LEGACY_FUSION_DEFAULT)
        else:
            self.optimizer_name = None
            self.optimizer_params = {}
            self.optimizer_legacy_fusion = False
        # optimizer.params.fused: the Pallas single-pass multi-tensor apply
        # (ops/fused_update.py). Default on; build_optimizer only honors it
        # for the Adam family, and the engine falls back to the optax chain
        # where fusion does not compose (TP param layouts).
        self.optimizer_fused = bool((self.optimizer_params or {}).get(
            C.OPTIMIZER_FUSED, C.OPTIMIZER_FUSED_DEFAULT))

        scheduler = d.get(C.SCHEDULER)
        if scheduler is not None:
            self.scheduler_name = scheduler.get(C.TYPE, C.SCHEDULER_TYPE_DEFAULT)
            self.scheduler_params = scheduler.get(C.SCHEDULER_PARAMS, {})
        else:
            self.scheduler_name = None
            self.scheduler_params = {}

        self.wall_clock_breakdown = get(d, C.WALL_CLOCK_BREAKDOWN,
                                        C.WALL_CLOCK_BREAKDOWN_DEFAULT)
        self.memory_breakdown = get(d, C.MEMORY_BREAKDOWN, C.MEMORY_BREAKDOWN_DEFAULT)

        # Normalized like the reference's get_sparse_attention
        # (config.py:192-362): mode-specific defaults filled, unknown modes
        # rejected at config time. sparsity_config_from_dict() turns this
        # into the layout object SparseSelfAttention consumes.
        from ..ops.sparse_attention.config_factory import \
            normalize_sparse_attention
        self.sparse_attention = normalize_sparse_attention(
            d.get(C.SPARSE_ATTENTION))

        ckpt = d.get(C.CHECKPOINT, {})
        self.checkpoint_config = CheckpointConfig(d)
        self.checkpoint_tag_validation_mode = get(
            ckpt, C.CHECKPOINT_TAG_VALIDATION, C.CHECKPOINT_TAG_VALIDATION_DEFAULT)
        if isinstance(self.checkpoint_tag_validation_mode, str):
            self.checkpoint_tag_validation_mode = self.checkpoint_tag_validation_mode.capitalize()
        self.checkpoint_tag_validation_enabled = \
            self.checkpoint_tag_validation_mode != "Ignore"
        self.checkpoint_tag_validation_fail = self.checkpoint_tag_validation_mode == "Fail"

    # ------------------------------------------------------------------ #
    def _configure_train_batch_size(self) -> None:
        """Solve train_batch = micro_batch * grad_accum * world (config.py:655-725)."""
        train_batch = self.train_batch_size
        micro_batch = self.train_micro_batch_size_per_gpu
        grad_acc = self.gradient_accumulation_steps
        world = self.world_size

        if train_batch is not None and micro_batch is not None and grad_acc is not None:
            pass  # all set; verified in sanity check
        elif train_batch is not None and micro_batch is not None:
            grad_acc = train_batch // micro_batch
            grad_acc //= world
            self.gradient_accumulation_steps = grad_acc
        elif train_batch is not None and grad_acc is not None:
            micro_batch = train_batch // world
            micro_batch //= grad_acc
            self.train_micro_batch_size_per_gpu = micro_batch
        elif micro_batch is not None and grad_acc is not None:
            self.train_batch_size = micro_batch * grad_acc * world
        elif train_batch is not None:
            self.gradient_accumulation_steps = 1
            self.train_micro_batch_size_per_gpu = train_batch // world
        elif micro_batch is not None:
            self.gradient_accumulation_steps = 1
            self.train_batch_size = micro_batch * world
        # else: all None → sanity check raises

    def _batch_assertion(self) -> None:
        train_batch = self.train_batch_size
        micro_batch = self.train_micro_batch_size_per_gpu
        grad_acc = self.gradient_accumulation_steps
        assert train_batch > 0, f"Train batch size: {train_batch} has to be greater than 0"
        assert micro_batch > 0, f"Micro batch size per device: {micro_batch} has to be greater than 0"
        assert grad_acc > 0, f"Gradient accumulation steps: {grad_acc} has to be greater than 0"
        assert train_batch == micro_batch * grad_acc * self.world_size, (
            f"Check batch related parameters. train_batch_size is not equal to "
            f"micro_batch_per_gpu * gradient_acc_step * world_size "
            f"{train_batch} != {micro_batch} * {grad_acc} * {self.world_size}")

    def _do_sanity_check(self) -> None:
        if self.train_batch_size is None and self.train_micro_batch_size_per_gpu is None:
            raise DeepSpeedConfigError(
                f"Either {C.TRAIN_BATCH_SIZE} or {C.TRAIN_MICRO_BATCH_SIZE_PER_GPU} "
                "must be set in the DeepSpeed config")
        self._batch_assertion()
        if self.fp16_enabled and self.bf16_enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")
        if self.bf16_stochastic_rounding and not self.bf16_enabled:
            raise DeepSpeedConfigError(
                "bf16.stochastic_rounding requires bf16.enabled (it is the "
                "master-free bf16 update mode)")
        if self.zero_enabled and self.zero_optimization_stage > C.MAX_STAGE_ZERO_OPTIMIZATION:
            raise DeepSpeedConfigError(
                f"ZeRO stage {self.zero_optimization_stage} > max "
                f"{C.MAX_STAGE_ZERO_OPTIMIZATION}")
        if self.zero_config.overlap_comm and not self.zero_enabled:
            logger.warning(
                f"{C.ZERO_OVERLAP_COMM} is set but zero_optimization is "
                "disabled — it only affects the ZeRO paths (for "
                "cpu_offload it selects the bucketed overlapped pipeline)")
        if self.optimizer_name is not None and \
                self.optimizer_name not in C.DEEPSPEED_OPTIMIZERS:
            logger.warning(
                f"Optimizer '{self.optimizer_name}' is not a built-in optimizer; "
                "it will be resolved against optax at engine construction.")

    # ------------------------------------------------------------------ #
    @property
    def precision_dtype(self) -> str:
        if self.bf16_enabled:
            return "bfloat16"
        if self.fp16_enabled:
            return "float16"
        return "float32"

    def print(self, name: str = "DeepSpeedConfig") -> None:
        logger.info(f"{name}:")
        for k in sorted(self.__dict__):
            if k.startswith("_"):
                continue
            logger.info(f"  {k} = {self.__dict__[k]}")

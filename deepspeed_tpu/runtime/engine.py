"""The core training engine.

Capability parity with reference ``runtime/engine.py`` (DeepSpeedEngine,
engine.py:95): config-driven construction, optimizer selection matrix
(engine.py:588-628), fp16/bf16 precision with dynamic loss scaling and
overflow-skip (engine.py:630-710, 1000-1085), gradient accumulation
boundaries, gradient clipping, data-parallel gradient averaging
(engine.py:1122-1195), LR scheduling tied to successful steps, checkpoint
save/load with tag dirs + ``latest`` pointer (engine.py:1472-1572), timers
and throughput reporting, ``deepspeed_io`` data loading.

TPU-native architecture (NOT a translation):
- One jit-compiled ``train_step`` fuses the whole iteration: a ``lax.scan``
  over grad-accumulation micro-batches computing grads (the reference's
  forward/backward/hook machinery), gradient averaging via XLA SPMD (the
  batch is sharded over the mesh "data" axis, so grads *are born* as partial
  sums that XLA reduces — the bucketed-allreduce engine code path),
  nan/inf-gated optimizer apply via ``jnp.where`` (the overflow-skip path),
  and loss-scale state update. No hooks, no streams: XLA's latency-hiding
  scheduler overlaps the reduction with backward compute.
- ZeRO stages 1/2 are *sharding annotations*: optimizer state (stage >= 1)
  is laid out with a "data"-axis NamedSharding, which makes XLA compile the
  grad reduction as reduce-scatter + sharded update + all-gather — exactly
  the communication schedule stage2.py implements by hand (see zero/
  partition.py for the spec builder).
- fp32 master params live in ``state.params``; compute casts to
  bf16/fp16 per the config (the reference's FP16_Optimizer master-weight
  copy, fused_optimizer.py:17).
- The torch-style ``forward()/backward()/step()`` trio is provided as a
  compatibility layer driving the same jitted paths.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .async_ckpt import (AsyncCheckpointer, CheckpointSnapshot,
                         LATEST_FILE, META_FILE, PreemptSaver,
                         commit_snapshot, crash_point, is_complete)
from .config import DeepSpeedConfig
from .dataloader import DeepSpeedDataLoader, RepeatingLoader
from .fp16.loss_scaler import (LossScaleState, make_loss_scale_state,
                               update_loss_scale)
from .lr_schedules import get_lr_schedule
from .progressive_layer_drop import ProgressiveLayerDrop
from .utils import (clip_coefficient, clip_grad_norm_, global_norm,
                    tree_has_inf_or_nan)
from .zero.partition import zero_shardings
from .. import constants as C
from ..monitor import Telemetry, startup
from ..monitor.memory import analytic_state_bytes
from ..monitor.telemetry import spans_recorded
from ..monitor.training import TrainingTimeline
from ..ops.optimizers import build_optimizer
from ..parallel import comm
from ..parallel.topology import (build_mesh, DP_AXIS, EP_AXIS, MP_AXIS,
                                 SLICE_AXIS)
from ..utils.logging import log_dist, logger
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer

try:
    from flax import serialization as flax_serialization
except Exception:  # pragma: no cover
    flax_serialization = None

MODEL_FILE = "mp_rank_00_model_states.msgpack"
MODEL_FILE_FMT = "mp_rank_{:02d}_model_states.msgpack"
OPTIM_FILE_FMT = "zero_pp_rank_0_mp_rank_00_optim_states.msgpack"
OPTIM_SHARD_FMT = "zero_pp_rank_{}_mp_rank_00_optim_states.msgpack"
# engine_meta.json's fused_moment_layout (save_checkpoint says which is which).
FUSED_MOMENT_LAYOUT = 3


def _spec_axis(sharding, axis_name: str):
    """Index of the dimension a NamedSharding partitions over ``axis_name``
    (None when unsharded on that axis)."""
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return None
    for i, entry in enumerate(spec):
        if entry == axis_name or (isinstance(entry, (tuple, list)) and
                                  axis_name in entry):
            return i
    return None


def _cast_floats(tree: Any, dtype) -> Any:
    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(cast, tree)


def _tree_select(pred, on_true: Any, on_false: Any) -> Any:
    """Elementwise pytree select (used for overflow-skip)."""
    return jax.tree_util.tree_map(
        lambda t, f: jnp.where(pred, t, f) if hasattr(t, "dtype") else t,
        on_true, on_false)


def _make_raw_scaled_loss(loss_fn, accepts_pld: bool, gas: int):
    """The scaled-loss core every grad builder shares: params arrive
    already in compute form (cast cache / the stage-3 gather's in-flight
    cast / the caller's _cast_floats wrapper). Returns
    ``(scaled_loss_for_backward, (raw_loss, aux))`` — scaled for the
    fp16 backward, divided by gas so accumulation averages; ``aux`` is
    the loss_fn's auxiliary output (None for plain-loss models — the MoE
    stats dict rides here). ONE definition so the main, trio, and
    offload paths cannot diverge on the scaling semantics."""
    import jax.numpy as _jnp

    def raw_scaled_loss(cparams, mb, key, scale, theta):
        out = loss_fn(cparams, mb, key, pld_theta=theta) if accepts_pld \
            else loss_fn(cparams, mb, key)
        loss, aux = (out if isinstance(out, tuple) else (out, None))
        return (loss.astype(_jnp.float32) * scale) / gas, (loss, aux)
    return raw_scaled_loss


def _overflow_resolution(state: "EngineState", overflow, *, fp16: bool,
                         static_scale: bool, scale_window: int,
                         min_scale: float, hysteresis_init: int
                         ) -> Dict[str, Any]:
    """The overflow-vote bookkeeping every train-step builder shares
    (reference engine.py:1000-1085): on overflow hold the step (so LR
    holds) and count the skip; drive the dynamic loss-scale machine either
    way. Returns the ``EngineState.replace`` fields — params/opt-state
    selection stays with the caller (each path has its own apply)."""
    fields: Dict[str, Any] = dict(
        step=state.step + jnp.where(overflow, 0, 1).astype(jnp.int32),
        skipped_steps=state.skipped_steps +
        jnp.where(overflow, 1, 0).astype(jnp.int32))
    if fp16 and not static_scale:
        ls = LossScaleState(
            loss_scale=state.loss_scale, growth_count=state.growth_count,
            hysteresis=state.hysteresis, dynamic=True,
            scale_window=scale_window, min_scale=min_scale,
            hysteresis_init=hysteresis_init, scale_factor=2.0)
        ls = update_loss_scale(ls, overflow)
        fields.update(loss_scale=ls.loss_scale, growth_count=ls.growth_count,
                      hysteresis=ls.hysteresis)
    return fields


def _clipped_update(grads: Any, state: "EngineState", grad_norm, *, tx,
                    fused_apply, clip: float, master_free: bool = False,
                    sr_key=None) -> Tuple[Any, Any]:
    """Global-norm clip + optimizer apply shared by the train-step
    builders: the fused single-pass Pallas kernel (clip coefficient folded
    into its grad read, stochastic rounding on the in-kernel param write)
    or the optax chain. Returns (new_params, new_opt_state)."""
    if fused_apply is not None:
        coeff = clip_coefficient(grad_norm, clip) \
            if (clip and clip > 0) else None
        return fused_apply(grads, state.opt_state, state.params,
                           clip_coeff=coeff, sr_key=sr_key)
    if clip and clip > 0:
        grads, _ = clip_grad_norm_(grads, clip, precomputed_norm=grad_norm)
    updates, new_opt = tx.update(grads, state.opt_state, state.params)
    import optax
    if master_free:
        # Master-free bf16: the f32 update lands on the bf16 param via
        # unbiased stochastic rounding — sub-ulp updates survive in
        # expectation instead of being dropped by round-to-nearest
        # (ops/stochastic_rounding.py).
        from ..ops.stochastic_rounding import tree_stochastic_round_bf16
        summed = jax.tree_util.tree_map(
            lambda p, u: p.astype(jnp.float32) + u, state.params, updates)
        return tree_stochastic_round_bf16(summed, sr_key), new_opt
    return optax.apply_updates(state.params, updates), new_opt


class EngineState:
    """Pytree of everything the jitted step carries. Registered manually to
    stay dependency-light and serialization-friendly."""

    def __init__(self, step, params, opt_state, loss_scale, growth_count, hysteresis,
                 skipped_steps, cast_params=None, dcn_error=None):
        self.step = step
        self.params = params
        self.opt_state = opt_state
        self.loss_scale = loss_scale
        self.growth_count = growth_count
        self.hysteresis = hysteresis
        self.skipped_steps = skipped_steps
        # Persistent compute-dtype copy of ``params`` (None when the
        # engine computes in fp32 / owns no cache): re-reading 3 GB of
        # fp32 masters to cast them every step is pure HBM waste; the
        # train step refreshes this cache in the same fused pass as the
        # optimizer update, and _place_state re-derives it whenever params
        # are replaced from outside (checkpoint load), so it can never
        # serve stale weights.
        self.cast_params = cast_params
        # Multi-slice DCN-compression error feedback (None unless
        # zero_optimization.dcn_compression is live): per-leaf
        # [slices, *shard] f32 buffers — each (slice, dp-rank) carries
        # the residual its 1-bit-compressed inter-slice transmissions
        # have not yet delivered (parallel/multislice.py). Like 1-bit
        # Adam's worker_error, it is genuinely per-member state; unlike
        # it, it is NOT checkpointed (a resume restarts the feedback at
        # zero — a one-step compression bias, self-correcting).
        self.dcn_error = dcn_error

    def replace(self, **kw) -> "EngineState":
        d = dict(step=self.step, params=self.params, opt_state=self.opt_state,
                 loss_scale=self.loss_scale, growth_count=self.growth_count,
                 hysteresis=self.hysteresis, skipped_steps=self.skipped_steps,
                 cast_params=self.cast_params, dcn_error=self.dcn_error)
        d.update(kw)
        return EngineState(**d)


jax.tree_util.register_pytree_node(
    EngineState,
    lambda s: ((s.step, s.params, s.opt_state, s.loss_scale, s.growth_count,
                s.hysteresis, s.skipped_steps, s.cast_params, s.dcn_error),
               None),
    lambda _, ch: EngineState(*ch))


class DeepSpeedEngine:
    """Config-driven training engine over a device mesh."""

    @startup.engine_init("training")    # a row of the start-up ledger
    def __init__(self, args=None, model=None, optimizer=None, model_params=None,
                 training_data=None, lr_scheduler=None, mpu=None,
                 dist_init_required=None, collate_fn=None,
                 config: Union[str, Dict[str, Any], None] = None, rng=None,
                 mesh: Optional[Mesh] = None, dont_change_device: bool = False,
                 param_shardings=None, sparse_grad_filter=None,
                 grads_fn=None, zero3_scan=None):
        if dist_init_required is None or dist_init_required:
            comm.init_distributed()

        # Manually-differentiated training path: ``grads_fn(params, batch,
        # rng, scale) -> (unscaled_loss, scale-multiplied grads)`` replaces
        # value_and_grad in the train step (the 1F1B pipeline computes its
        # gradients inside one primal scan — reverse-mode autodiff can't
        # interleave fwd/bwd ticks). ``scale`` is the fp16 loss scale (a
        # traced 1.0 otherwise); a 3-arg fn is accepted for scale-oblivious
        # models (bf16/fp32 only).
        if grads_fn is not None:
            import inspect
            try:
                n_params = len(inspect.signature(grads_fn).parameters)
            except (TypeError, ValueError):
                n_params = 4
            if n_params < 4:
                _inner_grads_fn = grads_fn
                grads_fn = lambda p, b, r, scale: _inner_grads_fn(p, b, r)
        self._direct_grads_fn = grads_fn
        self.mpu = mpu
        self.mesh = mesh if mesh is not None else self._build_mesh(config)
        self.dp_size = int(self.mesh.shape.get(DP_AXIS, 1))
        # MoE expert parallelism: the `expert` axis factors OUT OF data
        # (it reuses the dp devices), so the batch-replica count — the
        # world size the batch solver and throughput accounting see — is
        # ep * dp, while ZeRO keeps sharding over `data` (within-expert-
        # group) and expert weights shard over `expert`.
        self.ep_size = int(self.mesh.shape.get(EP_AXIS, 1))
        # Multi-slice scale-out: the `slice` axis is OUTERMOST (ICI
        # domains joined by DCN); dp factors WITHIN a slice, so the
        # batch-replica count is slices * ep * dp while ZeRO keeps
        # sharding over `data` (within one slice) and gradient sync goes
        # hierarchical (in-slice reduce-scatter over ICI, inter-slice
        # all-reduce of the 1/dp shards over DCN —
        # parallel/multislice.py).
        self.slice_size = int(self.mesh.shape.get(SLICE_AXIS, 1))
        self.replica_size = self.dp_size * self.ep_size * self.slice_size

        self.config = DeepSpeedConfig(config, mpu=mpu,
                                      world_size=self.replica_size) \
            if not isinstance(config, DeepSpeedConfig) else config
        # The `moe` ds_config block: engine-side expert-parallel truth
        # (mesh axis, metrics schema, wire model). The MODEL is built
        # separately (TransformerConfig.moe) — the train step validates
        # at trace time that a configured block actually has an MoE
        # model behind it.
        self._moe = self.config.moe_config \
            if self.config.moe_config.num_experts > 0 else None
        if self._moe is not None and \
                self._moe.expert_parallel_size != self.ep_size:
            raise ValueError(
                f"moe.expert_parallel_size={self._moe.expert_parallel_size}"
                f" but the mesh '{EP_AXIS}' axis has size {self.ep_size} —"
                " build the mesh with build_mesh(ep=...) to match")
        self._dcn_compression = bool(
            self.config.zero_config.dcn_compression)
        self._validate_engine_config()

        self.loss_fn, init_params = self._normalize_model(model, model_params)
        self.module = model  # reference-API alias

        # Precision: fp32 master weights; compute dtype per config.
        if self.config.bf16_enabled:
            self.compute_dtype = jnp.bfloat16
        elif self.config.fp16_enabled:
            self.compute_dtype = jnp.float16
        else:
            self.compute_dtype = jnp.float32
        # Master-free bf16 (bf16.stochastic_rounding): params live in bf16
        # — no fp32 master copy at all, halving param-state HBM — and the
        # optimizer apply rounds stochastically (unbiased), which is what
        # keeps sub-ulp updates from being systematically dropped
        # (reference stochastic_mode, ops/transformer/transformer.py:
        # 39-151; ops/stochastic_rounding.py here).
        self._master_free = bool(self.config.bf16_stochastic_rounding)
        master_params = _cast_floats(
            init_params,
            jnp.bfloat16 if self._master_free else jnp.float32)

        # LR schedule: config scheduler (pure fn of step) or client scheduler.
        self.lr_scheduler = None
        self._schedule_fn = None
        base_lr = float(self.config.optimizer_params.get("lr", 1e-3)) \
            if self.config.optimizer_params else 1e-3
        if lr_scheduler is not None:
            self.lr_scheduler = lr_scheduler
            self._schedule_fn = lr_scheduler.as_schedule_fn() \
                if hasattr(lr_scheduler, "as_schedule_fn") else lr_scheduler
        elif self.config.scheduler_name is not None:
            self.lr_scheduler = get_lr_schedule(self.config.scheduler_name,
                                                dict(self.config.scheduler_params))
            self._schedule_fn = self.lr_scheduler.as_schedule_fn()
        if self._schedule_fn is None:
            self._schedule_fn = lambda step: jnp.asarray(base_lr, jnp.float32)

        # Optimizer (selection matrix parity, engine.py:588-628).
        self.client_optimizer = optimizer
        self._onebit = (optimizer is None and
                        (self.config.optimizer_name or "").lower() ==
                        C.ONEBIT_ADAM_OPTIMIZER)
        # Persistent compute-dtype param cache (EngineState.cast_params):
        # only the main train-step path consumes it; the offload/onebit/
        # sparse paths cast inside their own programs, and fp32 compute
        # needs no cast at all.
        self._use_cast_cache = (
            self.compute_dtype != jnp.float32 and not self._onebit and
            not self.config.zero_config.cpu_offload and
            not self.config.sparse_gradients_enabled and
            not self._master_free and   # params already ARE compute dtype
            # Stage 3 with live dp: a replicated compute-dtype param
            # cache would defeat the sharded-param memory story; the
            # per-layer gather casts the master SHARD instead (1/dp of
            # the cast work, compute-dtype wire — stage3.gather_cast).
            # dp=1 stage-3 configs keep the cache: nothing is sharded
            # there, so losing it would just re-cast the tree per step.
            not (self.zero_optimization_stage() >= 3 and self.dp_size > 1))
        if self._master_free and (
                self._onebit or self.config.zero_config.cpu_offload or
                self.config.sparse_gradients_enabled):
            raise ValueError(
                "bf16.stochastic_rounding (master-free mode) composes with "
                "the main train path only — onebit/offload/sparse_gradients "
                "keep their own master-weight story")
        if self._onebit:
            if self.zero_optimization_stage() >= 1:
                raise ValueError(
                    "OnebitAdam composes with ZeRO stage 0 only (reference: "
                    "it is an fp16-wrapper-level optimizer, not a ZeRO one)")
            # fp16 composes: the loss-scale machinery (static or dynamic)
            # runs through BOTH phases, like the reference's OnebitAdam
            # which keeps overflow checks during compression
            # (onebit_adam.py:104-228). Overflow skips the step without
            # committing error feedback (ops/onebit.py).
            if param_shardings is not None:
                raise NotImplementedError(
                    "OnebitAdam + tensor-parallel param_shardings: the "
                    "compressed step runs params replicated over dp; "
                    "combining with a TP layout would silently all-gather "
                    "every step")
        self.tx = self._configure_optimizer(optimizer)
        if getattr(self.tx, "fused_apply", None) is not None and \
                param_shardings is not None and optimizer is None:
            # Fused apply flattens leaves into contiguous chunk buffers,
            # which would silently all-gather TP-sharded params every
            # step — fall back to the per-leaf optax chain there (parity
            # holds everywhere the fused path stays on).
            logger.info("optimizer.params.fused: disabled under tensor-"
                        "parallel param_shardings (flattened chunks do not "
                        "compose with TP layouts); using the optax apply")
            fallback = dict(self.config.optimizer_params or {})
            fallback[C.OPTIMIZER_FUSED] = False
            self.tx = build_optimizer(
                self.config.optimizer_name or C.ADAM_OPTIMIZER, fallback,
                self._schedule_fn)
        self._fused_apply = getattr(self.tx, "fused_apply", None)
        # One-pass clipped update (ops/fused_update.fused_step): the
        # global-norm reduction, fp16 unscale, overflow vote+skip, clip,
        # and the compute-dtype cast-cache refresh all ride the single
        # HBM pass over optimizer state — param/m/v are read exactly
        # once per step. None => the historical two-pass sequencing
        # (separate norm read before the fused apply).
        self._fused_step = getattr(self.tx, "fused_step", None)

        # ZeRO-Offload: masters + moments live in host RAM, updated by the
        # C++ SIMD Adam; the device holds ONLY compute-dtype params and
        # zero bytes of optimizer state (stage2.py:775-873 parity).
        scaler_cfg = self._loss_scaler_config()
        self._offload: Optional["ZeroOffloadOptimizer"] = None
        if self.config.zero_config.cpu_offload and \
                self.zero_optimization_stage() >= 1:
            from .zero.offload import ZeroOffloadOptimizer
            procs = jax.process_count()
            part_kwargs = {}
            if procs > 1:
                # Multi-host: each process owns host partition
                # process_index/process_count of the masters + moments
                # (reference stage2.py:775-873 each-rank-updates-its-
                # partition). The partition axis follows the dp shard rule
                # (axis_divisor=dp) so it is the same axis the device grads
                # are sharded on; grads/params are explicitly repartitioned
                # to process-local shardings around the host step
                # (_offload_partition_shardings), so no assumption about
                # device order is needed. The clip norm is allreduced
                # across processes via the host channel.
                divisor = self.dp_size if self.dp_size % procs == 0 \
                    else procs
                part_kwargs = dict(
                    partition_rank=jax.process_index(),
                    partition_num=procs, axis_divisor=divisor,
                    sumsq_allreduce=comm.host_allreduce_sum)
            if self._direct_grads_fn is not None:
                # train_batch routes offload configs to the offload grad
                # pass (its own autodiff) — a direct-grads model would be
                # silently ignored, not composed.
                raise ValueError(
                    "pipeline.schedule='1f1b' does not compose with "
                    "zero_optimization.cpu_offload: the offload path "
                    "computes grads via its own autodiff pass (use the "
                    "gpipe schedule)")
            self._offload = ZeroOffloadOptimizer(
                master_params, self.config.optimizer_name,
                dict(self.config.optimizer_params or {}), self._schedule_fn,
                self.compute_dtype,
                gradient_clipping=self.gradient_clipping(),
                fp16=self.config.fp16_enabled, scaler_cfg=scaler_cfg,
                bucket_bytes=self.config.zero_config.offload_bucket_size,
                host_threads=self.config.zero_config.offload_host_threads,
                **part_kwargs)
            # overlap_comm selects the bucketed overlapped pipeline (D2H /
            # host Adam / H2D streamed per bucket through the worker pool).
            # Multi-host keeps the serial path: its D2H/H2D go through
            # whole-tree XLA reshards (_local_offload_grads /
            # _assemble_offload_params), which have no per-bucket handle.
            self._offload_overlap = bool(
                self.config.zero_config.overlap_comm)
            if self._offload_overlap and procs > 1:
                log_dist("zero_optimization.overlap_comm: overlapped "
                         "offload is single-process only for now; "
                         "falling back to the serial offload step",
                         ranks=[0])
                self._offload_overlap = False
            self._offload_down = None   # lazy per-leaf process shardings
            self._offload_down_fn = None
            self._offload_up_fn = None
            self._offload_param_shardings = None  # lazy flat leaf shardings
            # device params = compute-dtype cast; no device moments at all.
            # (Multi-host: master_tree() is partition-local — keep the full
            # init params for the replicated device state; the per-step
            # H2D path assembles from partitions thereafter.)
            if self._offload.partition_num == 1:
                master_params = self._offload.master_tree()

        # State. The optimizer state is *born sharded*: its structure comes
        # from eval_shape (zero bytes), the shardings are computed from that,
        # and tx.init runs inside a jit with out_shardings — at no point do
        # two full copies of the moments exist (a doubled fp32 Adam state
        # for a 774M model is 12 GB and OOMs the init on one chip).
        self._static_loss_scale = scaler_cfg["static"]
        self._scale_window = scaler_cfg["scale_window"]
        self._min_scale = scaler_cfg["min_scale"]
        self._hysteresis = scaler_cfg["hysteresis"]
        # The shared overflow-resolution config every step builder closes
        # over (one source of truth for _overflow_resolution).
        self._scaler_kw = dict(
            fp16=self.config.fp16_enabled,
            static_scale=self._static_loss_scale,
            scale_window=self._scale_window, min_scale=self._min_scale,
            hysteresis_init=self._hysteresis)
        init_scale = scaler_cfg["init_scale"]
        hysteresis = scaler_cfg["hysteresis"]
        device_params = master_params if self._offload is None \
            else _cast_floats(master_params, self.compute_dtype)
        if self._offload is not None:
            opt_init = None
        elif self._onebit:
            from ..ops.onebit import init_state as onebit_init
            dp_ = self.dp_size

            def opt_init(params):
                # worker_error carries a leading [dp] axis (dp-sharded in
                # _make_state_shardings): it is genuinely PER-RANK state, so
                # declaring it replicated would save/restore only rank 0's
                # error feedback across checkpoints.
                st = onebit_init(params)
                werr = jax.tree_util.tree_map(
                    lambda p: jnp.zeros((dp_,) + p.shape, jnp.float32),
                    params)
                return st._replace(worker_error=werr)
        elif self._master_free:
            # bf16 params but f32 optimizer moments: init from an f32 view
            # so Adam's accumulators don't inherit the bf16 storage dtype
            # (updates then stay f32 end-to-end; only the final apply
            # rounds, stochastically).
            base_opt_init = self.tx.init
            opt_init = lambda params: base_opt_init(
                _cast_floats(params, jnp.float32))
        else:
            opt_init = self.tx.init
        opt_shape = () if opt_init is None \
            else jax.eval_shape(opt_init, device_params)
        self._param_specs = param_shardings
        # ZeRO-3: the parameter tree itself is born dp-sharded (same
        # first-divisible-dim rule as grads and moments — element
        # alignment keeps the optimizer apply shard-local). Leaves the
        # model gathers itself per layer (zero3_scan.covers) keep their
        # layer axis (dim 0) unsharded so per-layer slices stay
        # dp-sharded inside the scan.
        self._zero3 = self.zero_optimization_stage() >= 3 \
            and self.dp_size > 1
        self._zero3_scan_spec = zero3_scan
        self._stage3_specs = None
        self._zero3_covered = None
        if self._zero3:
            from .zero.partition import stage3_param_specs
            covers = zero3_scan.covers if zero3_scan is not None else None
            self._stage3_specs = stage3_param_specs(
                device_params, self.dp_size, DP_AXIS,
                param_specs=self._param_specs, scan_paths=covers)
            flat, ptdef = jax.tree_util.tree_flatten_with_path(
                device_params)
            self._zero3_covered = jax.tree_util.tree_unflatten(
                ptdef, [covers(jax.tree_util.keystr(p)) if covers
                        else False for p, _ in flat])
        self._state_shardings = self._make_state_shardings(
            device_params, opt_shape)
        offload = self._offload is not None
        use_cast_cache = self._use_cast_cache
        compute_dtype = self.compute_dtype
        dcn_live = self._dcn_compression and self.slice_size > 1
        n_slices = self.slice_size

        def _init_state(params):
            return EngineState(
                step=jnp.asarray(0, jnp.int32),
                params=params,
                opt_state=() if offload else opt_init(params),
                loss_scale=jnp.asarray(init_scale, jnp.float32),
                growth_count=jnp.asarray(0, jnp.int32),
                hysteresis=jnp.asarray(hysteresis, jnp.int32),
                skipped_steps=jnp.asarray(0, jnp.int32),
                cast_params=_cast_floats(params, compute_dtype)
                if use_cast_cache else None,
                dcn_error=jax.tree_util.tree_map(
                    lambda p: jnp.zeros(
                        (n_slices,) + tuple(getattr(p, "shape", ())),
                        jnp.float32), params) if dcn_live else None,
            )

        startup.register_program(_init_state, "init_state")
        with startup.span("shard_state", parent="engine_init"):
            self.state = jax.jit(
                _init_state, out_shardings=self._state_shardings)(
                jax.tree_util.tree_map(jnp.asarray, device_params))

        # Host-side counters (reference engine.py:151-158).
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0

        # RNG.
        self._base_rng = rng if rng is not None else jax.random.PRNGKey(42)

        # Data.
        self.collate_fn = collate_fn
        self.training_dataloader = self.deepspeed_io(training_data) \
            if training_data is not None else None
        self._data_iterator = None

        # PLD (reference engine.py:826-827 injects theta into every
        # forward). Detect once whether the loss_fn can consume it; every
        # grad-computing path (train step, offload, onebit, fwd/bwd split)
        # threads theta when it can.
        self.progressive_layer_drop = None
        self._accepts_pld = False
        if self.config.pld_config.enabled:
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=self.config.pld_config.theta,
                gamma=self.config.pld_config.gamma)
            import inspect
            try:
                self._accepts_pld = "pld_theta" in \
                    inspect.signature(self.loss_fn).parameters
            except (TypeError, ValueError):
                self._accepts_pld = False
            if not self._accepts_pld:
                logger.warning("progressive_layer_drop enabled but the "
                               "model's loss_fn takes no pld_theta kwarg — "
                               "layers will not drop")

        # Flops profiler (reference engine.py:801-824 auto-run window):
        # profiled once, analytically, at the configured global step.
        self.flops_profiler = None
        if self.config.flops_profiler_config.enabled:
            from ..profiling.flops_profiler import FlopsProfiler
            self.flops_profiler = FlopsProfiler(
                config=self.config.flops_profiler_config)

        # Observability.
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_micro_batch_size_per_gpu() *
            self.replica_size,
            start_step=2, steps_per_output=self.steps_per_print(),
            synchronized=self.wall_clock_breakdown())

        # Grad buffer for the forward/backward/step compatibility API.
        self._accum_grads = None
        self._stashed_batch = None

        # Jitted paths (built lazily on first use).
        self._train_step_fn = None
        self._eval_step_fn = None
        self._apply_grads_fn = None
        self._sparse_grad_fn = None
        self._sparse_apply_fn = None

        # Sparse (CSR) embedding gradients (reference engine.py:179-186
        # detects torch.nn.Embedding modules; :1197-1253 routes their grads
        # through a values+indices allgather instead of dense allreduce).
        self._sparse_mask = None
        self._sparse_names: List[str] = []
        self.sparse_comm_stats: Dict[str, int] = {}
        if self.config.sparse_gradients_enabled:
            self._init_sparse_gradients(sparse_grad_filter)
        self._grad_step_fn = None
        self._offload_grad_fn = None
        self.offload_timings = None   # last step's device/D2H/host breakdown

        # ZeRO-2 gradient-sync honesty: resolve which lowering this engine
        # actually runs (audited, not assumed) and say so — with the wire
        # bytes each lowering costs per step — instead of treating
        # reduce_scatter/overlap_comm as docstring-advisory knobs.
        self._grad_sync_mode = self._resolve_grad_sync()
        self._prefetch_depth = int(self.config.zero_config.prefetch_depth)
        if self._zero3 and zero3_scan is not None:
            self._bind_zero3_scan(zero3_scan)
        # MoE all-to-all pricing needs the per-device token count, which
        # only the first batch reveals (_maybe_refresh_moe_wire).
        self._moe_tokens_per_device = None
        if self._moe is not None and self.ep_size > 1 and \
                self._param_specs is None:
            logger.warning(
                "moe.expert_parallel_size > 1 without param_shardings: "
                "expert weights stay replicated on every device — pass "
                "deepspeed_tpu.moe.sharding specs (e.g. "
                "gpt2_moe_param_shardings) to born-shard them over the "
                "expert axis")
        self._wire_bytes, self._wire_detail = self._grad_wire_bytes()
        self._log_comm_plan()

        # Telemetry (monitor/): per-step records + spans + recompile
        # sentinel + memory watermarks. Inert when disabled; when enabled,
        # all device access is batched at report boundaries (zero added
        # hot-path syncs — the _maybe_log discipline, subsystem-wide).
        self.telemetry = Telemetry(
            self.config.telemetry_config,
            default_report_steps=self.steps_per_print(),
            meta=dict(
                dp=self.dp_size,
                ep=self.ep_size,
                slices=self.slice_size,
                zero_stage=self.zero_optimization_stage(),
                precision=self.config.precision_dtype,
                cpu_offload=self._offload is not None,
                grad_sync_mode=self._grad_sync_mode,
                wire_bytes_per_step=self._wire_bytes,
                wire_bytes_ici=self._wire_bytes - self._wire_bytes_dcn,
                wire_bytes_dcn=self._wire_bytes_dcn,
                dcn_compression=self._dcn_compression,
                wire_terms=self._wire_terms(),
                wire_detail=self._wire_detail,
                train_batch_size=self.train_batch_size(),
                gradient_accumulation_steps=
                self.gradient_accumulation_steps(),
                **({"moe": dict(
                    num_experts=self._moe.num_experts,
                    top_k=self._moe.top_k,
                    capacity_factor=self._moe.capacity_factor,
                    expert_parallel_size=self.ep_size)}
                   if self._moe is not None else {})))
        # The training timeline: one row a train_batch call, always on
        # (host clock reads only; monitor/training.py).
        self.timeline = TrainingTimeline()
        # Weakref, not a bound closure: the Telemetry outlives engines via
        # its atexit flush hook, and a strong closure here would pin the
        # engine's entire device state for process lifetime.
        import weakref
        _engine_ref = weakref.ref(self)
        self.telemetry.step_provider = lambda: (
            _engine_ref().global_steps if _engine_ref() is not None else -1)
        # Analytic per-device model-state footprint from the committed
        # shardings (host metadata only) — the watermark baseline. Under
        # stage 3 the params price at their dp-shard (the shardings say
        # so) and the bounded gather working set is ADDED: a healthy
        # stage-3 step legitimately holds prefetch_depth+1 gathered
        # layers (or the compute-dtype leaf-at-use set on generic
        # models) on top of the resident state.
        gather_ws = 0
        if self._zero3:
            from .zero.stage3 import gather_working_set_bytes
            _spec = self._zero3_scan_spec
            gather_ws = gather_working_set_bytes(
                self.state.params, self._stage3_specs, DP_AXIS,
                jnp.dtype(self.compute_dtype).itemsize,
                prefetch_depth=self._prefetch_depth,
                scan_paths=_spec.covers if _spec is not None else None,
                mesh=self.mesh)
            self.telemetry.meta["zero3_prefetch_depth"] = \
                self._prefetch_depth
            self.telemetry.meta["zero3_gather_working_set_bytes"] = \
                int(gather_ws)
        self.telemetry.set_analytic_footprint(
            analytic_state_bytes(self.state,
                                 gather_working_set=gather_ws))
        # Roofline cost model: built ONCE at the first report boundary
        # (every active step path has compiled by then); see
        # _maybe_build_cost_model.
        self._cost_model_built = False

        # Health taps (monitor/health.py): the step programs return one
        # [num_leaves] f32 array of per-leaf grad sum-of-squares that
        # rides the telemetry ring to the batched drain fetch — NaN/Inf
        # provenance (first non-finite leaf + layer) with zero added
        # device syncs. The TapSpec decoding it is host metadata from
        # the params tree.
        self._health_tap_fn = None
        hcfg = getattr(self.config.telemetry_config, "health", None)
        if self.telemetry.enabled and self.telemetry.health is not None \
                and hcfg is not None and hcfg.grad_taps:
            from ..monitor.health import TapSpec, leaf_sq_taps
            self.telemetry.set_tap_spec(TapSpec.from_tree(
                self.state.params))
            self._health_tap_fn = leaf_sq_taps

        # Async / preemption-safe checkpointing (runtime/async_ckpt.py):
        # the writer thread, the auto-save cadence, and the SIGTERM
        # final-save handler. All inert unless the `checkpoint` config
        # block opts in.
        ckcfg = self.config.checkpoint_config
        self._ckpt_dir = ckcfg.save_dir
        self._ckpt_every = int(ckcfg.snapshot_every)
        self._ckpt_max_pending = int(ckcfg.max_pending_snapshots)
        self._ckpt_writer_timeout = float(ckcfg.writer_timeout_s)
        self._ckpt_fsync = bool(ckcfg.fsync)
        self._last_saved_step = -1
        self._async_ckpt = None
        self._preempt_saver = None
        if ckcfg.async_save:
            self._async_ckpt = AsyncCheckpointer(
                telemetry=self.telemetry,
                writer_timeout_s=self._ckpt_writer_timeout,
                dump_dir=self.config.telemetry_config.output_path
                or "./runs")
        if self._ckpt_dir and ckcfg.preempt_save:
            # Installed AFTER Telemetry built its flight recorder: on
            # SIGTERM this handler runs FIRST (last installed wins),
            # commits the final checkpoint, then chains to the flight
            # recorder's handler — which persists FLIGHT.json and
            # re-raises so the exit code stays honest.
            self._preempt_saver = PreemptSaver(self, self._ckpt_dir)
            self._preempt_saver.install()
        if ckcfg.async_save or self._ckpt_every > 0:
            self.telemetry.meta.setdefault("checkpoint", {
                "async": bool(ckcfg.async_save),
                "snapshot_every": self._ckpt_every})

        if type(self.state.opt_state).__name__ == "FusedAdamState":
            # How often the in-place mechanism engages, from shapes only.
            # Last in start-up: an event writes the stream's meta record,
            # which everything above has been adding to.
            from ..ops.fused_update import plan_summary
            plan = plan_summary(self.state.params)
            log_dist(
                "fused optimizer plan: {leaves_in_place} leaves "
                "({bytes_in_place:,} B, {pct:.2f}% of optimizer bytes) "
                "updated in place, {leaves_packed} leaves "
                "({bytes_packed:,} B) packed, {kernel_programs} Adam "
                "kernel programs".format(
                    pct=100.0 * plan["share_in_place"], **plan), ranks=[0])
            self.telemetry.event("fused_update_plan", plan)
        log_dist(f"DeepSpeedEngine initialized: dp={self.dp_size}, "
                 f"dtype={self.compute_dtype.__name__}, "
                 f"zero_stage={self.zero_optimization_stage()}", ranks=[0])

    # ------------------------------------------------------------------ #
    # Construction helpers
    # ------------------------------------------------------------------ #
    def _build_mesh(self, config) -> Mesh:
        mp = pp = sp = ep = slices = 1
        if isinstance(config, str):
            from .config_utils import load_config_json
            config = load_config_json(config)
        if isinstance(config, DeepSpeedConfig):
            mc = config.mesh_config
            mp, pp, sp = (mc.model_parallel_size or 1, mc.pipe_parallel_size or 1,
                          mc.sequence_parallel_size or 1)
            ep = config.moe_config.expert_parallel_size or 1
            slices = mc.num_slices or 1
        elif isinstance(config, dict):
            mesh_cfg = config.get(C.MESH, {})
            mp = mesh_cfg.get(C.MESH_MODEL_PARALLEL_SIZE, 1) or 1
            pp = mesh_cfg.get(C.MESH_PIPE_PARALLEL_SIZE, 1) or 1
            sp = mesh_cfg.get(C.MESH_SEQUENCE_PARALLEL_SIZE, 1) or 1
            ep = config.get(C.MOE, {}).get(
                C.MOE_EXPERT_PARALLEL_SIZE, 1) or 1
            slices = mesh_cfg.get(C.MESH_NUM_SLICES, 1) or 1
        return build_mesh(mp=mp, pp=pp, sp=sp, ep=ep, slices=slices)

    def _validate_engine_config(self) -> None:
        # Stage 3 (parameter partitioning) goes PAST the reference, which
        # raises for any stage > 2 (engine.py:707-708). Composition
        # limits: the 1F1B pipeline computes grads inside its own primal
        # scan and cannot thread the per-layer gather/scatter schedule.
        if self.config.zero_optimization_stage >= 3 and \
                self._direct_grads_fn is not None:
            raise ValueError(
                "ZeRO stage 3 does not compose with pipeline grads_fn "
                "(1F1B computes grads inside its own primal scan); use "
                "stage <= 2 with the pipeline engine")
        if self.ep_size > 1:
            # Expert parallelism composes with the MAIN train path: the
            # paths below run their own shard_maps/autodiff over `data`
            # only and would silently mis-shard the (expert, data) batch.
            blockers = []
            if self._direct_grads_fn is not None:
                blockers.append("pipeline grads_fn (1F1B)")
            if self.config.zero_config.cpu_offload:
                blockers.append("zero_optimization.cpu_offload")
            if self.config.sparse_gradients_enabled:
                blockers.append("sparse_gradients")
            if (self.config.optimizer_name or "").lower() == \
                    C.ONEBIT_ADAM_OPTIMIZER:
                blockers.append("OnebitAdam")
            if blockers:
                raise ValueError(
                    "moe expert_parallel_size > 1 composes with the main "
                    f"train path only; drop {', '.join(blockers)}")
        if self.slice_size > 1:
            # Multi-slice scale-out composes with the MAIN train path on
            # a (slice, data) mesh under ZeRO stage >= 2 (stages 2 AND
            # 3: the axis-algebra planner places the stage-3 param
            # gathers on `data`/ICI and only the 1/dp residual on DCN).
            # Each remaining refusal is the planner-derived reason: the
            # hierarchical sync's DCN saving IS the in-slice reduce-
            # scatter (dense modes would ship grad-sized trees over
            # DCN), and every other path computes grads without the
            # slice axis in scope (silently missing the inter-slice
            # reduction entirely).
            from ..parallel.axis_algebra import MeshFactorization
            blockers = []
            if self.zero_optimization_stage() < 2:
                blockers.append("zero_optimization.stage >= 2 (got "
                                f"{self.zero_optimization_stage()}; the "
                                "planner's in-slice tier is a reduce-"
                                "scatter — dense grads have no 1/dp "
                                "residual to confine to DCN)")
            if not self.config.zero_config.reduce_scatter:
                blockers.append("reduce_scatter: true")
            try:
                MeshFactorization.from_mesh(self.mesh).outer_axis
            except ValueError as e:
                # slice x expert: the planner supports one outer
                # residual axis — quote its reason verbatim.
                blockers.append(f"expert_parallel_size == 1 ({e})")
            if self._direct_grads_fn is not None:
                blockers.append("no pipeline grads_fn (1F1B)")
            if self.config.zero_config.cpu_offload:
                blockers.append("no zero_optimization.cpu_offload")
            if self.config.sparse_gradients_enabled:
                blockers.append("no sparse_gradients")
            if (self.config.optimizer_name or "").lower() == \
                    C.ONEBIT_ADAM_OPTIMIZER:
                blockers.append("no OnebitAdam (dcn_compression is the "
                                "multislice home of the 1-bit wire)")
            # param_shardings (TP layouts) are re-checked when the grad
            # sync resolves — _param_specs is bound after this runs.
            if getattr(self, "_param_specs", None) is not None:
                blockers.append("no tensor-parallel param_shardings")
            for ax, size in self.mesh.shape.items():
                if ax not in (SLICE_AXIS, DP_AXIS) and int(size) > 1:
                    blockers.append(f"'{ax}' axis of size 1 (got {size})")
            if blockers:
                raise ValueError(
                    f"mesh slices={self.slice_size} (hierarchical "
                    "ICI/DCN gradient sync) requires: "
                    + "; ".join(blockers))
        if self._dcn_compression and self.slice_size <= 1:
            raise ValueError(
                "zero_optimization.dcn_compression requires a multi-"
                "slice mesh (mesh.slices > 1 / build_mesh(slices=...)): "
                "there is no DCN hop to compress on a single slice")

    def _normalize_model(self, model, model_params) -> Tuple[Callable, Any]:
        """Accept a flax module or a loss callable; return loss_fn(params,
        batch, rng) -> loss | (loss, aux) plus initial params."""
        if model is None:
            raise ValueError("deepspeed_tpu requires a model (flax module or "
                             "loss_fn(params, batch, rng))")
        if hasattr(model, "apply") and hasattr(model, "init"):
            if model_params is None:
                raise ValueError("Pass model_params=module.init(...) for flax modules")

            def loss_fn(params, batch, rng):
                inputs = batch if isinstance(batch, (tuple, list)) else (batch,)
                # flax ignores rng collections the module doesn't use.
                return model.apply(params, *inputs, rngs={"dropout": rng})
            return loss_fn, model_params
        if callable(model):
            if model_params is None:
                raise ValueError("Pass model_params with a callable loss_fn model")
            return model, model_params
        raise TypeError(f"Unsupported model type {type(model)}")

    def _configure_optimizer(self, client_optimizer):
        import optax
        if client_optimizer is not None:
            if isinstance(client_optimizer, optax.GradientTransformation):
                return client_optimizer
            if callable(client_optimizer):
                return client_optimizer(self._schedule_fn)
            raise TypeError("optimizer must be an optax.GradientTransformation "
                            "or callable(schedule_fn) -> transformation")
        name = self.config.optimizer_name or C.ADAM_OPTIMIZER
        # ZeRO-shard-local fused apply: on a pure-dp mesh with sharded
        # optimizer state, the fused kernels run under shard_map over dp
        # so the moments are never gathered (each device updates exactly
        # its ZeRO shard). Meshes with live pipe/seq/model axes keep the
        # plain lowering (partial-auto shard_map is outside this jax's
        # capability envelope — tests/capability.py).
        # In-place leaves enter the region by their ZeRO spec: stage 3's
        # (made after the optimizer, hence the late lookup) or, where
        # there is none, the first-divisible-dim rule. Weakref, not a
        # bound closure: self.tx would hold the engine in a cycle and
        # `del engine` would leave its device state to the garbage
        # collector's next pass.
        import weakref
        _engine_ref = weakref.ref(self)
        mesh_kw = dict(mesh=self.mesh, shard_axis=DP_AXIS,
                       leaf_specs=lambda: getattr(
                           _engine_ref(), "_stage3_specs", None)) \
            if self._fused_shard_local() else {}
        return build_optimizer(name, dict(self.config.optimizer_params or {}),
                               self._schedule_fn, **mesh_kw)

    def _fused_shard_local(self) -> bool:
        """True when the fused optimizer kernels run shard-local over dp
        (pure-dp mesh, ZeRO state sharded). The ONE predicate both the
        optimizer construction and the roofline's optimizer_apply
        pricing use — they must agree or the per-device byte figures
        lie."""
        return (self.zero_optimization_stage() >= 1 and self.dp_size > 1
                and all(int(s) == 1 for a, s in self.mesh.shape.items()
                        if a != DP_AXIS))

    def _grads_stay_narrow(self) -> bool:
        """True when the jitted train step hands the one-pass fused
        apply the gradients at the width the backward wrote them (the
        compute dtype) instead of widening them to f32 first: one
        device, one micro-batch, no loss scale. Every path that SUMS
        gradients (dp > 1, the accumulation scan, 1F1B, the trio's
        accumulator) sums in f32 and hands that on. The ONE predicate
        the step builder and the optimizer_apply pricing share."""
        return (self._fused_step is not None
                and not self.config.fp16_enabled
                and self.mesh.devices.size == 1
                and self._direct_grads_fn is None
                and self._scan_microbatches() == 1)

    def _apply_grad_dtype(self):
        """The width at which gradients reach the fused apply: the
        backward's own (the compute dtype, where the loss differentiates
        compute-dtype parameters) on the jitted step's narrow path, f32
        from every path that sums them."""
        if self._train_step_fn is not None and self._grads_stay_narrow() \
                and (self._use_cast_cache or self._master_free):
            return self.compute_dtype
        return jnp.float32

    def _loss_scaler_config(self) -> Dict[str, Any]:
        cfg = self.config
        if cfg.fp16_enabled:
            if cfg.fp16_loss_scale and cfg.fp16_loss_scale > 0:
                return dict(static=True, init_scale=float(cfg.fp16_loss_scale),
                            scale_window=cfg.fp16_loss_scale_window,
                            min_scale=float(cfg.fp16_min_loss_scale),
                            hysteresis=cfg.fp16_hysteresis)
            return dict(static=False, init_scale=2.0 ** cfg.fp16_initial_scale_power,
                        scale_window=cfg.fp16_loss_scale_window,
                        min_scale=float(cfg.fp16_min_loss_scale),
                        hysteresis=cfg.fp16_hysteresis)
        return dict(static=True, init_scale=1.0, scale_window=1000,
                    min_scale=1.0, hysteresis=2)

    def _resolve_grad_sync(self) -> str:
        """Which ZeRO-2 gradient-sync lowering this engine runs:

        - ``"none"``: stage < 2 or dp == 1 — nothing to scatter;
        - ``"allreduce"``: ``reduce_scatter: false`` — the dense all-reduce
          path (grads stay replicated, reference semantics);
        - ``"declarative"``: declared grad shardings, GSPMD lowers;
        - ``"explicit"``: grads computed under shard_map with
          ``lax.psum_scatter`` — the lowering is guaranteed by
          construction.

        ``grad_sync: auto`` (default) audits the declarative lowering via
        the hlo_audit probe and goes explicit iff the partitioner falls
        back to a full all-reduce + slice (the known declarative-ZeRO
        failure mode: grads materialize unpartitioned, 2x the wire).
        """
        zc = self.config.zero_config
        if self.zero_optimization_stage() < 2 or self.dp_size <= 1:
            return "none"
        if not zc.reduce_scatter:
            return "allreduce"
        # The explicit path wraps the grad computation in a fully-manual
        # shard_map over the REPLICA axes — plain dp, or the factored
        # (slice, data) / (expert, data) meshes (each leaf psum_scatters
        # over `data`, then the residual all-reduces over the outer
        # axis: the hierarchical DCN hop / the cross-expert-group dense
        # sync). Paths with their own grad programs (1F1B direct grads,
        # onebit, sparse-CSR) and meshes with additional live axes
        # (TP/PP/SP, where replica-manual + rest-auto is a partial-auto
        # shard_map) keep the declarative constraint. param_shardings
        # compose iff every spec is expert-only (the MoE layout — the
        # factored path slices those at the shard_map boundary); TP
        # layouts do not. The offload grad pass routes through the same
        # explicit builder since stage 3 landed (its bucket regroup
        # happens OUTSIDE the shard_map) — this is what retired the last
        # lint waiver (collective_placement:offload_grad_step:
        # grad-allreduce).
        replica_axes = (DP_AXIS, SLICE_AXIS, EP_AXIS)
        specs_ok = self._param_specs is None
        if not specs_ok and self.ep_size > 1:
            from ..moe.sharding import is_expert_spec

            def spec_manual_ok(sp) -> bool:
                if not isinstance(sp, P):
                    return False
                if is_expert_spec(sp):
                    return True
                # Entries over size-1 mesh axes are no-op shardings (the
                # gpt2 TP specs name `model` even on an mp=1 mesh).
                for entry in sp:
                    for ax in ((entry,) if isinstance(entry, str)
                               else (entry or ())):
                        if int(self.mesh.shape.get(ax, 1)) > 1:
                            return False
                return True

            spec_leaves = jax.tree_util.tree_leaves(
                self._param_specs, is_leaf=lambda x: isinstance(x, P))
            specs_ok = all(spec_manual_ok(sp) for sp in spec_leaves)
        explicit_ok = (
            specs_ok and not self._onebit
            and not self.config.sparse_gradients_enabled
            and self._direct_grads_fn is None
            and all(int(self.mesh.shape[a]) == 1
                    for a in self.mesh.axis_names
                    if a not in replica_axes))
        mode = zc.grad_sync
        if self.slice_size > 1:
            # Hierarchical sync EXISTS only on the explicit path (a
            # declarative lowering would emit whatever flat collective
            # GSPMD picks over the joint axes — grad-sized DCN traffic).
            if mode == "declarative" or not explicit_ok:
                raise ValueError(
                    "a multi-slice mesh (slices > 1) requires the "
                    "explicit hierarchical gradient path: set "
                    "zero_optimization.grad_sync to 'auto' or "
                    "'explicit' on a (slice, data) mesh with the main "
                    "train/offload path")
            return "explicit"
        if mode == "explicit":
            if not explicit_ok:
                raise ValueError(
                    "zero_optimization.grad_sync='explicit' supports the "
                    "main train and offload paths on a pure-dp (or "
                    "slice/expert-factored) mesh only (no TP/PP/SP axes, "
                    "onebit, sparse_gradients, or pipeline grads_fn) — "
                    "use 'auto' or 'declarative'")
            return "explicit"
        if mode == "declarative" or not explicit_ok:
            return "declarative"
        if self.ep_size > 1:
            # The declarative lowering for the (expert, data)-sharded
            # batch regresses to all-reduce + slice on this backend
            # (audited in COMM_AUDIT.json's moe flagship history) — the
            # factored explicit path closes it; no probe needed.
            return "explicit"
        from ..parallel import hlo_audit
        lowering = hlo_audit.zero2_grad_sync_lowering(self.mesh, DP_AXIS)
        return "declarative" if lowering == "reduce-scatter" else "explicit"

    def _grad_wire_bytes(self) -> Tuple[int, str]:
        """(analytic wire bytes/step, detail) for the RESOLVED gradient
        sync — the PR-3 wire model priced at the lowering this engine
        actually runs. One source of truth for the init log, the
        telemetry meta/records, and bench's dp_comm provenance."""
        self._wire_model = None
        # Two-tier split: everything is ICI wire except the inter-slice
        # hop of the hierarchical multislice sync (the only collective
        # in-tree that rides DCN).
        self._wire_bytes_dcn = 0
        if self.replica_size <= 1:
            return 0, "single replica (no gradient sync)"
        from ..parallel import hlo_audit
        if self.slice_size > 1:
            gas = self._scan_microbatches()
            zero3_kw = {}
            if self._zero3:
                zero3_kw = dict(
                    zero3=True,
                    param_bytes_per_el=jnp.dtype(
                        self.compute_dtype).itemsize,
                    gas=gas, param_specs=self._stage3_specs,
                    mesh=self.mesh)
            model = hlo_audit.grad_sync_wire_model(
                self.state.params, self.dp_size, slices=self.slice_size,
                dcn_compression=self._dcn_compression, **zero3_kw)
            self._wire_model = model
            dcn = model["dcn_wire_bytes_compressed"] \
                if self._dcn_compression else model["dcn_wire_bytes"]
            self._wire_bytes_dcn = int(dcn)
            # The tiers are per-STEP in the same units: the in-slice
            # collectives run once per micro-step inside the gas scan
            # (x gas), the DCN hop once per step on the accumulated
            # shard — summing a per-micro ICI term with a per-step DCN
            # term would misreport which tier binds. Under stage 3 the
            # ici term already includes both param gathers (the planner
            # binds them to `data`: ICI on every factorization).
            ici = int(model["ici_wire_bytes"]) * int(gas)
            comp = (" 1-bit-compressed (packed sign bits + per-chunk "
                    "scales — the DCN wire format; the emulation psums "
                    "decompressed values)") if self._dcn_compression \
                else ""
            z3 = (f" + 2 in-slice param gathers/micro-step "
                  f"({jnp.dtype(self.compute_dtype).name} wire, zero "
                  f"param bytes on DCN)") if self._zero3 else ""
            return int(ici + dcn), \
                (f"hierarchical {self._grad_sync_mode}: in-slice "
                 f"reduce-scatter over ICI (dp={self.dp_size}, "
                 f"x{gas} micro-steps){z3} + inter-slice all-reduce "
                 f"over DCN (slices={self.slice_size}) of the 1/dp "
                 f"residual only{comp} — {int(dcn):,} DCN B/step vs "
                 f"{model['flat_dcn_link_bytes']:,} for a flat joint "
                 f"sync")
        if self.ep_size > 1:
            return self._moe_wire_bytes(hlo_audit)
        if self._sparse_mask is not None:
            # Sparse embedding grads travel the data-dependent CSR
            # exchange (volume ~ nnz_rows/vocab of dense; see
            # sparse_comm_stats) — pricing them at the dense model would
            # overstate wire by orders of magnitude. Model the dense
            # leaves only and say so.
            dense_leaves = [
                l for l, m in zip(
                    jax.tree_util.tree_leaves(self.state.params),
                    jax.tree_util.tree_leaves(self._sparse_mask)) if not m]
            model = hlo_audit.grad_sync_wire_model(dense_leaves,
                                                   self.dp_size)
            self._wire_model = model
            return model["all_reduce_wire_bytes"], \
                ("dense all-reduce over non-sparse leaves only (sparse "
                 "embedding grads use the data-dependent CSR exchange; "
                 "see sparse_comm_stats)")
        if self._zero3:
            # Stage 3: the grads reduce-scatter AND the params cross the
            # wire twice more (fwd gather + bwd re-gather) per
            # micro-step, at the compute dtype.
            model = hlo_audit.grad_sync_wire_model(
                self.state.params, self.dp_size, zero3=True,
                param_bytes_per_el=jnp.dtype(self.compute_dtype).itemsize,
                gas=self._scan_microbatches(),
                param_specs=self._stage3_specs, mesh=self.mesh)
            self._wire_model = model
            return model["zero3_wire_bytes"], \
                (f"{self._grad_sync_mode} ZeRO-3: per micro-step, "
                 f"2 param gathers "
                 f"({jnp.dtype(self.compute_dtype).name} wire) + f32 "
                 f"grad reduce-scatter — "
                 f"{model['param_gather_wire_bytes']:,} gather B/step")
        model = hlo_audit.grad_sync_wire_model(self.state.params,
                                               self.dp_size)
        self._wire_model = model
        if self.zero_optimization_stage() < 2:
            return model["all_reduce_wire_bytes"], \
                "dense all-reduce (grads replicated below ZeRO stage 2)"
        mode = self._grad_sync_mode
        if mode == "allreduce":
            return model["all_reduce_wire_bytes"], \
                "dense all-reduce (reduce_scatter: false)"
        declared = hlo_audit.zero2_grad_sync_lowering(self.mesh, DP_AXIS)
        if mode == "declarative" and declared == "all-reduce":
            # The user pinned the declarative path on a backend whose
            # partitioner regresses it: report the wire it actually
            # costs, not the wire the declaration hoped for.
            return model["all_reduce_wire_bytes"], \
                ("declarative — REGRESSED to all-reduce + slice "
                 "on this backend (grad_sync: auto or explicit "
                 "restores the reduce-scatter)")
        return model["reduce_scatter_wire_bytes"], \
            (f"{mode} reduce-scatter (declared sharding "
             f"lowers to {declared} on this backend)")

    def _wire_terms(self) -> Optional[Dict[str, Dict[str, Any]]]:
        """Per-TERM split of the analytic wire figure on a multi-slice
        mesh, each term tagged with the tier it rides (the planner's
        assignment): the in-scan grad reduce-scatter and — under stage 3
        — both param gathers on ICI, the once-per-step residual
        all-reduce on DCN. None on single-slice meshes (one tier, no
        split to report). Telemetry meta carries it so the roofline's
        comm_tiers can be decomposed per collective, not just per tier."""
        wm = self._wire_model
        if not isinstance(wm, dict) or "ici_wire_bytes" not in wm:
            return None
        gas = int(self._scan_microbatches())
        rs = int(wm["reduce_scatter_wire_bytes"]) * gas
        terms = {
            "grad_reduce_scatter": {"tier": "ici", "bytes": rs,
                                    "placement": "in-scan"},
            "inter_slice_residual": {"tier": "dcn",
                                     "bytes": int(self._wire_bytes_dcn),
                                     "placement": "per-step"},
        }
        gather = int(wm["ici_wire_bytes"]) * gas - rs
        if gather > 0:
            terms["param_gather"] = {"tier": "ici", "bytes": gather,
                                     "placement": "in-scan"}
        return terms

    def _moe_layer_info(self) -> Tuple[int, int]:
        """(n_moe_layers, hidden) read off the expert up-projection leaf
        (path ``moe_fc_kernel``, stacked [n_moe, E, H, F]); (0, 0) when
        the param tree carries none."""
        flat, _ = jax.tree_util.tree_flatten_with_path(self.state.params)
        for path, leaf in flat:
            if "moe_fc_kernel" in jax.tree_util.keystr(path) and \
                    getattr(leaf, "ndim", 0) == 4:
                return int(leaf.shape[0]), int(leaf.shape[2])
        return 0, 0

    def _moe_wire_bytes(self, hlo_audit) -> Tuple[int, str]:
        """Expert-parallel (ep > 1) wire model:

        - DENSE leaves sync over the full ep x dp replica set (under
          ZeRO >= 2: all-reduce across expert groups + reduce-scatter
          within data — the declared dp shard);
        - EXPERT leaves (param spec on the `expert` axis) all-reduce
          their 1/ep shard over `data` ONLY — the moe shard_map
          transpose's within-expert-group psum; they are never
          replicated across experts;
        - the dispatch/combine all-to-alls price per token
          (hlo_audit.moe_alltoall_wire_model); the exact per-step figure
          resolves at the first batch (_maybe_refresh_moe_wire), when
          the engine learns the token count.
        """
        from ..moe.sharding import is_expert_spec
        ring = hlo_audit.ring_wire_bytes
        leaves = jax.tree_util.tree_leaves(self.state.params)
        if self._param_specs is not None:
            spec_leaves = jax.tree_util.tree_structure(
                self.state.params).flatten_up_to(self._param_specs)
        else:
            spec_leaves = [P()] * len(leaves)
        mask = [isinstance(sp, P) and is_expert_spec(sp)
                for sp in spec_leaves]
        dense_leaves = [l for l, m in zip(leaves, mask) if not m]
        expert_full = sum(int(np.prod(l.shape)) * 4
                          for l, m in zip(leaves, mask)
                          if m and hasattr(l, "shape"))
        expert_local = expert_full // self.ep_size
        n_moe, hidden = self._moe_layer_info()
        moe_kw = dict(
            hidden=hidden, num_experts=self._moe.num_experts,
            top_k=self._moe.top_k,
            capacity_factor=self._moe.capacity_factor,
            ep=self.ep_size, n_moe_layers=max(1, n_moe),
            bytes_per_el=jnp.dtype(self.compute_dtype).itemsize,
            tokens_per_device=self._moe_tokens_per_device,
            gas=self._scan_microbatches())
        model = dict(hlo_audit.grad_sync_wire_model(
            dense_leaves, self.dp_size, moe=moe_kw))
        # Only the EXPLICIT factored path earns the hierarchical
        # pricing: RS over data per micro-step, then the cross-group
        # all-reduce carries the 1/dp RESIDUAL only (pricing it at full
        # size would overstate the expert hop dp x). A user-pinned
        # declarative stage-2 keeps the regressed full all-reduce
        # figure — that IS what it compiles to on this backend.
        stage2_rs = self.zero_optimization_stage() >= 2 and \
            self._grad_sync_mode == "explicit"
        if stage2_rs and self.dp_size > 1:
            dense_wire = (
                ring("reduce-scatter", model["scatterable_bytes"],
                     self.dp_size)
                + ring("all-reduce",
                       model["scatterable_bytes"] // self.dp_size,
                       self.ep_size)
                + ring("all-reduce", model["replicated_bytes"],
                       self.dp_size)
                + ring("all-reduce", model["replicated_bytes"],
                       self.ep_size))
            dense_note = (f"dense grads reduce-scatter over data "
                          f"({self.dp_size}) + all-reduce their 1/dp "
                          f"residual across expert groups "
                          f"({self.ep_size})")
        else:
            dense_wire = ring("all-reduce", model["grad_bytes"],
                              self.replica_size)
            dense_note = (f"dense grads all-reduce over expert x data "
                          f"({self.replica_size})")
        # Expert grads sync over data-within-group only; under the
        # stage >= 2 explicit factored path they reduce-scatter there
        # (the declared dp dim layered onto the expert base spec), under
        # dense modes they all-reduce.
        expert_wire = ring("reduce-scatter" if stage2_rs else "all-reduce",
                           expert_local, self.dp_size)
        a2a = int(model.get("moe_alltoall_wire_bytes") or 0)
        # The honest dense-baseline comparator the init log prints: one
        # all-reduce of EVERYTHING (expert grads replicated across
        # experts — the failure mode) over the full replica set.
        model["all_reduce_wire_bytes"] = ring(
            "all-reduce", model["grad_bytes"] + expert_full,
            self.replica_size)
        model.update(expert_grad_bytes_local=int(expert_local),
                     expert_grad_wire_bytes=int(expert_wire),
                     dense_grad_wire_bytes=int(dense_wire))
        self._wire_model = model
        per_tok = model["moe"]["wire_bytes_per_token"]
        expert_sync = "reduce-scatter" if stage2_rs else "all-reduce"
        detail = (
            f"{self._grad_sync_mode} MoE ep={self.ep_size}: {dense_note}; "
            f"expert grads ({expert_local:,} B/device) {expert_sync} over "
            f"data within their expert group only; dispatch/combine "
            f"all-to-all {per_tok:,} B/token"
            + (f" = {a2a:,} B/step" if a2a
               else " (per-step figure resolves at the first batch)"))
        return int(dense_wire + expert_wire + a2a), detail

    def _maybe_refresh_moe_wire(self, micro_batches) -> None:
        """Resolve the MoE all-to-all wire term exactly once the token
        count is visible (first batch): tokens/device/micro-step = the
        per-device sample count x tokens-per-sample (LM token batches
        [gas, B, S+1] route S tokens; other shapes use the trailing-dim
        product). Updates the analytic wire bytes + telemetry meta —
        host metadata only, no device access."""
        if self._moe is None or self.ep_size <= 1 or \
                self._moe_tokens_per_device is not None:
            return
        leaves = [l for l in jax.tree_util.tree_leaves(micro_batches)
                  if hasattr(l, "shape") and getattr(l, "ndim", 0) >= 2]
        if not leaves:
            return
        leaf = leaves[0]
        per_dev = max(1, int(leaf.shape[1]) // max(1, self.replica_size))
        if len(leaves) == 1 and leaf.ndim == 3 and \
                jnp.issubdtype(leaf.dtype, jnp.integer):
            # The combined LM layout [gas, B, S+1] (inputs [:, :-1]):
            # S tokens route. A (tokens, targets) PAIR has two leaves
            # and routes all S — the generic branch below.
            per_sample = max(1, int(leaf.shape[2]) - 1)
        else:
            per_sample = int(np.prod(leaf.shape[2:])) or 1
        self._moe_tokens_per_device = per_dev * per_sample
        self._wire_bytes, self._wire_detail = self._grad_wire_bytes()
        tl = self.telemetry
        if tl.enabled:
            tl.meta["wire_bytes_per_step"] = self._wire_bytes
            tl.meta["wire_bytes_ici"] = \
                self._wire_bytes - self._wire_bytes_dcn
            tl.meta["wire_bytes_dcn"] = self._wire_bytes_dcn
            tl.meta["wire_terms"] = self._wire_terms()
            tl.meta["wire_detail"] = self._wire_detail
            if isinstance(self._wire_model, dict) and \
                    "moe" in self._wire_model:
                tl.meta["moe_alltoall_wire_bytes_per_step"] = \
                    int(self._wire_model["moe_alltoall_wire_bytes"])

    def _log_comm_plan(self) -> None:
        """Init-time communication honesty (audited lowering + analytic
        wire bytes/step) — the knobs act or report, never silently."""
        zc = self.config.zero_config
        if zc.overlap_comm and self._offload is None:
            log_dist(
                "zero_optimization.overlap_comm: device-side collectives "
                "are overlapped by XLA's latency-hiding scheduler "
                "automatically; the knob only selects the bucketed host "
                "pipeline under cpu_offload", ranks=[0])
        if self.slice_size > 1:
            log_dist(f"Multi-slice scale-out: {self._wire_detail}; "
                     f"~{self._wire_bytes:,} wire bytes/step "
                     f"({self._wire_bytes - self._wire_bytes_dcn:,} ICI + "
                     f"{self._wire_bytes_dcn:,} DCN; "
                     f"slices={self.slice_size} x dp={self.dp_size})",
                     ranks=[0])
            return
        if self.ep_size > 1:
            log_dist(f"MoE expert parallelism: {self._wire_detail}; "
                     f"~{self._wire_bytes:,} wire bytes/step "
                     f"(ep={self.ep_size} x dp={self.dp_size})", ranks=[0])
            return
        if self.zero_optimization_stage() < 2 or self.dp_size <= 1:
            return
        log_dist(
            f"ZeRO-{self.zero_optimization_stage()} grad sync: "
            f"{self._wire_detail}; "
            f"~{self._wire_bytes:,} wire bytes/step vs "
            f"{self._wire_model['all_reduce_wire_bytes']:,} for a full "
            f"all-reduce (dp={self.dp_size})", ranks=[0])

    def _grad_shardings(self):
        """ZeRO stage>=2 gradient shardings over dp (None for stage < 2,
        dp=1, or the honest ``reduce_scatter: false`` dense-allreduce
        path)."""
        if getattr(self, "_grad_sync_mode", None) in ("none", "allreduce"):
            return None
        if self.zero_optimization_stage() < 2 or self.dp_size <= 1:
            return None
        if self._zero3:
            # Grads land EXACTLY on the param layout (stage3_param_specs)
            # so the shard-local update consumes them in place.
            return jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec),
                self._stage3_specs, is_leaf=lambda x: isinstance(x, P))
        from .zero.partition import grad_shardings
        return grad_shardings(self.state.params, self.mesh, DP_AXIS,
                              self._param_specs)

    def _bind_zero3_scan(self, spec) -> None:
        """Bind the model's ``Zero3Scan`` contract to this engine's
        resolved stage-3 layout: the gather lowering mode (the same
        honesty split as grad_sync), each covered leaf's gather dim
        AFTER the per-layer slice (the stacked dp dim minus the layer
        axis), the gathered (dp-free) spec for the declarative
        constraint, and the configured prefetch depth. The loss_fn
        traces AFTER engine construction (first train step), so it reads
        the bound spec then."""
        from .zero.partition import spec_dp_dim
        mode = "explicit" if self._grad_sync_mode == "explicit" \
            else "declarative"
        layer_info = {}
        flat, _ = jax.tree_util.tree_flatten_with_path(
            self._stage3_specs, is_leaf=lambda x: isinstance(x, P))
        for path, sp in flat:
            if not spec.covers(jax.tree_util.keystr(path)):
                continue
            name = getattr(path[-1], "key", None) or str(path[-1])
            d = spec_dp_dim(sp, DP_AXIS)
            # stage3_param_specs never puts dp on a covered leaf's layer
            # axis; d >= 1 or None by construction.
            gdim = None if d is None else d - 1
            sliced = [None if e == DP_AXIS else e for e in list(sp)[1:]]
            layer_info[name] = (gdim, P(*sliced))
        spec.bind(mode=mode, mesh=self.mesh, axis_name=DP_AXIS,
                  compute_dtype=self.compute_dtype,
                  prefetch_depth=self._prefetch_depth,
                  layer_info=layer_info)
        # A constructor override on the spec wins over the config knob,
        # and the depth clamps to L-1 (the scan cannot hold more than
        # every layer); adopt the EFFECTIVE depth so the memory
        # watermark, telemetry meta, and the lint materialization
        # budget price the working set the compiled scan actually
        # holds — an unclamped budget would loosen the gate.
        if layer_info:
            leaves = [l for l, cov in zip(
                jax.tree_util.tree_leaves(self.state.params),
                jax.tree_util.tree_leaves(self._zero3_covered)) if cov]
            n_layers = int(leaves[0].shape[0]) if leaves else 1
            spec.prefetch_depth = max(
                0, min(int(spec.prefetch_depth), n_layers - 1))
        self._prefetch_depth = int(spec.prefetch_depth)
        log_dist(f"ZeRO-3 layer scan bound: mode={mode}, "
                 f"prefetch_depth={spec.prefetch_depth}, "
                 f"{len(layer_info)} scanned leaves", ranks=[0])

    def _make_state_shardings(self, params, opt_state) -> EngineState:
        """Params per TP spec (default replicated); ZeRO stage >= 1 shards
        optimizer state over dp, layered on top of the TP spec. ``params`` /
        ``opt_state`` may be shape structs (only shapes are inspected)."""
        def repl(tree):
            return jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P()), tree)
        if getattr(self, "_zero3", False):
            # Stage 3: params born dp-sharded (stage3_param_specs,
            # already layered over any TP base).
            params_sh = jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec),
                self._stage3_specs, is_leaf=lambda x: isinstance(x, P))
        elif self._param_specs is not None:
            params_sh = jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec),
                self._param_specs, is_leaf=lambda x: isinstance(x, P))
        else:
            params_sh = repl(params)
        if getattr(self, "_onebit", False) and opt_state != ():
            # m/v/server_error replicated; worker_error dp-sharded on its
            # leading [dp] axis (per-rank error feedback).
            opt_sh = repl(opt_state)
            opt_sh = opt_sh._replace(
                worker_error=jax.tree_util.tree_map(
                    lambda _: NamedSharding(self.mesh, P(DP_AXIS)),
                    opt_sh.worker_error))
        elif getattr(self, "_zero3", False):
            # Moments mirror the stage-3 param layout (param-structured
            # subtrees); the fused optimizer's flat buffers keep the
            # plain dp row sharding.
            from .zero.partition import stage3_state_shardings
            opt_sh = stage3_state_shardings(opt_state, self.mesh, DP_AXIS,
                                            params, self._stage3_specs)
        elif self.zero_optimization_stage() >= 1 and self.dp_size > 1:
            opt_sh = zero_shardings(opt_state, self.mesh, DP_AXIS,
                                    params=params,
                                    param_specs=self._param_specs)
        elif self._param_specs is not None:
            # Moments follow the param TP layout; no ZeRO axis.
            opt_sh = zero_shardings(opt_state, self.mesh, None,
                                    params=params,
                                    param_specs=self._param_specs)
        else:
            opt_sh = repl(opt_state)
        scalar = NamedSharding(self.mesh, P())
        # DCN-compression error feedback: per-leaf [slices, *leaf] f32,
        # slice-sharded on the leading axis (genuinely per-slice state)
        # and dp-sharded where the grad shard is (same _leaf_spec rule,
        # shifted one dim right) — each (slice, dp-rank) owns exactly
        # the residual of its own compressed transmissions.
        dcn_sh = None
        if getattr(self, "_dcn_compression", False) and \
                self.slice_size > 1:
            from .zero.partition import _leaf_spec
            # Under stage 3 the error leaf must mirror the STAGE-3 grad
            # spec (covered scanned leaves keep their layer axis
            # unsharded — the plain rule would disagree with the
            # builder's err_specs and force a reshard at the shard_map
            # boundary every step).
            z3_specs = self._stage3_specs \
                if getattr(self, "_zero3", False) else None

            def err_sharding(p, sp=None):
                if not hasattr(p, "shape") or getattr(p, "ndim", 0) < 1:
                    return NamedSharding(self.mesh, P(SLICE_AXIS))
                spec = sp if sp is not None \
                    else _leaf_spec(p.shape, self.dp_size, DP_AXIS)
                return NamedSharding(self.mesh, P(SLICE_AXIS, *spec))
            if z3_specs is not None:
                dcn_sh = jax.tree_util.tree_map(
                    err_sharding, params, z3_specs)
            else:
                dcn_sh = jax.tree_util.tree_map(err_sharding, params)
        return EngineState(step=scalar, params=params_sh, opt_state=opt_sh,
                           loss_scale=scalar, growth_count=scalar,
                           hysteresis=scalar, skipped_steps=scalar,
                           cast_params=(params_sh if self._use_cast_cache
                                        else None),
                           dcn_error=dcn_sh)

    def _metrics_shardings(self, with_taps: bool = False,
                           with_moe: bool = False
                           ) -> Dict[str, NamedSharding]:
        """Replicated shardings for the step-metrics dict. Declared (with
        ``_state_shardings``) as out_shardings on every DONATING step
        program: without declared outputs, jax pairs donated inputs to
        same-aval outputs sharding-blind, and under ZeRO the dp-sharded
        moments share global avals with the replicated params — the
        partitioner then drops the mispaired aliases and every
        param-sized donated buffer is freed-but-never-reused (the lint
        suite's donation finding, a full param-tree of transient HBM).
        ``with_taps`` adds the health tap's [num_leaves] entry (also
        replicated) for paths that emit it."""
        scalar = NamedSharding(self.mesh, P())
        out = {k: scalar for k in ("loss", "grad_norm", "lr",
                                   "loss_scale", "overflow")}
        if with_taps:
            out["health_leaf_sq"] = scalar
        if with_moe:
            # [num_experts] routed counts + scalar drop/aux/z, all
            # replicated — drain material, no hot-path syncs.
            for k in ("moe_expert_tokens", "moe_drop_fraction",
                      "moe_aux_loss", "moe_z_loss"):
                out[k] = scalar
        return out

    def _place_state(self, state: EngineState) -> EngineState:
        # Jitted identity, NOT device_put: device_put may alias caller-owned
        # arrays into the state, and the donated train step would delete the
        # user's model_params out from under them. jit outputs are always
        # fresh buffers.
        state = jax.tree_util.tree_map(jnp.asarray, state)
        if self._use_cast_cache:
            # Always re-derive the compute-dtype cache here: every external
            # params replacement (checkpoint load) funnels through this, so
            # the cache cannot go stale.
            dt = self.compute_dtype

            def place(s):
                return s.replace(cast_params=_cast_floats(s.params, dt))
        else:
            def place(s):
                return s
        return jax.jit(place, out_shardings=self._state_shardings)(state)

    def _batch_sharding(self, batch_tree, leading_dims: int = 1):
        """Shard batch arrays over the replica axes on the (micro-)batch
        dim — (expert, data) jointly when expert parallelism is live
        (expert factors out of data), (slice, data) jointly on a
        multi-slice mesh (slices factor OUTSIDE data, matching the
        outermost mesh axis), plain dp otherwise."""
        if self.slice_size > 1:
            batch_axes = (SLICE_AXIS, DP_AXIS)
        elif self.ep_size > 1:
            batch_axes = (EP_AXIS, DP_AXIS)
        else:
            batch_axes = DP_AXIS

        def spec(x):
            pspec = P(*([None] * (leading_dims - 1) + [batch_axes]))
            return NamedSharding(self.mesh, pspec)
        return jax.tree_util.tree_map(spec, batch_tree)

    # ------------------------------------------------------------------ #
    # Config accessors (reference engine.py getters)
    # ------------------------------------------------------------------ #
    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    def zero_optimization_stage(self) -> int:
        return self.config.zero_optimization_stage

    def zero_optimization(self) -> bool:
        return self.config.zero_enabled

    def fp16_enabled(self) -> bool:
        return self.config.fp16_enabled

    def bfloat16_enabled(self) -> bool:
        return self.config.bf16_enabled

    def gradient_clipping(self) -> float:
        return self.config.gradient_clipping

    def steps_per_print(self) -> int:
        return self.config.steps_per_print

    def wall_clock_breakdown(self) -> bool:
        return self.config.wall_clock_breakdown

    def is_gradient_accumulation_boundary(self) -> bool:
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    def _scan_microbatches(self) -> int:
        """How many micro-batches the jitted train step scans over. The
        pipeline engine overrides this to 1: its loss_fn consumes ALL
        grad-accum micro-batches in one pipelined pass."""
        return self.gradient_accumulation_steps()

    @property
    def optimizer(self):
        return self.tx

    def get_lr(self) -> List[float]:
        return [float(self._schedule_fn(self.global_steps))]

    def loss_scale(self) -> float:
        if self._offload is not None:
            return float(self._offload.loss_scale)
        return float(jax.device_get(self.state.loss_scale))

    # ------------------------------------------------------------------ #
    # Data path (reference engine.py:717-758)
    # ------------------------------------------------------------------ #
    def deepspeed_io(self, dataset, batch_size=None, route=C.ROUTE_TRAIN,
                     pin_memory=None, data_sampler=None, collate_fn=None,
                     num_local_io_workers=None):
        if dataset is None:
            return None
        if hasattr(dataset, "__iter__") and not hasattr(dataset, "__getitem__"):
            return RepeatingLoader(dataset)
        if batch_size is None:
            # One loader item = one micro step of this process's share of the
            # dp axis (the loader shards the dataset per process).
            local_dp = max(1, self.dp_size // jax.process_count())
            batch_size = self.train_micro_batch_size_per_gpu() * local_dp
        return DeepSpeedDataLoader(
            dataset=dataset, batch_size=batch_size,
            collate_fn=collate_fn or self.collate_fn,
            shuffle=route == C.ROUTE_TRAIN, drop_last=True,
            data_parallel_world_size=jax.process_count(),
            data_parallel_rank=jax.process_index())

    # ------------------------------------------------------------------ #
    # ZeRO-Offload step: device grads -> host SIMD Adam -> device params
    # ------------------------------------------------------------------ #
    def _build_offload_grad_fn(self, bucketed: bool = False):
        """Jitted grad-accumulation pass only (no optimizer apply): returns
        (loss-scaled summed grads, mean_loss). Grads stay dp-sharded under
        stage 2 until the host gather.

        ``bucketed``: emit the grads as a tuple of per-bucket leaf tuples
        (offload bucket order = flatten order) instead of one pytree, so
        the overlapped pipeline can enqueue each bucket's async D2H and
        wait on it independently of the others."""
        gas = self._scan_microbatches()
        loss_fn = self.loss_fn
        compute_dtype = self.compute_dtype
        grad_sh = self._grad_shardings()
        pld, accepts_pld = self.progressive_layer_drop, self._accepts_pld

        def constrain_grads(g):
            return g if grad_sh is None \
                else lax.with_sharding_constraint(g, grad_sh)

        raw_offload_loss = _make_raw_scaled_loss(loss_fn, accepts_pld,
                                                 gas)

        def scaled_loss(params, mb, key, scale, theta):
            return raw_offload_loss(_cast_floats(params, compute_dtype),
                                    mb, key, scale, theta)

        grad_fn = jax.value_and_grad(scaled_loss, has_aux=True)

        # Grad wire dtype: bf16 runs ship compute-dtype grads to the host
        # (half the D2H volume; matches the reference, whose cpu_offload
        # D2H copies the fp16 grads as-is, stage2.py:775-873). The host
        # optimizer upcasts to fp32 before the SIMD Adam. fp32 runs keep
        # the full-precision wire.
        wire_dtype = compute_dtype if compute_dtype == jnp.bfloat16 \
            else jnp.float32
        buckets = self._offload.buckets if bucketed else None

        def regroup(grads):
            if buckets is None:
                return grads
            flat = jax.tree_util.tree_leaves(grads)
            return tuple(tuple(flat[i] for i in b) for b in buckets)

        if self._grad_sync_mode == "explicit" and grad_sh is not None:
            # Guaranteed reduce-scatter for the offload grad pass too —
            # the bucket regroup happens outside the shard_map, so the
            # per-bucket D2H handles are unaffected. This retired the
            # last lint waiver (the offload declarative path regressing
            # to all-reduce + slice on this backend). Stage 3 gets the
            # CAST-FREE loss like the main path: the builder's gather
            # casts uncovered leaves in flight, and Zero3Scan-covered
            # shards must stay in the per_rank-widened f32 so the
            # per-layer grad scatter keeps f32 (a _cast_floats here
            # would narrow them to the compute dtype per layer).
            explicit = self._build_explicit_zero2_grads(
                raw_offload_loss if self._zero3 else scaled_loss,
                grad_sh, gas)

            def explicit_grads_step(params, micro_batches, rng, step,
                                    scale):
                rng = jax.random.fold_in(rng, step)
                theta = pld.theta_at(step.astype(jnp.float32)) \
                    if accepts_pld else None
                keys = jax.random.split(rng, gas)
                grads, mean_loss, _aux, _err = explicit(
                    params, micro_batches, keys, scale, theta)
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(wire_dtype), grads)
                return regroup(grads), mean_loss

            return jax.jit(explicit_grads_step)

        def grads_step(params, micro_batches, rng, step, scale):
            rng = jax.random.fold_in(rng, step)
            theta = pld.theta_at(step.astype(jnp.float32)) \
                if accepts_pld else None
            keys = jax.random.split(rng, gas)

            if gas == 1:
                # No accumulation buffer: saves a full fp32 zero-init +
                # add pass AND the fp32-sized transient (for the 1.5B
                # bench config that transient alone is 6 GB of HBM).
                mb = jax.tree_util.tree_map(lambda x: x[0], micro_batches)
                (_, (raw_loss, _aux)), grads = grad_fn(params, mb, keys[0],
                                                       scale, theta)
                grads = constrain_grads(grads)
                return (regroup(jax.tree_util.tree_map(
                    lambda g: g.astype(wire_dtype), grads)),
                    raw_loss.astype(jnp.float32))

            def accum(carry, xs):
                g_acc, loss_acc = carry
                mb, key = xs
                (_, (raw_loss, _aux)), grads = grad_fn(params, mb, key,
                                                       scale, theta)
                g_acc = constrain_grads(
                    jax.tree_util.tree_map(jnp.add, g_acc, grads))
                return (g_acc, loss_acc + raw_loss.astype(jnp.float32) / gas), None

            zero_grads = constrain_grads(jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32)
                if hasattr(p, "dtype") else p, params))
            (grads, mean_loss), _ = lax.scan(
                accum, (zero_grads, jnp.asarray(0.0, jnp.float32)),
                (micro_batches, keys))
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(wire_dtype), grads)
            return regroup(grads), mean_loss

        return jax.jit(grads_step)

    def _offload_partition_shardings(self, procs: Optional[int] = None):
        """Per-leaf NamedShardings placing each process's host partition on
        its own devices: the partition axis is sharded over a
        process-major mesh axis, everything else replicated. Repartitioning
        grads into these shardings before device_get (and params out of
        them after the host step) makes every host partition
        process-addressable via XLA collectives, with no assumption about
        how the dp shards were laid out."""
        procs = procs or jax.process_count()
        off = self._offload
        # jax.devices() is ordered by device id, which is NOT contiguous
        # per process on all topologies; row r of the proc-mesh must be
        # process r's devices or every host would update another host's
        # partition.
        devs = np.asarray(sorted(jax.devices(),
                                 key=lambda d: (d.process_index, d.id)))
        devs = devs.reshape(procs, -1)
        mesh = Mesh(devs, ("proc", "dev"))
        leaves, treedef = jax.tree_util.tree_flatten(
            jax.tree_util.tree_unflatten(off.treedef,
                                         list(range(len(off.full_shapes)))))
        specs = []
        for i in leaves:
            ax = off._axes[i]
            if ax is None:
                specs.append(NamedSharding(mesh, P()))
            else:
                spec = [None] * len(off.full_shapes[i])
                spec[ax] = "proc"
                specs.append(NamedSharding(mesh, P(*spec)))
        return jax.tree_util.tree_unflatten(treedef, specs)

    def _local_offload_grads(self, grads):
        """Multi-host D2H: repartition grads to the process shardings, then
        read the (now guaranteed-local) partition of each leaf."""
        if self._offload_down is None:
            self._offload_down = self._offload_partition_shardings()
            # jit caches by function identity: keep ONE identity fn per
            # direction or every step would retrace + recompile the
            # whole-tree reshard.
            self._offload_down_fn = jax.jit(
                lambda t: t, out_shardings=self._offload_down)
        grads = self._offload_down_fn(grads)
        return jax.tree_util.tree_map(
            lambda g: np.asarray(g.addressable_shards[0].data), grads)

    def _assemble_offload_params(self):
        """Multi-host H2D: each process contributes its updated partition;
        XLA all-gathers them into the engine's replicated param sharding."""
        off = self._offload
        if self._offload_down is None:
            self._offload_down = self._offload_partition_shardings()
        down_leaves = jax.tree_util.tree_leaves(self._offload_down)
        local = off.local_param_leaves()
        leaves = [jax.make_array_from_process_local_data(
                      sh, np.ascontiguousarray(l))
                  for sh, l in zip(down_leaves, local)]
        tree = jax.tree_util.tree_unflatten(off.treedef, leaves)
        if self._offload_up_fn is None:
            self._offload_up_fn = jax.jit(
                lambda t: t, out_shardings=self._state_shardings.params)
        return self._offload_up_fn(tree)

    def _offload_leaf_shardings(self):
        """Per-leaf target shardings for the bucketed param uploads, flat
        in offload leaf order (the state params tree has the offload
        treedef by construction)."""
        if self._offload_param_shardings is None:
            self._offload_param_shardings = jax.tree_util.tree_leaves(
                self._state_shardings.params)
        return self._offload_param_shardings

    def _train_batch_offload(self, micro_batches):
        import time as _time
        from .zero.offload import grad_to_host, run_bucketed_step
        if self._offload_grad_fn is None:
            self._offload_grad_fn = self.telemetry.instrument_step_fn(
                "offload_grad_step",
                self._build_offload_grad_fn(bucketed=self._offload_overlap))
        off = self._offload
        multihost = jax.process_count() > 1
        t_pre = _time.perf_counter()
        # Fence the PREVIOUS step's async param H2D here, in its own
        # bucket: without this, the upload time lands inside
        # device_step_ms and the recorded breakdown cannot reconcile.
        jax.block_until_ready(self.state.params)
        t0 = _time.perf_counter()
        grads, loss = self._offload_grad_fn(
            self.state.params, micro_batches, self._base_rng,
            jnp.asarray(self.global_steps, jnp.int32),
            jnp.asarray(off.loss_scale, jnp.float32))

        if self._offload_overlap:
            metrics, timings, loss = self._offload_step_overlapped(
                grads, loss, t0)
        else:
            # Serial parity path. The loss read fences the device step;
            # each bucket's device_get after it is then its own D2H fence
            # (nothing else in flight), so the per-bucket d2h timings
            # cannot bleed into one another (docs/tutorials/zero.md).
            loss = jax.device_get(loss)
            t1 = _time.perf_counter()
            reshard_ms = 0.0
            if multihost:
                # Whole-tree XLA reshard makes every partition process-
                # local; the bucket fetches below then index host arrays.
                # The real D2H happens HERE, so time it — otherwise the
                # components stop reconciling with wall_ms on multihost.
                host_leaves = jax.tree_util.tree_leaves(
                    self._local_offload_grads(grads))
                reshard_ms = (_time.perf_counter() - t1) * 1e3
                fetch = lambda b: [host_leaves[i] for i in off.buckets[b]]
            else:
                grad_leaves = jax.tree_util.tree_leaves(grads)

                def fetch(b):
                    got = jax.device_get([grad_leaves[i]
                                          for i in off.buckets[b]])
                    return [off.slice_leaf(i, grad_to_host(g))
                            for i, g in zip(off.buckets[b], got)]

            metrics, timings = run_bucketed_step(off, fetch, overlap=False)
            t3 = _time.perf_counter()
            if not metrics["overflow"]:
                # async H2D of the updated compute-dtype params, whole-tree
                new_params = self._assemble_offload_params() if multihost \
                    else off.device_params(self._state_shardings.params)
                self.state = self.state.replace(
                    params=new_params,
                    step=jnp.asarray(off.step_count, jnp.int32))
            timings["h2d_dispatch_ms"] = (_time.perf_counter() - t3) * 1e3
            timings["device_step_ms"] = (t1 - t0) * 1e3
            if reshard_ms:
                timings["d2h_reshard_ms"] = reshard_ms
                timings["d2h_ms"] += reshard_ms
        metrics["loss"] = loss
        self.skipped_steps = off.skipped_steps
        timings["h2d_wait_ms"] = (t0 - t_pre) * 1e3
        timings["wall_ms"] = (_time.perf_counter() - t_pre) * 1e3
        self.offload_timings = timings
        return metrics

    def _offload_step_overlapped(self, bucket_grads, loss, t0):
        """Overlapped bucket pipeline: enqueue every bucket's async D2H at
        dispatch, stream bucket waits on this thread while the worker pool
        runs the per-bucket norm kernels, resolve the overflow vote, then
        run per-bucket Adam in the pool and device_put each bucket the
        moment its apply lands (all jax dispatch stays on this thread).
        Next step's compute is fenced only by the param uploads
        (block_until_ready at the top of _train_batch_offload), so the
        H2D tail overlaps whatever host work follows train_batch."""
        import time as _time
        from .zero.offload import grad_to_host, run_bucketed_step
        off = self._offload
        for bucket in bucket_grads:
            for leaf in bucket:
                enqueue = getattr(leaf, "copy_to_host_async", None)
                if enqueue is not None:
                    enqueue()
        # Fences device compute (the transfers above are already in
        # flight); in overlap mode the fetch of bucket 0 would fence it
        # anyway — this just attributes the time to the right component.
        loss_val = jax.device_get(loss)
        t1 = _time.perf_counter()

        def fetch(b):
            return [off.slice_leaf(i, grad_to_host(g))
                    for i, g in zip(off.buckets[b], bucket_grads[b])]

        shardings = self._offload_leaf_shardings()
        dev_leaves: list = [None] * len(off.full_shapes)

        def upload(b, host_leaves):
            for i, leaf in zip(off.buckets[b], host_leaves):
                dev_leaves[i] = jax.device_put(leaf, shardings[i])

        metrics, timings = run_bucketed_step(off, fetch, upload,
                                             overlap=True)
        if not metrics["overflow"]:
            self.state = self.state.replace(
                params=jax.tree_util.tree_unflatten(off.treedef, dev_leaves),
                step=jnp.asarray(off.step_count, jnp.int32))
        timings["device_step_ms"] = (t1 - t0) * 1e3
        return metrics, timings, loss_val

    # ------------------------------------------------------------------ #
    # Sparse (CSR) embedding gradients
    # ------------------------------------------------------------------ #
    def _init_sparse_gradients(self, sparse_grad_filter) -> None:
        """Mark the param leaves whose grads travel the CSR path.

        The reference keys on ``torch.nn.Embedding`` instances
        (engine.py:179-186); the functional analogue is a predicate over
        param paths — by default 2-D leaves whose path contains "embed" or
        "wte" (lookup tables). ``sparse_grad_filter(path_str, leaf) -> bool``
        overrides the default.
        """
        if self.zero_optimization_stage() >= 1:
            raise ValueError(
                "sparse_gradients requires ZeRO stage 0: under ZeRO grads "
                "are born dp-sharded and the dense reduce-scatter already "
                "ships 1/dp of every tensor")
        if self._onebit:
            raise ValueError(
                "sparse_gradients does not compose with OnebitAdam (the "
                "compressed momentum exchange replaces the grad allreduce)")

        def default(path, leaf):
            p = path.lower()
            return getattr(leaf, "ndim", 0) == 2 and \
                ("embed" in p or "wte" in p)

        filt = sparse_grad_filter or default
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.state.params)
        mask_leaves, names = [], []
        for path, leaf in flat:
            path_str = jax.tree_util.keystr(path)
            is_sparse = bool(filt(path_str, leaf))
            mask_leaves.append(is_sparse)
            if is_sparse:
                names.append(path_str)
        if not names:
            logger.warning("sparse_gradients enabled but no param leaf "
                           "matched the embedding predicate — dense "
                           "allreduce will be used for everything")
            return
        self._sparse_mask = jax.tree_util.tree_unflatten(treedef, mask_leaves)
        self._sparse_names = names
        for n in names:
            log_dist(f"Will convert {n} to sparse (csr) tensor during "
                     "training", ranks=[0])

    def _build_sparse_grad_fn(self):
        """Per-rank grads under shard_map over dp: dense leaves are
        psum-averaged in-graph (ICI, where dense is the fast path); sparse
        embedding leaves come back per-rank [dp, V, H] for the host CSR
        exchange, whose wire volume is nnz_rows/vocab of dense (reference
        engine.py:1197-1253). Under fp16 the loss is scale-multiplied so
        grads come out SCALED (dense and sparse alike); the reported loss
        is the raw mean."""
        shard_map = comm.shard_map
        gas = self._scan_microbatches()
        loss_fn = self.loss_fn
        compute_dtype = self.compute_dtype
        dp, mesh = self.dp_size, self.mesh
        mask = self._sparse_mask
        pld, accepts_pld = self.progressive_layer_drop, self._accepts_pld

        def per_rank(params, step, micro_batches, keys, scale):
            rank = lax.axis_index(DP_AXIS)
            keys = jax.vmap(lambda k: jax.random.fold_in(k, rank))(keys)
            theta = pld.theta_at(step.astype(jnp.float32)) \
                if accepts_pld else None

            def mean_loss_fn(p):
                def one_micro(carry, xs):
                    scaled_acc, raw_acc = carry
                    mb, key = xs
                    cparams = _cast_floats(p, compute_dtype)
                    out = loss_fn(cparams, mb, key, pld_theta=theta) \
                        if accepts_pld else loss_fn(cparams, mb, key)
                    loss = out[0] if isinstance(out, tuple) else out
                    lf = loss.astype(jnp.float32)
                    return (scaled_acc + lf * scale / gas,
                            raw_acc + lf / gas), None

                (scaled, raw), _ = lax.scan(
                    one_micro, (jnp.asarray(0.0, jnp.float32),
                                jnp.asarray(0.0, jnp.float32)),
                    (micro_batches, keys))
                return scaled, raw

            (_, loss_val), grads = jax.value_and_grad(
                mean_loss_fn, has_aux=True)(params)
            grads = jax.tree_util.tree_map(
                lambda g, m: g[None] if m else lax.psum(g, DP_AXIS) / dp,
                grads, mask)
            return grads, lax.psum(loss_val, DP_AXIS) / dp

        def grad_step(params, step, micro_batches, rng, scale):
            rng = jax.random.fold_in(rng, step)
            keys = jax.random.split(rng, gas)
            batch_specs = jax.tree_util.tree_map(
                lambda _: P(None, DP_AXIS), micro_batches)
            grad_specs = jax.tree_util.tree_map(
                lambda m: P(DP_AXIS) if m else P(), mask)
            fn = shard_map(per_rank, mesh=mesh,
                           in_specs=(P(), P(), batch_specs, P(), P()),
                           out_specs=(grad_specs, P()),
                           check_vma=False)
            return fn(params, step, micro_batches, keys, scale)

        return jax.jit(grad_step)

    def _build_sparse_apply_fn(self):
        """Optimizer apply on the CSR-combined (now dense, replicated)
        grads: global-norm clip + tx update, same semantics as the main
        path's step. fp16: the sparse leaves arrive already unscaled (the
        host-side exchange divides by the scale), so only the dense leaves
        are unscaled here; the overflow vote spans BOTH (dense in-graph,
        sparse via the host-computed flag), and overflow skips the step
        and drives the dynamic scale machine exactly like the main path
        (reference engine.py:1000-1085). Returns the step's loss scale as
        a traced output: the donated input state's buffer is deleted on
        return, so the caller must not read it afterwards."""
        tx = self.tx
        fused_apply = self._fused_apply
        clip = self.gradient_clipping()
        schedule_fn = self._schedule_fn
        fp16 = self.config.fp16_enabled
        scaler_kw = self._scaler_kw
        mask = self._sparse_mask
        health_taps = self._health_tap_fn

        def apply_step(state, grads, sparse_overflow):
            scale = state.loss_scale
            tap = None
            if fp16:
                inv = 1.0 / scale
                grads = jax.tree_util.tree_map(
                    lambda g, m: g if m else g * inv, grads, mask)
                overflow = jnp.logical_or(sparse_overflow,
                                          tree_has_inf_or_nan(grads))
            else:
                overflow = jnp.asarray(False)
            # Health tap AFTER the unscale: here the whole tree is in
            # true magnitudes (the CSR exchange already unscaled the
            # sparse leaves host-side), so the reported per-layer norms
            # match grad_norm semantics — and a NaN shipped through the
            # CSR path is attributed too.
            if health_taps is not None:
                tap = health_taps(grads)
            grad_norm = global_norm(grads)
            # Same single-pass apply as the main step, clip folded in
            # (shared _clipped_update helper).
            new_params, new_opt = _clipped_update(
                grads, state, grad_norm, tx=tx, fused_apply=fused_apply,
                clip=clip)
            keep = overflow
            new_params = _tree_select(keep, state.params, new_params)
            new_opt = _tree_select(keep, state.opt_state, new_opt)
            new_state = state.replace(
                params=new_params, opt_state=new_opt,
                **_overflow_resolution(state, overflow, **scaler_kw))
            # ``scale`` is returned as a traced output: the input state is
            # DONATED, so reading state.loss_scale after this call would
            # touch a deleted buffer (the round-5 steps_per_print crash).
            return new_state, grad_norm, schedule_fn(state.step), overflow, \
                scale, tap

        scalar = NamedSharding(self.mesh, P())
        return jax.jit(apply_step, donate_argnums=(0,),
                       out_shardings=(self._state_shardings, scalar,
                                      scalar, scalar, scalar,
                                      scalar if health_taps is not None
                                      else None))

    def _csr_exchange(self, grads, inv_scale: float = 1.0):
        """Replace each sparse leaf's stacked per-rank grads [dp, V, H]
        with the CSR-allreduced dense mean. Mirrors the reference's
        csr_allreduce (engine.py:1212-1253): extract nonzero rows, gather
        values+indices across ranks (padded allgather across hosts),
        coalesce, densify. fp16: the gathered CSR values are unscaled
        HERE (``inv_scale``, nnz elements touched instead of V*H) and
        vetted for inf/NaN — the host half of the overflow vote. Returns
        (grads, shipped_elems, dense_elems, sparse_overflow)."""
        from .csr_tensor import CSRTensor, all_gather_csr
        procs = jax.process_count()
        repl = NamedSharding(self.mesh, P())
        shipped = [0]
        dense_n = [0]
        overflow = [False]

        def combine(g, m):
            if not m:
                return g
            if procs == 1:
                ranks = list(np.asarray(jax.device_get(g)))
            else:
                # Each process holds its local dp ranks; dedupe replicas
                # from other mesh axes by dp slot.
                seen = {}
                for sh in g.addressable_shards:
                    slot = sh.index[0].start or 0
                    if slot not in seen:
                        seen[slot] = np.asarray(sh.data)[0]
                ranks = [seen[k] for k in sorted(seen)]
            csr_shards = [CSRTensor.from_dense(r) for r in ranks]
            shipped[0] += sum(s.sparse_size() for s in csr_shards)
            local = all_gather_csr(csr_shards)
            if procs > 1:
                local = comm.csr_exchange_hosts(local)
            if not np.all(np.isfinite(local.values)):
                overflow[0] = True
            if inv_scale != 1.0:
                local = CSRTensor(local.row_indices,
                                  local.values * inv_scale,
                                  local.dense_shape)
            dense = (local.to_dense() / self.dp_size).astype(np.float32)
            dense_n[0] += local.dense_size
            if procs > 1:
                return jax.make_array_from_process_local_data(repl, dense)
            return jax.device_put(dense, repl)

        new_grads = jax.tree_util.tree_map(combine, grads, self._sparse_mask)
        return new_grads, shipped[0], dense_n[0], overflow[0]

    def _train_batch_sparse(self, micro_batches):
        if self._sparse_grad_fn is None:
            self._sparse_grad_fn = self.telemetry.instrument_step_fn(
                "sparse_grad_step", self._build_sparse_grad_fn())
            self._sparse_apply_fn = self.telemetry.instrument_step_fn(
                "sparse_apply_step", self._build_sparse_apply_fn())
        scale = self.state.loss_scale
        grads, loss = self._sparse_grad_fn(
            self.state.params, jnp.asarray(self.global_steps, jnp.int32),
            micro_batches, self._base_rng, scale)
        inv = 1.0 / float(jax.device_get(scale)) \
            if self.config.fp16_enabled else 1.0
        with self.telemetry.span("grad_sync", path="csr_exchange"):
            grads, shipped, dense_n, sp_overflow = self._csr_exchange(
                grads, inv_scale=inv)
        self.sparse_comm_stats = {"sparse_elements": int(shipped),
                                  "dense_elements": int(dense_n)}
        self.state, grad_norm, lr, overflow, scale_out, tap = \
            self._sparse_apply_fn(self.state, grads, jnp.asarray(sp_overflow))
        metrics = {"loss": loss, "grad_norm": grad_norm, "lr": lr,
                   "loss_scale": scale_out, "overflow": overflow}
        if tap is not None:
            metrics["health_leaf_sq"] = tap
        return metrics

    # ------------------------------------------------------------------ #
    # The jitted train step
    # ------------------------------------------------------------------ #
    def _build_onebit_train_step(self):
        """1-bit Adam step: per-rank local grads inside shard_map over dp,
        error-feedback sign-compressed momentum allreduce (ops/onebit.py;
        reference onebit_adam.py:104-228)."""
        shard_map = comm.shard_map
        from ..ops.onebit import onebit_adam_update
        gas = self._scan_microbatches()
        flat_batch = self.dp_size == 1 and jax.process_count() == 1
        loss_fn = self.loss_fn
        compute_dtype = self.compute_dtype
        schedule_fn = self._schedule_fn
        p = dict(self.config.optimizer_params or {})
        b1, b2 = tuple(p.get("betas", (0.9, 0.999)))
        eps = p.get("eps", 1e-8)
        wd = p.get("weight_decay", 0.0)
        freeze_step = int(p.get("freeze_step", 100000))
        clip = self.gradient_clipping()
        dp, mesh = self.dp_size, self.mesh
        pld, accepts_pld = self.progressive_layer_drop, self._accepts_pld
        fp16 = self.config.fp16_enabled
        scaler_kw = self._scaler_kw

        def per_rank(params, opt_state, step, scale, micro_batches, keys):
            # worker_error arrives [1, ...] (its dp axis split by shard_map)
            opt_state = opt_state._replace(
                worker_error=jax.tree_util.tree_map(
                    lambda w: w[0], opt_state.worker_error))
            if dp > 1:
                # Distinct dropout streams per dp rank (the SPMD path's
                # global-batch masks).
                rank = lax.axis_index(DP_AXIS)
                keys = jax.vmap(lambda k: jax.random.fold_in(k, rank))(keys)

            theta = pld.theta_at(step.astype(jnp.float32)) \
                if accepts_pld else None

            def mean_loss_fn(p):
                def one_micro(loss_acc, xs):
                    mb, key = xs
                    cparams = _cast_floats(p, compute_dtype)
                    out = loss_fn(cparams, mb, key, pld_theta=theta) \
                        if accepts_pld else loss_fn(cparams, mb, key)
                    loss = out[0] if isinstance(out, tuple) else out
                    return loss_acc + loss.astype(jnp.float32) / gas, None

                total, _ = lax.scan(one_micro, jnp.asarray(0.0, jnp.float32),
                                    (micro_batches, keys))
                return total * scale if fp16 else total

            loss_val, grads = jax.value_and_grad(mean_loss_fn)(params)
            if fp16:
                loss_val = loss_val / scale
            lr = schedule_fn(step)
            new_params, new_opt, aux = onebit_adam_update(
                grads, opt_state, params, lr=lr, b1=b1, b2=b2, eps=eps,
                weight_decay=wd, freeze_step=freeze_step,
                axis_name=DP_AXIS if dp > 1 else None, dp=dp, clip=clip,
                loss_scale=scale if fp16 else None)
            new_opt = new_opt._replace(
                worker_error=jax.tree_util.tree_map(
                    lambda w: w[None], new_opt.worker_error))
            loss_out = lax.psum(loss_val, DP_AXIS) / dp if dp > 1 else loss_val
            return (new_params, new_opt, loss_out, lr,
                    aux["grad_norm"], aux["overflow"])

        def train_step(state: EngineState, micro_batches, rng):
            rng = jax.random.fold_in(rng, state.step)
            keys = jax.random.split(rng, gas)
            if flat_batch:
                micro_batches = jax.tree_util.tree_map(
                    lambda x: x.reshape((gas, x.shape[0] // gas) +
                                        x.shape[1:]), micro_batches)
            if dp > 1:
                batch_specs = jax.tree_util.tree_map(
                    lambda _: P(None, DP_AXIS), micro_batches)
                from ..ops.onebit import OnebitState
                opt_specs = OnebitState(
                    step=P(), m=P(), v=P(), worker_error=P(DP_AXIS),
                    server_error=P())
                fn = shard_map(
                    per_rank, mesh=mesh,
                    in_specs=(P(), opt_specs, P(), P(), batch_specs, P()),
                    out_specs=(P(), opt_specs, P(), P(), P(), P()),
                    check_vma=False)
            else:
                fn = per_rank
            new_params, new_opt, loss, lr, gnorm, overflow = fn(
                state.params, state.opt_state, state.step, state.loss_scale,
                micro_batches, keys)
            # Overflow-skip parity with the main path (shared resolution):
            # hold step (LR holds), count the skip, drive the scale
            # machine. Params/opt already held inside the update.
            new_state = state.replace(
                params=new_params, opt_state=new_opt,
                **_overflow_resolution(state, overflow, **scaler_kw))
            metrics = {"loss": loss, "grad_norm": gnorm,
                       "lr": lr, "loss_scale": state.loss_scale,
                       "overflow": overflow}
            return new_state, metrics

        return jax.jit(train_step, donate_argnums=(0,),
                       out_shardings=(self._state_shardings,
                                      self._metrics_shardings()))

    def _build_explicit_zero2_grads(self, scaled_loss, grad_sh, gas: int):
        """The guaranteed ZeRO-2/3 reduce-scatter gradient path: per-rank
        grads under ``shard_map`` over dp, each leaf ``lax.psum_scatter``'d
        at its declared partition dim (non-divisible leaves psum) — the
        collective the declarative path *hopes* GSPMD emits, emitted by
        construction. Selected when ``grad_sync`` resolves to "explicit"
        (the hlo_audit probe caught the declared sharding lowering to a
        full all-reduce + slice on this backend).

        FACTORED replica meshes generalize the schedule hierarchically
        (parallel/multislice.py): the shard_map goes fully manual over
        (outer, data) where outer is the ``slice`` axis (multi-slice
        scale-out) or the ``expert`` axis (MoE), each leaf reduce-
        scatters over ``data`` INSIDE the gas scan exactly as before,
        and the accumulated 1/dp residual crosses the outer axis ONCE
        per step: slices all-reduce it over DCN (optionally 1-bit-
        compressed with carried error feedback —
        ``zero_optimization.dcn_compression``), expert groups all-reduce
        the DENSE leaves across groups while expert-sharded leaves
        (their grads are already per-expert) skip the outer hop
        entirely. The loss-mean correction divides by the FULL replica
        count (outer * dp), exact for power-of-two worlds — which makes
        one 2-slice step on a slice-duplicated batch BIT-identical to
        the single-slice step from the same state
        (tests/test_multislice.py; multi-step trajectories meet the
        usual cross-program few-ulp FMA limit).

        ``scaled_loss(params, mb, key, scale, theta) -> (scaled, raw)``
        is differentiated HERE. Under stage 2 it receives the full
        (replicated / cast-cached) params and the explicit scatter runs
        on the full-shape local grads. Under stage 3 the params ENTER
        the shard_map as their dp shards; ``zero/stage3.gather_cast``
        reconstructs each leaf just-in-time (compute-dtype all-gather of
        the fp32 master shard, wrapped in ``jax.checkpoint`` so backward
        RE-GATHERS instead of saving the gathered tree) and its custom
        transpose IS the reduce-scatter — widened to f32 before the
        collective, so one stage-3 step is bit-identical to the stage-2
        step from the same state. Leaves a bound ``Zero3Scan`` covers
        pass through as shards: the model gathers them per layer inside
        its scan, prefetch_depth layers ahead. On a MULTI-SLICE mesh
        stage 3 composes by the same algebra: the stage-3 specs shard
        over `data` only, so each slice holds the full shard set
        replicated across slices, every gather_cast / layer-scan gather
        binds `data` (ICI — zero param bytes ever cross DCN), the
        in-vjp scatter is the in-slice tier, and the accumulated 1/dp
        residual takes the same once-per-step DCN hop as stage 2.

        Parity with the declarative path (tests/test_hlo_audit.py): one
        step from identical state is BIT-identical — the local per-rank
        computation is the same program (GSPMD partitions the batch the
        same way), the cross-dp reduction is f32 per micro-step in both,
        and the local-vs-global loss-mean correction ``(g·dp)/dp`` is
        exact for power-of-two dp. Multi-step trajectories agree to a few
        f32 ulp: the two lowerings' collectives sum rank partials in
        different orders (ring reduce-scatter rotates each shard's start
        rank), the same cross-program limit PR 1 documented for FMA
        contraction. RNG: per-rank dropout streams via ``fold_in(rank)``
        (the joint replica index on factored meshes), like the onebit/
        sparse shard_map paths.
        Returns ``fn(params, micro_batches, keys, scale, theta,
        dcn_error=None) -> (dp-sharded f32 grads, mean_loss, aux,
        new_dcn_error)`` — ``new_dcn_error`` is None unless DCN
        compression is live.
        """
        from ..parallel.axis_algebra import (MeshFactorization,
                                             plan_grad_sync)
        from ..parallel.multislice import inter_slice_allreduce
        shard_map = comm.shard_map
        mesh, dp = self.mesh, self.dp_size
        accepts_pld = self._accepts_pld
        zero3 = self._zero3
        # The collective schedule is DERIVED from the mesh factorization
        # (parallel/axis_algebra): the single outer replica axis (None
        # on a plain-dp mesh — `slice` rides DCN, `expert` stays ICI),
        # the full replica count, the shard_map scope, and where each
        # collective sits. The lax calls below execute that plan; the
        # wire model prices it; lint/audit check the compiled program
        # against it.
        fact = MeshFactorization.from_mesh(mesh)
        plan = plan_grad_sync(fact, zero3=zero3,
                              dcn_compression=self._dcn_compression)
        outer_axis = fact.outer_axis
        outer = fact.size(outer_axis) if outer_axis is not None else 1
        replicas = fact.replicas
        moe_manual = self.ep_size > 1
        dcn_compress = (self._dcn_compression
                        and plan.residual is not None
                        and plan.residual.tier == "dcn")
        leaves, treedef = jax.tree_util.tree_flatten(grad_sh)
        dims_tree = jax.tree_util.tree_unflatten(
            treedef, [_spec_axis(sh, DP_AXIS) for sh in leaves])
        grad_out_specs = jax.tree_util.tree_unflatten(
            treedef, [sh.spec for sh in leaves])
        # Expert-sharded grads (spec on the `expert` axis) already live
        # per expert group — they take the in-group `data` reduction
        # only, never the outer hop (experts are not replicas).
        outer_skip = jax.tree_util.tree_unflatten(
            treedef, [_spec_axis(sh, EP_AXIS) is not None
                      for sh in leaves])
        if zero3:
            # Params enter AS SHARDS (the stage-3 layout == the grad
            # layout, so the same spec tree serves both directions).
            param_in_specs = grad_out_specs
            covered = self._zero3_covered
            compute_dtype = self.compute_dtype
            from .zero.stage3 import gather_cast

            def gather_params(p):
                def one(leaf, d, cov):
                    if cov or not hasattr(leaf, "dtype") or \
                            not jnp.issubdtype(leaf.dtype, jnp.floating):
                        return leaf     # model self-gathers per layer
                    return gather_cast(leaf, DP_AXIS, d, compute_dtype)
                return jax.tree_util.tree_map(one, p, dims_tree, covered)

            # checkpoint: backward re-gathers (2 gathers + 1 scatter per
            # param per micro-step — the ZeRO-3 3x wire schedule) instead
            # of holding the gathered tree from forward to backward.
            gather_ck = jax.checkpoint(gather_params)

            def loss_for_grad(p, mb, key, scale, theta):
                return scaled_loss(gather_ck(p), mb, key, scale, theta)

            grad_fn = jax.value_and_grad(loss_for_grad, has_aux=True)
        else:
            # MoE factored mesh: expert-sharded params enter AS their
            # expert-axis shards (the fully-manual shard_map slices them
            # at the boundary; moe_ffn detects the in-scope axes via
            # comm.axis_in_scope and runs its collectives bare).
            param_in_specs = self._param_specs \
                if moe_manual and self._param_specs is not None else P()
            grad_fn = jax.value_and_grad(scaled_loss, has_aux=True)

        def scatter_leaf(g, d):
            # f32 BEFORE the collective: the cross-dp reduction then runs
            # in f32 exactly like the declarative path's f32 accumulation
            # carry (a bf16 reduction would break parity AND precision).
            # Stage 3 never reaches here — its scatter IS gather_cast's
            # transpose (same widen-then-scatter, inside the vjp).
            g = g.astype(jnp.float32)
            if d is None:
                return lax.psum(g, DP_AXIS)
            return lax.psum_scatter(g, DP_AXIS, scatter_dimension=d,
                                    tiled=True)

        @jax.named_scope("grad_sync")
        def reduce_grads(g):
            if zero3:
                # Already reduced: gather_cast's transpose scattered the
                # gathered leaves and psummed the replicated ones; the
                # model's zero3 scan did the same for covered leaves.
                return jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.float32), g)
            return jax.tree_util.tree_map(scatter_leaf, g, dims_tree)

        def reduce_aux(aux):
            # Aux stats computed on each rank's LOCAL tokens (the MoE
            # layer's ep==1 path inside this shard_map): counts sum
            # over EVERY replica axis in scope — dp, plus the slice
            # axis on a multislice mesh (an ep=1 MoE model composes
            # with slices; reducing over dp alone would report one
            # slice's counts as global) — the rest mean. On the
            # FACTORED (expert, data) mesh the layer's manual path
            # already psum/pmean'd its stats over both axes —
            # re-reducing would double-count.
            if not isinstance(aux, dict) or "moe" not in aux:
                return aux
            if moe_manual:
                return aux
            axes = (DP_AXIS,) if outer_axis is None \
                else (DP_AXIS, outer_axis)
            moe = dict(aux["moe"])
            for k, v in moe.items():
                moe[k] = lax.psum(v, axes) if k == "expert_tokens" \
                    else lax.pmean(v, axes)
            return {**aux, "moe": moe}

        skip_leaves = [bool(s) for s in
                       jax.tree_util.tree_leaves(outer_skip)]

        @jax.named_scope("grad_sync")
        def outer_reduce(g, err, scale):
            """The once-per-step outer hop on the accumulated 1/dp
            residual: slices all-reduce over DCN (optionally 1-bit-
            compressed with error feedback), expert groups all-reduce
            the dense leaves across groups; expert-sharded leaves pass
            through. Compression runs in UNSCALED units: the grads here
            are still loss-scaled (downstream unscales at the update),
            but the carried error feedback must not be denominated in a
            scale that the dynamic scaler changes under it — so the
            shard divides by ``scale`` before compressing and the
            summed result multiplies back (both exact: the loss scale
            is a power of two; a traced 1.0 for non-fp16). Returns
            (reduced grads, new error tree | None)."""
            if outer_axis is None:
                return g, None
            g_leaves = treedef.flatten_up_to(g)
            err_leaves = treedef.flatten_up_to(err) if dcn_compress \
                else [None] * len(g_leaves)
            inv_scale = 1.0 / scale
            out, errs = [], []
            for gl, sk, el in zip(g_leaves, skip_leaves, err_leaves):
                if sk:
                    out.append(gl)
                    errs.append(el)
                    continue
                if dcn_compress:
                    # el enters as this slice's [1, *shard] slab of the
                    # [slices, *shard] error buffer.
                    summed, ne = inter_slice_allreduce(
                        gl * inv_scale, el[0], num_slices=outer,
                        compress=True)
                    out.append(summed * scale)
                    errs.append(ne[None])
                else:
                    out.append(lax.psum(gl, outer_axis))
                    errs.append(None)
            new_err = jax.tree_util.tree_unflatten(treedef, errs) \
                if dcn_compress else None
            return jax.tree_util.tree_unflatten(treedef, out), new_err

        def per_rank(params, micro_batches, keys, scale, theta,
                     dcn_error=None):
            rank = lax.axis_index(DP_AXIS)
            if outer_axis is not None:
                # Joint replica index: distinct dropout streams per
                # (outer member, dp rank), slice-major like the mesh.
                rank = lax.axis_index(outer_axis) * dp + rank
            keys = jax.vmap(lambda k: jax.random.fold_in(k, rank))(keys)
            theta_arg = theta if accepts_pld else None
            if zero3:
                # Widen the SHARDS to f32 OUTSIDE the grad boundary:
                # grads w.r.t. an f32 primal stay f32 after the in-vjp
                # scatter (bf16 master-free primals would otherwise
                # narrow the f32-reduced grads back to bf16 — breaking
                # bit-parity with stage 2, whose scatter runs on widened
                # local grads post-AD). A no-op copy for fp32 masters;
                # shard-sized either way.
                params = jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.float32)
                    if hasattr(x, "dtype") and
                    jnp.issubdtype(x.dtype, jnp.floating) else x, params)
            if gas == 1:
                mb = jax.tree_util.tree_map(lambda x: x[0], micro_batches)
                (_, (raw_loss, aux)), g = grad_fn(params, mb, keys[0],
                                                  scale, theta_arg)
                g = reduce_grads(g)
                loss = raw_loss.astype(jnp.float32)
            else:
                def accum(carry, xs):
                    g_acc, loss_acc = carry
                    mb, key = xs
                    (_, (raw_loss, aux)), g = grad_fn(params, mb, key,
                                                      scale, theta_arg)
                    # Scatter per micro-step and carry only the 1/dp
                    # shards: the accumulation buffer never holds an
                    # unpartitioned gradient (the stage-2 invariant).
                    g_acc = jax.tree_util.tree_map(
                        jnp.add, g_acc, reduce_grads(g))
                    return (g_acc, loss_acc +
                            raw_loss.astype(jnp.float32) / gas), aux

                def zero_shard(p, d):
                    shape = list(p.shape)
                    if d is not None and not zero3:
                        shape[d] //= dp
                    # zero3: params are ALREADY the local shard view.
                    return jnp.zeros(shape, jnp.float32)

                zeros = jax.tree_util.tree_map(zero_shard, params,
                                               dims_tree)
                (g, loss), aux_stack = lax.scan(
                    accum, (zeros, jnp.asarray(0.0, jnp.float32)),
                    (micro_batches, keys))
                # Aux rides as stacked scan outputs; report the
                # micro-step mean (None stays None).
                aux = jax.tree_util.tree_map(
                    lambda a: jnp.mean(a, axis=0), aux_stack)
            # loss_fn normalizes over its LOCAL shard, so the summed
            # grads and losses are replicas x the global-mean values;
            # /replicas is exact for power-of-two worlds (bit-parity
            # with the declarative path, and — via the exact scaling —
            # of a slice-duplicated 2-slice run with the 1-slice run).
            # The outer hop happens AFTER the division, ONCE on the
            # accumulated shard: the DCN hop costs 1/dp of the grads per
            # STEP, not per micro-step.
            g = jax.tree_util.tree_map(lambda x: x / replicas, g)
            g, new_err = outer_reduce(g, dcn_error, scale)
            loss = lax.psum(loss, DP_AXIS)
            if outer_axis is not None:
                loss = lax.psum(loss, outer_axis)
            loss = loss / replicas
            if dcn_compress:
                return g, loss, reduce_aux(aux), new_err
            return g, loss, reduce_aux(aux)

        batch_axes = fact.grad_shard_scope if outer_axis is not None \
            else DP_AXIS
        err_specs = jax.tree_util.tree_unflatten(
            treedef, [P(SLICE_AXIS, *sh.spec) for sh in leaves]) \
            if dcn_compress else None

        def explicit_grads(params, micro_batches, keys, scale, theta,
                           dcn_error=None):
            batch_specs = jax.tree_util.tree_map(
                lambda _: P(None, batch_axes), micro_batches)
            theta_in = theta if theta is not None \
                else jnp.zeros((), jnp.float32)
            in_specs = (param_in_specs, batch_specs, P(), P(), P())
            out_specs = (grad_out_specs, P(), P())
            if dcn_compress:
                if dcn_error is None:
                    raise ValueError(
                        "dcn_compression is live but no error-feedback "
                        "state was passed (state.dcn_error)")
                fn = shard_map(per_rank, mesh=mesh,
                               in_specs=in_specs + (err_specs,),
                               out_specs=out_specs + (err_specs,),
                               check_vma=False)
                g, loss, aux, new_err = fn(params, micro_batches, keys,
                                           scale, theta_in, dcn_error)
                return g, loss, aux, new_err
            fn = shard_map(per_rank, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
            g, loss, aux = fn(params, micro_batches, keys, scale,
                              theta_in)
            return g, loss, aux, None

        return explicit_grads

    def _build_train_step(self):
        if self._onebit:
            if self._direct_grads_fn is not None:
                raise ValueError("grads_fn does not compose with OnebitAdam")
            return self._build_onebit_train_step()
        # Device scopes (compile-time HLO metadata; docs/tutorials/
        # telemetry.md): inside fwd_bwd JAX itself marks the backward ops
        # transpose(jvp(...)) and the recomputed ones rematted_computation.
        fwd_bwd = jax.named_scope("fwd_bwd")
        direct_grads = self._direct_grads_fn and fwd_bwd(self._direct_grads_fn)
        gas = self._scan_microbatches()
        # Single-chip/single-process: the step consumes the user's flat
        # batch directly and splits micro-batches device-side.
        flat_batch = self.replica_size == 1 and jax.process_count() == 1
        clip = self.gradient_clipping()
        fp16 = self.config.fp16_enabled
        schedule_fn = self._schedule_fn
        loss_fn = self.loss_fn
        compute_dtype = self.compute_dtype
        tx = self.tx
        fused_apply = self._fused_apply
        fused_step = self._fused_step
        narrow_grads = self._grads_stay_narrow()
        scaler_kw = self._scaler_kw
        if float(self.config.gradient_predivide_factor or 1.0) != 1.0:
            # Subsumed by design: grads are accumulated in fp32 as the mean
            # over the global batch, so the fp16 reduction-range motivation
            # for predivide (reference engine.py:1130-1141) does not arise.
            logger.warning("gradient_predivide_factor has no effect on TPU: "
                           "reductions are fp32-accumulated by XLA")

        # ZeRO-2: grads are BORN dp-sharded. Constraining the accumulation
        # carry makes XLA compile the cross-dp gradient reduction as
        # reduce-scatter and keeps only 1/dp of every gradient per chip —
        # the memory story stage2.py:613-738 implements with hooks+buckets.
        # When the hlo_audit probe shows this backend's partitioner
        # regressing the declaration to all-reduce + slice, grad_sync
        # resolves to "explicit" and the psum_scatter path below replaces
        # the declarative grad computation outright.
        grad_sh = self._grad_shardings()
        explicit_grads_fn = None

        def constrain_grads(g):
            if grad_sh is None:
                return g
            return lax.with_sharding_constraint(g, grad_sh)

        pld = self.progressive_layer_drop
        accepts_pld = self._accepts_pld
        use_cache = self._use_cast_cache
        master_free = self._master_free
        health_taps = self._health_tap_fn
        moe_cfg = self._moe

        raw_scaled_loss = _make_raw_scaled_loss(loss_fn, accepts_pld,
                                                gas)

        def scaled_loss(params, mb, key, scale, theta):
            # With the cast cache, ``params`` arrive already in the compute
            # dtype (state.cast_params); grads w.r.t. them equal the grads
            # the cast chain would deliver (the cast vjp is a dtype-widen).
            cparams = params if use_cache \
                else _cast_floats(params, compute_dtype)
            return raw_scaled_loss(cparams, mb, key, scale, theta)

        grad_fn = fwd_bwd(jax.value_and_grad(scaled_loss, has_aux=True))
        if self._grad_sync_mode == "explicit" and grad_sh is not None \
                and direct_grads is None:
            # Stage 3 hands the builder the CAST-FREE loss: the gather
            # performs the master-shard -> compute-dtype cast in flight,
            # and Zero3Scan-covered leaves must reach the model's layer
            # scan as fp32 shards (its custom transpose widens before the
            # per-layer reduce-scatter).
            explicit_grads_fn = fwd_bwd(self._build_explicit_zero2_grads(
                raw_scaled_loss if self._zero3 else scaled_loss,
                grad_sh, gas))

        def train_step(state: EngineState, micro_batches, rng):
            # Derive the per-step key INSIDE jit (a host-side fold_in would
            # dispatch eager device ops every step).
            rng = jax.random.fold_in(rng, state.step)
            scale = state.loss_scale
            theta = pld.theta_at(state.step.astype(jnp.float32)) \
                if accepts_pld else None
            keys = jax.random.split(rng, gas)
            if flat_batch:
                # Flat batches are split into [gas, micro, ...] HERE, inside
                # jit — a host-side eager reshape is one more dispatch
                # per step ahead of the async pipeline.
                micro_batches = jax.tree_util.tree_map(
                    lambda x: x.reshape((gas, x.shape[0] // gas) + x.shape[1:]),
                    micro_batches)

            loss_params = state.cast_params if use_cache else state.params
            new_dcn_error = None
            if direct_grads is not None:
                # Manual-VJP model (1F1B pipeline): one call yields loss
                # AND grads; it consumes all micro-batches itself. Params
                # arrive in the compute dtype like every other path (the
                # T-tick scan would otherwise re-read fp32 masters each
                # tick).
                mb = jax.tree_util.tree_map(lambda x: x[0], micro_batches)
                mean_loss, grads = direct_grads(
                    loss_params if use_cache else
                    _cast_floats(state.params, compute_dtype), mb, keys[0],
                    scale)
                grads = constrain_grads(_cast_floats(grads, jnp.float32))
                mean_loss = mean_loss.astype(jnp.float32)
                aux = None
            elif explicit_grads_fn is not None:
                # Guaranteed reduce-scatter: grads leave the shard_map
                # already dp-sharded and f32 (no constraint needed — the
                # out_specs ARE the ZeRO-2 layout). On multi-slice
                # meshes this is the HIERARCHICAL path; with DCN
                # compression the error-feedback buffers thread through.
                grads, mean_loss, aux, new_dcn_error = explicit_grads_fn(
                    loss_params, micro_batches, keys, scale, theta,
                    state.dcn_error)
            elif gas == 1:
                # Fast path: no accumulation scan — saves a full zero-init +
                # add pass over the fp32 grad tree every step. The optax
                # fallback gets the grads promoted to f32 here, so its
                # second moment is (f32 g)^2, never a bf16 square; XLA
                # folds that cast into its consumer. A Pallas call is
                # opaque to XLA's fusion — ahead of one the cast is a
                # materialized pass (7.4 ms a step at gpt2-large,
                # PERF.md PR 49) — so the one-pass fused apply takes the
                # grads at the width the backward wrote them: its kernel
                # and its norm widen on read, which is exact. (fp16's
                # loss-scaled grads stay wide: the v5e's Mosaic refuses
                # an f16 load, "Invalid vector type for load".)
                mb = jax.tree_util.tree_map(lambda x: x[0], micro_batches)
                (_, (raw_loss, aux)), grads = grad_fn(
                    loss_params, mb, keys[0], scale, theta)
                if not narrow_grads:
                    grads = _cast_floats(grads, jnp.float32)
                grads = constrain_grads(grads)
                mean_loss = raw_loss.astype(jnp.float32)
            else:
                def accum(carry, xs):
                    g_acc, loss_acc = carry
                    mb, key = xs
                    (_, (raw_loss, aux)), grads = grad_fn(loss_params, mb,
                                                          key, scale, theta)
                    g_acc = constrain_grads(
                        jax.tree_util.tree_map(jnp.add, g_acc, grads))
                    return (g_acc,
                            loss_acc + raw_loss.astype(jnp.float32) / gas), \
                        aux

                zero_grads = constrain_grads(jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32)
                    if hasattr(p, "dtype") else p, state.params))
                (grads, mean_loss), aux_stack = lax.scan(
                    accum, (zero_grads, jnp.asarray(0.0, jnp.float32)),
                    (micro_batches, keys))
                aux = jax.tree_util.tree_map(
                    lambda a: jnp.mean(a, axis=0), aux_stack)

            # Health tap BEFORE the apply consumes the grads: one small
            # stacked array of per-leaf sum-of-squares (non-finite entry
            # == the overflow vote's information, with provenance). The
            # grads are still loss-scaled here; dividing the tap by
            # scale^2 (one scalar multiply on [L]) reports TRUE norms —
            # anomaly events must match grad_norm semantics, not show
            # 65536x-inflated layers. A finite scale preserves
            # (non-)finiteness either way.
            tap = None
            if health_taps is not None:
                with jax.named_scope("health_tap"):
                    tap = health_taps(grads)
                    if fp16:
                        tap = tap / (scale * scale)

            sr_key = jax.random.fold_in(rng, 0x5352) if master_free \
                else None
            with jax.named_scope("optimizer"):
                if fused_step is not None:
                    # One-pass clipped update: the norm reduction (which
                    # doubles as the fp16 overflow vote — inf/nan in any grad
                    # surfaces as a non-finite sum of squares), the unscale
                    # multiply, the clip coefficient, the overflow-skip
                    # select, and the compute-dtype cast-cache refresh ALL
                    # ride the fused kernels' single read/write of
                    # grad+param+m+v. No separate global_norm pass, no
                    # full-tree unscale, no post-apply jnp.where select, no
                    # standalone cast pass.
                    out = fused_step(
                        grads, state.opt_state, state.params, clip=clip,
                        inv_scale=(1.0 / scale) if fp16 else None, fp16=fp16,
                        compute_norm=bool(clip and clip > 0) or fp16,
                        sr_key=sr_key,
                        cast_dtype=compute_dtype if use_cache else None)
                    new_params, new_opt_state = out.params, out.state
                    new_cast = out.cast_params if use_cache else None
                    grad_norm, overflow = out.grad_norm, out.overflow
                else:
                    # Two-pass path (optax chain / per-leaf fused ablation):
                    # unscale the loss-scaled gradients. Non-fp16 runs at a
                    # static scale of 1.0 — skip the full-tree multiply.
                    if fp16:
                        inv = 1.0 / scale
                        grads = jax.tree_util.tree_map(
                            lambda g: g * inv, grads)

                    overflow = tree_has_inf_or_nan(grads) if fp16 \
                        else jnp.asarray(False)

                    if (clip and clip > 0) or fp16:
                        grad_norm = global_norm(grads)
                    else:
                        # Full-tree norm is an extra HBM pass; only pay for it
                        # when something consumes it (clipping / overflow
                        # diagnostics).
                        grad_norm = jnp.asarray(-1.0, jnp.float32)
                    new_params, new_opt_state = _clipped_update(
                        grads, state, grad_norm, tx=tx,
                        fused_apply=fused_apply, clip=clip,
                        master_free=master_free, sr_key=sr_key)
                    # Refresh the compute-dtype cache in the same fused pass as
                    # the param update (one extra compute-dtype write instead
                    # of next step's full fp32 re-read + cast).
                    new_cast = _cast_floats(new_params, compute_dtype) \
                        if use_cache else None

                    # Overflow-skip (reference step semantics
                    # engine.py:1000-1085): keep old params/opt state, don't
                    # advance step (so LR holds).
                    keep = overflow
                    new_params = _tree_select(keep, state.params, new_params)
                    new_opt_state = _tree_select(keep, state.opt_state,
                                                 new_opt_state)
                    if use_cache:
                        new_cast = _tree_select(keep, state.cast_params,
                                                new_cast)

            # Shared overflow-vote resolution: step/skip bookkeeping +
            # loss-scale state machine. DCN-compression error feedback
            # commits only on a taken step (an overflow must not poison
            # the feedback with garbage residuals — the onebit rule).
            new_dcn = state.dcn_error
            if new_dcn_error is not None:
                new_dcn = _tree_select(overflow, state.dcn_error,
                                       new_dcn_error)
            new_state = state.replace(
                params=new_params, opt_state=new_opt_state,
                cast_params=new_cast, dcn_error=new_dcn,
                **_overflow_resolution(state, overflow, **scaler_kw))
            metrics = {
                "loss": mean_loss,
                "grad_norm": grad_norm,
                "lr": schedule_fn(state.step),
                "loss_scale": scale,
                "overflow": overflow,
            }
            if tap is not None:
                metrics["health_leaf_sq"] = tap
            if moe_cfg is not None:
                # The moe block promises MoE metrics (the out_shardings
                # schema is fixed pre-trace); a dense model behind it is
                # a config error, said plainly.
                if not (isinstance(aux, dict) and "moe" in aux):
                    raise ValueError(
                        "ds_config has a `moe` block but the model's "
                        "loss_fn returned no moe stats — build the model "
                        "with TransformerConfig.moe "
                        "(deepspeed_tpu.moe.MoEConfig) or drop the block")
                st = aux["moe"]
                metrics["moe_expert_tokens"] = \
                    st["expert_tokens"].astype(jnp.float32)
                metrics["moe_drop_fraction"] = st["drop_fraction"]
                metrics["moe_aux_loss"] = st["aux_loss"]
                metrics["moe_z_loss"] = st["z_loss"]
            return new_state, metrics

        return jax.jit(train_step, donate_argnums=(0,),
                       out_shardings=(self._state_shardings,
                                      self._metrics_shardings(
                                          with_taps=health_taps is not None,
                                          with_moe=moe_cfg is not None)))

    def _build_eval_step(self):
        loss_fn = self.loss_fn
        compute_dtype = self.compute_dtype

        def eval_step(params, batch, rng):
            cparams = _cast_floats(params, compute_dtype)
            out = loss_fn(cparams, batch, rng)
            loss, _ = (out if isinstance(out, tuple) else (out, None))
            return loss

        return jax.jit(eval_step)

    # ------------------------------------------------------------------ #
    # Public train/eval API
    # ------------------------------------------------------------------ #
    def _next_rng(self):
        return jax.random.fold_in(self._base_rng, self.global_steps)

    def _check_batch_divisible(self, batch) -> None:
        gas = self._scan_microbatches()
        for x in jax.tree_util.tree_leaves(batch):
            lead = getattr(x, "shape", (0,))[0] if getattr(x, "ndim", 1) else 0
            if lead % gas != 0:
                # ValueError, not assert: under ``python -O`` an assert is
                # stripped and the in-jit reshape fails with an opaque XLA
                # shape error instead.
                raise ValueError(
                    f"batch dim {lead} not divisible by grad-accum {gas}")

    def _stack_micro_batches(self, batch):
        """Reshape to [gas, per_micro_step, ...]. Device arrays stay on
        device (np.asarray on a jax.Array would be a synchronous D2H
        round-trip every step)."""
        gas = self._scan_microbatches()

        def reshape(x):
            if not isinstance(x, (jax.Array, np.ndarray)):
                x = np.asarray(x)
            lead = x.shape[0]
            if lead % gas != 0:
                raise ValueError(
                    f"batch dim {lead} not divisible by grad-accum {gas}")
            return x.reshape((gas, lead // gas) + x.shape[1:])
        return jax.tree_util.tree_map(reshape, batch)

    def train_batch(self, batch=None, data_iter=None):
        """Run one full training iteration (all grad-accum micro steps + one
        optimizer step). Parity with PipelineEngine.train_batch semantics for
        the non-pipeline engine; the preferred TPU API.

        ``batch``: pytree with leading dim ``gas * micro * dp_local``; or pull
        ``gas`` micro-batches from ``data_iter`` / the engine's dataloader.

        Host spans (``Telemetry.span`` — profiler annotations, a flag
        test outside a profiler session): ``train_batch`` > ``data_prep``,
        ``step_dispatch`` (``offload_step`` when offloading), ``step_log``.

        Every call is one row of ``self.timeline`` (monitor/training.py),
        telemetry on or off: four clock reads at the spans' boundaries
        and no device sync. While something records spans, ``train_batch``
        carries the row as args: ``row``, ``gap_ms`` (entry to entry),
        ``outside_ms`` (since the call before returned), ``host_ms`` =
        ``data_ms`` + ``dispatch_ms`` + ``log_ms`` (the three child
        spans), ``in_flight`` (earlier steps not yet seen complete at the
        dispatch), ``completed`` (steps first seen complete since the
        entry before) and ``built`` (step programs this call built or
        compiled: 1 on the first call; later, a recompile).
        """
        tl = self.telemetry
        tm = self.timeline
        step = self.global_steps
        tm.enter(step)
        saved_s = tl.checkpoint_exposed_s
        tl.profiler_tick(step)
        with tl.span("train_batch", step_num=step) as span:
            with tl.span("data_prep", step=step):
                micro_batches = self._prepare_batch(batch, data_iter)
            tm.lap("data_s")
            programs = startup.own_builds()
            with tl.span("offload_step" if self._offload is not None
                         else "step_dispatch", step=step):
                metrics = self._dispatch_step(micro_batches)
            tm.dispatched(metrics["loss"], startup.own_builds() - programs)
            with tl.span("step_log", step=step):
                self._record_telemetry(metrics, tm.wall_s)
                self._maybe_log(metrics)
                self._maybe_auto_save()
            tm.leave(tl.checkpoint_exposed_s - saved_s)
            if spans_recorded(tl):
                span.set_metadata(**tm.span_args())
        return metrics["loss"]

    def _prepare_batch(self, batch, data_iter):
        """train_batch's ``data_prep``: the iterator pull, the micro-batch
        layout and the ``device_put``; builds the step on first use."""
        tl = self.telemetry
        sparse_path = self._sparse_mask is not None and self.dp_size > 1
        if self._train_step_fn is None and self._offload is None \
                and not sparse_path:
            # Recompile-sentinel instrumentation (a no-op pass-through
            # when telemetry is off): a jit cache miss after warmup is an
            # unexpected retrace — logged, optionally fatal.
            self._train_step_fn = tl.instrument_step_fn(
                "train_step", self._build_train_step())

        if batch is None:
            it = data_iter
            if it is None:
                if self._data_iterator is None:
                    assert self.training_dataloader is not None, \
                        "train_batch() needs a batch, data_iter, or training_data"
                    self._data_iterator = iter(RepeatingLoader(self.training_dataloader))
                it = self._data_iterator
            gas = self.gradient_accumulation_steps()
            # Fetch-wait accounting for the goodput ledger: host wall the
            # engine spends waiting on the input pipeline (monotonic clock
            # only, no device access). Covers any iterator — the
            # dataloader's own fetch_wait_s counter is the loader-local
            # view of the same stall.
            t_fetch0 = time.perf_counter()
            micro = [next(it) for _ in range(gas)]
            batch = jax.tree_util.tree_map(
                lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
                *micro)
            if tl.ledger is not None:
                tl.ledger.note("data_stall",
                               time.perf_counter() - t_fetch0)

        if self._offload is None and self.replica_size == 1 \
                and jax.process_count() == 1:
            # Flat fast path: no host-side tree ops at all; the jitted step
            # does the micro-batch split on device.
            self._check_batch_divisible(batch)
            micro_batches = batch
        else:
            micro_batches = self._stack_micro_batches(batch)
        if self.replica_size > 1:
            # Shard the per-micro-step batch dim over dp so XLA partitions
            # the whole forward/backward data-parallel. Multi-process: each
            # process holds only its local dp share, so assemble the global
            # array from per-process shards instead of device_put (which
            # would treat every local array as the full global batch).
            shardings = self._batch_sharding(micro_batches, leading_dims=2)
            if jax.process_count() > 1:
                micro_batches = jax.tree_util.tree_map(
                    lambda x, sh: jax.make_array_from_process_local_data(
                        sh, np.asarray(x)),
                    micro_batches, shardings)
            else:
                micro_batches = jax.device_put(micro_batches, shardings)
        if (self.flops_profiler is not None and
                self.global_steps == self.config.flops_profiler_config.profile_step):
            self._run_flops_profiler(micro_batches)
        return micro_batches

    def _dispatch_step(self, micro_batches):
        """train_batch's ``step_dispatch``: the compiled step's call (it
        returns without waiting for the device) and the step counters."""
        self._maybe_refresh_moe_wire(micro_batches)
        self.tput_timer.start()
        if self._offload is not None:
            metrics = self._train_batch_offload(micro_batches)
        elif self._sparse_mask is not None and self.dp_size > 1:
            metrics = self._train_batch_sparse(micro_batches)
        else:
            self.state, metrics = self._train_step_fn(
                self.state, micro_batches, self._base_rng)

        self.global_steps += 1
        self.micro_steps += self.gradient_accumulation_steps()
        self.global_samples += self.train_batch_size()
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "step"):
            self.lr_scheduler.last_batch_iteration = self.global_steps - 1
        self.tput_timer.stop()
        return metrics

    def _maybe_auto_save(self) -> None:
        """Auto-save (checkpoint.snapshot_every): tag global_stepN into
        the configured save_dir — the resume anchor the crash/kill
        harness (tools/crashkill.py) loads from. Shared by every
        optimizer-step boundary: train_batch AND the
        forward/backward/step trio honor the same cadence."""
        if self._ckpt_every > 0 and \
                self.global_steps % self._ckpt_every == 0:
            self.save_checkpoint(self._ckpt_dir)

    # Alias matching common JAX naming.
    train_step = train_batch

    def _record_telemetry(self, metrics, wall_s: float) -> None:
        """Buffer this step's telemetry record — append-only, no device
        access (the metrics dict's jax scalars ride as futures and sync
        at the next report-boundary drain). ``wall_s`` becomes the
        record's ``wall_ms`` and the watchdog's beat: from train_batch it
        is the timeline row's ``data_s`` + ``dispatch_s`` (entry to the
        step's dispatch: the same clock reads, ``self.timeline`` holds
        the call's other figures); on the jitted paths that is DISPATCH
        wall (steps pipeline asynchronously — the fenced truth is the
        throughput timer's window average in the report record), on the
        host-synchronous offload path it is true step wall."""
        tl = self.telemetry
        if not tl.enabled:
            return
        # Deferred fail_on_recompile surfaces HERE — after the donated
        # step's returned state was stored, so a caught RecompileError
        # leaves the engine usable (e.g. to checkpoint before dying).
        tl.raise_pending()
        host: Dict[str, Any] = {
            "wall_ms": wall_s * 1e3,
            "wire_bytes": self._wire_bytes,
            "samples": self.train_batch_size(),
        }
        if self._offload is not None and self.offload_timings:
            t = self.offload_timings
            off = {k: round(float(t[k]), 3) for k in (
                "device_step_ms", "d2h_ms", "host_norm_ms", "host_step_ms",
                "h2d_dispatch_ms", "h2d_wait_ms", "wall_ms") if k in t}
            off["overlap_fraction"] = round(
                float(t.get("overlap_fraction", 0.0)), 4)
            off["num_buckets"] = int(t.get("num_buckets", 1))
            off["overlapped"] = bool(t.get("overlapped", False))
            host["offload"] = off
            tl.add_offload_trace(t)
        tl.record_step(self.global_steps, metrics, **host)

    def _startup_args(self) -> Dict[str, int]:
        return {"param_bytes": sum(
            int(getattr(leaf, "nbytes", 0))
            for leaf in jax.tree_util.tree_leaves(self.state.params))}

    def _report_extra(self) -> Dict[str, Any]:
        """Report-boundary fields for the telemetry drain record. Called
        ONLY at a drain boundary (the skipped_steps read is a sync)."""
        self._maybe_build_cost_model()
        extra: Dict[str, Any] = {
            "global_samples": self.global_samples,
            "samples_per_sec": self.tput_timer.avg_samples_per_sec(),
            "samples_per_sec_valid": self.tput_timer.has_samples(),
            "startup": startup.snapshot(with_rows=False),
        }
        if self._offload is not None:
            extra["skipped_steps"] = self._offload.skipped_steps
        else:
            self.skipped_steps = int(
                jax.device_get(self.state.skipped_steps))
            extra["skipped_steps"] = self.skipped_steps
        return extra

    def profile_window(self, steps: int,
                       start_step: Optional[int] = None) -> Optional[str]:
        """Arm a ``jax.profiler`` capture over ``steps`` hot training
        steps (default: starting at the next ``train_batch``). The
        trace is ingested into the per-step wall decomposition and
        reconciled against the roofline cost model at the next telemetry
        drain (``telemetry.profile`` block); with telemetry off this is
        a no-op returning None. Returns the capture dir. Zero device
        syncs are added when no window is armed — the PR-4 fence
        contract."""
        return self.telemetry.arm_profile_window(
            int(steps), start_step=self.global_steps + 1
            if start_step is None else int(start_step))

    # ------------------------------------------------------------------ #
    # Roofline cost model (monitor/cost_model.py)
    # ------------------------------------------------------------------ #
    def _maybe_build_cost_model(self) -> None:
        """Build the roofline cost model ONCE, at the first report
        boundary — every active step path has compiled by then, and the
        recompile sentinel holds each one's abstract signature. The build
        AOT-relowers each path host-side (no device traffic, no fences);
        any failure degrades to a structured event, never to a dead
        training loop."""
        tl = self.telemetry
        if self._cost_model_built or not tl.enabled \
                or tl.sentinel is None \
                or not getattr(self.config.telemetry_config,
                               "cost_model", True):
            return
        self._cost_model_built = True
        try:
            from ..monitor.cost_model import build_cost_model
            step_paths = self._cost_model_step_paths()
            # Wire bytes are PER STEP; price them on the grad-computing
            # path, split per invocation so the step total reconciles.
            # Two tiers: the inter-slice DCN hop is priced against its
            # own (much lower) bandwidth ceiling — a step can be
            # DCN-bound while ICI idles.
            comm: Dict[str, float] = {}
            dcn: Dict[str, float] = {}
            ici_bytes = self._wire_bytes - self._wire_bytes_dcn
            for p in ("train_step", "offload_grad_step",
                      "sparse_grad_step", "grad_step"):
                if p in step_paths and self._wire_bytes:
                    comm[p] = float(ici_bytes) / step_paths[p]
                    if self._wire_bytes_dcn:
                        dcn[p] = float(self._wire_bytes_dcn) / \
                            step_paths[p]
                    break
            payload = build_cost_model(
                tl.sentinel, comm_bytes_by_path=comm,
                step_paths=step_paths, n_devices=int(self.mesh.size),
                dcn_bytes_by_path=dcn)
            pricing = self._optimizer_apply_pricing()
            if pricing is not None:
                payload["optimizer_apply"] = pricing
            payload.update(self._cost_model_extras(payload))
            tl.set_cost_model(payload,
                              samples_per_step=self.train_batch_size())
            step = payload.get("step", {})
            if step.get("bound"):
                log_dist(
                    "cost model: step is "
                    f"{step['bound']}-bound, analytic floor "
                    f"{step['floor_ms']:.3f} ms/step "
                    f"({payload['chip']['name']} peaks"
                    f"{', ASSUMED' if payload['chip']['assumed'] else ''})",
                    ranks=[0])
        except Exception as e:   # observability must not kill training
            tl.event("cost_model_error",
                     {"error": f"{type(e).__name__}: {e}"[:300]})

    def _optimizer_apply_pricing(self) -> Optional[Dict[str, Any]]:
        """Analytic HBM bytes the optimizer APPLY phase moves per step
        (ops/fused_update.apply_hbm_bytes): the active mode priced
        against the alternative, so the roofline record carries the
        one-pass-vs-two-pass ratio explicitly.  Figures are per replica
        of the full tree; under ZeRO the apply runs shard-local, so
        per-DEVICE bytes divide by ``zero_shard_divisor`` uniformly.
        None for engines whose apply is not the fused family (offload's
        host Adam, onebit's compressed exchange price differently)."""
        if self._fused_apply is None or self._offload is not None \
                or self._onebit:
            return None
        from ..ops.fused_update import apply_hbm_bytes
        # Sparse-gradient engines route the apply through the two-pass
        # sparse_apply_step regardless of fused_step availability.
        one_pass = self._fused_step is not None and \
            self._sparse_mask is None
        pricing = apply_hbm_bytes(
            self.state.params, one_pass=one_pass,
            cast_dtype=(self.compute_dtype if self._use_cast_cache
                        else None),
            fp16=self.config.fp16_enabled,
            clip=bool(self.gradient_clipping()),
            grad_dtype=self._apply_grad_dtype())
        # Per-device bytes divide by dp only where the kernels actually
        # run shard-local — the same predicate that handed the mesh to
        # fused_adam (a live mp/pp axis keeps the plain lowering on
        # full buffers).
        shard = self.dp_size if self._fused_shard_local() else 1
        return {
            "mode": "one_pass" if one_pass else "two_pass",
            "per_replica": pricing,
            "zero_shard_divisor": shard,
            "active_bytes_per_device": int(pricing["active"] // shard),
        }

    def _cost_model_step_paths(self) -> Dict[str, float]:
        """{path_name: invocations per optimizer step} for the paths that
        compose ONE train step in the engine's active mode."""
        if self._offload is not None:
            return {"offload_grad_step": 1.0}
        if self._sparse_mask is not None and self.dp_size > 1:
            return {"sparse_grad_step": 1.0, "sparse_apply_step": 1.0}
        if self._train_step_fn is not None:
            return {"train_step": 1.0}
        # forward/backward/step trio: one grad program per micro-batch,
        # one apply at the accumulation boundary.
        return {"grad_step": float(self.gradient_accumulation_steps()),
                "apply_grads": 1.0}

    def _cost_model_extras(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        """Subclass hook for extra cost-model payload sections (the
        pipeline engine adds per-stage attribution)."""
        return {}

    # ------------------------------------------------------------------ #
    # Static lint audit (analysis/)
    # ------------------------------------------------------------------ #
    def _lint_path_meta(self, name: str) -> Dict[str, Any]:
        """Engine-truth metadata for the lint passes auditing path
        ``name`` (analysis/passes.py): which paths carry the gradient
        sync, at which DECLARED mode, the per-leaf payload sizes a
        grad-sync collective may legally carry, and the analytic
        per-device state bytes the materialization threshold scales
        from. Host metadata only — no device access."""
        from .zero.partition import _leaf_spec
        grad_paths = ("train_step", "offload_grad_step",
                      "sparse_grad_step", "grad_step")
        param_leaves = [l for l in
                        jax.tree_util.tree_leaves(self.state.params)
                        if hasattr(l, "shape")]
        param_bytes_full = sum(
            int(l.size) * int(l.dtype.itemsize) for l in param_leaves)
        # Largest single UNSHARDED leaf at f32 (grads promote to f32 on
        # every sync path): the materialization pass exempts buffers up
        # to one full leaf — per-leaf transients are inherent to any
        # lowering; the gate is about tree-scale materialization.
        largest_leaf = max(
            (int(l.size) * max(4, int(l.dtype.itemsize))
             for l in param_leaves), default=0)
        scatterable: set = set()
        if self.dp_size > 1:
            wire_itemsize = jnp.dtype(self.compute_dtype).itemsize
            # Under ZeRO >= 2 only the partitionable leaves reduce-
            # scatter; dense modes ("none"/"allreduce") sync EVERY grad
            # leaf — the pass still needs those payload sizes to judge
            # placement (an all-reduce trapped inside the gas scan).
            partitioned_only = self.zero_optimization_stage() >= 2
            for l in param_leaves:
                if partitioned_only and not any(
                        s is not None for s in
                        _leaf_spec(l.shape, self.dp_size, DP_AXIS)):
                    continue
                n = int(l.size)
                # Grads sync in f32 on the main paths; the offload
                # wire dtype is the compute dtype under bf16.
                scatterable.add(n * 4)
                scatterable.add(n * int(wire_itemsize))
        # Stage 3: the materialization gate's budget is the declared
        # (sharded) per-device state PLUS the bounded gather working set
        # — generic paths gather leaf-at-use (full tree at COMPUTE
        # dtype, transient), the layer-scan path holds prefetch_depth+1
        # gathered layers. Never the fp32 master tree.
        gather_ws = 0
        if self._zero3:
            from .zero.stage3 import gather_working_set_bytes
            spec = self._zero3_scan_spec
            gather_ws = gather_working_set_bytes(
                self.state.params, self._stage3_specs, DP_AXIS,
                jnp.dtype(self.compute_dtype).itemsize,
                prefetch_depth=self._prefetch_depth,
                scan_paths=spec.covers if spec is not None else None,
                mesh=self.mesh)
        # Expert-sharded leaves (MoE, ep > 1): the payload sizes an
        # expert-grad collective may legally carry (the per-device 1/ep
        # shard, and its per-layer slice inside the block scan) — any
        # all-reduce of one with replica groups WIDER than the data axis
        # spans the expert axis, i.e. treats experts as replicas: the
        # seeded-violation case collective_placement catches.
        expert_bytes: set = set()
        if self.ep_size > 1 and self._param_specs is not None:
            from ..moe.sharding import is_expert_spec
            all_leaves = jax.tree_util.tree_leaves(self.state.params)
            spec_leaves = jax.tree_util.tree_structure(
                self.state.params).flatten_up_to(self._param_specs)
            itemsizes = (4, int(jnp.dtype(self.compute_dtype).itemsize))
            from jax.sharding import PartitionSpec as _P

            def payloads(nelems, ndim, lead):
                # Full local buffer + its per-layer slice inside the
                # block scan, at f32 and the wire dtype.
                out = set()
                for b in itemsizes:
                    out.add(nelems * b)
                    if ndim >= 3 and lead > 0:
                        out.add(nelems // lead * b)
                return out

            dense_payloads: set = set()
            for l, sp in zip(all_leaves, spec_leaves):
                if not hasattr(l, "shape") or \
                        (isinstance(sp, _P) and is_expert_spec(sp)):
                    continue
                dense_payloads |= payloads(
                    int(l.size), getattr(l, "ndim", 0),
                    int(l.shape[0]) if getattr(l, "ndim", 0) else 0)
            for l, sp in zip(all_leaves, spec_leaves):
                if not hasattr(l, "shape") or \
                        not (isinstance(sp, _P) and is_expert_spec(sp)):
                    continue
                for payload in payloads(
                        int(l.size) // self.ep_size,
                        getattr(l, "ndim", 0),
                        int(l.shape[0]) if getattr(l, "ndim", 0) else 0):
                    # The check is a payload-size heuristic, so two
                    # guards against false positives: a 64 KiB floor
                    # (bias-sized expert leaves are byte-identical to
                    # small dense grads — a [H, E] router grad matches
                    # an expert-bias slice) and exclusion of any size a
                    # DENSE leaf could legally all-reduce at across the
                    # full replica set. A colliding size loses coverage
                    # for that one leaf, never CI.
                    if payload >= 64 * 1024 and \
                            payload not in dense_payloads:
                        expert_bytes.add(payload)
        # Factored replica meshes: the per-rank payloads the OUTER-axis
        # hop may legally carry — the 1/dp shard of every scatterable
        # dense leaf and the full replicated tail (f32; the compressed
        # DCN emulation psums the same shapes). collective_placement
        # whitelists outer-group all-reduces of these (a shard payload
        # can coincide byte-for-byte with a smaller leaf's full size)
        # and, on multislice meshes, flags anything grad-sized spanning
        # the slice axis (a flat joint sync over DCN). Expert-sharded
        # leaves are excluded — they never take the outer hop and have
        # their own check.
        dcn_shard_bytes: set = set()
        outer_factored = self.slice_size > 1 or (
            self.ep_size > 1 and
            getattr(self, "_grad_sync_mode", "none") == "explicit")
        if outer_factored:
            all_leaves = jax.tree_util.tree_leaves(self.state.params)
            if self._param_specs is not None and self.ep_size > 1:
                from ..moe.sharding import is_expert_spec
                spec_l = jax.tree_util.tree_structure(
                    self.state.params).flatten_up_to(self._param_specs)
            else:
                is_expert_spec = None
                spec_l = [None] * len(all_leaves)
            for l, sp in zip(all_leaves, spec_l):
                if not hasattr(l, "shape"):
                    continue
                if sp is not None and is_expert_spec is not None \
                        and is_expert_spec(sp):
                    continue
                n = int(l.size)
                if any(s is not None for s in
                       _leaf_spec(l.shape, self.dp_size, DP_AXIS)):
                    dcn_shard_bytes.add(n // self.dp_size * 4)
                else:
                    dcn_shard_bytes.add(n * 4)
        # Stage 3: the per-leaf GATHERED payload sizes (full leaf at the
        # wire dtypes, plus the per-layer slice for scanned leaves) — on
        # a multislice mesh collective_placement flags any all-gather of
        # one whose groups are wider than dp (param bytes over DCN; the
        # planner binds every stage-3 gather to `data`/ICI).
        z3_gather_leaf: set = set()
        if self._zero3 and self.dp_size > 1:
            from .zero.partition import spec_dp_dim
            wire_itemsize = int(jnp.dtype(self.compute_dtype).itemsize)
            leaves = jax.tree_util.tree_leaves(self.state.params)
            spec_l = jax.tree_util.tree_structure(
                self.state.params).flatten_up_to(self._stage3_specs)
            cov_l = jax.tree_util.tree_leaves(self._zero3_covered)
            for l, sp, cov in zip(leaves, spec_l, cov_l):
                if not hasattr(l, "shape"):
                    continue
                if spec_dp_dim(sp, DP_AXIS) is None:
                    continue
                n = int(l.size)
                for b in (wire_itemsize, 4):
                    z3_gather_leaf.add(n * b)
                    if cov and getattr(l, "ndim", 0) >= 1 and \
                            int(l.shape[0]) > 0:
                        z3_gather_leaf.add(n // int(l.shape[0]) * b)
        # The derived collective schedule (axis_algebra) the explicit
        # path executes — serialized for lint/audit consumers.
        plan_meta = None
        if getattr(self, "_grad_sync_mode", "none") == "explicit" and \
                self.replica_size > 1:
            from ..parallel.axis_algebra import (MeshFactorization,
                                                 plan_grad_sync)
            try:
                plan_meta = plan_grad_sync(
                    MeshFactorization.from_mesh(self.mesh),
                    zero3=bool(self._zero3),
                    dcn_compression=bool(self._dcn_compression)).to_meta()
            except ValueError:
                plan_meta = None
        return {
            "grad_sync_path": name in grad_paths,
            "grad_sync_mode": getattr(self, "_grad_sync_mode", "none"),
            # The trio's grad_step is one micro-batch per invocation; the
            # fused paths scan gas micro-batches inside one program.
            "gas": 1 if name == "grad_step" else self._scan_microbatches(),
            "scatterable_leaf_bytes": sorted(scatterable),
            "declared_state_bytes": int(analytic_state_bytes(self.state)),
            "param_bytes_full": int(param_bytes_full),
            "largest_leaf_bytes": int(largest_leaf),
            "dp": self.dp_size,
            "ep": self.ep_size,
            "slices": self.slice_size,
            "dcn_shard_bytes": sorted(dcn_shard_bytes),
            "expert_leaf_bytes": sorted(expert_bytes),
            "expert_group_size": self.dp_size,
            "zero_stage": self.zero_optimization_stage(),
            "zero3": bool(self._zero3),
            "zero3_gather_bytes": int(gather_ws),
            "zero3_gather_leaf_bytes": sorted(z3_gather_leaf),
            "collective_plan": plan_meta,
        }

    def lint_audit(self, config=None, waivers=None, passes=None):
        """Run the compile-time lint suite (analysis/) over every step
        path this engine has compiled — host-side re-lower from the
        recompile sentinel's recorded abstract signatures; zero device
        fences. Returns an ``analysis.findings.LintReport``."""
        from ..analysis.auditor import lint_engine
        return lint_engine(self, config=config, waivers=waivers,
                           passes=passes)

    def eval_batch(self, batch, rng=None):
        if self._eval_step_fn is None:
            self._eval_step_fn = self._build_eval_step()
        rng = rng if rng is not None else self._next_rng()
        return self._eval_step_fn(self.state.params, batch, rng)

    def _run_flops_profiler(self, micro_batches) -> None:
        """Trace the full train step and print the per-module FLOPs table
        (reference engine.py:801-824 runs its hook profiler over one forward
        at flops_profiler.profile_step; here the jaxpr walk covers
        forward+backward+optimizer in one analytic pass, no monkey-patching)."""
        from ..profiling.flops_profiler import profile_fn
        cfg = self.config.flops_profiler_config
        # The sentinel wrapper keeps the raw jitted fn on __wrapped__;
        # profile the raw fn so the jaxpr walk sees the same callable
        # either way (and the profiling trace is not counted as a call).
        step_fn = self._train_step_fn
        step_fn = getattr(step_fn, "__wrapped__", step_fn)
        if step_fn is None:     # offload path: profile the grad function
            if self._offload_grad_fn is None:
                self._offload_grad_fn = self.telemetry.instrument_step_fn(
                    "offload_grad_step",
                    self._build_offload_grad_fn(
                        bucketed=self._offload_overlap))
            grad_fn = getattr(self._offload_grad_fn, "__wrapped__",
                              self._offload_grad_fn)
            res = profile_fn(
                grad_fn, self.state.params, micro_batches,
                self._base_rng, jnp.asarray(self.global_steps, jnp.int32),
                jnp.asarray(self._offload.loss_scale, jnp.float32),
                params=self.state.params, run=False)
        else:
            res = profile_fn(step_fn, self.state, micro_batches,
                             self._base_rng, params=self.state.params,
                             run=False)
        self.flops_profiler.result = res
        if jax.process_index() == 0:
            self.flops_profiler.print_model_profile(
                module_depth=cfg.module_depth, top_modules=cfg.top_modules,
                detailed=cfg.detailed)

    def _maybe_log(self, metrics) -> None:
        """Log at steps_per_print boundaries ONLY — any device_get here is a
        host↔device sync that would stall the async dispatch pipeline (the
        TPU analogue of the reference keeping cuda.synchronize behind
        wall_clock_breakdown). skipped_steps syncs lazily from state. The
        telemetry drain rides the same boundary discipline (its own
        report_steps cadence, defaulting to steps_per_print)."""
        if self.global_steps % max(1, self.steps_per_print()) == 0:
            # Scalars only: the health tap rides metrics as a
            # [num_leaves] array and is drain/event material, not a
            # print-line field.
            m = {k: (float(jax.device_get(v)) if hasattr(v, "dtype") else v)
                 for k, v in metrics.items()
                 if getattr(v, "ndim", 0) == 0 or not hasattr(v, "dtype")}
            if m.get("grad_norm", 0.0) < 0:
                # Sentinel: norm computation skipped (no clipping, no fp16) —
                # don't surface a bogus value to logs/monitors.
                m.pop("grad_norm", None)
            if self._offload is None:
                self.skipped_steps = int(
                    jax.device_get(self.state.skipped_steps))
            gn = f"grad_norm={m['grad_norm']:.4f} " if "grad_norm" in m else ""
            off = ""
            if self._offload is not None and self.offload_timings:
                # The offload breakdown used to die as an undocumented
                # engine attribute; surface it where the operator looks.
                t = self.offload_timings
                host_ms = t.get("host_norm_ms", 0.0) + \
                    t.get("host_step_ms", 0.0)
                off = (f" offload[d2h={t.get('d2h_ms', 0.0):.0f}ms "
                       f"host={host_ms:.0f}ms "
                       f"h2d={t.get('h2d_dispatch_ms', 0.0):.0f}ms "
                       f"overlap={t.get('overlap_fraction', 0.0):.2f}]")
            log_dist(
                f"step={self.global_steps} loss={m['loss']:.6f} "
                f"lr={m['lr']:.3e} {gn}"
                f"loss_scale={m['loss_scale']:.1f} "
                f"overflow={bool(m['overflow'])}{off}",
                ranks=[0])
        self.telemetry.maybe_drain(self.global_steps,
                                   extra_fn=self._report_extra)

    # ------------------------------------------------------------------ #
    # torch-style compatibility trio (forward → backward → step)
    # ------------------------------------------------------------------ #
    def forward(self, batch):
        """Compute loss *and* grads in one jitted pass; grads are stashed for
        backward(). One forward execution per micro-batch, unlike a literal
        forward/backward split which would run the model twice."""
        if self._onebit:
            raise NotImplementedError(
                "OnebitAdam supports train_batch() only: the compressed "
                "allreduce lives inside the fused step, which the "
                "forward/backward/step split cannot drive")
        if self._dcn_compression:
            raise NotImplementedError(
                "zero_optimization.dcn_compression supports train_batch()"
                " only: the error-feedback buffers thread through the "
                "fused step, which the forward/backward/step split "
                "cannot drive")
        if self._grad_step_fn is None:
            self._build_grad_paths()
        if getattr(self, "_trio_t0", None) is None:
            # Start of an accumulation window: step()'s telemetry wall_ms
            # must cover forward+backward+apply, not just the apply.
            self._trio_t0 = time.perf_counter()
        theta = jnp.asarray(
            self.progressive_layer_drop.theta_at(self.global_steps),
            jnp.float32) if self._accepts_pld else None
        with self.telemetry.span("grad_compute"):
            grads, raw_loss = self._grad_step_fn(
                self.state.cast_params if self._use_cast_cache
                else self.state.params,
                batch, self._next_rng(), self.state.loss_scale, theta)
        self._stashed_grads = grads
        return raw_loss

    def backward(self, loss=None, allreduce_gradients: bool = True):
        """Accumulate the grads computed in forward()."""
        assert getattr(self, "_stashed_grads", None) is not None, \
            "call forward() before backward()"
        grads = self._stashed_grads
        self._stashed_grads = None
        if self._accum_grads is None:
            self._accum_grads = grads
        else:
            self._accum_grads = jax.tree_util.tree_map(
                jnp.add, self._accum_grads, grads)
        self.micro_steps += 1
        return loss

    def step(self):
        """Apply the optimizer at a grad-accum boundary (engine.py:1000-1085)."""
        if self.micro_steps % self.gradient_accumulation_steps() != 0:
            return  # not at boundary; parity with reference gating
        assert self._accum_grads is not None, "no gradients accumulated"
        # Window wall from the first forward() of this accumulation cycle
        # (fallback: apply-only, when step() is driven without forward).
        t0 = getattr(self, "_trio_t0", None) or time.perf_counter()
        self._trio_t0 = None
        with self.telemetry.span("optimizer_apply"):
            self.state, metrics = self._apply_grads_fn(self.state,
                                                       self._accum_grads)
        self._accum_grads = None
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._record_telemetry(metrics, time.perf_counter() - t0)
        self._maybe_log(metrics)
        self._maybe_auto_save()

    def _build_grad_paths(self):
        gas = self.gradient_accumulation_steps()
        loss_fn = self.loss_fn
        compute_dtype = self.compute_dtype
        fp16 = self.config.fp16_enabled
        clip = self.gradient_clipping()
        tx = self.tx
        schedule_fn = self._schedule_fn
        scaler_kw = self._scaler_kw

        pld, accepts_pld = self.progressive_layer_drop, self._accepts_pld
        use_cache = self._use_cast_cache

        raw_scaled_loss = _make_raw_scaled_loss(loss_fn, accepts_pld,
                                                gas)

        def scaled_loss(params, mb, key, scale, theta):
            # forward() hands in state.cast_params when the cache is on.
            cparams = params if use_cache \
                else _cast_floats(params, compute_dtype)
            return raw_scaled_loss(cparams, mb, key, scale, theta)

        # Same device scopes as _build_train_step.
        fwd_bwd = jax.named_scope("fwd_bwd")
        vg = fwd_bwd(jax.value_and_grad(scaled_loss, has_aux=True))

        grad_sh = self._grad_shardings()
        # Resolved-explicit engines route the trio's backward through the
        # same guaranteed psum_scatter path as the fused train step: the
        # declarative out_shardings below regress to a full all-reduce +
        # slice on this backend (the lint suite's grad-materialization
        # finding — grads would cross the wire unpartitioned at 2x the
        # reduce-scatter bytes, every micro-step).
        explicit_fn = None
        if self._grad_sync_mode == "explicit" and grad_sh is not None:
            explicit_fn = fwd_bwd(self._build_explicit_zero2_grads(
                raw_scaled_loss if self._zero3 else scaled_loss,
                grad_sh, gas=1))

        def grad_step(params, mb, key, scale, theta=None):
            if explicit_fn is not None:
                # One micro-batch per trio call: wrap in the [gas=1]
                # leading axis the explicit path scans over. The trio
                # has no metrics dict for MoE stats to ride — aux drops
                # (the aux LOSS is already inside raw_loss).
                mb1 = jax.tree_util.tree_map(lambda x: x[None], mb)
                g, loss, _aux, _err = explicit_fn(params, mb1, key[None],
                                                  scale, theta)
                return g, loss
            (_, (raw_loss, _aux)), grads = vg(params, mb, key, scale,
                                              theta)
            # fp32 grads regardless of compute dtype: backward() accumulates
            # micro-batches in these, and apply_grads clips/updates in fp32.
            return _cast_floats(grads, jnp.float32), raw_loss

        # ZeRO-2: grads leave the jitted backward already dp-sharded.
        grad_step = jax.jit(grad_step, out_shardings=(
            grad_sh, NamedSharding(self.mesh, P()))) \
            if grad_sh is not None else jax.jit(grad_step)

        fused_apply = self._fused_apply
        fused_step = self._fused_step
        use_cache = self._use_cast_cache
        health_taps = self._health_tap_fn

        def apply_grads(state: EngineState, grads):
            scale = state.loss_scale
            # Same in-graph health tap as the main train step — the trio
            # applies the ACCUMULATED (still loss-scaled) grads, so
            # provenance covers the whole window; unscale the tap so the
            # reported norms are true magnitudes (scale traces as 1.0
            # when not fp16).
            tap = None
            if health_taps is not None:
                with jax.named_scope("health_tap"):
                    tap = health_taps(grads) / (scale * scale)
            with jax.named_scope("optimizer"):
                if fused_step is not None:
                    # One-pass clipped update, same contract as the main
                    # train step: unscale (scale is a traced 1.0 when not
                    # fp16 — the kernel's scalar multiply replaces the
                    # historical full-tree g/scale pass either way), norm,
                    # overflow vote, clip, skip-select and cast-cache
                    # refresh inside the single optimizer-state HBM pass.
                    out = fused_step(
                        grads, state.opt_state, state.params, clip=clip,
                        inv_scale=1.0 / scale, fp16=fp16, compute_norm=True,
                        cast_dtype=compute_dtype if use_cache else None)
                    new_params, new_opt = out.params, out.state
                    new_cast = out.cast_params if use_cache else None
                    grad_norm, overflow = out.grad_norm, out.overflow
                else:
                    grads = jax.tree_util.tree_map(lambda g: g / scale, grads)
                    overflow = tree_has_inf_or_nan(grads) if fp16 \
                        else jnp.asarray(False)
                    grad_norm = global_norm(grads)
                    new_params, new_opt = _clipped_update(
                        grads, state, grad_norm, tx=tx,
                        fused_apply=fused_apply, clip=clip)
                    # Same cache refresh as the fused train step: the next
                    # train_batch reads state.cast_params.
                    new_cast = None
                    if state.cast_params is not None:
                        new_cast = _tree_select(
                            overflow, state.cast_params,
                            _cast_floats(new_params, compute_dtype))
                    new_params = _tree_select(overflow, state.params,
                                              new_params)
                    new_opt = _tree_select(overflow, state.opt_state, new_opt)
            new_state = state.replace(
                params=new_params, opt_state=new_opt, cast_params=new_cast,
                **_overflow_resolution(state, overflow, **scaler_kw))
            metrics = {"loss": raw_metric_placeholder(), "grad_norm": grad_norm,
                       "lr": schedule_fn(state.step), "loss_scale": scale,
                       "overflow": overflow}
            if tap is not None:
                metrics["health_leaf_sq"] = tap
            return new_state, metrics

        def raw_metric_placeholder():
            return jnp.asarray(0.0, jnp.float32)

        self._grad_step_fn = self.telemetry.instrument_step_fn(
            "grad_step", grad_step)
        self._apply_grads_fn = self.telemetry.instrument_step_fn(
            "apply_grads",
            jax.jit(apply_grads, donate_argnums=(0,),
                    out_shardings=(self._state_shardings,
                                   self._metrics_shardings(
                                       with_taps=health_taps is not None))))
        return self._grad_step_fn

    # ------------------------------------------------------------------ #
    # Checkpointing (reference engine.py:1472-1572, §3.5)
    # ------------------------------------------------------------------ #
    def _get_ckpt_name(self, checkpoints_path: str, tag: str) -> str:
        return os.path.join(checkpoints_path, str(tag), MODEL_FILE)

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict[str, Any]] = None,
                        save_latest: bool = True) -> bool:
        """Save a checkpoint. With ``checkpoint.async`` the call returns
        after the in-step-window SNAPSHOT (one batched device fetch) and
        a background thread serializes + commits; otherwise the whole
        save runs inline. Both routes share the snapshot builder and the
        two-phase atomic commit (runtime/async_ckpt.py), so the written
        artifact is byte-identical either way."""
        if self._async_ckpt is not None:
            return self._save_checkpoint_async(save_dir, tag, client_state,
                                               save_latest)
        with self.telemetry.span("checkpoint_save",
                                 tag=str(tag) if tag is not None else "auto"):
            return self._save_checkpoint(save_dir, tag, client_state,
                                         save_latest)

    def _save_checkpoint_async(self, save_dir: str, tag: Optional[str],
                               client_state: Optional[Dict[str, Any]],
                               save_latest: bool) -> bool:
        """Async save: the exposed cost is the ``checkpoint_snapshot``
        span below (snapshot fetch + any blocking wait for writer-queue
        room); serialization and the commit happen on the writer thread
        and are priced into the ledger's background bucket."""
        err = self._async_ckpt.last_error
        if err is not None:
            # Surface a failed background write on the NEXT save, where
            # a caller can react — not silently in a daemon thread.
            self._async_ckpt.last_error = None
            raise RuntimeError(
                "a previous background checkpoint write failed "
                f"({type(err).__name__}: {err}); the checkpoint it was "
                "writing is lost (latest still names the prior one)") \
                from err
        with self.telemetry.span(
                "checkpoint_snapshot",
                tag=str(tag) if tag is not None else "auto"):
            # Bound host memory: each pending snapshot is a full host
            # copy of the state. Waiting here is exposed wall and lands
            # in the checkpoint bucket — honest accounting of a writer
            # that cannot keep up with snapshot_every. A writer still
            # wedged after writer_timeout_s fails the save LOUDLY:
            # queueing another full-state copy would break the
            # max_pending_snapshots bound, and the guard watchdog's
            # stack dump already names what it is stuck on.
            if not self._async_ckpt.wait_below(
                    self._ckpt_max_pending,
                    timeout=self._ckpt_writer_timeout):
                raise RuntimeError(
                    "checkpoint writer still busy after "
                    f"{self._ckpt_writer_timeout:.0f}s — refusing to "
                    "queue another full-state host snapshot past "
                    f"max_pending_snapshots={self._ckpt_max_pending} "
                    "(see the writer watchdog's stack dump)")
            snap = self._snapshot_checkpoint(save_dir, tag, client_state,
                                             save_latest)
            crash_point("after_snapshot")
            self._async_ckpt.submit(snap)
        self._note_saved(save_dir, save_latest)
        log_dist(f"checkpoint snapshot {snap.path} taken "
                 "(background write queued)", ranks=[0])
        return True

    def _save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                         client_state: Optional[Dict[str, Any]] = None,
                         save_latest: bool = True) -> bool:
        """Synchronous save: snapshot + inline commit."""
        snap = self._snapshot_checkpoint(save_dir, tag, client_state,
                                         save_latest)
        path = commit_snapshot(snap)
        self._note_saved(save_dir, save_latest)
        log_dist(f"saved checkpoint {path}", ranks=[0])
        return True

    def _note_saved(self, save_dir: str, save_latest: bool) -> None:
        """Track the last step whose state reached the AUTO-SAVE dir's
        ``latest`` — the preemption handler's dedup key. Saves into other
        dirs (or without the latest flip) don't count: a final SIGTERM
        save must still land in ``checkpoint.save_dir``."""
        if save_latest and self._ckpt_dir and \
                os.path.abspath(save_dir) == os.path.abspath(self._ckpt_dir):
            self._last_saved_step = self.global_steps

    def _snapshot_checkpoint(self, save_dir: str, tag: Optional[str],
                             client_state: Optional[Dict[str, Any]],
                             save_latest: bool) -> CheckpointSnapshot:
        """Capture the engine state into a host-side CheckpointSnapshot
        with the reference's sharded layout (engine.py:1472-1572, §3.5):

        - ``mp_rank_XX_model_states.msgpack`` — model params, one file per
          TP rank when mp > 1 (each holds only that rank's slice).
        - ``zero_pp_rank_D_mp_rank_00_optim_states.msgpack`` — one file per
          dp rank with that rank's ZeRO shard of the optimizer state; no
          host ever materializes the full unsharded moments. When
          multislice DCN compression is live, the error-feedback buffers
          ride these files under ``dcnN`` keys, sharded the same way.
        - ``latest`` pointer + ``engine_meta.json`` (counters + shard map;
          the meta file doubles as the commit's completeness seal).

        The device fetch is ONE batched ``jax.device_get`` over every
        leaf the checkpoint needs — the telemetry drain's batched-fetch
        discipline (fence-asserted in tier-1); serialization is deferred
        to lazy blob builders so the async writer pays it, not the step
        window. Load re-assembles full arrays from the shards and
        re-partitions for the CURRENT mesh, so dp-resize-on-load
        (stage1.py:848-1106 elastic checkpoints) works across any dp
        sizes.
        """
        if tag is None:
            tag = f"global_step{self.global_steps}"
        self._checkpoint_tag_validation(tag)
        # Non-array metadata goes in a JSON sidecar: msgpack restore is
        # target-structured and would drop arbitrary client_state shapes.
        meta: Dict[str, Any] = {
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "skipped_steps": self.skipped_steps,
            "dp_world_size": self.dp_size,
            "ds_config_precision": self.config.precision_dtype,
            "client_state": client_state or {},
        }
        if type(getattr(self.state, "opt_state", None)).__name__ == \
                "FusedAdamState":
            # Moment layout version (FUSED_MOMENT_LAYOUT): 3 = in-place
            # leaves keep moments of their own shape, the rest share
            # V-interleaved flat buffers (ops/fused_update.update_plan);
            # 2 = every leaf in the V-interleaved buffers (ISSUE 8);
            # 1 = end-to-end leaf concatenation. Flat sizes can coincide
            # between versions, so a silent restore would scramble
            # moments across leaves; the load path refuses another
            # version instead.
            meta["fused_moment_layout"] = FUSED_MOMENT_LAYOUT
        if self.lr_scheduler is not None and \
                hasattr(self.lr_scheduler, "state_dict"):
            meta["lr_scheduler"] = self.lr_scheduler.state_dict()

        blobs: List[Any] = []
        if self._offload is not None:
            # Host masters ARE canonical; host-resident state saves
            # whole. COPY the arrays: the background writer serializes
            # this instant's values while the next steps mutate the
            # buffers in place.
            def _host_copy(x):
                return np.array(x, copy=True) if isinstance(
                    x, np.ndarray) else np.asarray(x)
            host_params = jax.tree_util.tree_map(
                _host_copy, self._offload.master_tree())
            off_state = jax.tree_util.tree_map(
                lambda x: np.array(x, copy=True)
                if isinstance(x, np.ndarray) else x,
                self._offload.state_dict())
            blobs.append((MODEL_FILE,
                          lambda hp=host_params:
                          flax_serialization.to_bytes({"module": hp})))
            blobs.append((OPTIM_FILE_FMT,
                          lambda st=off_state:
                          flax_serialization.to_bytes({"offload": st})))
        else:
            # THE batched fetch: every device leaf the checkpoint needs,
            # in one device_get (params + moments + scalars + DCN error
            # feedback). The host counter refresh rides it too — the
            # old separate skipped_steps sync is gone.
            param_leaves = jax.tree_util.tree_leaves(self.state.params)
            opt_leaves = jax.tree_util.tree_leaves(self.state.opt_state)
            scalars = [self.state.step, self.state.loss_scale,
                       self.state.growth_count, self.state.hysteresis,
                       self.state.skipped_steps]
            dcn_leaves = [] if self.state.dcn_error is None else \
                jax.tree_util.tree_leaves(self.state.dcn_error)
            fetched = [np.asarray(x) for x in jax.device_get(
                param_leaves + opt_leaves + scalars + dcn_leaves)]
            n_p, n_o = len(param_leaves), len(opt_leaves)
            host_param_leaves = fetched[:n_p]
            host_opt_leaves = fetched[n_p:n_p + n_o]
            step_v, scale_v, growth_v, hyst_v, skipped_v = \
                fetched[n_p + n_o:n_p + n_o + 5]
            host_dcn_leaves = fetched[n_p + n_o + 5:]
            self.skipped_steps = int(skipped_v)
            meta["skipped_steps"] = self.skipped_steps
            blobs += self._snapshot_model_blobs(meta, host_param_leaves)
            scalars_blob = {"__scalars__": {
                "step": step_v, "loss_scale": scale_v,
                "growth_count": growth_v, "hysteresis": hyst_v,
                "skipped": skipped_v}}
            blobs += self._snapshot_optim_blobs(
                meta, host_opt_leaves, scalars_blob, host_dcn_leaves)
        return CheckpointSnapshot(
            save_dir=save_dir, tag=str(tag), save_latest=save_latest,
            meta=meta, blobs=blobs,
            is_writer=jax.process_index() == 0, fsync=self._ckpt_fsync)

    def preempt_save(self, reason: str = "SIGTERM") -> bool:
        """Final snapshot+commit for a dying run — the PreemptSaver's
        SIGTERM entry (callable directly). When a background write is
        already in flight, WAIT for it instead of snapshotting again:
        that commit IS the final checkpoint. When the current step is
        already saved, do nothing. True when ``latest`` names a
        checkpoint of the current step on return."""
        if not self._ckpt_dir:
            return False
        ck = self._async_ckpt
        awaited_ok = True
        if ck is not None and ck.in_flight:
            awaited_ok = bool(ck.wait(timeout=self._ckpt_writer_timeout))
            self.telemetry.event("preempt_save", {
                "reason": reason, "mode": "awaited_inflight",
                "ok": awaited_ok})
        # _last_saved_step is stamped at SUBMIT time; only trust it when
        # the writer actually committed — a failed (or still-wedged)
        # background write means `latest` never flipped, and skipping
        # here would lose up to snapshot_every steps on the exact event
        # this handler exists for. Fall through to the inline save
        # instead.
        write_failed = ck is not None and ck.last_error is not None
        if awaited_ok and not write_failed and \
                self._last_saved_step == self.global_steps:
            return True
        # Inline save even under async config: the process is dying and
        # a queued write would die with it.
        with self.telemetry.span("checkpoint_save", tag="preempt"):
            snap = self._snapshot_checkpoint(self._ckpt_dir, None, None,
                                             True)
            commit_snapshot(snap)
        if write_failed:
            # The inline commit just superseded the lost write: latest
            # now names the CURRENT step, so the stale error must not
            # fail a later save for an already-recovered checkpoint.
            ck.last_error = None
        self._last_saved_step = self.global_steps
        self.telemetry.event("preempt_save", {
            "reason": reason, "mode": "saved", "tag": snap.tag,
            "step": self.global_steps})
        log_dist(f"preemption save: committed {snap.path}", ranks=[0])
        return True

    @staticmethod
    def _effective_axes(leaves, sh_leaves, axis_name: str, n: int):
        """Per-leaf shard axis, demoted to None (replicated in the files)
        when the leaf can't be split evenly."""
        axes = []
        for leaf, sh in zip(leaves, sh_leaves):
            ax = _spec_axis(sh, axis_name)
            if ax is not None and (not hasattr(leaf, "ndim") or leaf.ndim == 0
                                   or leaf.shape[ax] % n != 0):
                ax = None
            axes.append(ax)
        return axes

    @staticmethod
    def _shard_blob_builders(fmt: str, n: int, leaves, axes,
                             extras_shard0: Optional[Dict[str, Any]] = None,
                             groups: Optional[Dict[str, Any]] = None):
        """One LAZY msgpack builder per rank with that rank's slices of
        the already-fetched HOST leaves; replicated leaves and extras
        ride shard 0 only. Slicing host arrays is views — the expensive
        serialization happens when the builder runs, on the writer
        thread under async saving. ``groups`` adds key-prefixed leaf
        families to every shard file (the DCN error-feedback buffers
        ride the optim shards under ``dcnN`` keys)."""
        groups = groups or {}

        def build(r: int) -> bytes:
            blob: Dict[str, Any] = {}

            def put(prefix, lvs, axs):
                for i, (leaf, ax) in enumerate(zip(lvs, axs)):
                    if ax is None:
                        if r == 0:
                            blob[f"{prefix}{i}"] = np.asarray(leaf)
                        continue
                    c = leaf.shape[ax] // n
                    sl = [slice(None)] * leaf.ndim
                    sl[ax] = slice(r * c, (r + 1) * c)
                    blob[f"{prefix}{i}"] = np.ascontiguousarray(
                        leaf[tuple(sl)])

            put("", leaves, axes)
            for prefix, (glvs, gaxs) in groups.items():
                put(prefix, glvs, gaxs)
            if r == 0 and extras_shard0:
                blob.update(extras_shard0)
            return flax_serialization.msgpack_serialize(blob)

        return [(fmt.format(r), lambda r=r: build(r)) for r in range(n)]

    def _snapshot_model_blobs(self, meta: Dict[str, Any],
                              host_param_leaves):
        """Model blob builders from the already-fetched host leaves:
        single mp_rank_00 file, or per-TP-rank slice files when mp > 1
        (reference mp_rank_XX naming, engine.py:1275-1280)."""
        mp = int(self.mesh.shape.get(MP_AXIS, 1))
        sh_leaves = jax.tree_util.tree_leaves(self._state_shardings.params)
        axes = self._effective_axes(host_param_leaves, sh_leaves, MP_AXIS, mp)
        if mp > 1 and any(ax is not None for ax in axes):
            meta["mp_shards"] = mp
            meta["param_shard_axes"] = axes
            return self._shard_blob_builders(MODEL_FILE_FMT, mp,
                                             host_param_leaves, axes)
        treedef = jax.tree_util.tree_structure(self.state.params)
        host_params = jax.tree_util.tree_unflatten(treedef,
                                                   host_param_leaves)
        return [(MODEL_FILE,
                 lambda hp=host_params:
                 flax_serialization.to_bytes({"module": hp}))]

    def _snapshot_optim_blobs(self, meta: Dict[str, Any], host_opt_leaves,
                              scalars_blob: Dict[str, Any],
                              host_dcn_leaves):
        """One optim blob per dp rank holding that rank's ZeRO shard
        (zero_pp_rank_D naming, engine.py:1262-1268). Scalars and
        replicated leaves ride shard 0; the multislice DCN
        error-feedback buffers (when compression is live) ride every
        shard under ``dcnN`` keys, dp-sliced like the moments — so a
        resume no longer restarts the feedback at zero (the old
        documented one-step bias)."""
        dp = self.dp_size
        sh_leaves = jax.tree_util.tree_leaves(self._state_shardings.opt_state)
        axes = self._effective_axes(host_opt_leaves, sh_leaves, DP_AXIS, dp)
        meta["optim_shards"] = dp
        meta["optim_shard_axes"] = axes
        groups: Dict[str, Any] = {}
        if host_dcn_leaves:
            dcn_sh = jax.tree_util.tree_leaves(
                self._state_shardings.dcn_error)
            dcn_axes = self._effective_axes(host_dcn_leaves, dcn_sh,
                                            DP_AXIS, dp)
            meta["dcn_error_shard_axes"] = dcn_axes
            groups["dcn"] = (host_dcn_leaves, dcn_axes)
        return self._shard_blob_builders(OPTIM_SHARD_FMT, dp,
                                         host_opt_leaves, axes,
                                         extras_shard0=scalars_blob,
                                         groups=groups)

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_module_strict: bool = True,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True):
        """Telemetry-spanned entry; see ``_load_checkpoint``."""
        with self.telemetry.span("checkpoint_load", dir=str(load_dir)):
            return self._load_checkpoint(load_dir, tag, load_module_strict,
                                         load_optimizer_states,
                                         load_lr_scheduler_states)

    def _load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                         load_module_strict: bool = True,
                         load_optimizer_states: bool = True,
                         load_lr_scheduler_states: bool = True):
        if tag is None:
            latest = os.path.join(load_dir, LATEST_FILE)
            if not os.path.isfile(latest):
                logger.warning(f"no 'latest' file at {load_dir}; nothing loaded")
                return None, {}
            with open(latest) as f:
                tag = f.read().strip()
        path = os.path.join(load_dir, str(tag))
        if not os.path.isdir(path):
            logger.warning(f"checkpoint {path} not found; nothing loaded")
            return None, {}
        if not is_complete(path):
            # Torn tag: the commit protocol writes engine_meta.json LAST
            # (inside the tmp dir, before the atomic rename), so a tag
            # dir without it was produced by an interrupted pre-protocol
            # writer. Refuse cleanly BEFORE touching any engine state —
            # a half-restored engine is worse than no restore.
            logger.warning(
                f"checkpoint {path} is INCOMPLETE (no engine_meta.json "
                "completeness seal) — a torn/interrupted save; refusing "
                "to load it. Delete the tag dir (and repoint 'latest' at "
                "an intact tag) to clear this.")
            return None, {}
        meta_file = os.path.join(path, META_FILE)
        meta = {}
        if os.path.isfile(meta_file):
            with open(meta_file) as f:
                meta = json.load(f)

        # cast_params is re-derived by _place_state; dcn_error restores
        # from its own shard keys using tree STRUCTURE only — fetching
        # either here would pull full-model-sized trees device-to-host
        # for nothing (the skip-fetch survives whether or not
        # compression is on).
        host_state = jax.device_get(self.state.replace(cast_params=None,
                                                       dcn_error=None))
        if load_optimizer_states and \
                type(host_state.opt_state).__name__ == "FusedAdamState" \
                and int(meta.get("fused_moment_layout", 1)) != \
                FUSED_MOMENT_LAYOUT:
            # The fused moments changed layout twice (end-to-end leaf
            # concatenation -> V-interleaved rows, ISSUE 8 -> in-place
            # leaves out of the flat buffers, ISSUE 26). The flat sizes
            # can coincide, so a structural restore would SILENTLY
            # scramble Adam moments across leaves — refuse loudly,
            # BEFORE any engine state (params, counters) is touched so a
            # caller catching the error keeps a consistent engine.
            raise ValueError(
                f"checkpoint {path} stores fused optimizer moments in "
                f"layout {int(meta.get('fused_moment_layout', 1))} "
                f"(fused_moment_layout in engine_meta.json; none = 1), "
                f"incompatible with the layout {FUSED_MOMENT_LAYOUT} this "
                "engine runs (large leaves keep moments of their own "
                "shape, ops/fused_update.py); load with "
                "load_optimizer_states=False (params restore fine, "
                "moments re-initialize) or re-save from the writing "
                "version")
        params_target = host_state.params if self._offload is None \
            else jax.device_get(self._offload.master_tree())
        if meta.get("pipeline_layer_files"):
            new_params = self._load_pipeline_layer_states(
                path, meta, params_target)
            if new_params is None:
                return None, {}
        elif meta.get("mp_shards"):
            new_params = self._assemble_shards(
                path, MODEL_FILE_FMT, int(meta["mp_shards"]),
                meta["param_shard_axes"], params_target)
            if new_params is None:
                return None, {}
        else:
            model_file = os.path.join(path, MODEL_FILE)
            if not os.path.isfile(model_file):
                logger.warning(f"checkpoint {model_file} not found")
                return None, {}
            with open(model_file, "rb") as f:
                raw_model = f.read()
            probe = flax_serialization.msgpack_restore(raw_model)
            if not (isinstance(probe, dict) and "module" in probe):
                # mp-sharded shard 0 reuses the legacy filename; without the
                # sidecar we can't know the shard axes.
                raise ValueError(
                    f"{model_file} is a SHARDED (mp_rank) model checkpoint "
                    "but engine_meta.json is missing/unreadable — restore "
                    "the sidecar to load it")
            model_blob = flax_serialization.from_state_dict(
                {"module": params_target}, probe)
            new_params = model_blob["module"]
        self.global_steps = int(meta.get("global_steps", 0))
        self.global_samples = int(meta.get("global_samples", 0))
        self.skipped_steps = int(meta.get("skipped_steps", 0))
        self.micro_steps = self.global_steps * self.gradient_accumulation_steps()

        updates: Dict[str, Any] = {"params": new_params}
        if self._offload is not None:
            # masters are canonical; device params re-derive from them.
            # set_masters refreshes the bf16 staging buffers — without it,
            # device_params() at step_count>0 would serve the PRE-load
            # staging weights on the load_optimizer_states=False path.
            self._offload.set_masters(jax.tree_util.tree_leaves(new_params))
            if load_optimizer_states:
                optim_file = os.path.join(path, OPTIM_FILE_FMT)
                if os.path.isfile(optim_file):
                    with open(optim_file, "rb") as f:
                        blob = flax_serialization.from_bytes(
                            {"offload": self._offload.state_dict()}, f.read())
                    self._offload.load_state_dict(blob["offload"])
                    self.skipped_steps = self._offload.skipped_steps
            if load_lr_scheduler_states and self.lr_scheduler is not None \
                    and "lr_scheduler" in meta \
                    and hasattr(self.lr_scheduler, "load_state_dict"):
                self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
            updates["params"] = self._offload.device_params()
            updates["step"] = jnp.asarray(self._offload.step_count, jnp.int32)
            self.state = self._place_state(self.state.replace(**updates))
            log_dist(f"loaded offload checkpoint {path} at "
                     f"global_step={self.global_steps}", ranks=[0])
            return path, meta.get("client_state", {})
        if load_optimizer_states and meta.get("optim_shards"):
            # Sharded layout: re-assemble the full state from every saved
            # dp rank's file; _place_state re-partitions for the CURRENT
            # mesh — elastic dp-resize (stage1.py:848-1106).
            saved_dp = int(meta["optim_shards"])
            # One parse of the shard files feeds the optim state, the
            # scalars, AND the dcn error family — these are the largest
            # blobs in the checkpoint; deserializing them twice would
            # double the load's heaviest phase.
            shard_blobs = self._read_shard_blobs(path, OPTIM_SHARD_FMT,
                                                 saved_dp)
            assembled = self._assemble_shards(
                path, OPTIM_SHARD_FMT, saved_dp, meta["optim_shard_axes"],
                host_state.opt_state, blobs=shard_blobs)
            if assembled is not None:
                scalars = shard_blobs[0]["__scalars__"]
                updates.update(
                    opt_state=assembled,
                    step=jnp.asarray(scalars["step"]),
                    loss_scale=jnp.asarray(scalars["loss_scale"]),
                    growth_count=jnp.asarray(scalars["growth_count"]),
                    hysteresis=jnp.asarray(scalars["hysteresis"]),
                    skipped_steps=jnp.asarray(scalars["skipped"]))
            if self.state.dcn_error is not None:
                # DCN-compression error feedback: restore the carried
                # residuals (dp/slice-elastic like everything else — a
                # slice-count change shape-mismatches per leaf and keeps
                # the fresh zeros with a warning). Skipped entirely when
                # compression is off.
                if meta.get("dcn_error_shard_axes"):
                    dcn = self._assemble_shards(
                        path, OPTIM_SHARD_FMT, saved_dp,
                        meta["dcn_error_shard_axes"],
                        self.state.dcn_error, key_prefix="dcn",
                        blobs=shard_blobs)
                    if dcn is not None:
                        updates["dcn_error"] = dcn
                else:
                    logger.warning(
                        f"checkpoint {path} carries no dcn_error "
                        "buffers (pre-resilience save); DCN error "
                        "feedback restarts at zero — a one-step "
                        "compression bias, self-correcting")
        elif load_optimizer_states:
            optim_file = os.path.join(path, OPTIM_FILE_FMT)
            if os.path.isfile(optim_file):
                with open(optim_file, "rb") as f:
                    raw = f.read()
                # New sharded files reuse the legacy rank-0 name; without
                # engine_meta.json we can't know the shard axes — fail with
                # a real message, not a flax structure explosion.
                probe = flax_serialization.msgpack_restore(raw)
                if isinstance(probe, dict) and "__scalars__" in probe:
                    raise ValueError(
                        f"{optim_file} is a SHARDED optimizer checkpoint "
                        "but engine_meta.json is missing/unreadable — "
                        "restore the sidecar to load it")
                optim_blob = flax_serialization.from_state_dict(
                    {"opt_state": host_state.opt_state,
                     "step": np.asarray(host_state.step),
                     "loss_scale": np.asarray(host_state.loss_scale),
                     "growth_count": np.asarray(host_state.growth_count),
                     "hysteresis": np.asarray(host_state.hysteresis),
                     "skipped": np.asarray(host_state.skipped_steps)}, probe)
                updates.update(
                    opt_state=optim_blob["opt_state"],
                    step=jnp.asarray(optim_blob["step"]),
                    loss_scale=jnp.asarray(optim_blob["loss_scale"]),
                    growth_count=jnp.asarray(optim_blob["growth_count"]),
                    hysteresis=jnp.asarray(optim_blob["hysteresis"]),
                    skipped_steps=jnp.asarray(optim_blob["skipped"]))
        if load_lr_scheduler_states and self.lr_scheduler is not None and \
                "lr_scheduler" in meta and \
                hasattr(self.lr_scheduler, "load_state_dict"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])

        self.state = self._place_state(self.state.replace(**updates))
        log_dist(f"loaded checkpoint {path} at global_step={self.global_steps}",
                 ranks=[0])
        return path, meta.get("client_state", {})

    @staticmethod
    def _read_shard_blobs(path: str, fmt: str, n: int):
        """Deserialize all ``n`` shard files once (the heaviest part of
        a load — full Adam moment shards); None if any is missing.
        Callers assembling multiple leaf families from the same files
        (optim state + dcn error feedback) share one parse."""
        blobs = []
        for r in range(n):
            fp = os.path.join(path, fmt.format(r))
            if not os.path.isfile(fp):
                logger.warning(f"checkpoint shard {fp} not found")
                return None
            with open(fp, "rb") as f:
                blobs.append(flax_serialization.msgpack_restore(f.read()))
        return blobs

    def _assemble_shards(self, path: str, fmt: str, n: int, axes,
                         target_tree, key_prefix: str = "",
                         blobs=None):
        """Read ``n`` shard files (or reuse pre-parsed ``blobs``) and
        concatenate each leaf along its recorded axis (replicated leaves
        come from shard 0). Returns the full tree with ``target_tree``'s
        structure, or None if files are missing. ``key_prefix`` selects
        a prefixed leaf family riding the same files (the DCN error
        buffers' ``dcnN`` keys)."""
        if blobs is None:
            blobs = self._read_shard_blobs(path, fmt, n)
        if blobs is None:
            return None
        leaves, treedef = jax.tree_util.tree_flatten(target_tree)
        if len(leaves) != len(axes):
            raise ValueError(
                f"checkpoint shard layout has {len(axes)} leaves but the "
                f"current state has {len(leaves)} — the optimizer/model "
                "structure changed since this checkpoint was saved")
        out = []
        for i, (leaf, ax) in enumerate(zip(leaves, axes)):
            if ax is None:
                val = blobs[0][f"{key_prefix}{i}"]
            else:
                val = np.concatenate([b[f"{key_prefix}{i}"] for b in blobs],
                                     axis=int(ax))
            if hasattr(leaf, "shape") and np.shape(val) != np.shape(leaf):
                # Elastic-incompatible leaf (e.g. onebit worker_error's
                # per-rank [dp] axis under a different dp): keep the current
                # (fresh) value rather than loading a wrong-shaped one.
                logger.warning(
                    f"checkpoint leaf {i}: saved shape {np.shape(val)} != "
                    f"current {np.shape(leaf)}; keeping current value")
                val = leaf
            out.append(val)
        return jax.tree_util.tree_unflatten(treedef, out)

    def _load_pipeline_layer_states(self, path, meta, params_target):
        raise NotImplementedError(
            "checkpoint has pipeline per-layer files; load it through a "
            "PipelineEngine")

    def _checkpoint_tag_validation(self, tag: str) -> None:
        """Cross-host tag consistency vote (engine.py:1455-1470): under SPMD
        all hosts run the same program so mismatch can only come from
        client-supplied tags; verify by hashing when multi-host."""
        if jax.process_count() == 1 or not self.config.checkpoint_tag_validation_enabled:
            return
        import hashlib
        h = int(hashlib.sha1(tag.encode()).hexdigest()[:8], 16)
        arr = jnp.asarray([h], jnp.int32)
        # max == min across hosts iff all tags equal.
        mx = jax.device_get(comm.all_reduce_host(arr, op="max")) \
            if hasattr(comm, "all_reduce_host") else arr
        mn = jax.device_get(comm.all_reduce_host(arr, op="min")) \
            if hasattr(comm, "all_reduce_host") else arr
        if int(mx[0]) != int(mn[0]):
            msg = f"checkpoint tag '{tag}' differs across hosts"
            if self.config.checkpoint_tag_validation_fail:
                raise ValueError(msg)
            logger.warning(msg)


# The engine's old private ``_Monitor`` (tensorboard-gated JSONL sink that
# every process appended to and never closed) is subsumed by the telemetry
# subsystem: ``monitor/telemetry.py::JsonlSink`` is the process-0-guarded,
# close()/atexit-managed successor, and the ``tensorboard`` config block
# is an alias for a telemetry sink (runtime/config.py::TelemetryConfig).

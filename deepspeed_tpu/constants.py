"""Central registry of config keys and defaults.

Capability parity with the reference's ``runtime/constants.py`` (326 LoC of key
names/defaults) and ``runtime/zero/constants.py``: every knob a ``ds_config.json``
file may contain is named here, with its default, so config handling stays
table-driven and existing DeepSpeed-style JSON configs parse unmodified.

TPU-native deltas: ``bf16`` is first-class (the natural TPU dtype); ``fp16``
keys are retained for parity configs and drive the dynamic loss scaler.
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

# optimizer.params.fused: route Adam/AdamW through the single-pass Pallas
# multi-tensor apply (ops/fused_update.py). On by default where parity
# holds; false restores the optax chain.
OPTIMIZER_FUSED = "fused"
OPTIMIZER_FUSED_DEFAULT = True

SCHEDULER = "scheduler"
SCHEDULER_TYPE_DEFAULT = None
SCHEDULER_PARAMS = "params"

MAX_GRAD_NORM = "max_grad_norm"

# Optimizer names understood by the engine's selection matrix.
ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
LAMB_OPTIMIZER = "lamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
SGD_OPTIMIZER = "sgd"
ADAGRAD_OPTIMIZER = "adagrad"
RMSPROP_OPTIMIZER = "rmsprop"
LION_OPTIMIZER = "lion"
DEEPSPEED_OPTIMIZERS = [
    ADAM_OPTIMIZER,
    ADAMW_OPTIMIZER,
    LAMB_OPTIMIZER,
    ONEBIT_ADAM_OPTIMIZER,
    SGD_OPTIMIZER,
    ADAGRAD_OPTIMIZER,
    RMSPROP_OPTIMIZER,
    LION_OPTIMIZER,
]

#############################################
# Precision
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

BF16 = "bf16"
BF16_ENABLED = "enabled"
# TPU-native default: bf16 on unless a parity config says otherwise.
BF16_ENABLED_DEFAULT = False
# Master-free bf16: params live in bf16 and the optimizer apply rounds
# stochastically (the reference transformer kernel's stochastic_mode,
# ops/transformer/transformer.py:39-151, re-done as a TPU bit trick).
BF16_STOCHASTIC_ROUNDING = "stochastic_rounding"
BF16_STOCHASTIC_ROUNDING_DEFAULT = False

PRECISION_DEFAULT = "fp32"

AMP = "amp"
AMP_ENABLED = "enabled"
AMP_ENABLED_DEFAULT = False

GRADIENT_CLIPPING = "gradient_clipping"
GRADIENT_CLIPPING_DEFAULT = 0.0

PRESCALE_GRADIENTS = "prescale_gradients"
PRESCALE_GRADIENTS_DEFAULT = False

GRADIENT_PREDIVIDE_FACTOR = "gradient_predivide_factor"
GRADIENT_PREDIVIDE_FACTOR_DEFAULT = 1.0

SPARSE_GRADIENTS = "sparse_gradients"
SPARSE_GRADIENTS_DEFAULT = False

ALLREDUCE_ALWAYS_FP32 = "fp32_allreduce"
ALLREDUCE_ALWAYS_FP32_DEFAULT = False

#############################################
# Steps / logging
#############################################
STEPS_PER_PRINT = "steps_per_print"
STEPS_PER_PRINT_DEFAULT = 10

DISABLE_ALLGATHER = "disable_allgather"
DISABLE_ALLGATHER_DEFAULT = False

DUMP_STATE = "dump_state"
DUMP_STATE_DEFAULT = False

WALL_CLOCK_BREAKDOWN = "wall_clock_breakdown"
WALL_CLOCK_BREAKDOWN_DEFAULT = False

MEMORY_BREAKDOWN = "memory_breakdown"
MEMORY_BREAKDOWN_DEFAULT = False

TENSORBOARD = "tensorboard"
TENSORBOARD_ENABLED = "enabled"
TENSORBOARD_ENABLED_DEFAULT = False
TENSORBOARD_OUTPUT_PATH = "output_path"
TENSORBOARD_OUTPUT_PATH_DEFAULT = ""
TENSORBOARD_JOB_NAME = "job_name"
TENSORBOARD_JOB_NAME_DEFAULT = "DeepSpeedJobName"

#############################################
# Telemetry (monitor/ subsystem)
#############################################
# The "telemetry" block subsumes "tensorboard" (which stays as an alias:
# a config with only a tensorboard block gets a telemetry sink with the
# same output_path/job_name). All collection is report-boundary batched —
# the monitor/ subsystem adds zero host<->device syncs on the hot path.
TELEMETRY = "telemetry"
TELEMETRY_ENABLED = "enabled"
TELEMETRY_ENABLED_DEFAULT = False
TELEMETRY_OUTPUT_PATH = "output_path"
TELEMETRY_OUTPUT_PATH_DEFAULT = ""
TELEMETRY_JOB_NAME = "job_name"
TELEMETRY_JOB_NAME_DEFAULT = "DeepSpeedJobName"
# Ring-buffer capacity for per-step records between drains; overflow drops
# the OLDEST records and the drain reports how many were dropped.
TELEMETRY_BUFFER_SIZE = "buffer_size"
TELEMETRY_BUFFER_SIZE_DEFAULT = 1024
# Drain cadence in global steps; 0 = follow steps_per_print.
TELEMETRY_REPORT_STEPS = "report_steps"
TELEMETRY_REPORT_STEPS_DEFAULT = 0
# Host-side span tracing: path of the Chrome-trace/Perfetto JSON to write
# ("" = tracing off; span collection costs nothing when off).
TELEMETRY_TRACE_PATH = "trace_path"
TELEMETRY_TRACE_PATH_DEFAULT = ""
# Recompile sentinel: a jit cache miss on an instrumented step function
# after its warmup calls logs a structured event naming the function and
# the abstract-signature delta; fail_on_recompile raises instead.
TELEMETRY_FAIL_ON_RECOMPILE = "fail_on_recompile"
TELEMETRY_FAIL_ON_RECOMPILE_DEFAULT = False
# Default 2: call 0 is the cold compile, and call 1 may legitimately
# recompile once when the donated output state (whose shardings/layouts
# the compiler chose) becomes the next call's input — steady state starts
# at call 2.
TELEMETRY_RECOMPILE_WARMUP = "recompile_warmup_calls"
TELEMETRY_RECOMPILE_WARMUP_DEFAULT = 2
# Device-memory watermarks, sampled at report boundaries across ALL local
# devices and compared against the analytic ZeRO-partitioned model-state
# footprint: peak > analytic * ratio + slack emits a watermark event.
TELEMETRY_MEMORY_WATERMARKS = "memory_watermarks"
TELEMETRY_MEMORY_WATERMARKS_DEFAULT = True
TELEMETRY_WATERMARK_RATIO = "watermark_ratio"
TELEMETRY_WATERMARK_RATIO_DEFAULT = 2.0
TELEMETRY_WATERMARK_SLACK_BYTES = "watermark_slack_bytes"
TELEMETRY_WATERMARK_SLACK_BYTES_DEFAULT = 256 * 2 ** 20
# Optional jax.profiler device-trace window: capture num_steps starting at
# start_step into profile_dir (default: <output_path>/jax_trace).
# start_step -1 = off.
TELEMETRY_PROFILE_START_STEP = "profile_start_step"
TELEMETRY_PROFILE_START_STEP_DEFAULT = -1
TELEMETRY_PROFILE_NUM_STEPS = "profile_num_steps"
TELEMETRY_PROFILE_NUM_STEPS_DEFAULT = 1
TELEMETRY_PROFILE_DIR = "profile_dir"
TELEMETRY_PROFILE_DIR_DEFAULT = ""
# --- telemetry.profile: trace capture + ingestion + reconciliation -----
# The nested block form (the flat profile_* keys above stay as aliases).
# start_step >= 0 arms a jax.profiler window of window_steps hot steps;
# after the window closes, the capture is ingested
# (monitor/profile_ingest.py) into the per-step wall decomposition,
# reconciled against the cost model's floors (monitor/reconcile.py), and
# drained into the JSONL as the ``profile`` report section. Components
# measuring more than divergence_threshold x their analytic floor (or,
# for the zero-floor host bucket, more than host_frac of the step wall)
# fire ``reconcile_divergence`` events.
TELEMETRY_PROFILE = "profile"
TELEMETRY_PROFILE_BLOCK_START = "start_step"
TELEMETRY_PROFILE_BLOCK_START_DEFAULT = -1
TELEMETRY_PROFILE_BLOCK_STEPS = "window_steps"
TELEMETRY_PROFILE_BLOCK_STEPS_DEFAULT = 2
TELEMETRY_PROFILE_BLOCK_DIR = "out_dir"
TELEMETRY_PROFILE_BLOCK_DIR_DEFAULT = ""
TELEMETRY_PROFILE_THRESHOLD = "divergence_threshold"
TELEMETRY_PROFILE_THRESHOLD_DEFAULT = 3.0
TELEMETRY_PROFILE_HOST_FRAC = "host_frac"
TELEMETRY_PROFILE_HOST_FRAC_DEFAULT = 0.10
# Roofline cost model: at the FIRST report boundary, AOT-relower every
# compiled step path from its recorded abstract signature, pull XLA's
# cost_analysis() (flops + bytes accessed), fuse it with the jaxpr-walk
# analytic flops and the grad-sync wire model, and emit per-path
# compute/HBM/interconnect-bound verdicts + per-step MFU (one-time
# host-side compile at the boundary; no device traffic, no fences).
TELEMETRY_COST_MODEL = "cost_model"
TELEMETRY_COST_MODEL_DEFAULT = True
# Multi-host: rank 0 writes the primary JSONL; with per_host_shards every
# other SPMD process writes its own ``<job>.rankK.jsonl`` shard (and
# ``<trace>.rankK.json`` when tracing) instead of silently discarding its
# ring records. tools/telemetry_report.py aggregates the shards:
# per-host step-wall skew (straggler detection) and step-count/loss-hash
# desync checks.
TELEMETRY_PER_HOST = "per_host_shards"
TELEMETRY_PER_HOST_DEFAULT = False

# --- telemetry.health: anomaly detection, hang watchdog, flight recorder
# The forensic layer (monitor/health.py + monitor/flight.py). All
# detection is drain-time host work on already-fetched scalars; the only
# in-graph piece is the per-leaf grad tap below.
TELEMETRY_HEALTH = "health"
TELEMETRY_HEALTH_ENABLED = "enabled"
TELEMETRY_HEALTH_ENABLED_DEFAULT = True
# In-graph per-leaf grad sum-of-squares tap ([num_leaves] f32, riding
# the ring to the batched drain fetch — zero added device syncs, one
# extra read of the grad tree per step). Gives NaN/Inf provenance: the
# first non-finite leaf and its layer. Wired on the main train step, the
# forward/backward trio, and the sparse apply; the offload path's host
# Adam and onebit's in-shard_map update keep their own overflow
# machinery (grad_norm still feeds the spike detector there).
TELEMETRY_HEALTH_GRAD_TAPS = "grad_taps"
TELEMETRY_HEALTH_GRAD_TAPS_DEFAULT = True
# EWMA z-score spike detection on loss and grad_norm: flag |z| above the
# threshold after warmup_steps finite samples.
TELEMETRY_HEALTH_Z_THRESHOLD = "z_threshold"
TELEMETRY_HEALTH_Z_THRESHOLD_DEFAULT = 6.0
TELEMETRY_HEALTH_EWMA_ALPHA = "ewma_alpha"
TELEMETRY_HEALTH_EWMA_ALPHA_DEFAULT = 0.1
TELEMETRY_HEALTH_WARMUP_STEPS = "warmup_steps"
TELEMETRY_HEALTH_WARMUP_STEPS_DEFAULT = 20
# Hang watchdog (off by default: it is a per-engine daemon thread):
# fires when no step completes within max(watchdog_min_s,
# watchdog_factor * p95(recent step walls)) — all-thread stack dump
# (faulthandler), device memory_stats sample, pending step signature.
TELEMETRY_HEALTH_WATCHDOG = "watchdog"
TELEMETRY_HEALTH_WATCHDOG_DEFAULT = False
TELEMETRY_HEALTH_WATCHDOG_FACTOR = "watchdog_factor"
TELEMETRY_HEALTH_WATCHDOG_FACTOR_DEFAULT = 10.0
TELEMETRY_HEALTH_WATCHDOG_MIN_S = "watchdog_min_s"
TELEMETRY_HEALTH_WATCHDOG_MIN_S_DEFAULT = 120.0
# Crash flight recorder: SIGTERM/SIGINT/atexit handlers persist the last
# flight_window drained step records, the unsettled goodput window,
# anomaly events, and a config/mesh/env snapshot to FLIGHT.json
# (atomically; flight_path "" = <output_path>/FLIGHT.json, per-host
# shards get FLIGHT.rankK.json).
TELEMETRY_HEALTH_FLIGHT = "flight_recorder"
TELEMETRY_HEALTH_FLIGHT_DEFAULT = True
TELEMETRY_HEALTH_FLIGHT_PATH = "flight_path"
TELEMETRY_HEALTH_FLIGHT_PATH_DEFAULT = ""
TELEMETRY_HEALTH_FLIGHT_WINDOW = "flight_window"
TELEMETRY_HEALTH_FLIGHT_WINDOW_DEFAULT = 64

#############################################
# Inference / serving (inference/ subsystem)
#############################################
# The "inference" block configures the batched autoregressive serving
# tier (deepspeed_tpu/inference/): the slot count of the static KV
# cache, the cache sequence capacity, weight quantization, and the
# prefill chunking. All of it is STATIC program shape — the continuous-
# batching scheduler inserts/evicts requests without changing any
# compiled signature (the recompile sentinel is the regression gate).
INFERENCE = "inference"
# Number of concurrent request slots in the KV cache. Must be divisible
# by the mesh dp-axis size (slots are the data-parallel dimension of
# serving).
INFERENCE_MAX_SLOTS = "max_slots"
INFERENCE_MAX_SLOTS_DEFAULT = 8
# KV-cache sequence capacity per slot; 0 = the model's max_seq_length.
INFERENCE_MAX_SEQ_LEN = "max_seq_len"
INFERENCE_MAX_SEQ_LEN_DEFAULT = 0
# Weight quantization applied at engine construction: "none" keeps the
# checkpoint dtype, "bf16" stochastically rounds fp32 weights to bf16
# (ops/stochastic_rounding.py — the master-free training machinery),
# "int8" stores per-output-channel symmetric int8 (stochastic rounding
# onto the integer grid) and dequantizes inside the compiled step.
INFERENCE_QUANTIZE = "quantize"
INFERENCE_QUANTIZE_DEFAULT = "none"
INFERENCE_QUANTIZE_MODES = ("none", "bf16", "int8")
# Prefill chunk length: prompts are right-padded to a multiple and run
# chunk-by-chunk against the cache (static shapes at every prompt
# length): a positive int, the only admission path.
INFERENCE_PREFILL_CHUNK = "prefill_chunk"
INFERENCE_PREFILL_CHUNK_DEFAULT = 32
# Paged KV cache (the PagedAttention design): the cache is a pool of
# fixed-size blocks and a slot holds a list of block ids, so short and
# long requests share HBM and common prompt prefixes are shared
# copy-on-write across requests (full-block granularity, chain-hashed).
# block_size is the tokens-per-block page size: a positive int that
# divides max_seq_len.
INFERENCE_BLOCK_SIZE = "block_size"
INFERENCE_BLOCK_SIZE_DEFAULT = 16
# Total blocks in the pool; 0 = full provisioning (max_slots *
# max_seq_len / block_size — every slot can reach max_seq_len, so
# admission never blocks on HBM). Smaller pools oversubscribe: the
# scheduler's admission gate then accounts free blocks (the serving
# snapshot's hbm_bytes_per_token is the HBM held per context token). Must
# be divisible by the mesh dp-axis size (blocks are born sharded over
# dp alongside the slots they serve). A served model that keeps its layers
# in several CLASSES (say full and sliding-window attention;
# inference/kv_cache.py) has a pool a class and takes {class name: blocks};
# a class left out, or at 0, is fully provisioned (every slot's table full).
INFERENCE_NUM_BLOCKS = "num_blocks"
INFERENCE_NUM_BLOCKS_DEFAULT = 0
# Speculative decoding (draft-then-verify, Leviathan et al. 2023):
# spec_k > 0 proposes k tokens per live slot from the self-drafting
# n-gram cache (prompt-lookup decoding — no drafter model) and one
# batched verify step accepts the longest agreeing prefix plus one
# corrected token. Greedy output is bit-identical to non-speculative
# greedy decode; the scheduler falls back to plain decode when
# temperature > 0 (exact rejection sampling is not implemented).
INFERENCE_SPEC_K = "spec_k"
INFERENCE_SPEC_K_DEFAULT = 0
# n-gram context length the drafter matches against the slot's token
# history (it tries n, n-1, ..., 1 and proposes the continuation of the
# most recent prior occurrence; repeat-last-token when nothing matches).
INFERENCE_SPEC_NGRAM = "spec_ngram"
INFERENCE_SPEC_NGRAM_DEFAULT = 3
# KV-pool storage dtype: "model" stores blocks at the model compute
# dtype; "bf16" halves fp32 KV HBM at rest (scores are fp32 either way).
INFERENCE_KV_DTYPE = "kv_cache_dtype"
INFERENCE_KV_DTYPE_DEFAULT = "model"
INFERENCE_KV_DTYPE_MODES = ("model", "bf16")
# Replica label stamped on this engine's telemetry + aggregator
# snapshots ("" = unlabeled single replica). The multi-replica router
# (inference/router.py) sets it so telemetry_report can keep replicas'
# percentile streams apart.
INFERENCE_REPLICA = "replica"
INFERENCE_REPLICA_DEFAULT = ""
# Pallas paged-attention kernel for the paged decode/verify/prefill
# attends (ops/paged_attention.py): table-driven block slices do
# O(context) work instead of the one-hot contraction's O(pool). True /
# False force it; "auto" enables on TPU only (the DS_PAGED_KERNEL env
# var overrides "auto"). Forced on without a TPU the kernel runs in
# interpret mode — same program, pure XLA — which is how the CPU-mesh
# tier-1 proves logit parity.
INFERENCE_PAGED_KERNEL = "paged_kernel"
INFERENCE_PAGED_KERNEL_DEFAULT = "auto"
# inference.slo — serving SLO targets (monitor/serving_slo.py). A
# request is "good" when its TTFT and TPOT are both inside target; an
# unset target (0) always passes, and with both unset the tracker is
# off (snapshots omit the slo section). availability is the target
# good-fraction whose complement is the error budget the burn rate is
# measured against (burn_rate > 1 = budget consumed faster than the
# SLO allows); window_s is the trailing window for the windowed
# attainment/burn view.
INFERENCE_SLO = "slo"
INFERENCE_SLO_TTFT_MS = "ttft_ms"
INFERENCE_SLO_TTFT_MS_DEFAULT = 0.0
INFERENCE_SLO_TPOT_MS = "tpot_ms"
INFERENCE_SLO_TPOT_MS_DEFAULT = 0.0
INFERENCE_SLO_AVAILABILITY = "availability"
INFERENCE_SLO_AVAILABILITY_DEFAULT = 0.99
INFERENCE_SLO_WINDOW_S = "window_s"
INFERENCE_SLO_WINDOW_S_DEFAULT = 60.0

#############################################
# ZeRO
#############################################
ZERO_OPTIMIZATION = "zero_optimization"
ZERO_OPTIMIZATION_DISABLED = 0
ZERO_OPTIMIZATION_OPTIMIZER_STATES = 1
ZERO_OPTIMIZATION_GRADIENTS = 2
ZERO_OPTIMIZATION_WEIGHTS = 3
MAX_STAGE_ZERO_OPTIMIZATION = ZERO_OPTIMIZATION_WEIGHTS
ZERO_OPTIMIZATION_DEFAULT = ZERO_OPTIMIZATION_DISABLED

ZERO_STAGE = "stage"
ZERO_STAGE_DEFAULT = ZERO_OPTIMIZATION_DISABLED
ZERO_CONTIGUOUS_GRADIENTS = "contiguous_gradients"
ZERO_CONTIGUOUS_GRADIENTS_DEFAULT = False
ZERO_REDUCE_BUCKET_SIZE = "reduce_bucket_size"
ZERO_REDUCE_BUCKET_SIZE_DEFAULT = 500_000_000
ZERO_REDUCE_SCATTER = "reduce_scatter"
ZERO_REDUCE_SCATTER_DEFAULT = True
# How the stage-2 reduce-scatter is obtained when reduce_scatter is on:
# "declarative" trusts the GSPMD partitioner to lower the declared grad
# sharding; "explicit" computes grads under shard_map with lax.psum_scatter
# (guaranteed lowering); "auto" probes the compiled lowering once per
# backend (parallel/hlo_audit.py) and goes explicit iff the declarative
# path regresses to a full all-reduce + slice.
ZERO_GRAD_SYNC = "grad_sync"
ZERO_GRAD_SYNC_DEFAULT = "auto"
ZERO_GRAD_SYNC_MODES = ("auto", "declarative", "explicit")
# ZeRO-3 layer-gather prefetch: how many layers ahead the per-layer
# param all-gather is issued inside the model's layer scan (runtime/zero/
# stage3.py). 0 = gather at use (the parity baseline: no overlap
# structure); k >= 1 = the scan carries k gathered layers so layer i+k's
# gather overlaps layer i's compute. Only the stacked-layer scan path
# consumes the knob; unstacked models gather leaf-at-use regardless.
ZERO_PREFETCH_DEPTH = "prefetch_depth"
ZERO_PREFETCH_DEPTH_DEFAULT = 1
# Multi-slice DCN compression: 1-bit (error-feedback sign + per-chunk
# scale) compression of the INTER-SLICE gradient hop only — the slow
# DCN tier is where the 1-bit wire format (ops/onebit.py) pays; the
# in-slice ICI reduce-scatter is never compressed. Requires a mesh with
# slices > 1 (parallel/multislice.py) and the explicit hierarchical
# grad path (ZeRO stage >= 2).
ZERO_DCN_COMPRESSION = "dcn_compression"
ZERO_DCN_COMPRESSION_DEFAULT = False
ZERO_OVERLAP_COMM = "overlap_comm"
ZERO_OVERLAP_COMM_DEFAULT = False
ZERO_ALLGATHER_PARTITIONS = "allgather_partitions"
ZERO_ALLGATHER_PARTITIONS_DEFAULT = True
ZERO_ALLGATHER_BUCKET_SIZE = "allgather_bucket_size"
ZERO_ALLGATHER_BUCKET_SIZE_DEFAULT = 500_000_000
ZERO_LOAD_FROM_FP32_WEIGHTS = "load_from_fp32_weights"
ZERO_LOAD_FROM_FP32_WEIGHTS_DEFAULT = True
ZERO_CPU_OFFLOAD = "cpu_offload"
ZERO_CPU_OFFLOAD_DEFAULT = False
# Offload overlap pipeline: the host masters are split into ~bucket_size-
# byte groups (fp32 master bytes) so D2H, host Adam, and H2D stream
# per-bucket; overlap_comm toggles the concurrent executor, host_threads
# sizes its worker pool (0 = os.cpu_count()).
ZERO_OFFLOAD_BUCKET_SIZE = "offload_bucket_size"
ZERO_OFFLOAD_BUCKET_SIZE_DEFAULT = 64 * 2 ** 20
ZERO_OFFLOAD_HOST_THREADS = "offload_host_threads"
ZERO_OFFLOAD_HOST_THREADS_DEFAULT = 0
ZERO_ELASTIC_CHECKPOINT = "elastic_checkpoint"
ZERO_ELASTIC_CHECKPOINT_DEFAULT = True
ZERO_MAX_ELEMENTS_PER_COMM = "max_elements_per_comm"
ZERO_MAX_ELEMENTS_PER_COMM_DEFAULT = 500_000_000

#############################################
# Activation checkpointing
#############################################
ACTIVATION_CHECKPOINTING = "activation_checkpointing"
ACT_CHKPT_PARTITION_ACTIVATIONS = "partition_activations"
ACT_CHKPT_PARTITION_ACTIVATIONS_DEFAULT = False
ACT_CHKPT_NUMBER_CHECKPOINTS = "number_checkpoints"
ACT_CHKPT_NUMBER_CHECKPOINTS_DEFAULT = None
ACT_CHKPT_CONTIGUOUS_MEMORY_OPTIMIZATION = "contiguous_memory_optimization"
ACT_CHKPT_CONTIGUOUS_MEMORY_OPTIMIZATION_DEFAULT = False
ACT_CHKPT_SYNCHRONIZE_CHECKPOINT_BOUNDARY = "synchronize_checkpoint_boundary"
ACT_CHKPT_SYNCHRONIZE_CHECKPOINT_BOUNDARY_DEFAULT = False
ACT_CHKPT_CPU_CHECKPOINTING = "cpu_checkpointing"
ACT_CHKPT_CPU_CHECKPOINTING_DEFAULT = False
ACT_CHKPT_PROFILE = "profile"
ACT_CHKPT_PROFILE_DEFAULT = False

#############################################
# Sparse attention
#############################################
SPARSE_ATTENTION = "sparse_attention"
SPARSE_MODE = "mode"
SPARSE_MODE_DEFAULT = "fixed"
SPARSE_DENSE_MODE = "dense"
SPARSE_FIXED_MODE = "fixed"
SPARSE_VARIABLE_MODE = "variable"
SPARSE_BIGBIRD_MODE = "bigbird"
SPARSE_BSLONGFORMER_MODE = "bslongformer"
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
# Pipeline
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
PIPELINE_SCHEDULE = "schedule"
PIPELINE_SCHEDULE_DEFAULT = "gpipe"

#############################################
# Gradient noise scale / progressive layer drop
#############################################
PROGRESSIVE_LAYER_DROP = "progressive_layer_drop"
PLD_ENABLED = "enabled"
PLD_ENABLED_DEFAULT = False
PLD_THETA = "theta"
PLD_THETA_DEFAULT = 1.0
PLD_GAMMA = "gamma"
PLD_GAMMA_DEFAULT = 0.001

#############################################
# Flops profiler
#############################################
FLOPS_PROFILER = "flops_profiler"
FLOPS_PROFILER_ENABLED = "enabled"
FLOPS_PROFILER_ENABLED_DEFAULT = False
FLOPS_PROFILER_PROFILE_STEP = "profile_step"
FLOPS_PROFILER_PROFILE_STEP_DEFAULT = 1
FLOPS_PROFILER_MODULE_DEPTH = "module_depth"
FLOPS_PROFILER_MODULE_DEPTH_DEFAULT = -1
FLOPS_PROFILER_TOP_MODULES = "top_modules"
FLOPS_PROFILER_TOP_MODULES_DEFAULT = 1
FLOPS_PROFILER_DETAILED = "detailed"
FLOPS_PROFILER_DETAILED_DEFAULT = True

#############################################
# Elasticity
#############################################
ELASTICITY = "elasticity"
ENABLED = "enabled"
ENABLED_DEFAULT = False
MAX_ACCEPTABLE_BATCH_SIZE = "max_train_batch_size"
MAX_ACCEPTABLE_BATCH_SIZE_DEFAULT = 2000
MICRO_BATCHES = "micro_batch_sizes"
MICRO_BATCHES_DEFAULT = [2, 4, 6]
MIN_GPUS = "min_gpus"
MIN_GPUS_DEFAULT = 1
MAX_GPUS = "max_gpus"
MAX_GPUS_DEFAULT = 10000
MIN_TIME = "min_time"
MIN_TIME_DEFAULT = 0
VERSION = "version"
VERSION_DEFAULT = 0.1
LATEST_ELASTICITY_VERSION = 0.1
IGNORE_NON_ELASTIC_BATCH_INFO = "ignore_non_elastic_batch_info"
IGNORE_NON_ELASTIC_BATCH_INFO_DEFAULT = False
PREFER_LARGER_BATCH = "prefer_larger_batch"
PREFER_LARGER_BATCH_DEFAULT = True

#############################################
# MoE expert parallelism (moe/ subsystem)
#############################################
# The "moe" block configures the engine side of expert parallelism:
# the `expert` mesh axis size (factors out of data — reuses the dp
# devices), the metrics schema (per-expert token counts / drop fraction
# / aux loss ride the telemetry drain), and the all-to-all wire model.
# The MODEL side is TransformerConfig.moe (deepspeed_tpu.moe.MoEConfig
# — build it with MoEConfig.from_ds_config so the two cannot drift).
MOE = "moe"
# 0 = MoE disabled (the block is inert).
MOE_NUM_EXPERTS = "num_experts"
MOE_NUM_EXPERTS_DEFAULT = 0
# Router top-k (1 or 2 — Switch vs GShard gating).
MOE_TOP_K = "top_k"
MOE_TOP_K_DEFAULT = 2
# Per-expert slot count C = ceil(capacity_factor * k * T / E) per
# device; tokens beyond capacity drop to the residual path. One
# compiled shape regardless of routing.
MOE_CAPACITY_FACTOR = "capacity_factor"
MOE_CAPACITY_FACTOR_DEFAULT = 1.25
# Load-balance aux loss weight (Switch: E * sum(f_e * P_e)).
MOE_AUX_LOSS_WEIGHT = "aux_loss_weight"
MOE_AUX_LOSS_WEIGHT_DEFAULT = 1e-2
# Router z-loss weight (mean(logsumexp(logits)^2) — logit drift guard).
MOE_Z_LOSS_WEIGHT = "z_loss_weight"
MOE_Z_LOSS_WEIGHT_DEFAULT = 1e-3
# The `expert` mesh axis size (must divide num_experts AND the device
# count alongside the other axes). 1 = no expert axis: experts run
# data-parallel-replicated, no all-to-all (the dev/CI path).
MOE_EXPERT_PARALLEL_SIZE = "expert_parallel_size"
MOE_EXPERT_PARALLEL_SIZE_DEFAULT = 1
# Expert-FFN compute path: the grouped-GEMM Pallas kernel
# (ops/grouped_gemm.py) vs the batched einsum. "auto" = kernel on TPU,
# einsum on CPU (DS_GROUPED_GEMM=0/1 overrides); True/False force —
# the same contract as TransformerConfig.fused_kernels.
MOE_GROUPED_GEMM = "grouped_gemm"
MOE_GROUPED_GEMM_DEFAULT = "auto"

#############################################
# Mesh / parallelism (TPU-native extension keys)
#############################################
MESH = "mesh"
MESH_DATA_PARALLEL_SIZE = "data_parallel_size"
MESH_MODEL_PARALLEL_SIZE = "model_parallel_size"
MESH_PIPE_PARALLEL_SIZE = "pipe_parallel_size"
MESH_SEQUENCE_PARALLEL_SIZE = "sequence_parallel_size"
# Multi-slice scale-out: how many ICI domains (slices) the mesh spans —
# the OUTERMOST mesh axis; dp factors within a slice and only the
# `slice`-axis collectives cross DCN (parallel/multislice.py).
MESH_NUM_SLICES = "slices"

#############################################
# Checkpoint
#############################################
CHECKPOINT = "checkpoint"
CHECKPOINT_TAG_VALIDATION = "tag_validation"
CHECKPOINT_TAG_VALIDATION_DEFAULT = "Warn"
CHECKPOINT_TAG_VALIDATION_MODES = ["Warn", "Ignore", "Fail"]
# Async checkpointing (runtime/async_ckpt.py): save_checkpoint() runs a
# fast in-step-window SNAPSHOT (one batched device_get into host
# buffers) and hands serialization + the two-phase atomic commit to a
# background writer thread. Sync and async paths share the commit
# byte-for-byte; both flip `latest` via tmp + os.replace.
CHECKPOINT_ASYNC = "async"
CHECKPOINT_ASYNC_DEFAULT = False
# Auto-save cadence: > 0 saves a checkpoint (tag global_stepN) into
# `save_dir` every N completed steps from inside train_batch.
CHECKPOINT_SNAPSHOT_EVERY = "snapshot_every"
CHECKPOINT_SNAPSHOT_EVERY_DEFAULT = 0
# Directory for auto-saves and the SIGTERM final save. Required when
# snapshot_every > 0; enables the preemption handler when set.
CHECKPOINT_SAVE_DIR = "save_dir"
CHECKPOINT_SAVE_DIR_DEFAULT = ""
# SIGTERM handler (chains with the flight recorder's): requests a final
# snapshot+commit when one isn't already in flight, then re-raises so
# the exit code stays honest. Effective only with a save_dir.
CHECKPOINT_PREEMPT_SAVE = "preempt_save"
CHECKPOINT_PREEMPT_SAVE_DEFAULT = True
# Writer knobs: max snapshots allowed in the writer queue before the
# NEXT save blocks (each pending snapshot is a full host copy of the
# state — this bounds host memory; the blocking wait is exposed and
# priced into the goodput checkpoint bucket, honestly), and the
# hang-watchdog timeout guarding each background write.
CHECKPOINT_MAX_PENDING = "max_pending_snapshots"
CHECKPOINT_MAX_PENDING_DEFAULT = 1
CHECKPOINT_WRITER_TIMEOUT_S = "writer_timeout_s"
CHECKPOINT_WRITER_TIMEOUT_S_DEFAULT = 300.0
# fsync blobs + dirs at commit: required for durability across MACHINE
# crashes; a plain process kill (preemption) never needs it, and the
# CPU-mesh test tier keeps it off for speed.
CHECKPOINT_FSYNC = "fsync"
CHECKPOINT_FSYNC_DEFAULT = False

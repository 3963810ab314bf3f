"""monitor/ — the unified telemetry subsystem.

First-class operational visibility for TPU training runs: structured
per-step records (ring-buffered, drained to JSONL at report boundaries
with zero added hot-path syncs), host-side Chrome-trace spans, a
recompile sentinel over the engine's compiled step functions,
device-memory watermarks checked against the analytic ZeRO-partitioned
model-state footprint, a roofline cost model fusing XLA's compiled cost
analysis with the jaxpr-walk flops profiler and the interconnect wire
model (per-path compute/HBM/interconnect-bound verdicts + per-step MFU),
a goodput ledger attributing every wall-clock second between report
boundaries, and the measured half of the roofline story: jax.profiler
trace ingestion into a bucketed per-step wall decomposition
(profile_ingest) reconciled against the analytic floors (reconcile).
Always on, telemetry or not: the serving loop's and the training loop's
timelines (serving, training), one row an iteration / a train_batch call,
with the stalls they caught; and the start-up ledger (startup): what the
process built before it served, one row a constructor, a program built or
loaded and a call served, on the clock of process age.
See docs/tutorials/telemetry.md.
"""
from .cost_model import (BOUND_COMPUTE, BOUND_HBM, BOUND_INTERCONNECT,
                         build_cost_model, mfu, roofline)
from .flight import FlightRecorder
from .goodput import BUCKETS as GOODPUT_BUCKETS
from .goodput import GoodputLedger
from .health import (EwmaDetector, HangWatchdog, HealthMonitor, TapSpec,
                     leaf_sq_taps)
from .hostinfo import process_identity, resolve_writer, shard_path
from .memory import (MemoryWatermark, analytic_state_bytes,
                     device_memory_stats)
from .peaks import (TPU_PEAK_TFLOPS, ChipPeaks, chip_peak_tflops,
                    chip_peaks)
from .profile_ingest import (ingest, ingest_from_telemetry,
                             parse_trace_events)
from .recompile import RecompileError, RecompileSentinel
from .reconcile import reconcile
from .request_trace import RequestTrace, validate_timeline
from .serving import ServingAggregator
from .serving_slo import (SERVING_BUCKETS, ServingGoodputLedger, SLOTracker)
from .telemetry import JsonlSink, Telemetry
from .trace import ProfilerWindow, TraceWriter
from .training import TrainingTimeline

__all__ = [
    "Telemetry", "JsonlSink", "TraceWriter", "ProfilerWindow",
    "RecompileSentinel", "RecompileError", "MemoryWatermark",
    "analytic_state_bytes", "device_memory_stats",
    "GoodputLedger", "GOODPUT_BUCKETS", "ServingAggregator",
    "TrainingTimeline",
    "ServingGoodputLedger", "SLOTracker", "SERVING_BUCKETS",
    "RequestTrace", "validate_timeline",
    "HealthMonitor", "EwmaDetector", "HangWatchdog", "TapSpec",
    "leaf_sq_taps", "FlightRecorder",
    "process_identity", "resolve_writer", "shard_path",
    "build_cost_model", "roofline", "mfu",
    "ingest", "ingest_from_telemetry", "parse_trace_events", "reconcile",
    "BOUND_COMPUTE", "BOUND_HBM", "BOUND_INTERCONNECT",
    "ChipPeaks", "chip_peaks", "chip_peak_tflops", "TPU_PEAK_TFLOPS",
]

"""inference/ — the batched autoregressive serving tier.

The training side of this framework ends at a checkpoint; this package
is what stands between that checkpoint and heavy traffic: a paged,
prefix-shared KV cache born sharded over the training mesh — fixed-size
blocks behind a block-table indirection, copy-on-write prefix sharing,
reservation-gated admission (kv_cache.py) — jitted single-token decode,
chunked prefill and the speculative draft-then-verify step behind the
served-model interface (served.py; GPT-2's in decode.py), the
self-drafting n-gram proposer (spec.py), iteration-level continuous
batching with an open-loop request queue (scheduler.py), weight
quantization via stochastic rounding (quantize.py), the InferenceEngine tying
it to the telemetry spine — decode-step JSONL records, prefill spans,
the recompile sentinel over every compiled path, per-request
TTFT/TPOT/occupancy goodput plus HBM-bytes-per-token, prefix-hit and
spec-acceptance accounting (engine.py) — and the prefix-affinity
multi-replica admission router (router.py). See
docs/tutorials/inference.md.
"""
from ..monitor import startup as _startup
_import_began = _startup.now()      # (this package loads at first use)

from .engine import InferenceEngine
from .kv_cache import (BlockAllocator, PagedKVCacheSpec, PoolExhausted,
                       init_paged_cache, paged_partition_spec)
from .quantize import dequantize, quantize_params
from .router import ReplicaRouter
from .scheduler import (ContinuousBatchingScheduler, Request,
                        shared_prefix_requests, synthetic_requests)
from .spec import NGramDrafter

__all__ = [
    "InferenceEngine", "PagedKVCacheSpec", "BlockAllocator",
    "PoolExhausted", "paged_partition_spec", "init_paged_cache",
    "quantize_params", "dequantize", "Request", "synthetic_requests",
    "shared_prefix_requests", "ContinuousBatchingScheduler",
    "ReplicaRouter", "NGramDrafter",
]

_startup.imported("inference", _import_began)

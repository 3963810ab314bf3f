"""Per-request goodput metrics for the serving tier.

The serving analogues of the training spine's step records: while the
trainer's unit of accounting is the optimizer step, serving accounts per
REQUEST (TTFT — time to first token, queue wait included; TPOT — mean
time per output token after the first) and per decode ITERATION (batch
occupancy = active slots / total slots; the number that says whether
continuous batching is actually keeping the chip busy).

All inputs are host wall-clock and host counters — aggregation adds
zero device syncs. ``ServingAggregator.snapshot()`` is the one shape
every consumer speaks: the engine's drain extra, SERVE_BENCH.json, and
``tools/telemetry_report.py``'s ``serving`` section.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional


def percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile on an already-sorted list (the same rule
    tools/telemetry_report.py uses — keep the figures comparable)."""
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1,
                   int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return float(sorted_vals[k])


def _pcts(vals: List[float]) -> Dict[str, float]:
    s = sorted(vals)
    return {"p50": round(percentile(s, 50), 3),
            "p95": round(percentile(s, 95), 3),
            "mean": round(sum(s) / len(s), 3) if s else 0.0,
            "n": len(s)}


class ServingAggregator:
    """Accumulates per-iteration and per-request serving metrics.

    ``label`` names the replica this aggregator feeds (the multi-
    replica router runs one engine — and one aggregator — per replica);
    snapshots carry it so downstream consumers (telemetry_report,
    SERVE_BENCH.json) never interleave two replicas' percentile streams
    into one misleading distribution. ``ServingAggregator.merged``
    builds the honest aggregate view by POOLING the raw samples.
    """

    def __init__(self, max_slots: int, label: Optional[str] = None):
        self.max_slots = max(1, int(max_slots))
        self.label = label
        self.t0 = time.perf_counter()
        self.iterations = 0
        self.decode_tokens = 0
        self.prefill_tokens = 0
        self.completed = 0
        # Paged-cache accounting (engine-fed; stays empty — and out of
        # the snapshot — until the engine feeds it: the scheduler tests'
        # fake engines never do).
        self.prompt_tokens_admitted = 0
        self.cached_tokens_admitted = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self._model_counters: Dict[str, Any] = {}   # name -> (sum, n)
        self._state: Dict[str, int] = {}    # a per-stream state pool's
        # Analytic attend-work accounting (engine-fed): the same
        # iterations priced BOTH ways — the Pallas kernel's live-context
        # term vs the one-hot contraction's pool-capacity term. ``attend_mode`` names which one actually
        # ran; the totals are host arithmetic (projections), never
        # device measurements.
        self.attend_mode: Optional[str] = None
        self.attend_flops_kernel = 0
        self.attend_flops_onehot = 0
        self.attend_bytes_kernel = 0
        self.attend_bytes_onehot = 0
        self.attend_tokens = 0
        # The paged kernel's sequencing steps a layer, and those of them
        # that touched a live block (the ``decode`` span's counters).
        self.attend_steps = 0
        self.attend_live_steps = 0
        # Admission-rejection accounting (the reservation gate's retries
        # used to be invisible): total rejected reservations plus the
        # per-completed-request attempt counts.
        self.reservations_rejected = 0
        self._admission_attempts: List[float] = []
        # Optional overlays (engine-attached): a ServingGoodputLedger
        # and an SLOTracker (monitor/serving_slo.py) — or, on a merged
        # aggregator, their already-settled snapshot dicts. When unset
        # the snapshot omits the sections (skip-never-fail downstream).
        self.ledger: Optional[Any] = None
        self.slo: Optional[Any] = None
        self._occupancy: List[float] = []
        self._decode_ms: List[float] = []
        self._ttft_ms: List[float] = []
        self._tpot_ms: List[float] = []
        self._queue_wait_ms: List[float] = []
        self._service_ttft_ms: List[float] = []
        self._hbm_per_token: List[float] = []
        self._cache_bytes: List[int] = []

    # ---- per decode iteration ---- #
    def note_iteration(self, active_slots: int, decode_s: float,
                       cache_bytes: Optional[int] = None,
                       context_tokens: Optional[int] = None,
                       emitted_tokens: Optional[int] = None) -> None:
        """``emitted_tokens`` defaults to one per active slot (plain
        decode); the speculative verify step passes the real count.
        ``cache_bytes`` / ``context_tokens`` sample the HBM the cache
        holds against the tokens it serves — the hbm_bytes_per_token
        series the paging win is measured on."""
        self.iterations += 1
        self.decode_tokens += int(emitted_tokens
                                  if emitted_tokens is not None
                                  else active_slots)
        self._occupancy.append(active_slots / self.max_slots)
        self._decode_ms.append(decode_s * 1e3)
        if cache_bytes is not None and context_tokens:
            self._cache_bytes.append(int(cache_bytes))
            self._hbm_per_token.append(cache_bytes / context_tokens)

    def note_prefill(self, prompt_tokens: int) -> None:
        self.prefill_tokens += int(prompt_tokens)

    def note_admit(self, prompt_tokens: int, cached_tokens: int) -> None:
        """Prefix-cache accounting at admission: how many of the
        prompt's tokens rode already-resident blocks."""
        self.prompt_tokens_admitted += int(prompt_tokens)
        self.cached_tokens_admitted += int(cached_tokens)

    def note_model_counters(self, args: Dict[str, Any]) -> None:
        """One fetch's worth of the served model's own counters (the
        ``decode`` / ``prefill`` span args of a model that has any: the
        expert layer's held-row counts). Numeric ones keep a running
        mean in the snapshot (``model_counters``)."""
        for name, value in args.items():
            if isinstance(value, (int, float)):
                tot, n = self._model_counters.get(name, (0.0, 0))
                self._model_counters[name] = (tot + float(value), n + 1)

    def note_state(self, *, resumed_tokens: int, state_copy_bytes: int,
                   snapshots_taken: int, snapshot_hits: int,
                   snapshots_evicted: int) -> None:
        """One admission batch into a per-stream state pool (the
        ``prefill`` span's ``resumed_tokens`` / ``state_copy_bytes``,
        summed) and the allocator's running totals of snapshots taken /
        hit / evicted."""
        st = self._state
        st["resumed_tokens"] = st.get("resumed_tokens", 0) \
            + int(resumed_tokens)
        st["state_copy_bytes"] = st.get("state_copy_bytes", 0) \
            + int(state_copy_bytes)
        st.update(snapshots_taken=int(snapshots_taken),
                  snapshot_hits=int(snapshot_hits),
                  snapshots_evicted=int(snapshots_evicted))

    def note_spec(self, proposed: int, accepted: int) -> None:
        self.spec_proposed += int(proposed)
        self.spec_accepted += int(accepted)

    def note_attend(self, flops_kernel: int, flops_onehot: int,
                    bytes_kernel: int, bytes_onehot: int,
                    tokens: int) -> None:
        """One iteration's analytic attend work, both ways (see
        InferenceEngine._attend_work); ``tokens`` are the iteration's
        emitted tokens — the per-token denominators."""
        self.attend_flops_kernel += int(flops_kernel)
        self.attend_flops_onehot += int(flops_onehot)
        self.attend_bytes_kernel += int(bytes_kernel)
        self.attend_bytes_onehot += int(bytes_onehot)
        self.attend_tokens += int(tokens)

    def note_attend_steps(self, steps: int, live_steps: int) -> None:
        """One iteration's attend steps a layer and the live ones among
        them (InferenceEngine._attend_steps; zeros off the kernel)."""
        self.attend_steps += int(steps)
        self.attend_live_steps += int(live_steps)

    def note_reject(self) -> None:
        """One reservation-gate / slot-pool admission rejection."""
        self.reservations_rejected += 1

    # ---- per completed request ---- #
    def note_request(self, ttft_s: float, tpot_s: Optional[float],
                     new_tokens: int,
                     queue_wait_s: Optional[float] = None,
                     service_ttft_s: Optional[float] = None,
                     admission_attempts: Optional[int] = None) -> None:
        """``queue_wait_s``/``service_ttft_s`` split the end-to-end TTFT
        at the admission instant (router backlog vs slow prefill —
        indistinguishable in the pooled ttft figure alone)."""
        self.completed += 1
        self._ttft_ms.append(ttft_s * 1e3)
        if tpot_s is not None:
            self._tpot_ms.append(tpot_s * 1e3)
        if queue_wait_s is not None:
            self._queue_wait_ms.append(queue_wait_s * 1e3)
        if service_ttft_s is not None:
            self._service_ttft_ms.append(service_ttft_s * 1e3)
        if admission_attempts is not None:
            self._admission_attempts.append(float(admission_attempts))

    @property
    def occupancy_mean(self) -> float:
        if not self._occupancy:
            return 0.0
        return sum(self._occupancy) / len(self._occupancy)

    def snapshot(self, wall_s: Optional[float] = None) -> Dict[str, Any]:
        """The canonical serving summary. ``tokens_per_s`` counts
        GENERATED (decode) tokens over the serve wall — prefill tokens
        are reported separately, not inflated into throughput. Fields
        the engine never fed (no paged cache, no spec decode) are
        omitted so pre-paging consumers and the bench gate's
        skip-never-fail rule keep working. ``attend_live_step_share``
        (paged kernel only) is the share of the attend's sequencing
        steps that touched a live block, over all iterations so far: the
        rest are the empty steps of dead streams."""
        wall = wall_s if wall_s is not None \
            else time.perf_counter() - self.t0
        snap = {
            "iterations": self.iterations,
            "completed": self.completed,
            "occupancy_mean": round(self.occupancy_mean, 4),
            "occupancy_p50": round(
                percentile(sorted(self._occupancy), 50), 4),
            "decode_tokens": self.decode_tokens,
            "prefill_tokens": self.prefill_tokens,
            "tokens_per_s": round(self.decode_tokens / wall, 3)
            if wall > 0 else 0.0,
            "wall_s": round(wall, 6),
            "ttft_ms": _pcts(self._ttft_ms),
            "tpot_ms": _pcts(self._tpot_ms),
            "decode_step_ms": _pcts(self._decode_ms),
        }
        if self.label is not None:
            snap["replica"] = self.label
        if self._queue_wait_ms:
            snap["queue_wait_ms"] = _pcts(self._queue_wait_ms)
        if self._service_ttft_ms:
            snap["service_ttft_ms"] = _pcts(self._service_ttft_ms)
        if self.reservations_rejected or self._admission_attempts:
            snap["admission"] = {
                "reservations_rejected": self.reservations_rejected,
                "attempts": _pcts(self._admission_attempts),
            }
        if self.ledger is not None:
            snap["ledger"] = self.ledger.snapshot(wall_s=wall) \
                if hasattr(self.ledger, "snapshot") else self.ledger
        if self.slo is not None:
            slo = self.slo.snapshot() if hasattr(self.slo, "snapshot") \
                else self.slo
            if slo is not None:
                snap["slo"] = slo
        if self._hbm_per_token:
            snap["hbm_bytes_per_token"] = _pcts(self._hbm_per_token)
            snap["cache_bytes_p95"] = int(percentile(
                sorted(self._cache_bytes), 95))
        if self.prompt_tokens_admitted:
            snap["prefix"] = {
                "prompt_tokens": self.prompt_tokens_admitted,
                "cached_tokens": self.cached_tokens_admitted,
                "hit_rate": round(self.cached_tokens_admitted /
                                  self.prompt_tokens_admitted, 4),
            }
        if self.spec_proposed:
            snap["spec"] = {
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "acceptance_rate": round(self.spec_accepted /
                                         self.spec_proposed, 4),
            }
        if self._state:
            snap["state"] = dict(self._state)
        if self._model_counters:
            snap["model_counters"] = {
                name: round(tot / n, 4)
                for name, (tot, n) in self._model_counters.items()}
        if self.attend_tokens:
            t = self.attend_tokens
            snap["attend"] = {
                "mode": self.attend_mode or "onehot",
                "flops_per_token": {
                    "kernel": round(self.attend_flops_kernel / t, 1),
                    "onehot": round(self.attend_flops_onehot / t, 1)},
                "hbm_bytes_per_token": {
                    "kernel": round(self.attend_bytes_kernel / t, 1),
                    "onehot": round(self.attend_bytes_onehot / t, 1)},
                "projection": "analytic (host-priced, not a device "
                              "measurement)",
            }
            if self.attend_bytes_kernel:
                # The structural headline: one-hot HBM traffic over the
                # kernel's, same iterations — >1 means the pool
                # outweighs the live contexts it served.
                snap["attend_work_ratio"] = round(
                    self.attend_bytes_onehot / self.attend_bytes_kernel,
                    4)
        if self.attend_steps:
            snap["attend_live_step_share"] = round(
                self.attend_live_steps / self.attend_steps, 4)
        return snap

    @classmethod
    def merged(cls, aggs: List["ServingAggregator"],
               label: str = "aggregate") -> "ServingAggregator":
        """The honest aggregate over replicas: raw samples POOLED, not
        percentiles-of-percentiles, counters summed, capacity summed."""
        out = cls(sum(a.max_slots for a in aggs) or 1, label=label)
        for a in aggs:
            out.iterations += a.iterations
            out.decode_tokens += a.decode_tokens
            out.prefill_tokens += a.prefill_tokens
            out.completed += a.completed
            out.prompt_tokens_admitted += a.prompt_tokens_admitted
            out.cached_tokens_admitted += a.cached_tokens_admitted
            out.spec_proposed += a.spec_proposed
            out.spec_accepted += a.spec_accepted
            out.attend_flops_kernel += a.attend_flops_kernel
            out.attend_flops_onehot += a.attend_flops_onehot
            out.attend_bytes_kernel += a.attend_bytes_kernel
            out.attend_bytes_onehot += a.attend_bytes_onehot
            out.attend_tokens += a.attend_tokens
            out.attend_steps += a.attend_steps
            out.attend_live_steps += a.attend_live_steps
            if out.attend_mode is None:
                out.attend_mode = a.attend_mode
            # Occupancy normalizes per-replica (active/its own slots):
            # pooling the normalized samples keeps the mean meaningful
            # as "fraction of owned capacity busy".
            out._occupancy.extend(a._occupancy)
            out._decode_ms.extend(a._decode_ms)
            out._ttft_ms.extend(a._ttft_ms)
            out._tpot_ms.extend(a._tpot_ms)
            out._queue_wait_ms.extend(a._queue_wait_ms)
            out._service_ttft_ms.extend(a._service_ttft_ms)
            out._admission_attempts.extend(a._admission_attempts)
            out.reservations_rejected += a.reservations_rejected
            out._hbm_per_token.extend(a._hbm_per_token)
            out._cache_bytes.extend(a._cache_bytes)
        # Fleet-level SLO/ledger views: pooled outcomes and bucket-wise
        # sums, stored as settled dicts (a merged aggregator keeps
        # accumulating nothing).
        from .serving_slo import ServingGoodputLedger, SLOTracker
        trackers = [a.slo for a in aggs if isinstance(a.slo, SLOTracker)]
        if trackers:
            out.slo = SLOTracker.merged(trackers)
        led = [a.ledger.snapshot() for a in aggs
               if a.ledger is not None and hasattr(a.ledger, "snapshot")]
        led += [a.ledger for a in aggs if isinstance(a.ledger, dict)]
        if led:
            out.ledger = ServingGoodputLedger.merged(led)
        return out


__all__ = ["ServingAggregator", "percentile"]

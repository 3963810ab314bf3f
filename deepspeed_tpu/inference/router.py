"""Multi-replica serving: an admission router over N InferenceEngines.

The thin front end production traffic needs once one replica saturates:
each replica is a full ``InferenceEngine`` (its own KV pool, compiled
paths, ``ServingAggregator`` feed); the router owns the open-loop
arrival queue and decides, per request, WHICH replica admits it —

- **load**: occupancy (active slots / capacity) plus local queue depth,
  straight from each replica's aggregator-fed counters — the router
  never touches a device;
- **prefix affinity**: a replica already holding the prompt's cached
  prefix blocks scores higher, so shared prefixes land where their
  blocks live and the paged cache's hit rate survives scale-out
  (consistent-hashing-by-content, in effect).

Replicas then run the same iteration-level continuous batching the
single-engine scheduler runs: admit from the local queue, one
decode/verify step for every live slot, evict finished. On real
hardware each replica owns a disjoint mesh and the steps run in
parallel; the CPU-mesh emulation interleaves them on one mesh, so
per-iteration WALL times stack — tokens/s and TTFT measured here are a
lower bound on what disjoint replicas would do (the honest-methodology
note SERVE_BENCH.json repeats).

Reports keep replicas apart: per-replica aggregator snapshots plus the
pooled ``ServingAggregator.merged`` aggregate — never
percentiles-of-percentiles, never one interleaved stream.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .scheduler import Request
from ..monitor.serving import ServingAggregator


class ReplicaRouter:
    """Route + serve an open-loop stream over N engine replicas."""

    def __init__(self, engines: Sequence[Any], temperature: float = 0.0,
                 eos_token: Optional[int] = None,
                 affinity_weight: float = 1.0,
                 idle_sleep_s: float = 0.0005,
                 max_wall_s: Optional[float] = None):
        if not engines:
            raise ValueError("ReplicaRouter needs at least one engine")
        self.engines = list(engines)
        self.temperature = float(temperature)
        self.eos_token = eos_token
        self.affinity_weight = float(affinity_weight)
        self.idle_sleep_s = float(idle_sleep_s)
        self.max_wall_s = max_wall_s
        self.routed: List[int] = [0] * len(self.engines)
        self.affinity_hits = 0
        # Per-request decision records: every candidate's occupancy /
        # queue depth / affinity tokens / composite score at route time
        # (ring-capped). The chosen replica is argmax of the recorded
        # scores BY CONSTRUCTION — the test gate replays them.
        self.decisions: List[dict] = []
        self.decision_capacity = 4096
        self.trace = None               # RequestTrace, built in serve()

    # ------------------------------------------------------------------ #
    def _score_parts(self, eng, queue_len: int, req: Request) -> dict:
        """One candidate's routing signals (all host counters): higher
        composite score is better — prefix affinity minus load
        (occupancy + normalized queue depth)."""
        plen = max(len(req.prompt), 1)
        affinity_tokens = eng.prefix_match_tokens(req.chained())
        occupancy = eng.active_slots / eng.max_slots
        queue_load = queue_len / eng.max_slots
        return {
            "occupancy": round(occupancy, 4),
            "queue_depth": queue_len,
            "affinity_tokens": affinity_tokens,
            "score": self.affinity_weight * (affinity_tokens / plen)
            - (occupancy + queue_load),
        }

    def _score(self, eng, queue_len: int, req: Request) -> float:
        return self._score_parts(eng, queue_len, req)["score"]

    def route(self, req: Request, queues: List[deque]) -> int:
        """Pick the admitting replica for one request (called once, at
        arrival — affinity is sticky by construction afterwards). The
        full candidate table is recorded so every choice is explainable
        after the fact."""
        cands = []
        for i, eng in enumerate(self.engines):
            parts = self._score_parts(eng, len(queues[i]), req)
            parts["replica"] = i
            label = getattr(eng, "replica", None)
            if label:
                parts["label"] = label
            cands.append(parts)
        scores = [c["score"] for c in cands]
        best = int(np.argmax(scores))
        plain = [-(c["occupancy"] + c["queue_depth"]
                   / self.engines[i].max_slots)
                 for i, c in enumerate(cands)]
        if best != int(np.argmax(plain)):
            self.affinity_hits += 1     # affinity overrode pure load
        self.routed[best] += 1
        decision = {"rid": req.rid, "chosen": best, "candidates": cands}
        if len(self.decisions) < self.decision_capacity:
            self.decisions.append(decision)
        if self.trace is not None:
            self.trace.route(req.rid, best, cands)
        return best

    # ------------------------------------------------------------------ #
    def serve(self, requests: Sequence[Request]) -> Dict[str, Any]:
        """Run the stream to completion across all replicas; returns the
        multi-replica report: pooled aggregate + per-replica snapshots +
        per-request records (each naming its replica)."""
        n_rep = len(self.engines)
        t0 = time.perf_counter()
        for eng in self.engines:     # each replica's ``stalls`` from here
            eng.serving.note_serve_start()
        pending = deque(sorted(requests, key=lambda r: r.arrival_s))
        queues: List[deque] = [deque() for _ in range(n_rep)]
        active: List[Dict[int, Request]] = [{} for _ in range(n_rep)]
        replica_of: Dict[int, int] = {}
        spec = [bool(getattr(e, "spec_enabled", False))
                and self.temperature == 0.0 for e in self.engines]
        # One shared request trace for the fleet: the route decision and
        # the replica-side spans land in the same record; each finished
        # request drains into ITS replica's telemetry stream.
        if self.trace is None and any(e.telemetry.enabled
                                      for e in self.engines):
            from ..monitor.request_trace import RequestTrace
            self.trace = RequestTrace()
        trace = self.trace

        def label(i: int) -> str:
            return getattr(self.engines[i], "replica", "") or f"r{i}"

        def ledger_of(eng):
            return getattr(eng.serving, "ledger", None)

        def finished(req: Request, eng, slot: int) -> bool:
            if len(req.out_tokens) >= req.max_new_tokens:
                return True
            if self.eos_token is not None and req.out_tokens and \
                    req.out_tokens[-1] == self.eos_token:
                return True
            return eng.context_len(slot) >= eng.max_len

        def complete(req: Request, eng) -> None:
            if req.row_first >= 0:       # the latest row is its last
                req.row_last = eng.serving.rows - 1
            eng.complete_request(req.rid, req.ttft_s or 0.0, req.tpot_s,
                                 prompt_tokens=len(req.prompt),
                                 new_tokens=len(req.out_tokens),
                                 queue_wait_s=req.queue_wait_s,
                                 service_ttft_s=req.service_ttft_s,
                                 admission_attempts=req.admission_attempts)
            if trace is not None:
                trace.complete(req.rid, t=req.t_last,
                               telemetry=eng.telemetry)

        while pending or any(queues) or any(active):
            now = time.perf_counter() - t0
            if self.max_wall_s is not None and now > self.max_wall_s:
                t_ab = time.perf_counter()
                for i, eng in enumerate(self.engines):
                    abort = getattr(eng, "abort_request", None)
                    for slot in list(active[i]):
                        req = active[i][slot]
                        if req.row_first >= 0:
                            req.row_last = eng.serving.rows - 1
                        if trace is not None:
                            trace.abort(req.rid, "max_wall", t=t_ab,
                                        telemetry=eng.telemetry)
                        if abort is not None:
                            abort(req.rid, "max_wall")
                        eng.release_slot(slot)
                        del active[i][slot]
                    for req in queues[i]:
                        if trace is not None:
                            trace.abort(req.rid, "starved", t=t_ab,
                                        telemetry=eng.telemetry)
                        if abort is not None:
                            abort(req.rid, "starved")
                break
            # 1. arrivals route to a replica queue immediately.
            while pending and pending[0].arrival_s <= now:
                req = pending.popleft()
                req.t_arrival = t0 + req.arrival_s
                if trace is not None:
                    trace.enqueue(req.rid, t=req.t_arrival)
                i = self.route(req, queues)
                replica_of[req.rid] = i
                queues[i].append(req)
            stepped = False
            for i, eng in enumerate(self.engines):
                # 2. per-replica admissions (FCFS within the replica),
                # in one-slot-per-group batches.
                while queues[i]:
                    batch = []
                    used: set = set()
                    while queues[i]:
                        req = queues[i][0]
                        slot = eng.select_slot(
                            req.chained(), req.max_new_tokens,
                            exclude_groups=used)
                        if slot is None:
                            # Genuine head-of-queue rejection only when
                            # no batch exclusions could explain it.
                            if not used:
                                req.admission_attempts += 1
                                reason = getattr(
                                    eng, "last_admit_block",
                                    None) or "no_slot"
                                if trace is not None:
                                    trace.admit_reject(req.rid,
                                                       reason=reason)
                                note = getattr(
                                    eng, "note_admission_reject", None)
                                if note is not None:
                                    note(req.rid, reason,
                                         req.admission_attempts,
                                         len(queues[i]))
                            break
                        queues[i].popleft()
                        req.t_admit = time.perf_counter()
                        used.add(eng.group_of(slot))
                        batch.append((req, slot))
                    if not batch:
                        break
                    # The engine opens the ``prefill`` host span itself.
                    results = eng.prefill_many(
                        [(slot, req.chained(), req.max_new_tokens)
                         for req, slot in batch], self.temperature,
                        rids=[req.rid for req, _ in batch])
                    # the clock of the timeline the first tokens join
                    t_now = eng.serving.clock()
                    for (req, slot), (tok, _) in zip(batch, results):
                        req.slot = slot
                        req.t_first = req.t_last = t_now
                        req.out_tokens = [tok]
                        eng.activate_slot(slot, len(req.prompt), tok)
                        eng.serving.note_prefill(len(req.prompt))
                        if trace is not None:
                            trace.admit(req.rid, slot, t=req.t_admit,
                                        replica=label(i))
                            info_fn = getattr(eng, "last_admit_info",
                                              None)
                            info = info_fn(slot) if info_fn else {}
                            trace.prefill(
                                req.rid, t_now - (req.t_admit or t_now),
                                tokens=len(req.prompt),
                                chunks=info.get("chunks", 1),
                                cached_tokens=info.get(
                                    "cached_tokens", 0),
                                cow_fork=info.get("cow_fork", False))
                            trace.first_token(req.rid, t=t_now)
                        if finished(req, eng, slot):
                            complete(req, eng)
                            eng.release_slot(slot)
                        else:
                            # From here it emits in every row of its
                            # replica's timeline (monitor/serving.py).
                            req.timeline = eng.serving
                            req.row_first = eng.serving.rows
                            eng.serving.note_first_token(t_now)
                            active[i][slot] = req
                # 3. one iteration for this replica's live slots.
                if not active[i]:
                    continue
                stepped = True
                if spec[i]:
                    emitted, n_new = eng.spec_decode_once(
                        self.temperature)
                    occ = len(active[i])
                    t_now, row = eng.serving.note_emit(occ)
                    for slot in list(active[i]):
                        req = active[i][slot]
                        budget = req.max_new_tokens - len(req.out_tokens)
                        n = int(n_new[slot])
                        toks = [int(t) for t in emitted[slot, :n]]
                        if self.eos_token is not None and \
                                self.eos_token in toks:
                            toks = toks[:toks.index(self.eos_token) + 1]
                        req.out_tokens.extend(toks[:max(budget, 0)])
                        req.t_last = t_now
                        if trace is not None:
                            trace.tick(req.rid, occ, n, t=t_now,
                                       proposed=eng.spec_k,
                                       accepted=max(n - 1, 0), row=row)
                        if finished(req, eng, slot):
                            complete(req, eng)
                            eng.release_slot(slot)
                            del active[i][slot]
                else:
                    sampled, _ = eng.decode_once(self.temperature)
                    occ = len(active[i])
                    t_now, row = eng.serving.note_emit(occ)
                    for slot in list(active[i]):
                        req = active[i][slot]
                        req.out_tokens.append(int(sampled[slot]))
                        req.t_last = t_now
                        if trace is not None:
                            trace.tick(req.rid, occ, 1, t=t_now, row=row)
                        if finished(req, eng, slot):
                            complete(req, eng)
                            eng.release_slot(slot)
                            del active[i][slot]
            if not stepped and (pending or any(queues)):
                # Same loud-failure rule as the single-engine
                # scheduler: when every replica is idle, nothing is in
                # flight, and no future arrival can change the picture,
                # a queued head that still cannot admit NEVER will
                # (its worst-case block need exceeds its replica's
                # per-group pool) — raise instead of spinning.
                if not pending and not any(active) and \
                        not any(e.active.any() for e in self.engines):
                    req = next(q[0] for q in queues if q)
                    raise RuntimeError(
                        f"request {req.rid} can never be admitted on "
                        f"its routed replica: {len(req.prompt)} prompt "
                        f"+ {req.max_new_tokens} new tokens exceeds "
                        "the block pool's per-group capacity")
                for eng in self.engines:
                    eng.telemetry.heartbeat()
                t_sl = time.perf_counter()
                time.sleep(self.idle_sleep_s)
                dt = time.perf_counter() - t_sl
                for i, eng in enumerate(self.engines):
                    led = ledger_of(eng)
                    if led is not None:
                        led.note(
                            "admission_blocked" if queues[i] else "idle",
                            dt)

        wall = time.perf_counter() - t0
        per_replica = []
        for eng in self.engines:
            if eng.telemetry.enabled:
                eng.telemetry.drain({"serving": eng.serving.snapshot(
                    wall_s=wall)})
            per_replica.append(eng.serving.snapshot(wall_s=wall))
        merged = ServingAggregator.merged(
            [e.serving for e in self.engines])
        report = dict(merged.snapshot(wall_s=wall))
        report["recompiles"] = sum(e.telemetry.recompile_count
                                   for e in self.engines)
        report["unfinished"] = len(pending) + sum(map(len, queues)) + \
            sum(map(len, active))
        report["replicas"] = per_replica
        report["router"] = {
            "replicas": len(self.engines),
            "routed": list(self.routed),
            "affinity_overrides": self.affinity_hits,
            "affinity_weight": self.affinity_weight,
            "decisions_recorded": len(self.decisions),
        }
        if trace is not None:
            report["trace"] = trace.summary()
        report["requests"] = [
            {"rid": r.rid, "replica": replica_of.get(r.rid),
             "prompt_tokens": len(r.prompt),
             "new_tokens": len(r.out_tokens),
             "ttft_ms": round(r.ttft_s * 1e3, 3)
             if r.ttft_s is not None else None,
             "tpot_ms": round(r.tpot_s * 1e3, 3)
             if r.tpot_s is not None else None,
             "tokens": list(map(int, r.out_tokens))}
            for r in sorted(requests, key=lambda r: r.rid)]
        return report


__all__ = ["ReplicaRouter"]

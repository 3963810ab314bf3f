#!/usr/bin/env python
"""Summarize a telemetry JSONL run into TELEMETRY.json.

Usage:
    python tools/telemetry_report.py runs/MyJob.jsonl [-o TELEMETRY.json]

Reads the line records the monitor/ subsystem emits (kind: meta | step |
report | event | cost_model) and produces one machine-diffable summary
so benches and CI can compare runs:

- step time p50/p95/mean (ms) — per-step host wall. On the jitted paths
  this is DISPATCH wall (steps pipeline asynchronously); the fenced
  ground truth is ``throughput.samples_per_sec`` from the report
  record's synchronized window average.
- throughput (samples/sec, window-averaged) and total samples.
- recompile count + the offending functions/signature deltas.
- peak device memory vs the analytic ZeRO model-state footprint (and any
  watermark events). ``memory.available: false`` when the backend
  reports no ``memory_stats()`` (e.g. CPU).
- wire bytes/step from the grad-sync wire model, with a consistency
  check between the meta record and the per-step records.
- overflow/skipped-step counts and dropped-record accounting (a ring
  overflow between drains is reported, never silent).
- ``mfu``: per-step MFU stats (dispatch-wall based) plus the fenced
  ``window_mfu`` from the last closed throughput window.
- ``roofline``: the cost_model record's per-path verdicts
  (compute/HBM/interconnect-bound), the fused per-step analytic floor,
  and measured-p50 vs floor (how far the run sits from the ceiling).
- ``goodput``: bucket totals aggregated across every settled window,
  the goodput fraction, and the sum-to-wall consistency verdict.
- ``serving``: present when the stream came from the inference tier
  (meta ``mode: "serving"`` or serving-shaped records): batch occupancy
  over decode iterations, TTFT/TPOT p50/p95 from ``request_complete``
  events, tokens/s and decode-step percentiles from the last report's
  aggregator snapshot.
- ``serving_slo``: request-scoped observability for serving streams —
  per-replica serving goodput ledger (prefill / decode_useful /
  spec_wasted / admission_blocked / idle buckets summing to the serve
  wall, with a double-attribution ``consistent`` verdict), SLO
  attainment + burn-rate verdicts per replica (``slo: null`` with a
  reason when no request completed or no target is configured — never
  a crash), and the slowest-TTFT request exemplars with their full
  span timelines from the ``request_trace`` events, audited for
  contiguity (spans must tile [0, total_ms] with no gaps/overlaps).
- ``moe``: present when the run carried MoE metrics (the engine's
  ``moe`` config block): drop-fraction p50/p95/last, expert-load
  imbalance (max/mean routed counts — 1.0 is balanced), last aux loss,
  and the analytic all-to-all wire bytes/step from the meta record.
  ``tools/bench_gate.py`` gates drop-fraction rises across rounds.
- ``health``: anomaly counts (non-finite provenance events, EWMA
  spikes), watchdog fires, flight-recorder presence (FLIGHT.json next
  to the stream, with its recorded reason), the ``truncated`` verdict,
  and multi-host aggregation over per-host shards
  (``<job>.rankK.jsonl``): per-host step-wall p50 with straggler skew,
  step-count desync, and loss-hash desync (SPMD processes must see the
  same loss — a differing hash means the pod diverged).
- ``truncated`` (top level): a marker-capable stream (meta
  ``emits_final``) whose latest segment lacks the terminal ``final``
  record ended in a crash/kill — its window stats describe a PARTIAL
  run and are labeled so instead of being reported as a complete one.
  Pre-marker streams get ``null`` (unknown), never a false verdict.

``tools/bench_gate.py`` diffs the mfu/goodput sections across bench
rounds — and the serving section across serving rounds — and fails CI
on regression; a ``health`` section with non-finite anomalies, watchdog
fires, or a truncated stream fails the round outright.
"""
from __future__ import annotations

import argparse
import glob
import hashlib
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile on an already-sorted list (no numpy dep so
    the tool runs anywhere)."""
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1,
                   int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return float(sorted_vals[k])


def _parse_segment(jsonl_path: str) -> Tuple[Dict[str, Any],
                                             List[Dict[str, Any]],
                                             List[Dict[str, Any]],
                                             List[Dict[str, Any]],
                                             Dict[str, Any], bool]:
    """(meta, steps, reports, events, cost_model, saw_final) of the
    LATEST segment in an append-mode stream (a meta record resets)."""
    meta: Dict[str, Any] = {}
    steps: List[Dict[str, Any]] = []
    reports: List[Dict[str, Any]] = []
    events: List[Dict[str, Any]] = []
    cost_model: Dict[str, Any] = {}
    saw_final = False
    with open(jsonl_path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            kind = rec.get("kind")
            if kind == "meta":
                meta, steps, reports, events = dict(rec), [], [], []
                cost_model = {}
                saw_final = False
            elif kind == "step":
                steps.append(rec)
            elif kind == "report":
                reports.append(rec)
            elif kind == "event":
                events.append(rec)
            elif kind == "cost_model":
                cost_model = dict(rec)
            elif kind == "final":
                saw_final = True
    return meta, steps, reports, events, cost_model, saw_final


def _loss_hash(steps: List[Dict[str, Any]]) -> Optional[str]:
    """Order-sensitive digest of the (rounded) loss series — SPMD
    processes compute the same global loss, so differing hashes across
    host shards mean the pod DIVERGED (desync), the check no per-host
    eyeball could do."""
    losses = [round(float(r["loss"]), 5) for r in steps
              if isinstance(r.get("loss"), (int, float))
              and not isinstance(r.get("loss"), bool)]
    if not losses:
        return None
    return hashlib.md5(json.dumps(losses).encode()).hexdigest()[:12]


def _host_entry(rank: int, steps: List[Dict[str, Any]],
                saw_final: bool) -> Dict[str, Any]:
    walls = sorted(float(r["wall_ms"]) for r in steps if "wall_ms" in r)
    return {"rank": rank, "steps": len(steps),
            "last_step": steps[-1].get("step") if steps else None,
            "wall_p50_ms": round(_percentile(walls, 50), 3),
            "loss_hash": _loss_hash(steps),
            "final": bool(saw_final)}


def aggregate_hosts(jsonl_path: str, meta: Dict[str, Any],
                    steps: List[Dict[str, Any]],
                    saw_final: bool) -> Dict[str, Any]:
    """Cross-host view from the per-host shards next to the primary
    stream: straggler skew (per-host step-wall p50 spread), step-count
    desync, and loss-hash desync."""
    root, ext = os.path.splitext(jsonl_path)
    shard_paths = sorted(glob.glob(f"{root}.rank*{ext}"))
    entries = [_host_entry(int(meta.get("process_index", 0) or 0),
                           steps, saw_final)]
    # Stale-shard guard: the sink appends, so a relaunch with a smaller
    # world (or per_host_shards off) leaves orphaned rank files whose
    # LAST segment belongs to the previous run — comparing them against
    # the new primary would fabricate desync/straggler verdicts. A shard
    # is stale when its rank falls outside the primary's process_count,
    # or its segment-start ts is far (>15 min) from the primary's —
    # SPMD processes of one run start near-simultaneously.
    primary_ts = float(meta.get("ts") or 0.0)
    pcount = int(meta.get("process_count") or 0)
    stale: List[Dict[str, Any]] = []
    for p in shard_paths:
        m = re.search(r"\.rank(\d+)" + re.escape(ext) + "$", p)
        meta_s, steps_s, _, _, _, fin_s = _parse_segment(p)
        rank = int(meta_s.get("process_index",
                              m.group(1) if m else -1) or 0)
        ts_s = float(meta_s.get("ts") or 0.0)
        reason = None
        if pcount and rank >= pcount:
            reason = f"rank {rank} outside process_count {pcount}"
        elif primary_ts and ts_s and abs(ts_s - primary_ts) > 900.0:
            reason = "segment start >15min from the primary's"
        if reason is not None:
            stale.append({"rank": rank, "path": os.path.basename(p),
                          "reason": reason})
            continue
        entries.append(_host_entry(rank, steps_s, fin_s))
    out: Dict[str, Any] = {"available": len(entries) > 1,
                           "n_hosts": len(entries)}
    if stale:
        out["stale_shards"] = stale
    if len(entries) < 2:
        return out
    entries.sort(key=lambda e: e["rank"])
    p50s = [e["wall_p50_ms"] for e in entries if e["wall_p50_ms"] > 0]
    skew = None
    slowest = None
    if p50s and min(p50s) > 0:
        skew = round((max(p50s) - min(p50s)) / min(p50s), 4)
        slowest = max((e for e in entries if e["wall_p50_ms"] > 0),
                      key=lambda e: e["wall_p50_ms"])["rank"]
    lasts = {e["last_step"] for e in entries if e["last_step"] is not None}
    hashes = {e["loss_hash"] for e in entries if e["loss_hash"]}
    out.update({
        "per_host": entries,
        "straggler_skew_rel": skew,
        "slowest_rank": slowest,
        "step_count_desync": len(lasts) > 1,
        "loss_desync": len(hashes) > 1,
    })
    return out


def summarize(jsonl_path: str) -> Dict[str, Any]:
    """Summary of the LATEST run in the stream: the sink appends (so a
    resumed/re-launched job with the same job_name extends one file), and
    every run opens with a ``meta`` record — seeing one resets the
    accumulators so earlier runs' steps can't contaminate this run's
    percentiles, recompile counts, or consistency checks."""
    meta, steps, reports, events, cost_model, saw_final = \
        _parse_segment(jsonl_path)

    walls = sorted(float(r["wall_ms"]) for r in steps if "wall_ms" in r)
    recompiles = [e for e in events if e.get("event") == "recompile"]
    watermarks = [e for e in events if e.get("event") == "memory_watermark"]

    # Throughput: the last report with a closed (valid) window wins.
    samples_per_sec: Optional[float] = None
    for rep in reversed(reports):
        if rep.get("samples_per_sec_valid"):
            samples_per_sec = float(rep["samples_per_sec"])
            break

    # Wire bytes: meta is authoritative; per-step records must agree.
    wire_meta = meta.get("wire_bytes_per_step")
    step_wires = {int(r["wire_bytes"]) for r in steps if "wire_bytes" in r}
    wire_consistent = (wire_meta is None and not step_wires) or \
        (wire_meta is not None and
         (not step_wires or step_wires == {int(wire_meta)}))

    # Memory: peak across every drain sample vs the analytic footprint.
    peaks = [int(rep["memory"]["peak_bytes_in_use_max"]) for rep in reports
             if isinstance(rep.get("memory"), dict)
             and "peak_bytes_in_use_max" in rep["memory"]]
    analytic = meta.get("analytic_state_bytes")
    memory: Dict[str, Any] = {"available": bool(peaks)}
    if analytic is not None:
        memory["analytic_state_bytes"] = int(analytic)
    if peaks:
        memory["peak_bytes_in_use_max"] = max(peaks)
        if analytic:
            memory["peak_vs_analytic_ratio"] = round(
                max(peaks) / max(1, int(analytic)), 4)
    memory["watermark_events"] = len(watermarks)

    overflows = sum(1 for r in steps if r.get("overflow"))
    skipped = None
    for rep in reversed(reports):
        if "skipped_steps" in rep:
            skipped = int(rep["skipped_steps"])
            break

    # MoE section: per-step expert load-balance stats from the moe_*
    # metrics the engine rides on the drain (meta `moe` block = the
    # config truth). Imbalance = max/mean of the per-expert routed
    # token counts — 1.0 is perfectly balanced; bench_gate diffs the
    # drop-fraction percentiles across rounds.
    moe: Dict[str, Any] = {"available": False}
    moe_steps = [r for r in steps if "moe_drop_fraction" in r]
    if moe_steps:
        drops = sorted(float(r["moe_drop_fraction"]) for r in moe_steps)
        aux = [float(r["moe_aux_loss"]) for r in moe_steps
               if "moe_aux_loss" in r]
        imbalance = []
        for r in moe_steps:
            counts = r.get("moe_expert_tokens")
            if isinstance(counts, list) and counts:
                mean = sum(counts) / len(counts)
                if mean > 0:
                    imbalance.append(max(counts) / mean)
        moe = {
            "available": True,
            "config": meta.get("moe") or {},
            "ep": meta.get("ep"),
            "steps": len(moe_steps),
            "drop_fraction": {
                "p50": round(_percentile(drops, 50), 5),
                "p95": round(_percentile(drops, 95), 5),
                "last": round(drops and float(
                    moe_steps[-1]["moe_drop_fraction"]) or 0.0, 5),
            },
            "aux_loss_last": round(aux[-1], 5) if aux else None,
            "expert_imbalance": {
                "p50": round(_percentile(sorted(imbalance), 50), 4),
                "max": round(max(imbalance), 4) if imbalance else None,
            } if imbalance else {"p50": None, "max": None},
            "alltoall_wire_bytes_per_step":
                meta.get("moe_alltoall_wire_bytes_per_step"),
        }

    # MFU: per-step figures are dispatch-wall based (honest but loose on
    # jitted paths); window_mfu comes from the fenced throughput window.
    step_mfus = [float(r["mfu"]) for r in steps if "mfu" in r]
    window_mfu: Optional[float] = None
    for rep in reversed(reports):
        if "window_mfu" in rep:
            window_mfu = float(rep["window_mfu"])
            break
    mfu: Dict[str, Any] = {"available": bool(step_mfus)}
    if step_mfus:
        s = sorted(step_mfus)
        mfu.update({
            "per_step_mean": float(f"{sum(s) / len(s):.4g}"),
            "per_step_p50": float(f"{_percentile(s, 50):.4g}"),
            "n": len(s),
        })
    if window_mfu is not None:
        mfu["window_mfu"] = window_mfu
    chip = cost_model.get("chip") or {}
    if chip:
        mfu["peak_bf16_tflops"] = chip.get("bf16_tflops")
        mfu["peak_assumed"] = bool(chip.get("assumed"))

    # Roofline: the cost_model record, slimmed to the decision fields,
    # plus measured-vs-floor (dispatch p50 over the analytic floor — how
    # far the run sits from the perfect-overlap ceiling; <1 would mean
    # the model is wrong or the wall clock lies).
    roofline: Dict[str, Any] = {"available": bool(cost_model)}
    if cost_model:
        cm_step = cost_model.get("step") or {}
        paths = {}
        for name, p in (cost_model.get("paths") or {}).items():
            if not isinstance(p, dict):
                continue
            paths[name] = {k: p.get(k) for k in
                           ("bound", "floor_ms", "t_compute_ms", "t_hbm_ms",
                            "t_comm_ms", "t_dcn_ms", "scan_scale",
                            "available")
                           if k in p}
        roofline.update({
            "chip": chip,
            "n_devices": cost_model.get("n_devices"),
            "paths": paths,
            "step_bound": cm_step.get("bound"),
            "step_floor_ms": cm_step.get("floor_ms"),
            "flops_per_step": cm_step.get("flops_per_step"),
            "missing_paths": cm_step.get("missing_paths"),
        })
        # Two-tier interconnect verdict (multislice runs): the wire
        # bytes each tier moves per step (telemetry meta) and which
        # tier binds comm — a step can be DCN-bound while ICI idles,
        # and the fused t_comm figure alone would hide it.
        if int(meta.get("slices") or 1) > 1:
            t_ici = sum((p.get("t_comm_ms") or 0.0) for p in paths.values())
            t_dcn = sum((p.get("t_dcn_ms") or 0.0) for p in paths.values())
            roofline["comm_tiers"] = {
                "slices": int(meta["slices"]),
                "wire_bytes_ici": meta.get("wire_bytes_ici"),
                "wire_bytes_dcn": meta.get("wire_bytes_dcn"),
                "dcn_compression": bool(meta.get("dcn_compression")),
                "t_ici_ms": round(t_ici, 6),
                "t_dcn_ms": round(t_dcn, 6),
                "comm_bound_tier": "dcn" if t_dcn > t_ici else "ici",
            }
        # Optimizer-apply analytic pricing (one-pass vs two-pass HBM
        # bytes) rides the cost_model record when the engine runs the
        # fused apply family.
        if isinstance(cost_model.get("optimizer_apply"), dict):
            roofline["optimizer_apply"] = cost_model["optimizer_apply"]
        floor = cm_step.get("floor_ms")
        p50 = _percentile(walls, 50)
        if floor and p50 > 0:
            roofline["measured_p50_over_floor"] = round(p50 / floor, 3)

    # Goodput: aggregate every settled window. The per-window sum-to-wall
    # identity holds by construction (other is the residual); the real
    # checks are each window's `consistent` flag (no double-attribution)
    # and the aggregated accounted fraction.
    gp_windows = [rep["goodput"] for rep in reports
                  if isinstance(rep.get("goodput"), dict)]
    goodput: Dict[str, Any] = {"available": bool(gp_windows)}
    if gp_windows:
        # Only the ledger's CLOSED bucket set joins the accounted sum —
        # everything else a window carries (`*_bg_s` background wall
        # measured on another thread, sub-figures like
        # `checkpoint_snapshot_s` that are subsets of a bucket, future
        # additions) is reported-only, and summing it would double-count
        # seconds the ledger deliberately kept apart. An allowlist keeps
        # that exclusion fail-safe for sub-figures added later.
        ledger_buckets = {"useful_compute", "data_stall", "recompile",
                          "overflow_skipped", "checkpoint",
                          "offload_exposed", "other"}

        def _is_bucket(k: str) -> bool:
            return k.endswith("_s") and k[:-2] in ledger_buckets

        all_keys = set().union(*(w.keys() for w in gp_windows))
        bucket_keys = sorted(k for k in all_keys if _is_bucket(k))
        totals = {k: sum(float(w.get(k, 0.0)) for w in gp_windows)
                  for k in bucket_keys}
        total_window = sum(float(w.get("window_s", 0.0)) for w in gp_windows)
        ck_exposed = totals.get("checkpoint_s", 0.0)
        ck_snapshot = sum(float(w.get("checkpoint_snapshot_s", 0.0))
                          for w in gp_windows)
        ck_write_bg = sum(float(w.get("checkpoint_write_bg_s", 0.0))
                          for w in gp_windows)
        goodput.update({
            "windows": len(gp_windows),
            "total_window_s": round(total_window, 6),
            "buckets_s": {k[:-2]: round(v, 6) for k, v in totals.items()},
            "goodput_fraction": round(
                totals.get("useful_compute_s", 0.0) / total_window, 6)
                if total_window > 0 else 0.0,
            "accounted_fraction": round(
                sum(totals.values()) / total_window, 6)
                if total_window > 0 else 1.0,
            "consistent": all(w.get("consistent", False)
                              for w in gp_windows),
        })
        # The resilience split: exposed (paid) checkpoint wall vs the
        # background writer's overlapped wall. exposed_share is what
        # bench_gate's checkpoint gate reads.
        goodput["checkpoint"] = {
            "exposed_s": round(ck_exposed, 6),
            "snapshot_s": round(ck_snapshot, 6),
            "write_bg_s": round(ck_write_bg, 6),
            "exposed_share": round(ck_exposed / total_window, 6)
            if total_window > 0 else 0.0,
        }
        if isinstance(meta.get("checkpoint"), dict):
            goodput["checkpoint"]["snapshot_every"] = \
                meta["checkpoint"].get("snapshot_every")
            goodput["checkpoint"]["async"] = \
                meta["checkpoint"].get("async")

    # Serving: occupancy from the decode-step records, per-request
    # latency percentiles recomputed from the request_complete events
    # (ground truth, not a snapshot), throughput from the last report's
    # aggregator snapshot.
    completions = [e for e in events
                   if e.get("event") == "request_complete"]
    occ = sorted(float(r["occupancy"]) for r in steps
                 if "occupancy" in r)
    serve_snap: Dict[str, Any] = {}
    for rep in reversed(reports):
        if isinstance(rep.get("serving"), dict):
            serve_snap = rep["serving"]
            break
    is_serving = meta.get("mode") == "serving" or bool(occ) or \
        bool(completions)
    serving: Dict[str, Any] = {"available": is_serving}
    if is_serving:
        ttfts = sorted(float(e["ttft_ms"]) for e in completions
                       if "ttft_ms" in e)
        tpots = sorted(float(e["tpot_ms"]) for e in completions
                       if "tpot_ms" in e)
        serving.update({
            "decode_iterations": len(occ),
            "occupancy_mean": round(sum(occ) / len(occ), 4)
            if occ else 0.0,
            "occupancy_p50": round(_percentile(occ, 50), 4),
            "completed": len(completions),
            "ttft_ms": {"p50": round(_percentile(ttfts, 50), 3),
                        "p95": round(_percentile(ttfts, 95), 3),
                        "n": len(ttfts)},
            "tpot_ms": {"p50": round(_percentile(tpots, 50), 3),
                        "p95": round(_percentile(tpots, 95), 3),
                        "n": len(tpots)},
            "tokens_per_s": serve_snap.get("tokens_per_s"),
            "decode_step_ms": serve_snap.get("decode_step_ms"),
            "prefill_tokens": serve_snap.get("prefill_tokens"),
            "decode_tokens": serve_snap.get("decode_tokens"),
        })
        # Queue-wait vs service-TTFT split, recomputed from the
        # request_complete events (ground truth): queue_wait is router/
        # scheduler hold time before admission, service_ttft is
        # admission→first-token — they sum to ttft exactly, so a TTFT
        # regression is attributable to queuing vs prefill at a glance.
        qws = sorted(float(e["queue_wait_ms"]) for e in completions
                     if "queue_wait_ms" in e)
        svc = sorted(float(e["service_ttft_ms"]) for e in completions
                     if "service_ttft_ms" in e)
        if qws:
            serving["queue_wait_ms"] = {
                "p50": round(_percentile(qws, 50), 3),
                "p95": round(_percentile(qws, 95), 3),
                "n": len(qws)}
        if svc:
            serving["service_ttft_ms"] = {
                "p50": round(_percentile(svc, 50), 3),
                "p95": round(_percentile(svc, 95), 3),
                "n": len(svc)}
        # Paged-cache / spec-decode / attend-work / admission sections
        # of the aggregator snapshot pass through when present
        # (pre-paging streams carry none; ``attend`` is the analytic
        # kernel-vs-one-hot pricing, projection-labeled at the source).
        for sec in ("hbm_bytes_per_token", "prefix", "spec", "replica",
                    "attend", "attend_work_ratio", "admission",
                    # the serving timeline's figures (monitor/serving.py)
                    "itl_ms", "itl_split_ms", "itl_stalled_share",
                    "prefill_row_fill", "stalls", "lookahead_share",
                    "lookahead_dropped_rows"):
            if serve_snap.get(sec) is not None:
                serving[sec] = serve_snap[sec]
        # Multi-replica streams: request_complete events carry replica
        # labels — split the per-request percentiles per replica so two
        # replicas' latency distributions never interleave into one
        # misleading stream (the pooled figures above remain the honest
        # aggregate).
        labels = sorted({str(e["replica"]) for e in completions
                         if e.get("replica") is not None})
        if len(labels) > 1 or (labels and serving.get("replica")
                               not in (None, labels[0])):
            per_rep: Dict[str, Any] = {}
            for lab in labels:
                evs = [e for e in completions
                       if str(e.get("replica")) == lab]
                tt = sorted(float(e["ttft_ms"]) for e in evs
                            if "ttft_ms" in e)
                tp = sorted(float(e["tpot_ms"]) for e in evs
                            if "tpot_ms" in e)
                per_rep[lab] = {
                    "completed": len(evs),
                    "ttft_ms": {"p50": round(_percentile(tt, 50), 3),
                                "p95": round(_percentile(tt, 95), 3),
                                "n": len(tt)},
                    "tpot_ms": {"p50": round(_percentile(tp, 50), 3),
                                "p95": round(_percentile(tp, 95), 3),
                                "n": len(tp)},
                }
            serving["replicas"] = per_rep

    # Serving SLO / goodput-ledger section — everything re-validates
    # from the JSONL alone:
    # - per-replica wall-time ledger (prefill/decode_useful/spec_wasted/
    #   admission_blocked/idle buckets summing to the serve wall;
    #   `consistent` false means double-attribution),
    # - SLO attainment + burn rate per replica (burn > 1 = the error
    #   budget is being spent faster than the window allows),
    # - worst-TTFT request exemplars with their FULL span timelines
    #   from the `request_trace` events, plus a contiguity audit over
    #   every recorded timeline (gaps/overlaps = instrumentation bugs).
    # Zero completed requests is a reported condition (`slo: null` with
    # the reason), never a crash — a saturated/aborted stream still gets
    # its ledger and traces summarized.
    traces = [e for e in events if e.get("event") == "request_trace"]
    led_by_rep: Dict[str, Any] = {}
    slo_by_rep: Dict[str, Any] = {}
    for rep in reports:
        s = rep.get("serving")
        if not isinstance(s, dict):
            continue
        lab = str(s.get("replica") or "default")
        if isinstance(s.get("ledger"), dict):
            led_by_rep[lab] = s["ledger"]
        if isinstance(s.get("slo"), dict):
            slo_by_rep[lab] = s["slo"]
    serving_slo: Dict[str, Any] = {
        "available": bool(is_serving
                          and (led_by_rep or slo_by_rep or traces))}
    if serving_slo["available"]:
        if led_by_rep:
            serving_slo["ledger"] = {
                "replicas": led_by_rep,
                "consistent": all(bool(l.get("consistent"))
                                  for l in led_by_rep.values()),
            }
        if not completions:
            serving_slo["slo"] = None
            serving_slo["slo_unavailable_reason"] = \
                "no completed requests in this segment"
        elif slo_by_rep:
            burn: Dict[str, Any] = {}
            for lab, s in slo_by_rep.items():
                br = s.get("burn_rate")
                burn[lab] = {
                    "attainment": s.get("attainment"),
                    "burn_rate": br,
                    "verdict": ("no_target" if br is None
                                else "burning" if br > 1.0 else "ok"),
                }
            serving_slo["slo"] = {"replicas": slo_by_rep, "burn": burn}
        else:
            serving_slo["slo"] = None
            serving_slo["slo_unavailable_reason"] = \
                "no slo targets configured (inference.slo unset)"
        if traces:
            def _tl_errors(tl: Dict[str, Any]) -> int:
                """Gap/overlap count: spans must tile [0, total_ms]
                exactly (shared endpoints by construction)."""
                spans = tl.get("spans") or []
                errs = 0 if spans else 1
                cur = 0.0
                for sp in spans:
                    if abs(float(sp.get("t_ms", 0.0)) - cur) > 1e-6:
                        errs += 1
                    cur = float(sp.get("t_ms", 0.0)) + \
                        float(sp.get("dur_ms", 0.0))
                if spans and abs(cur - float(tl.get("total_ms", 0.0))) \
                        > 1e-6:
                    errs += 1
                return errs

            keep = ("rid", "outcome", "replica", "spans", "total_ms",
                    "ttft_ms", "queue_wait_ms", "service_ttft_ms",
                    "admission_attempts", "new_tokens", "route",
                    "abort_reason")
            done = sorted(
                (e for e in traces if e.get("ttft_ms") is not None),
                key=lambda e: -float(e["ttft_ms"]))
            serving_slo["traces"] = {
                "recorded": len(traces),
                "completed": sum(1 for e in traces
                                 if e.get("outcome") == "complete"),
                "aborted": sum(1 for e in traces
                               if e.get("outcome") == "abort"),
                "contiguity_violations": sum(
                    1 for e in traces if _tl_errors(e)),
                "worst_ttft": [
                    {k: e[k] for k in keep if k in e}
                    for e in done[:3]],
            }

    # Truncation: a marker-capable segment without the terminal `final`
    # record died mid-run — its partial-window stats must not read as a
    # complete run. Pre-marker streams: unknown (None), never a false
    # verdict.
    truncated: Optional[bool] = (not saw_final) \
        if meta.get("emits_final") else None
    if truncated:
        goodput["truncated"] = True
        mfu["truncated"] = True

    # Health: anomaly/watchdog events, flight-recorder presence, and
    # the multi-host shard aggregation.
    anomalies = [e for e in events if e.get("event") == "anomaly"]
    watchdogs = [e for e in events if e.get("event") == "watchdog"]
    counts: Dict[str, int] = {}
    nonfinite = 0
    nonfinite_unskipped = 0
    for a in anomalies:
        k = str(a.get("anomaly", "unknown"))
        counts[k] = counts.get(k, 0) + 1
        if k.startswith("nonfinite"):
            nonfinite += 1
            # Overflow-SKIPPED steps are routine fp16 loss-scale
            # mechanics (update discarded); a non-finite value that was
            # NOT skipped entered the params/loss — the defect class.
            if not a.get("overflow"):
                nonfinite_unskipped += 1
    flight: Dict[str, Any] = {"present": False}
    stream_dir = os.path.dirname(os.path.abspath(jsonl_path))
    candidates = []
    meta_fp = meta.get("flight_path")
    if meta_fp:
        # The recorded path may be relative to the RUN's cwd, not ours;
        # fall back to the same basename next to the analyzed stream.
        # No meta flight_path = this segment never armed a recorder —
        # do NOT glob for an artifact, or a previous run's crash file
        # in the same directory gets attributed to a clean run.
        candidates.append(meta_fp)
        candidates.append(os.path.join(stream_dir,
                                       os.path.basename(meta_fp)))
    fpath = next((c for c in candidates if os.path.exists(c)), None)
    if fpath:
        flight = {"present": True, "path": fpath}
        try:
            with open(fpath) as f:
                fdoc = json.load(f)
            flight.update({"reason": fdoc.get("reason"),
                           "closed_clean": fdoc.get("closed_clean"),
                           "last_steps": len(fdoc.get("last_steps") or []),
                           "watchdog_fires": fdoc.get("watchdog_fires")})
        except (OSError, json.JSONDecodeError):
            flight["parse_error"] = True
    hosts = aggregate_hosts(jsonl_path, meta, steps, saw_final)
    health: Dict[str, Any] = {
        "available": bool(meta.get("health_enabled")) or bool(anomalies)
        or bool(watchdogs),
        "anomalies": {
            "total": len(anomalies),
            "nonfinite": nonfinite,
            "nonfinite_unskipped": nonfinite_unskipped,
            "counts": counts,
            # `anomaly_step` is the step the anomaly happened AT;
            # the record's `step` field is the drain-time counter and
            # would mislabel every anomaly in a window with the report
            # boundary's step.
            "events": [dict(
                {k: a.get(k) for k in
                 ("anomaly", "first_nonfinite_leaf",
                  "first_nonfinite_layer", "overflow", "metric", "z")
                 if k in a},
                step=a.get("anomaly_step", a.get("step")))
                for a in anomalies[:8]],
        },
        "watchdog_fires": len(watchdogs),
        "flight_recorder": flight,
        "truncated": truncated,
        "hosts": hosts,
    }

    offload_steps = [r["offload"] for r in steps
                     if isinstance(r.get("offload"), dict)]
    offload: Optional[Dict[str, Any]] = None
    if offload_steps:
        fracs = [float(o.get("overlap_fraction", 0.0))
                 for o in offload_steps]
        offload = {
            "steps": len(offload_steps),
            "overlap_fraction_mean": round(sum(fracs) / len(fracs), 4),
            "num_buckets": offload_steps[-1].get("num_buckets"),
            "overlapped": offload_steps[-1].get("overlapped"),
        }

    # Profile: the measured half of the roofline story — capture-window
    # outcomes (structured profile_window events), the bucketed per-step
    # wall decomposition from the ingested jax.profiler trace, and the
    # reconciliation verdict + divergences against the analytic floors.
    windows = [e for e in events if e.get("event") == "profile_window"]
    prof_events = [e for e in events if e.get("event") == "profile"]
    div_events = [e for e in events
                  if e.get("event") == "reconcile_divergence"]
    profile: Dict[str, Any] = {"available": bool(prof_events)}
    if windows:
        profile["windows"] = [
            {k: w.get(k) for k in ("phase", "path", "start_step",
                                   "stop_step", "ok", "reason") if k in w}
            for w in windows]
    if prof_events:
        last = prof_events[-1]
        d = last.get("decomposition") or {}
        r = last.get("reconciliation") or {}
        profile.update({
            "steps": d.get("steps"),
            "per_step_wall_ms": d.get("per_step_wall_ms"),
            "per_step_ms": d.get("per_step_ms"),
            "sum_check": d.get("sum_check"),
            "pallas_families_ms": d.get("pallas_families_ms"),
            "n_device_ops": d.get("n_device_ops"),
        })
        if last.get("error"):
            profile["error"] = last["error"]
        if r:
            profile["reconciliation"] = {
                "verdict": r.get("verdict"),
                "dominant_bucket": r.get("dominant_bucket"),
                "predicted_bound": r.get("predicted_bound"),
                "components": r.get("components"),
                "paths": r.get("paths"),
            }
    if div_events:
        profile["divergences"] = [
            {k: e.get(k) for k in ("component", "measured_ms", "floor_ms",
                                   "measured_over_floor", "wall_frac",
                                   "threshold", "step") if k in e}
            for e in div_events]

    return {
        "source": os.path.basename(jsonl_path),
        "meta": {k: v for k, v in meta.items() if k not in ("kind", "ts")},
        "steps_recorded": len(steps),
        "dropped_records": sum(int(rep.get("dropped_records", 0))
                               for rep in reports),
        "step_time_ms": {
            "p50": round(_percentile(walls, 50), 3),
            "p95": round(_percentile(walls, 95), 3),
            "mean": round(sum(walls) / len(walls), 3) if walls else 0.0,
            "n": len(walls),
            "note": "host wall per train_batch: dispatch wall on jitted "
                    "paths, true wall on the host-synchronous offload path",
        },
        "throughput": {
            "samples_per_sec": samples_per_sec,
            "window_valid": samples_per_sec is not None,
        },
        "recompiles": {
            "count": len(recompiles),
            "events": [{"fn": e.get("fn"),
                        "step": e.get("step"),
                        "signature_delta": e.get("signature_delta")}
                       for e in recompiles],
        },
        "memory": memory,
        "wire_bytes_per_step": wire_meta,
        "wire_bytes_consistent": wire_consistent,
        "overflow_steps": overflows,
        "skipped_steps": skipped,
        "offload": offload,
        "mfu": mfu,
        "roofline": roofline,
        "goodput": goodput,
        "serving": serving,
        "serving_slo": serving_slo,
        "moe": moe,
        "health": health,
        "profile": profile,
        "truncated": truncated,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("jsonl", help="telemetry JSONL stream to summarize")
    ap.add_argument("-o", "--output", default="TELEMETRY.json",
                    help="summary output path (default TELEMETRY.json)")
    args = ap.parse_args(argv)
    summary = summarize(args.jsonl)
    with open(args.output, "w") as f:
        json.dump(summary, f, indent=2)
    st = summary["step_time_ms"]
    mfu = summary["mfu"].get("window_mfu") or \
        summary["mfu"].get("per_step_p50")
    gp = summary["goodput"].get("goodput_fraction")
    ck = summary["goodput"].get("checkpoint")
    ck_share = ck["exposed_share"] if isinstance(ck, dict) and \
        ck.get("exposed_s", 0) > 0 else None
    bound = summary["roofline"].get("step_bound")
    srv = summary["serving"]
    hl = summary["health"]
    health_bits = ""
    if hl.get("available"):
        health_bits = (f", anomalies={hl['anomalies']['total']}, "
                       f"watchdog={hl['watchdog_fires']}")
        if hl["hosts"].get("available"):
            health_bits += (f", hosts={hl['hosts']['n_hosts']} "
                            f"(skew={hl['hosts'].get('straggler_skew_rel')})")
    print(f"{args.output}: {summary['steps_recorded']} steps, "
          f"p50={st['p50']}ms p95={st['p95']}ms, "
          f"recompiles={summary['recompiles']['count']}, "
          f"watermarks={summary['memory']['watermark_events']}"
          + (f", mfu={mfu}" if mfu is not None else "")
          + (f", {bound}-bound" if bound else "")
          + (f", goodput={gp:.1%}" if gp is not None else "")
          + (f", ckpt exposed={ck_share:.2%}"
             if ck_share is not None else "")
          + (f", serving: occ={srv['occupancy_mean']}, "
             f"ttft p50={srv['ttft_ms']['p50']}ms"
             if srv.get("available") else "")
          + (f", attend x{srv['attend_work_ratio']} "
             f"({srv['attend']['mode']}, projected)"
             if srv.get("attend_work_ratio") is not None else "")
          + (", slo=" + ",".join(
              f"{lab}:{b['verdict']}" for lab, b in
              summary["serving_slo"]["slo"]["burn"].items())
             if summary["serving_slo"].get("slo") else "")
          + health_bits
          + ((lambda p: f", profiled: {p['reconciliation']['verdict']} "
              f"(dominant={p['reconciliation']['dominant_bucket']}, "
              f"predicted={p['reconciliation']['predicted_bound']})"
              if p.get("reconciliation") else ", profiled")(
                  summary["profile"])
             if summary["profile"].get("available") else "")
          + (" — TRUNCATED segment (no final drain marker): stats "
             "cover a partial run" if summary["truncated"] else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())

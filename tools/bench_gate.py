#!/usr/bin/env python
"""Bench gate: fail CI when MFU or goodput regresses between rounds.

Usage:
    python tools/bench_gate.py                      # latest two BENCH_r*.json
    python tools/bench_gate.py OLD NEW              # explicit files
    python tools/bench_gate.py --mfu-drop 0.10 --goodput-drop 0.05

Accepted file shapes (auto-detected per file):

- a driver round file ``BENCH_r*.json`` (``{"n": .., "parsed": {bench
  record}}``) — MFU comes from the bench record's ``mfu`` field (the
  shared monitor/peaks.py denominator);
- a raw bench record (the JSON line bench.py prints);
- a ``TELEMETRY.json`` from tools/telemetry_report.py — MFU is the
  fenced ``window_mfu`` (per-step p50 as fallback), goodput is the
  ledger's ``goodput_fraction``;
- a ``SERVE_BENCH.json``-shaped serving record (or a serving-mode
  TELEMETRY.json) — serving throughput is generated ``tokens_per_s``,
  serving latency is ``ttft_ms.p95``.

Gate semantics: MFU regresses when it drops by more than ``--mfu-drop``
RELATIVE (default 10%); goodput regresses when the fraction drops by
more than ``--goodput-drop`` ABSOLUTE (default 5 points); serving
tokens/s regresses on a relative drop beyond ``--serve-drop`` (default
10%) and TTFT p95 on a relative RISE beyond ``--ttft-rise`` (default
25% — latency percentiles on a CPU mesh are noisy; the gate catches
step changes, not jitter); the fused-kernel ablation speedup (the
``kernels.fused_speedup`` field a DS_BENCH_KERNELS=1 bench or
the BENCH_r06/r07 projections record) regresses on a relative drop beyond
``--kernel-drop`` (default 10%); the autotuned-tile speedup (the
``kernels.tile_speedup`` field of an autotune ablation record
— geomean of the per-kernel winner-over-heuristic ratios) regresses on
a relative drop beyond ``--tile-drop`` (default 10%), and pre-autotune
rounds skip, never fail; the ZeRO-3 prefetch overlap fraction
(``zero3.overlap_fraction`` from ablate_zero3_prefetch.py's
ZERO3_BENCH.json) regresses on the same relative threshold. Paged-cache
serving rounds additionally gate ``serving.hbm_bytes_per_token`` (p50;
regression = a relative RISE beyond ``--hbm-rise``, default 15%) and
the spec-decode ``serving.spec.acceptance_rate`` (new side must clear
``--accept-floor``, default 0.05, and must not drop more than
``--serve-drop`` relative vs the old side when both carry it) —
pre-paging/pre-spec rounds skip these, never fail. Paged-attention
rounds gate ``serving.attend_work_ratio`` (the analytic one-hot-over-
kernel attend HBM ratio the engine prices per iteration; regression =
a relative DROP beyond ``--attend-drop``, default 10% — the structural
win shrank); pre-kernel rounds skip, never fail. A TELEMETRY.json carrying a ``health``
SLO rounds (a serving record carrying the ``slo`` tracker snapshot, or
a TELEMETRY.json ``serving_slo`` section) gate the SLO attainment
fraction on an ABSOLUTE drop beyond ``--slo-drop`` (default 0.05), and
validate the serving goodput ledger's ``consistent`` verdict on the
NEW side alone — double-attribution (a wall second charged to two
buckets) is a defect to refuse, not a regression to diff; pre-SLO
rounds skip both, never fail. A TELEMETRY.json carrying a ``health``
section is additionally validated on the NEW side alone: UNSKIPPED
non-finite anomalies (overflow-skipped steps are routine fp16
loss-scale mechanics and do not gate), watchdog fires, or a ``truncated`` stream (a segment that
died without its final drain marker) fail the round — those are not
regressions to diff but defects to refuse. MoE rounds (a ``moe``
section in TELEMETRY.json, or MOE_BENCH.json) gate the drop-fraction
p95 on an ABSOLUTE rise beyond ``--moe-drop-rise`` (default 0.05) —
dropped tokens are silently-skipped compute; pre-MoE rounds skip,
never fail. Multislice rounds (a ``multislice`` record in
MULTISLICE_BENCH.json, or a TELEMETRY.json roofline ``comm_tiers``
section) gate DCN bytes/step on a RELATIVE rise beyond ``--dcn-rise``
(default 10%) — the slow tier is the scale-out ceiling; pre-multislice
rounds skip, never fail. Stage-3-across-slices rounds (a ``zero3``
record with ``dcn_bytes_per_step`` in MULTISLICE_BENCH.json, from
``ablate_multislice.py --zero3``) gate the hierarchical schedule's DCN
bytes/step on the same relative rise, and the DCN *param* bytes/step
against a relative ceiling over the planner's structural 0 — any param
byte leaking onto the slow tier fails; pre-composition rounds skip,
never fail. Resilience rounds (a ``checkpoint`` record in
RESILIENCE_BENCH.json from ``tools/crashkill.py bench``, or a
TELEMETRY.json goodput section carrying a ``checkpoint`` sub-dict with
nonzero exposed wall) gate the checkpoint-EXPOSED goodput share on the
NEW side against an ABSOLUTE ceiling (``--ckpt-share-max``, default 5%
— the ISSUE-15 acceptance bar at ``snapshot_every: 50``); background
write wall overlaps training and is not charged. Pre-resilience rounds
skip, never fail. A metric missing on either
side is skipped with a notice, never a failure — rounds recorded before
this tool (or before the serving tier / health layer) existed have no
such field, and the gate must not retroactively break them. Exit 0 =
pass/skip, 1 = regression, 2 = usage error.

Opt-in from CI: ``tools/run_tier1.sh --bench-gate`` (or BENCH_GATE=1).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, Optional, Tuple


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def extract_metrics(doc: Dict[str, Any]) -> Dict[str, Optional[float]]:
    """{"mfu", "goodput", "serve_tps", "ttft_p95", "kernel_speedup"}
    (None when the file doesn't carry one)."""
    # Driver round file: the bench record rides in "parsed".
    if isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    mfu: Optional[float] = None
    goodput: Optional[float] = None
    serve_tps: Optional[float] = None
    ttft_p95: Optional[float] = None
    kernel_speedup: Optional[float] = None
    zero3_overlap: Optional[float] = None
    # ZERO3_BENCH.json (ablate_zero3_prefetch.py): the analytic fraction
    # of the per-layer gather the depth-1 prefetch hides.
    z3 = doc.get("zero3")
    if isinstance(z3, dict) and z3.get("overlap_fraction") is not None:
        zero3_overlap = float(z3["overlap_fraction"])
    # MULTISLICE_BENCH.json's `zero3` record (ablate_multislice.py
    # --zero3): stage-3-across-slices DCN figures under the planner's
    # hierarchical schedule. Two gated numbers: total DCN bytes/step
    # (regression = RISE, same rule as the stage-2 multislice gate) and
    # the PARAM bytes on DCN — structurally zero under the planner, so
    # the relative ceiling over an old value of 0 is 0 and ANY param
    # byte that leaks onto the slow tier fails the round. Pre-
    # composition rounds carry no record -> skipped, never failed.
    z3_dcn_bytes: Optional[float] = None
    z3_dcn_param: Optional[float] = None
    if isinstance(z3, dict) and z3.get("available", True):
        if z3.get("dcn_bytes_per_step") is not None:
            z3_dcn_bytes = float(z3["dcn_bytes_per_step"])
        if z3.get("dcn_param_bytes_per_step") is not None:
            z3_dcn_param = float(z3["dcn_param_bytes_per_step"])
    # DS_BENCH_KERNELS ablation record: the fused-over-unfused step
    # speedup (bench.py bench_kernels_ablation).
    krn = doc.get("kernels")
    if isinstance(krn, dict) and krn.get("fused_speedup") is not None:
        kernel_speedup = float(krn["fused_speedup"])
    # Autotune ablation record: geomean step-level
    # speedup of the autotuned tiles over the static heuristics.
    # Pre-autotune rounds carry no field -> skipped, never failed.
    tile_speedup: Optional[float] = None
    if isinstance(krn, dict) and krn.get("tile_speedup") is not None:
        tile_speedup = float(krn["tile_speedup"])
    # TELEMETRY.json shape: structured mfu/goodput sections.
    if isinstance(doc.get("mfu"), dict):
        sec = doc["mfu"]
        v = sec.get("window_mfu", sec.get("per_step_p50"))
        mfu = float(v) if v is not None else None
    elif isinstance(doc.get("mfu"), (int, float)):
        # Bench record shape: flat fraction-of-peak field.
        mfu = float(doc["mfu"])
    if isinstance(doc.get("goodput"), dict):
        v = doc["goodput"].get("goodput_fraction")
        goodput = float(v) if v is not None else None
    # Serving shape: SERVE_BENCH.json's "serving" record, or a
    # serving-mode TELEMETRY.json's "serving" section (same keys).
    hbm_per_token: Optional[float] = None
    accept_rate: Optional[float] = None
    attend_ratio: Optional[float] = None
    srv = doc.get("serving")
    if isinstance(srv, dict) and (srv.get("available", True)):
        v = srv.get("tokens_per_s")
        serve_tps = float(v) if v is not None else None
        ttft = srv.get("ttft_ms")
        if isinstance(ttft, dict) and ttft.get("p95") is not None:
            ttft_p95 = float(ttft["p95"])
        # Paged-cache rounds: HBM held per cached token (regression =
        # RISE) and the spec-decode acceptance rate (regression = drop
        # below the floor or vs the previous round). Pre-paging rounds
        # carry neither -> skipped, never failed.
        hbm = srv.get("hbm_bytes_per_token")
        if isinstance(hbm, dict) and hbm.get("p50") is not None:
            hbm_per_token = float(hbm["p50"])
        spec = srv.get("spec")
        if isinstance(spec, dict) and \
                spec.get("acceptance_rate") is not None:
            accept_rate = float(spec["acceptance_rate"])
        # Paged-attention rounds: the analytic kernel-vs-one-hot
        # attend-work ratio (one-hot pool-capacity HBM bytes over the
        # kernel's live-context bytes, same iterations — regression =
        # DROP: the structural win shrank). Pre-kernel rounds carry no
        # field -> skipped, never failed.
        if srv.get("attend_work_ratio") is not None:
            attend_ratio = float(srv["attend_work_ratio"])
    # Serving SLO shape: SERVE_BENCH.json's serving record carries the
    # pooled SLO tracker snapshot ("slo") and the serving goodput
    # ledger ("ledger"); a TELEMETRY.json carries the same figures in
    # its "serving_slo" section. Gated: SLO attainment (ABSOLUTE drop)
    # plus the ledger `consistent` verdict validated on the NEW side
    # alone (double-attribution is a defect, not a diff). Pre-SLO
    # rounds carry neither -> skipped, never failed.
    slo_attainment: Optional[float] = None
    ledger_consistent: Optional[bool] = None
    if isinstance(srv, dict):
        sslo = srv.get("slo")
        if isinstance(sslo, dict) and sslo.get("attainment") is not None:
            slo_attainment = float(sslo["attainment"])
        sled = srv.get("ledger")
        if isinstance(sled, dict) and "consistent" in sled:
            ledger_consistent = bool(sled["consistent"])
    ssec = doc.get("serving_slo")
    if isinstance(ssec, dict) and ssec.get("available", True):
        tslo = ssec.get("slo")
        if slo_attainment is None and isinstance(tslo, dict):
            atts = [b["attainment"] for b in
                    (tslo.get("burn") or {}).values()
                    if b.get("attainment") is not None]
            if atts:
                slo_attainment = min(float(a) for a in atts)
        tled = ssec.get("ledger")
        if ledger_consistent is None and isinstance(tled, dict) \
                and "consistent" in tled:
            ledger_consistent = bool(tled["consistent"])
    # MoE shape: a TELEMETRY.json `moe` section or an MOE_BENCH.json
    # record — the gated figure is the drop-fraction p95 (regression =
    # an ABSOLUTE rise: dropped tokens are silently-skipped compute).
    # Pre-MoE rounds carry no section -> skipped, never failed.
    moe_drop: Optional[float] = None
    msec = doc.get("moe")
    if isinstance(msec, dict) and msec.get("available", True):
        df = msec.get("drop_fraction")
        if isinstance(df, dict) and df.get("p95") is not None:
            moe_drop = float(df["p95"])
        elif isinstance(df, (int, float)):
            moe_drop = float(df)
    # Multislice shape: MULTISLICE_BENCH.json's `multislice` record, or
    # a TELEMETRY.json roofline's `comm_tiers` section — the gated
    # figure is DCN bytes/step (regression = a RISE: the slow tier is
    # the scale-out ceiling, and a change that silently moves more
    # bytes over DCN eats it). Pre-multislice rounds carry neither ->
    # skipped, never failed.
    dcn_bytes: Optional[float] = None
    msl = doc.get("multislice")
    if isinstance(msl, dict) and msl.get("available", True) and \
            msl.get("dcn_bytes_per_step") is not None:
        dcn_bytes = float(msl["dcn_bytes_per_step"])
    elif isinstance(doc.get("roofline"), dict):
        tiers = doc["roofline"].get("comm_tiers")
        if isinstance(tiers, dict) and \
                tiers.get("wire_bytes_dcn") is not None:
            dcn_bytes = float(tiers["wire_bytes_dcn"])
    # Resilience shape: RESILIENCE_BENCH.json's top-level `checkpoint`
    # record (tools/crashkill.py bench), or a TELEMETRY.json goodput
    # section's `checkpoint` sub-dict — the gated figure is the
    # checkpoint-EXPOSED goodput share (background write wall overlaps
    # and is free). Validated on the NEW side alone against an absolute
    # ceiling; pre-resilience rounds carry neither -> skipped, never
    # failed.
    ckpt_share: Optional[float] = None
    ckpt_every: Optional[int] = None
    cksec = doc.get("checkpoint")
    if not (isinstance(cksec, dict) and
            cksec.get("exposed_share") is not None) and \
            isinstance(doc.get("goodput"), dict):
        cksec = doc["goodput"].get("checkpoint")
    if isinstance(cksec, dict) and cksec.get("exposed_share") is not None \
            and float(cksec.get("exposed_s", 1.0)) > 0.0:
        ckpt_share = float(cksec["exposed_share"])
        if cksec.get("snapshot_every"):
            ckpt_every = int(cksec["snapshot_every"])
    # Health-layer TELEMETRY.json shape: validated (new side only), not
    # diffed. Pre-health rounds carry no section -> None -> skipped.
    health: Optional[Dict[str, Any]] = None
    hl = doc.get("health")
    if isinstance(hl, dict):
        anom = hl.get("anomalies") or {}
        # Gate on UNSKIPPED non-finite events only: overflow-skipped
        # steps are routine fp16 dynamic-loss-scale mechanics (a healthy
        # fp16 round backs its scale off without being a defect).
        health = {
            "truncated": bool(doc.get("truncated")
                              or hl.get("truncated")),
            "watchdog_fires": int(hl.get("watchdog_fires") or 0),
            "nonfinite": int(anom.get("nonfinite_unskipped",
                                      anom.get("nonfinite")) or 0),
        }
    return {"mfu": mfu, "goodput": goodput, "serve_tps": serve_tps,
            "ttft_p95": ttft_p95, "kernel_speedup": kernel_speedup,
            "tile_speedup": tile_speedup,
            "zero3_overlap": zero3_overlap, "health": health,
            "z3_dcn_bytes": z3_dcn_bytes, "z3_dcn_param": z3_dcn_param,
            "hbm_per_token": hbm_per_token, "accept_rate": accept_rate,
            "attend_ratio": attend_ratio,
            "slo_attainment": slo_attainment,
            "ledger_consistent": ledger_consistent,
            "moe_drop": moe_drop, "dcn_bytes": dcn_bytes,
            "ckpt_share": ckpt_share, "ckpt_every": ckpt_every}


# Measurement-label ranks for the trace-truth ratchet (tools/
# tpu_truth.py): "projected" = analytic model only; "cpu-structural" =
# the identical pipeline ran end-to-end on a CPU mesh (structure
# verified, magnitudes not TPU); "measured" = a real TPU trace backs the
# number. Moving DOWN from "measured" is a regression.
LABEL_RANK = {"projected": 0, "cpu-structural": 1, "measured": 2}


def extract_labels(doc: Dict[str, Any]
                   ) -> Optional[Dict[str, Dict[str, Any]]]:
    """{artifact_name: {"label", "reconciled"}} from a TRUTH.json-style
    doc (``artifacts`` map), a bench doc carrying a ``labels`` map, or a
    single-artifact doc with a top-level ``label``. None = the doc
    predates the truth campaign (ratchet skips, never fails)."""
    arts = doc.get("artifacts")
    if not isinstance(arts, dict):
        d = doc.get("parsed") if isinstance(doc.get("parsed"), dict) \
            else doc
        arts = d.get("labels")
        if not isinstance(arts, dict):
            if isinstance(d.get("label"), str):
                arts = {str(d.get("artifact", "bench")): d}
            else:
                return None
    out: Dict[str, Dict[str, Any]] = {}
    for name, rec in arts.items():
        if not isinstance(rec, dict) or not isinstance(rec.get("label"),
                                                       str):
            continue
        out[name] = {
            "label": rec["label"],
            "reconciled": isinstance(rec.get("reconciliation"), dict),
        }
    return out or None


def label_ratchet(old_doc: Dict[str, Any], new_doc: Dict[str, Any]
                  ) -> Optional[List[str]]:
    """The measured-stays-measured ratchet. Returns None when either
    side predates the truth campaign (skip); otherwise the list of
    ratchet violations (empty = OK): an artifact labeled ``measured``
    in the old round that is missing, downgraded, or stripped of its
    reconciliation section in the new round."""
    old_labels = extract_labels(old_doc)
    new_labels = extract_labels(new_doc)
    if old_labels is None or new_labels is None:
        return None
    failures: List[str] = []
    for name, o in sorted(old_labels.items()):
        o_rank = LABEL_RANK.get(o["label"], 0)
        n = new_labels.get(name)
        if o_rank >= LABEL_RANK["measured"]:
            if n is None:
                failures.append(
                    f"{name}: measured artifact dropped from the round")
                continue
            n_rank = LABEL_RANK.get(n["label"], 0)
            if n_rank < o_rank:
                failures.append(
                    f"{name}: label regressed measured -> "
                    f"{n['label']!r}")
        if o["reconciled"] and n is not None and not n["reconciled"]:
            failures.append(
                f"{name}: reconciliation section present in the old "
                f"round, dropped in the new")
    return failures


def _round_key(path: str) -> Tuple[int, str]:
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    return (int(m.group(1)) if m else -1, path)


def latest_rounds(directory: str) -> Optional[Tuple[str, str]]:
    """The previous and latest BENCH_r*.json in ``directory`` (round
    number order), or None when fewer than two exist."""
    rounds = sorted(glob.glob(os.path.join(directory, "BENCH_r*.json")),
                    key=_round_key)
    # Side files like BENCH_r09_builder.json are not rounds.
    rounds = [p for p in rounds
              if re.fullmatch(r"BENCH_r\d+\.json", os.path.basename(p))]
    if len(rounds) < 2:
        return None
    return rounds[-2], rounds[-1]


def gate(old_path: str, new_path: str, mfu_drop: float,
         goodput_drop: float, serve_drop: float = 0.10,
         ttft_rise: float = 0.25, kernel_drop: float = 0.10,
         hbm_rise: float = 0.15, accept_floor: float = 0.05,
         moe_drop_rise: float = 0.05, dcn_rise: float = 0.10,
         ckpt_share_max: float = 0.05, tile_drop: float = 0.10,
         attend_drop: float = 0.10, slo_drop: float = 0.05) -> int:
    old = extract_metrics(_load(old_path))
    new = extract_metrics(_load(new_path))
    name_old, name_new = os.path.basename(old_path), \
        os.path.basename(new_path)
    rc = 0
    compared = 0

    if old["mfu"] is not None and new["mfu"] is not None:
        compared += 1
        floor = old["mfu"] * (1.0 - mfu_drop)
        verdict = "OK" if new["mfu"] >= floor else "REGRESSION"
        print(f"mfu: {name_old}={old['mfu']:.4g} -> "
              f"{name_new}={new['mfu']:.4g} "
              f"(floor {floor:.4g}, -{mfu_drop:.0%} rel): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["mfu"] is None]
        print(f"mfu: skipped (no mfu field in {', '.join(missing)})")

    if old["goodput"] is not None and new["goodput"] is not None:
        compared += 1
        floor = old["goodput"] - goodput_drop
        verdict = "OK" if new["goodput"] >= floor else "REGRESSION"
        print(f"goodput: {name_old}={old['goodput']:.4f} -> "
              f"{name_new}={new['goodput']:.4f} "
              f"(floor {floor:.4f}, -{goodput_drop:.2f} abs): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["goodput"] is None]
        print(f"goodput: skipped (no goodput section in "
              f"{', '.join(missing)})")

    if old["serve_tps"] is not None and new["serve_tps"] is not None:
        compared += 1
        floor = old["serve_tps"] * (1.0 - serve_drop)
        verdict = "OK" if new["serve_tps"] >= floor else "REGRESSION"
        print(f"serving tokens/s: {name_old}={old['serve_tps']:.4g} -> "
              f"{name_new}={new['serve_tps']:.4g} "
              f"(floor {floor:.4g}, -{serve_drop:.0%} rel): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        # Pre-serving rounds skip, never fail.
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["serve_tps"] is None]
        print(f"serving tokens/s: skipped (no serving section in "
              f"{', '.join(missing)})")

    if old["ttft_p95"] is not None and new["ttft_p95"] is not None:
        compared += 1
        ceil = old["ttft_p95"] * (1.0 + ttft_rise)
        verdict = "OK" if new["ttft_p95"] <= ceil else "REGRESSION"
        print(f"serving ttft p95: {name_old}={old['ttft_p95']:.4g}ms -> "
              f"{name_new}={new['ttft_p95']:.4g}ms "
              f"(ceiling {ceil:.4g}ms, +{ttft_rise:.0%} rel): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["ttft_p95"] is None]
        print(f"serving ttft p95: skipped (no serving section in "
              f"{', '.join(missing)})")

    if old["kernel_speedup"] is not None and \
            new["kernel_speedup"] is not None:
        compared += 1
        floor = old["kernel_speedup"] * (1.0 - kernel_drop)
        verdict = "OK" if new["kernel_speedup"] >= floor else "REGRESSION"
        print(f"kernel fused speedup: {name_old}="
              f"{old['kernel_speedup']:.4g}x -> "
              f"{name_new}={new['kernel_speedup']:.4g}x "
              f"(floor {floor:.4g}x, -{kernel_drop:.0%} rel): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        # Pre-kernel-ablation rounds skip, never fail.
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["kernel_speedup"] is None]
        print(f"kernel fused speedup: skipped (no kernels record in "
              f"{', '.join(missing)})")

    if old["tile_speedup"] is not None and \
            new["tile_speedup"] is not None:
        compared += 1
        floor = old["tile_speedup"] * (1.0 - tile_drop)
        verdict = "OK" if new["tile_speedup"] >= floor else "REGRESSION"
        print(f"autotune tile speedup: {name_old}="
              f"{old['tile_speedup']:.4g}x -> "
              f"{name_new}={new['tile_speedup']:.4g}x "
              f"(floor {floor:.4g}x, -{tile_drop:.0%} rel): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        # Pre-autotune rounds skip, never fail.
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["tile_speedup"] is None]
        print(f"autotune tile speedup: skipped (no tile record in "
              f"{', '.join(missing)})")

    if old["attend_ratio"] is not None and \
            new["attend_ratio"] is not None:
        compared += 1
        floor = old["attend_ratio"] * (1.0 - attend_drop)
        verdict = "OK" if new["attend_ratio"] >= floor else "REGRESSION"
        print(f"serving attend work ratio: {name_old}="
              f"{old['attend_ratio']:.4g}x -> "
              f"{name_new}={new['attend_ratio']:.4g}x "
              f"(floor {floor:.4g}x, -{attend_drop:.0%} rel): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        # Pre-paged-kernel rounds skip, never fail.
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["attend_ratio"] is None]
        print(f"serving attend work ratio: skipped (no attend record in "
              f"{', '.join(missing)})")

    if old["hbm_per_token"] is not None and \
            new["hbm_per_token"] is not None:
        compared += 1
        ceil = old["hbm_per_token"] * (1.0 + hbm_rise)
        verdict = "OK" if new["hbm_per_token"] <= ceil else "REGRESSION"
        print(f"serving hbm bytes/token: {name_old}="
              f"{old['hbm_per_token']:.4g}B -> "
              f"{name_new}={new['hbm_per_token']:.4g}B "
              f"(ceiling {ceil:.4g}B, +{hbm_rise:.0%} rel): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        # Pre-paging rounds skip, never fail.
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["hbm_per_token"] is None]
        print(f"serving hbm bytes/token: skipped (no paged-cache "
              f"record in {', '.join(missing)})")

    if new["accept_rate"] is not None:
        compared += 1
        bad = []
        if new["accept_rate"] < accept_floor:
            bad.append(f"below floor {accept_floor:.2f}")
        if old["accept_rate"] is not None:
            drop_floor = old["accept_rate"] * (1.0 - serve_drop)
            if new["accept_rate"] < drop_floor:
                bad.append(f"dropped >{serve_drop:.0%} rel vs "
                           f"{old['accept_rate']:.4g}")
        verdict = "OK" if not bad else "REGRESSION"
        print(f"spec-decode acceptance: {name_new}="
              f"{new['accept_rate']:.4g}"
              + (f" (prev {old['accept_rate']:.4g})"
                 if old["accept_rate"] is not None else "")
              + f": {'; '.join(bad) if bad else 'above floor'}"
              f": {verdict}")
        if bad:
            rc = 1
    else:
        # Pre-spec-decode rounds skip, never fail.
        print(f"spec-decode acceptance: skipped (no spec record in "
              f"{name_new})")

    if old["slo_attainment"] is not None and \
            new["slo_attainment"] is not None:
        compared += 1
        floor = old["slo_attainment"] - slo_drop
        verdict = "OK" if new["slo_attainment"] >= floor else "REGRESSION"
        print(f"serving slo attainment: {name_old}="
              f"{old['slo_attainment']:.4f} -> "
              f"{name_new}={new['slo_attainment']:.4f} "
              f"(floor {floor:.4f}, -{slo_drop:.2f} abs): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        # Pre-SLO rounds (no inference.slo target configured, or
        # recorded before the SLO tracker existed) skip, never fail.
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["slo_attainment"] is None]
        print(f"serving slo attainment: skipped (no slo record in "
              f"{', '.join(missing)} — pre-SLO round)")

    # Serving-ledger consistency: NEW side only (a defect to refuse,
    # not a regression to diff) — `consistent: false` means some wall
    # second was attributed to two buckets at once, and every share the
    # ledger reports is suspect. Pre-ledger rounds skip, never fail.
    if new["ledger_consistent"] is not None:
        compared += 1
        verdict = "OK" if new["ledger_consistent"] else "FAIL"
        print(f"serving ledger consistency: {name_new}: "
              + ("buckets sum to wall (no double-attribution)"
                 if new["ledger_consistent"] else
                 "double-attribution detected (buckets overlap)")
              + f": {verdict}")
        if not new["ledger_consistent"]:
            rc = 1
    else:
        print(f"serving ledger consistency: skipped (no ledger record "
              f"in {name_new} — pre-ledger round)")

    if old["zero3_overlap"] is not None and \
            new["zero3_overlap"] is not None:
        compared += 1
        floor = old["zero3_overlap"] * (1.0 - kernel_drop)
        verdict = "OK" if new["zero3_overlap"] >= floor else "REGRESSION"
        print(f"zero3 prefetch overlap: {name_old}="
              f"{old['zero3_overlap']:.4g} -> "
              f"{name_new}={new['zero3_overlap']:.4g} "
              f"(floor {floor:.4g}, -{kernel_drop:.0%} rel): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        # Pre-ZeRO-3 rounds skip, never fail.
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["zero3_overlap"] is None]
        print(f"zero3 prefetch overlap: skipped (no zero3 record in "
              f"{', '.join(missing)})")

    if old["dcn_bytes"] is not None and new["dcn_bytes"] is not None:
        compared += 1
        ceil = old["dcn_bytes"] * (1.0 + dcn_rise)
        verdict = "OK" if new["dcn_bytes"] <= ceil else "REGRESSION"
        print(f"multislice dcn bytes/step: {name_old}="
              f"{old['dcn_bytes']:.4g}B -> "
              f"{name_new}={new['dcn_bytes']:.4g}B "
              f"(ceiling {ceil:.4g}B, +{dcn_rise:.0%} rel): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        # Pre-multislice rounds skip, never fail.
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["dcn_bytes"] is None]
        print(f"multislice dcn bytes/step: skipped (no multislice "
              f"record in {', '.join(missing)})")

    if old["z3_dcn_bytes"] is not None and \
            new["z3_dcn_bytes"] is not None:
        compared += 1
        ceil = old["z3_dcn_bytes"] * (1.0 + dcn_rise)
        verdict = "OK" if new["z3_dcn_bytes"] <= ceil else "REGRESSION"
        print(f"zero3 multislice dcn bytes/step: {name_old}="
              f"{old['z3_dcn_bytes']:.4g}B -> "
              f"{name_new}={new['z3_dcn_bytes']:.4g}B "
              f"(ceiling {ceil:.4g}B, +{dcn_rise:.0%} rel): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        # Pre-composition (stage-3 x slices) rounds skip, never fail.
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["z3_dcn_bytes"] is None]
        print(f"zero3 multislice dcn bytes/step: skipped (no zero3 "
              f"record in {', '.join(missing)})")

    if old["z3_dcn_param"] is not None and \
            new["z3_dcn_param"] is not None:
        compared += 1
        # Relative ceiling over the planner's structural 0 is 0: a
        # single param byte leaking onto DCN fails the round.
        ceil = old["z3_dcn_param"] * (1.0 + dcn_rise)
        verdict = "OK" if new["z3_dcn_param"] <= ceil else "REGRESSION"
        print(f"zero3 multislice dcn PARAM bytes/step: {name_old}="
              f"{old['z3_dcn_param']:.4g}B -> "
              f"{name_new}={new['z3_dcn_param']:.4g}B "
              f"(ceiling {ceil:.4g}B, +{dcn_rise:.0%} rel): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["z3_dcn_param"] is None]
        print(f"zero3 multislice dcn PARAM bytes/step: skipped (no "
              f"zero3 record in {', '.join(missing)})")

    if old["moe_drop"] is not None and new["moe_drop"] is not None:
        compared += 1
        ceil = old["moe_drop"] + moe_drop_rise
        verdict = "OK" if new["moe_drop"] <= ceil else "REGRESSION"
        print(f"moe drop fraction p95: {name_old}={old['moe_drop']:.4f} "
              f"-> {name_new}={new['moe_drop']:.4f} "
              f"(ceiling {ceil:.4f}, +{moe_drop_rise:.2f} abs): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        # Pre-MoE rounds skip, never fail.
        missing = [n for n, m in ((name_old, old), (name_new, new))
                   if m["moe_drop"] is None]
        print(f"moe drop fraction: skipped (no moe record in "
              f"{', '.join(missing)})")

    # Checkpoint-exposed goodput share: NEW side only, against an
    # ABSOLUTE ceiling — a checkpointing run that pays more than
    # ckpt_share_max of its wall in exposed checkpoint time has lost
    # the async overlap (the resilience subsystem's whole point).
    # Pre-resilience rounds carry no checkpoint record -> skip, never
    # fail.
    if new["ckpt_share"] is not None:
        compared += 1
        cadence = (f" at snapshot_every={new['ckpt_every']}"
                   if new["ckpt_every"] else "")
        verdict = "OK" if new["ckpt_share"] <= ckpt_share_max \
            else "REGRESSION"
        print(f"checkpoint exposed share: {name_new}="
              f"{new['ckpt_share']:.4%}{cadence} "
              f"(ceiling {ckpt_share_max:.0%} abs): {verdict}")
        if verdict != "OK":
            rc = 1
    else:
        print(f"checkpoint exposed share: skipped (no checkpoint "
              f"record in {name_new} — pre-resilience round)")

    # Health validation: NEW side only (defects, not diffs). Pre-health
    # rounds skip, never fail.
    nh = new.get("health")
    if nh is not None:
        compared += 1
        bad = []
        if nh["truncated"]:
            bad.append("stream truncated (no final drain marker)")
        if nh["watchdog_fires"] > 0:
            bad.append(f"{nh['watchdog_fires']} hang-watchdog fire(s)")
        if nh["nonfinite"] > 0:
            bad.append(f"{nh['nonfinite']} unskipped non-finite "
                       f"anomaly event(s)")
        verdict = "OK" if not bad else "FAIL"
        print(f"health: {name_new}: "
              + ("; ".join(bad) if bad else
                 "no non-finite anomalies, no watchdog fires, "
                 "final marker present")
              + f": {verdict}")
        if bad:
            rc = 1
    else:
        print(f"health: skipped (no health section in {name_new} — "
              "pre-health round)")

    # Trace-truth label ratchet: an artifact that earned its "measured"
    # label (a real TPU trace backs the number) must keep it — a round
    # regressing it to "projected"/"cpu-structural", dropping it, or
    # stripping its reconciliation section FAILS. Pre-truth rounds
    # (no labels either side) skip, never fail.
    ratchet = label_ratchet(_load(old_path), _load(new_path))
    if ratchet is None:
        print("label ratchet: skipped (no measurement labels in "
              f"{name_old} and/or {name_new} — pre-truth rounds)")
    else:
        compared += 1
        verdict = "OK" if not ratchet else "REGRESSION"
        print(f"label ratchet: {name_old} -> {name_new}: "
              + ("; ".join(ratchet) if ratchet
                 else "measured labels and reconciliation sections "
                      "preserved")
              + f": {verdict}")
        if ratchet:
            rc = 1

    if compared == 0:
        print("bench_gate: nothing comparable between the two files "
              "(pre-MFU / pre-serving rounds?) — passing")
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*",
                    help="OLD NEW (default: latest two BENCH_r*.json)")
    ap.add_argument("--dir", default=".",
                    help="where to glob BENCH_r*.json (default .)")
    ap.add_argument("--mfu-drop", type=float, default=0.10,
                    help="max tolerated RELATIVE MFU drop (default 0.10)")
    ap.add_argument("--goodput-drop", type=float, default=0.05,
                    help="max tolerated ABSOLUTE goodput-fraction drop "
                         "(default 0.05)")
    ap.add_argument("--serve-drop", type=float, default=0.10,
                    help="max tolerated RELATIVE serving tokens/s drop "
                         "(default 0.10)")
    ap.add_argument("--ttft-rise", type=float, default=0.25,
                    help="max tolerated RELATIVE TTFT p95 rise "
                         "(default 0.25)")
    ap.add_argument("--kernel-drop", type=float, default=0.10,
                    help="max tolerated RELATIVE drop of the fused-"
                         "kernel speedup (default 0.10)")
    ap.add_argument("--tile-drop", type=float, default=0.10,
                    help="max tolerated RELATIVE drop of the autotuned-"
                         "tile speedup vs heuristics (default 0.10)")
    ap.add_argument("--attend-drop", type=float, default=0.10,
                    help="max tolerated RELATIVE drop of the serving "
                         "kernel-vs-one-hot attend-work ratio "
                         "(default 0.10)")
    ap.add_argument("--hbm-rise", type=float, default=0.15,
                    help="max tolerated RELATIVE rise of serving HBM "
                         "bytes per cached token (default 0.15)")
    ap.add_argument("--accept-floor", type=float, default=0.05,
                    help="spec-decode acceptance-rate floor on the new "
                         "side (default 0.05)")
    ap.add_argument("--moe-drop-rise", type=float, default=0.05,
                    help="max tolerated ABSOLUTE rise of the MoE "
                         "drop-fraction p95 (default 0.05)")
    ap.add_argument("--dcn-rise", type=float, default=0.10,
                    help="max tolerated RELATIVE rise of multislice "
                         "DCN bytes/step (default 0.10)")
    ap.add_argument("--ckpt-share-max", type=float, default=0.05,
                    help="ABSOLUTE ceiling on the checkpoint-exposed "
                         "goodput share, new side (default 0.05)")
    ap.add_argument("--slo-drop", type=float, default=0.05,
                    help="max tolerated ABSOLUTE serving SLO-attainment "
                         "drop (default 0.05)")
    args = ap.parse_args(argv)
    if len(args.files) == 2:
        old_path, new_path = args.files
    elif not args.files:
        pair = latest_rounds(args.dir)
        if pair is None:
            print("bench_gate: fewer than two BENCH_r*.json rounds in "
                  f"{args.dir!r} — nothing to gate, passing")
            return 0
        old_path, new_path = pair
    else:
        ap.error("pass exactly two files, or none for auto-discovery")
        return 2
    try:
        return gate(old_path, new_path, args.mfu_drop, args.goodput_drop,
                    args.serve_drop, args.ttft_rise, args.kernel_drop,
                    args.hbm_rise, args.accept_floor, args.moe_drop_rise,
                    args.dcn_rise, args.ckpt_share_max,
                    tile_drop=args.tile_drop,
                    attend_drop=args.attend_drop,
                    slo_drop=args.slo_drop)
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench_gate: cannot read inputs: {e}")
        return 2


if __name__ == "__main__":
    sys.exit(main())

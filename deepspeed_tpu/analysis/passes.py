"""The lint passes: five static checks over one compiled program.

Each pass is a pure function ``(LintContext) -> List[LintFinding]`` over
host-side artifacts only (the program's jaxpr and its optimized-HLO
text) — no step executes, no device fence is issued. The catalog:

- ``materialization`` — an HLO intermediate whose buffer exceeds a
  configurable fraction of the declared (sharded, per-device) state
  bytes: the "XLA materialized what the sharding said it wouldn't"
  gate ZeRO-3 depends on, and the generalization of COMM_AUDIT.json's
  ``fused_chunk_gather`` finding.
- ``dtype_flow`` — ``convert_element_type`` round-trips in the jaxpr
  (a value upcast to a wider float whose widened form feeds ONLY the
  converts back down): pure HBM waste on the hot path, the cast class
  ROADMAP item 2 targets.
- ``donation`` — declared ``donate_argnums`` diffed against the compiled
  module's input/output alias table: a donated-but-unaliased buffer
  stays live across the call and silently doubles its share of the
  memory watermark.
- ``host_sync`` — ``pure_callback``/``debug_callback``/``io_callback``
  primitives and host-transfer HLO (callback custom-calls, infeed/
  outfeed) inside a compiled step fn: each is a host round-trip that
  stalls the async dispatch pipeline; this is the compile-time
  complement of the runtime ``device_sync_count`` fence counter.
- ``collective_placement`` — the compiled gradient-sync collectives
  diffed against the engine's DECLARED grad-sync mode: grads
  materializing unpartitioned via all-reduce under declared ZeRO-2
  sharding, reduce-scatters hoisted out of (or all-reduces trapped
  inside) the gas scan, or a declared reduce-scatter that emits none.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from . import hlo_text
from .findings import LintConfig, LintContext, LintFinding

# ------------------------------------------------------------------ #
# 1. materialization
# ------------------------------------------------------------------ #
# Opcodes that never allocate a fresh buffer of their shape (views,
# tuple plumbing) or that ARE the declared inputs.
_NO_ALLOC_OPS = frozenset({
    "parameter", "get-tuple-element", "tuple", "bitcast",
    "bitcast-convert",
})


def materialization_pass(ctx: LintContext) -> List[LintFinding]:
    declared = int(ctx.meta.get("declared_state_bytes") or 0)
    if declared <= 0:
        return []
    # A buffer the size of ONE full (unsharded) leaf is inherent to any
    # lowering (a per-micro-batch gradient before its scatter, a ZeRO-3
    # per-layer gather) — the invariant this pass guards is TREE-scale
    # materialization, so the largest single leaf is exempt. Stage-3
    # engines additionally budget their declared gather working set
    # (``zero3_gather_bytes``: the compute-dtype leaf-at-use gathers, or
    # prefetch_depth+1 layers on the scan path) — peak live buffers must
    # stay under declared per-device state + that bound, NEVER the full
    # fp32 master tree (the stage-3 correctness gate; a concat of
    # gathered leaves into one tree-scale buffer still fires). Paged
    # serving engines running the ONE-HOT attend similarly budget their
    # fp32 score transient (``paged_score_bytes``: [G, Q, K, nH, B, bs]
    # per layer — it scales with pool capacity, so pool growth alone
    # must not blow the watermark); a full-pool K/V GATHER is head_dim
    # times bigger and still fires. Kernel-on engines declare 0 — the
    # transient must not exist at all.
    thresh = max(int(ctx.config.materialize_floor_bytes),
                 int(ctx.config.materialize_fraction * declared)
                 + int(ctx.meta.get("zero3_gather_bytes") or 0)
                 + int(ctx.meta.get("paged_score_bytes") or 0),
                 int(ctx.meta.get("largest_leaf_bytes") or 0))
    # Aggregate by largest-buffer SHAPE: one oversized buffer flows
    # through many opcodes (broadcast -> fusion -> copy -> ...); the
    # shape is the stable identity a waiver can pin, the opcode list is
    # detail. Instruction names are compile-run noise and never used.
    agg: Dict[str, Dict[str, Any]] = {}
    for ins in hlo_text.iter_instructions(ctx.hlo_text):
        op = ins.opcode[:-6] if ins.opcode.endswith("-start") else ins.opcode
        if op in _NO_ALLOC_OPS:
            continue
        nbytes, shapes = hlo_text.parse_shape_bytes(ins.shape_str,
                                                    largest_only=True)
        if nbytes <= thresh:
            continue
        shape = max(shapes, key=lambda s: hlo_text.parse_shape_bytes(s)[0]) \
            if shapes else ins.shape_str
        rec = agg.setdefault(shape, {
            "bytes": nbytes, "count": 0, "in_loop": False, "op_name": "",
            "opcodes": set()})
        rec["count"] += 1
        rec["opcodes"].add(op)
        rec["in_loop"] = rec["in_loop"] or ins.in_loop
        if not rec["op_name"] and ins.op_name:
            rec["op_name"] = ins.op_name
    out: List[LintFinding] = []
    for shape, rec in sorted(agg.items(), key=lambda kv: -kv[1]["bytes"]):
        out.append(LintFinding(
            lint="materialization", path=ctx.name, key=shape,
            summary=(f"{shape} materialized ({rec['bytes']:,} B, "
                     f"{rec['count']} instruction(s): "
                     f"{', '.join(sorted(rec['opcodes']))}) — "
                     f"{rec['bytes'] / declared:.1f}x the declared "
                     f"per-device state ({declared:,} B)"),
            bytes=rec["bytes"], priced=False, in_loop=rec["in_loop"],
            count=rec["count"],
            details={"opcodes": sorted(rec["opcodes"]), "shape": shape,
                     "declared_state_bytes": declared,
                     "threshold_bytes": thresh,
                     "op_name": rec["op_name"]}))
    return out


# ------------------------------------------------------------------ #
# 2. dtype_flow
# ------------------------------------------------------------------ #
def _subjaxprs(eqn) -> List[Any]:
    """Inner jaxprs of a higher-order eqn (scan/while/cond/pjit/...)."""
    subs: List[Any] = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for x in vs:
            j = getattr(x, "jaxpr", None)     # ClosedJaxpr
            if j is not None and hasattr(j, "eqns"):
                subs.append(j)
            elif hasattr(x, "eqns"):          # open Jaxpr
                subs.append(x)
    return subs


def _is_float(dtype) -> bool:
    # NOT dtype.kind: the ml_dtypes extension floats (bfloat16, f8) have
    # kind 'V', and bf16 is precisely the dtype this pass exists for.
    try:
        import jax.numpy as jnp
        return bool(jnp.issubdtype(dtype, jnp.floating))
    except Exception:   # pragma: no cover - jax-less use
        return getattr(dtype, "kind", "") == "f"


def dtype_flow_pass(ctx: LintContext) -> List[LintFinding]:
    findings: Dict[str, LintFinding] = {}

    def walk(jaxpr, in_loop: bool) -> None:
        uses: Dict[Any, List[Any]] = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                # Vars (hashable, carry .count) index the use map;
                # Literals are unhashable constants — never a cast chain.
                if hasattr(v, "aval") and hasattr(v, "count"):
                    uses.setdefault(v, []).append(eqn)
        outvars = {v for v in jaxpr.outvars if hasattr(v, "count")}
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in ("scan", "while", "cond"):
                for sub in _subjaxprs(eqn):
                    walk(sub, True)
                continue
            if prim not in ("convert_element_type",):
                for sub in _subjaxprs(eqn):
                    walk(sub, in_loop)
                continue
            src = eqn.invars[0]
            if not hasattr(src, "aval"):      # literal operand
                continue
            src_dt, dst_dt = src.aval.dtype, eqn.outvars[0].aval.dtype
            if not (_is_float(src_dt) and _is_float(dst_dt)):
                continue
            if dst_dt.itemsize <= src_dt.itemsize:
                continue                      # only upcasts start a trip
            wide = eqn.outvars[0]
            if wide in outvars:
                continue                      # the widened value escapes
            consumers = uses.get(wide, [])
            if not consumers:
                continue
            if not all(c.primitive.name == "convert_element_type" and
                       c.outvars[0].aval.dtype == src_dt
                       for c in consumers):
                continue                      # widened form does real work
            aval = wide.aval
            nbytes = int(aval.size) * int(dst_dt.itemsize)
            if nbytes < ctx.config.dtype_floor_bytes:
                continue
            shape = f"{dst_dt.name}[{','.join(str(d) for d in aval.shape)}]"
            key = f"{src_dt.name}->{dst_dt.name}->{src_dt.name}:{shape}"
            f = findings.get(key)
            if f is None:
                findings[key] = LintFinding(
                    lint="dtype_flow", path=ctx.name, key=key,
                    summary=(f"cast round-trip {src_dt.name} -> "
                             f"{dst_dt.name} -> {src_dt.name} on {shape} "
                             f"({nbytes:,} B widened and thrown away)"),
                    bytes=nbytes, priced=False, in_loop=in_loop,
                    details={"src_dtype": src_dt.name,
                             "wide_dtype": dst_dt.name, "shape": shape})
            else:
                f.count += 1
                f.bytes += nbytes
                f.in_loop = f.in_loop or in_loop

    if ctx.jaxpr is not None:
        inner = getattr(ctx.jaxpr, "jaxpr", ctx.jaxpr)
        walk(inner, False)
    return list(findings.values())


# ------------------------------------------------------------------ #
# 3. donation
# ------------------------------------------------------------------ #
def _aval_desc(aval) -> str:
    shape = ",".join(str(d) for d in getattr(aval, "shape", ()))
    return f"{getattr(aval, 'dtype', '?')}[{shape}]"


def donation_pass(ctx: LintContext) -> List[LintFinding]:
    donated = ctx.donated_invars or ()
    if not any(donated):
        return []
    param_shapes = hlo_text.entry_parameter_shapes(ctx.hlo_text)
    aliased = set(hlo_text.input_output_alias_params(ctx.hlo_text))
    # Entry parameter j holds flat input kept[j]: jit's keep_unused=False
    # drops unused inputs from the executable, so alias-table parameter
    # numbers must be mapped back onto the declared donation vector. A
    # DROPPED donated input never reaches the device — its donation is
    # trivially honored (jax deletes it at dispatch).
    kept = list(ctx.kept_var_idx) if ctx.kept_var_idx is not None \
        else list(range(len(donated)))
    attributable = len(kept) == len(param_shapes)
    if not attributable:
        # Mapping unavailable (exotic backend / API drift): judge by
        # count only — fewer aliases than kept donated inputs means
        # un-returned buffers exist, but per-leaf attribution is gone.
        # A DROPPED donated arg must not count toward the expectation:
        # with kept_var_idx in hand the kept donated args are exact;
        # without it, at most len(donated)-len(param_shapes) args were
        # dropped, bounding the donated-and-kept count from below.
        if ctx.kept_var_idx is not None:
            n_donated_kept = sum(1 for flat in kept
                                 if flat < len(donated) and donated[flat])
        else:
            n_dropped_max = max(0, len(donated) - len(param_shapes))
            n_donated_kept = max(
                0, sum(1 for d in donated if d) - n_dropped_max)
        if len(aliased) >= n_donated_kept:
            return []
        missing = list(range(n_donated_kept - len(aliased)))
        un_bytes = 0
        leaves = ["<unattributable: executable parameter mapping "
                  "unavailable>"]
    else:
        missing = [p for p, flat in enumerate(kept)
                   if flat < len(donated) and donated[flat]
                   and p not in aliased]
        # Entry-layout shapes are the PER-DEVICE truth (post
        # partitioning), so sharded donated leaves are priced at what a
        # device actually holds live.
        un_bytes = sum(hlo_text.parse_shape_bytes(param_shapes[p])[0]
                       for p in missing)
        leaves = [f"param{p}(arg{kept[p]}):{param_shapes[p]}"
                  for p in missing]
    if not missing:
        return []
    # The byte floor only applies when bytes are attributable — the
    # degraded count-only fallback prices nothing (un_bytes == 0) and a
    # floor of 0 would otherwise silently swallow its findings.
    if attributable and un_bytes <= ctx.config.donation_floor_bytes:
        return []
    return [LintFinding(
        lint="donation", path=ctx.name,
        key=f"unaliased:{len(missing)}x:{un_bytes}B",
        summary=(f"{len(missing)} donated input buffer(s) "
                 f"({un_bytes:,} B) have no entry in the compiled "
                 "input/output alias table — the donation freed nothing "
                 "and the buffers stay live across the call"),
        bytes=int(un_bytes), priced=False, count=len(missing),
        details={"unaliased_params": leaves[:16],
                 "aliased_param_count": len(aliased),
                 "donated_arg_count": sum(1 for d in donated if d)})]


# ------------------------------------------------------------------ #
# 4. host_sync
# ------------------------------------------------------------------ #
_CALLBACK_PRIMS = frozenset({"pure_callback", "io_callback",
                             "debug_callback"})
_HOST_HLO_OPS = frozenset({"infeed", "outfeed"})


def host_sync_pass(ctx: LintContext) -> List[LintFinding]:
    out: List[LintFinding] = []

    hits: Dict[str, Dict[str, Any]] = {}

    def walk(jaxpr, in_loop: bool) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            loop_here = in_loop or prim in ("scan", "while")
            if prim in _CALLBACK_PRIMS:
                rec = hits.setdefault(prim, {"count": 0, "in_loop": False})
                rec["count"] += 1
                rec["in_loop"] = rec["in_loop"] or in_loop
            for sub in _subjaxprs(eqn):
                walk(sub, loop_here)

    if ctx.jaxpr is not None:
        walk(getattr(ctx.jaxpr, "jaxpr", ctx.jaxpr), False)
    for prim, rec in sorted(hits.items()):
        out.append(LintFinding(
            lint="host_sync", path=ctx.name, key=prim,
            summary=(f"{prim} inside the compiled step fn "
                     f"({rec['count']}x"
                     f"{', in a scan body' if rec['in_loop'] else ''}) — "
                     "every call is a host round-trip that stalls the "
                     "async dispatch pipeline"),
            priced=False, in_loop=rec["in_loop"], count=rec["count"],
            details={"primitive": prim}))

    # HLO side: callback custom-calls (belt and suspenders for programs
    # whose jaxpr was unavailable) and explicit host transfers.
    hlo_hits: Dict[str, Dict[str, Any]] = {}
    for ins in hlo_text.iter_instructions(ctx.hlo_text):
        key = None
        if ins.opcode == "custom-call" and "callback" in ins.rest:
            key = "custom-call:callback"
        elif ins.opcode in _HOST_HLO_OPS or "is_host_transfer=true" in \
                ins.rest:
            key = f"host-transfer:{ins.opcode}"
        if key is None:
            continue
        rec = hlo_hits.setdefault(key, {"count": 0, "in_loop": False})
        rec["count"] += 1
        rec["in_loop"] = rec["in_loop"] or ins.in_loop
    jaxpr_total = sum(r["count"] for r in hits.values())
    for key, rec in sorted(hlo_hits.items()):
        if jaxpr_total and key == "custom-call:callback":
            continue    # already attributed at the jaxpr level
        out.append(LintFinding(
            lint="host_sync", path=ctx.name, key=key,
            summary=(f"{key} in the compiled program ({rec['count']}x) — "
                     "a host transfer inside the step"),
            priced=False, in_loop=rec["in_loop"], count=rec["count"],
            details={"hlo": key}))
    return out


# ------------------------------------------------------------------ #
# 5. collective_placement
# ------------------------------------------------------------------ #
def collective_placement_pass(ctx: LintContext) -> List[LintFinding]:
    meta = ctx.meta
    out: List[LintFinding] = []
    # MoE expert placement: an expert-sharded gradient may all-reduce
    # over `data` (within its expert group) ONLY — replica groups wider
    # than the data axis span the `expert` axis, i.e. the lowering
    # treated experts as replicas and ships every group every other
    # group's expert grads (the seeded-violation case; engine meta
    # carries the legal per-device payload sizes + the max group width).
    expert_bytes = {int(b) for b in (meta.get("expert_leaf_bytes") or ())}
    if expert_bytes and ctx.audit is not None:
        max_group = int(meta.get("expert_group_size") or 1)
        for o in ctx.audit.of_kind("all-reduce"):
            if o.payload_bytes in expert_bytes and o.group_size > max_group:
                out.append(LintFinding(
                    lint="collective_placement", path=ctx.name,
                    key=f"expert-grad-allreduce:{','.join(o.out_shapes)}",
                    summary=("expert-sharded gradient all-reduced ACROSS "
                             f"the expert axis: {o.out_shapes} in groups "
                             f"of {o.group_size} (data axis is "
                             f"{max_group}) — experts are not replicas; "
                             "their grads sync over data within the "
                             "expert group only"),
                    bytes=o.payload_bytes, wire_bytes=o.wire_bytes,
                    priced=True, in_loop=o.in_loop,
                    details={"op_name": o.op_name,
                             "group_size": o.group_size,
                             "expert_group_size": max_group}))
    if not meta.get("grad_sync_path"):
        return out
    mode = str(meta.get("grad_sync_mode", "none"))
    gas = int(meta.get("gas", 1))
    scatterable = {int(b) for b in (meta.get("scatterable_leaf_bytes") or ())}
    if not scatterable or ctx.audit is None:
        return out
    expects_rs = mode in ("explicit", "declarative")
    # A gradient is told by what the op carries, not by its bytes alone:
    # XLA's all-reduce combiner merges scalar statistics (the health
    # tap's per-leaf sums, a loss mean) into one tuple all-reduce whose
    # bytes can equal a small leaf's (four f32[] = one f32[4]), and a
    # scatterable leaf is never rank 0.
    grad_ars = [o for o in ctx.audit.of_kind("all-reduce")
                if o.payload_bytes in scatterable
                and not all(s.endswith("[]") for s in o.out_shapes)]
    grad_rs = [o for o in ctx.audit.of_kind("reduce-scatter")
               if o.payload_bytes in scatterable]
    # Factored replica hierarchy (multislice slices > 1, or the MoE
    # explicit path's ep > 1): the LEGAL wire is an in-group reduce-
    # scatter (groups of dp) + ONE outer-axis all-reduce (groups of
    # `slices` / `ep`) carrying only the 1/dp residual payloads
    # (dcn_shard_bytes). Whitelist that outer hop out of the
    # grad-allreduce check — a shard payload can coincide byte-for-byte
    # with a smaller leaf's full size. On multislice meshes additionally
    # flag any grad-sized collective whose groups SPAN the slice axis
    # (wider than dp): a flat joint-(slice, data) sync pushes grad-sized
    # traffic over every DCN boundary link.
    slices = int(meta.get("slices", 1) or 1)
    ep = int(meta.get("ep", 1) or 1)
    dp = int(meta.get("dp", 1) or 1)
    # The expected schedule is DERIVED from the mesh factorization (the
    # axis-algebra planner — the same derivation the builders execute
    # and the wire model prices), not re-cased per axis pair here.
    from ..parallel.axis_algebra import MeshFactorization
    fact = MeshFactorization.from_sizes(slice=slices, expert=ep, data=dp)
    try:
        outer_axis = fact.outer_axis
    except ValueError:
        outer_axis = None   # unsupported factorization: no legal hop
    outer = fact.size(outer_axis) if outer_axis else 1
    dcn_shard = {int(b) for b in (meta.get("dcn_shard_bytes") or ())}
    if outer > 1 and str(meta.get("grad_sync_mode")) == "explicit":
        grad_ars = [o for o in grad_ars
                    if not (o.group_size == outer
                            and o.payload_bytes in dcn_shard)]
    if slices > 1:
        for o in ctx.audit.ops:
            if o.kind not in ("all-reduce", "reduce-scatter"):
                continue
            if o.payload_bytes not in scatterable:
                continue
            # The whitelisted inter-slice hop itself: when slices > dp
            # its groups are wider than dp while carrying only a 1/dp
            # shard whose size collides with a smaller leaf's full size
            # — same exclusion as the grad-allreduce check above.
            if o.group_size == slices and o.payload_bytes in dcn_shard:
                continue
            if o.group_size > dp:
                out.append(LintFinding(
                    lint="collective_placement", path=ctx.name,
                    key=f"grad-spans-dcn:{','.join(o.out_shapes)}",
                    summary=(f"grad-sized {o.kind} of {o.out_shapes} in "
                             f"groups of {o.group_size} (> dp={dp}) "
                             f"spans the slice axis — a flat joint sync "
                             "pushes grad-sized traffic over DCN; the "
                             "hierarchy moves only the 1/dp residual "
                             "there"),
                    bytes=o.payload_bytes, wire_bytes=o.wire_bytes,
                    priced=True, in_loop=o.in_loop,
                    details={"op_name": o.op_name,
                             "group_size": o.group_size,
                             "dp": dp, "slices": slices}))
    # Stage 3 across slices: the planner binds BOTH param gathers to
    # `data` — an ICI axis on every factorization — so a param-sized
    # gather whose replica groups are wider than dp spans the slice
    # axis and ships param bytes over DCN (the joint-axis schedule the
    # hierarchy exists to avoid). Engine meta carries the legal
    # gathered-leaf payload sizes (zero3_gather_leaf_bytes).
    z3_gather = {int(b)
                 for b in (meta.get("zero3_gather_leaf_bytes") or ())}
    if slices > 1 and z3_gather:
        for o in ctx.audit.of_kind("all-gather"):
            if o.payload_bytes not in z3_gather:
                continue
            if o.group_size > dp:
                out.append(LintFinding(
                    lint="collective_placement", path=ctx.name,
                    key=f"param-spans-dcn:{','.join(o.out_shapes)}",
                    summary=(f"param-sized all-gather of {o.out_shapes} "
                             f"in groups of {o.group_size} (> dp={dp}) "
                             "spans the slice axis — stage-3 gathers "
                             "bind `data` (ICI only); a joint-axis "
                             "gather ships param bytes over DCN every "
                             "micro-step"),
                    bytes=o.payload_bytes, wire_bytes=o.wire_bytes,
                    priced=True, in_loop=o.in_loop,
                    details={"op_name": o.op_name,
                             "group_size": o.group_size,
                             "dp": dp, "slices": slices}))
    if expects_rs:
        for o in grad_ars:
            out.append(LintFinding(
                lint="collective_placement", path=ctx.name,
                key=f"grad-allreduce:{','.join(o.out_shapes)}",
                summary=("gradient materializes unpartitioned: all-reduce "
                         f"of {o.out_shapes} under declared ZeRO "
                         f"grad sharding (grad_sync={mode}) — the known "
                         "GSPMD fallback, 2x the reduce-scatter wire"),
                bytes=o.payload_bytes, wire_bytes=o.wire_bytes,
                priced=True, in_loop=o.in_loop,
                details={"op_name": o.op_name, "group_size": o.group_size,
                         "declared_mode": mode}))
        if gas > 1:
            for o in grad_rs:
                if not o.in_loop:
                    out.append(LintFinding(
                        lint="collective_placement", path=ctx.name,
                        key=f"rs-hoisted:{','.join(o.in_shapes)}",
                        summary=("reduce-scatter of "
                                 f"{o.in_shapes} sits OUTSIDE the gas={gas} "
                                 "accumulation scan — the carry holds the "
                                 "full unpartitioned gradient across every "
                                 "micro-step"),
                        bytes=o.payload_bytes, wire_bytes=o.wire_bytes,
                        priced=True, in_loop=False,
                        details={"op_name": o.op_name, "gas": gas,
                                 "declared_mode": mode}))
        if not grad_rs and not grad_ars:
            out.append(LintFinding(
                lint="collective_placement", path=ctx.name,
                key="no-grad-sync",
                summary=(f"grad_sync={mode} declares a reduce-scattered "
                         "gradient sync but the compiled program emits no "
                         "gradient-sized reduce-scatter (or all-reduce) "
                         "at all"),
                priced=False,
                details={"declared_mode": mode,
                         "scatterable_leaf_bytes": sorted(scatterable)}))
    else:   # "none" (stage<2 dense) / "allreduce" (reduce_scatter: false)
        for o in grad_rs:
            out.append(LintFinding(
                lint="collective_placement", path=ctx.name,
                key=f"unexpected-rs:{','.join(o.in_shapes)}",
                summary=("reduce-scatter of "
                         f"{o.in_shapes} under a REPLICATED grad "
                         f"declaration (grad_sync={mode}) — downstream "
                         "consumers see 1/dp shards the declaration "
                         "promised whole"),
                bytes=o.payload_bytes, wire_bytes=o.wire_bytes,
                priced=True, in_loop=o.in_loop,
                details={"op_name": o.op_name, "declared_mode": mode}))
        if gas > 1:
            for o in grad_ars:
                if o.in_loop:
                    out.append(LintFinding(
                        lint="collective_placement", path=ctx.name,
                        key=f"ar-in-scan:{','.join(o.out_shapes)}",
                        summary=("gradient all-reduce of "
                                 f"{o.out_shapes} TRAPPED inside the "
                                 f"gas={gas} scan — dense sync pays "
                                 f"{gas}x the wire it needs (accumulate "
                                 "locally, reduce once)"),
                        bytes=o.payload_bytes,
                        wire_bytes=o.wire_bytes * gas, priced=True,
                        in_loop=True,
                        details={"op_name": o.op_name, "gas": gas,
                                 "wire_bytes_per_trip": o.wire_bytes}))
    return out


# The pipeline, in report order. Dict, not list: tools/tests select
# subsets by name and the names are part of the finding fingerprint.
PASSES = {
    "materialization": materialization_pass,
    "dtype_flow": dtype_flow_pass,
    "donation": donation_pass,
    "host_sync": host_sync_pass,
    "collective_placement": collective_placement_pass,
}

__all__ = ["PASSES", "materialization_pass", "dtype_flow_pass",
           "donation_pass", "host_sync_pass", "collective_placement_pass"]

"""Communication audit of compiled XLA programs.

The repo's ZeRO communication schedule — reduce-scatter(grads) → sharded
update → all-gather(params), the wire-volume win of ZeRO (Rajbhandari et
al., 2020) — is *declared* through GSPMD shardings (zero/partition.py) and
trusted to the SPMD partitioner (Xu et al., GSPMD 2021). Nothing about a
declaration guarantees the lowering: the known failure mode of declarative
ZeRO is the partitioner falling back to a full all-reduce + slice, which
materializes every gradient unpartitioned and doubles the wire bytes.

This module turns the schedule from prose into a checkable artifact:

- ``parse_hlo_collectives`` walks a compiled program's HLO text and
  extracts every collective (all-reduce, reduce-scatter, all-gather,
  collective-permute, all-to-all) with its shapes, byte volume, replica
  groups and enclosing computation (collectives inside a ``while`` body —
  a ``lax.scan`` — appear once; the caller multiplies by the analytic trip
  count, which the schedule oracle provides).
- ``CommAudit`` summarizes the ops and prices each with the standard ring
  wire model (all-reduce = 2(g-1)/g·B, reduce-scatter/all-gather =
  (g-1)/g·B, permute = B), the same model the analytic per-config
  expectations in tools/comm_audit.py use — so compiled reality and the
  paper's arithmetic are compared in the same currency.
- ``zero2_grad_sync_lowering`` is a cached capability probe (the
  tests/capability.py idiom): compile a minimal declared-reduce-scatter
  program once per (backend, mesh axis) and report whether THIS
  partitioner honors the declaration. The engine consults it to pick the
  guaranteed explicit ``lax.psum_scatter`` gradient path when the
  declarative one regresses.

Everything here is static analysis of ``jit(...).lower(...).compile()``
output — no step is executed, so auditing a multi-GB config costs only a
compile.

The generic HLO-text mechanics (computation splitting, shape sizing,
loop attribution, trip counts) live in ``analysis/hlo_text.py`` — the
shared parsing layer of the lint-pass framework (analysis/) — so the
collective audit and the lint suite read compiled programs identically.
This module keeps the COLLECTIVE-specific analysis: replica groups, the
ring wire model, the ZeRO-2 lowering probe, and the grad-sync pricing.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..analysis.hlo_text import (
    DTYPE_BYTES, INSTR_RE as _INSTR_RE,
    parse_shape_bytes as _parse_shapes,
    split_computations as _split_computations,
    loop_computations as _loop_computations,
    while_trip_counts)

__all__ = [
    "CollectiveOp", "CommAudit", "parse_hlo_collectives", "audit_text",
    "audit_jit", "ring_wire_bytes", "zero2_grad_sync_lowering",
    "grad_sync_wire_model", "moe_alltoall_wire_model", "DTYPE_BYTES",
    "while_trip_counts",
]

COLLECTIVE_KINDS = ("all-reduce", "reduce-scatter", "all-gather",
                    "collective-permute", "all-to-all")

_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_LIST_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def ring_wire_bytes(kind: str, payload_bytes: int, group_size: int) -> int:
    """Per-participant wire bytes of one collective under the standard ring
    model — the currency the ZeRO paper's 2x claim is stated in:

    - all-reduce: 2(g-1)/g · B  (reduce-scatter phase + all-gather phase)
    - reduce-scatter / all-gather / all-to-all: (g-1)/g · B over the FULL
      (unscattered) buffer B
    - collective-permute: B (each source ships its buffer once)
    """
    g = max(1, group_size)
    if kind == "all-reduce":
        return 2 * (g - 1) * payload_bytes // g
    if kind in ("reduce-scatter", "all-gather", "all-to-all"):
        return (g - 1) * payload_bytes // g
    if kind == "collective-permute":
        return payload_bytes
    raise ValueError(f"unknown collective kind {kind!r}")


@dataclasses.dataclass
class CollectiveOp:
    kind: str                 # normalized (no -start suffix)
    name: str                 # HLO instruction name
    computation: str          # enclosing HLO computation ("" if unknown)
    out_bytes: int
    in_bytes: int
    out_shapes: List[str]
    in_shapes: List[str]
    group_size: int           # participants per replica group
    num_groups: int
    source_target_pairs: Optional[List[Tuple[int, int]]]
    op_name: str              # jax op metadata (attribution)
    in_loop: bool = False     # inside a while (lax.scan) body: executes
                              # once per trip, not once per step

    @property
    def payload_bytes(self) -> int:
        """The full (unscattered) buffer the wire model prices: the input
        for reduce-scatter (its output is the 1/g shard), the output for
        all-gather (its input is the shard), the buffer itself otherwise."""
        if self.kind == "reduce-scatter":
            return self.in_bytes
        return self.out_bytes

    @property
    def wire_bytes(self) -> int:
        if self.kind == "collective-permute":
            # A device only transmits if it appears as a source; shaped as
            # per-participating-device bytes.
            return self.out_bytes
        return ring_wire_bytes(self.kind, self.payload_bytes, self.group_size)

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["wire_bytes"] = self.wire_bytes
        d["payload_bytes"] = self.payload_bytes
        return d


def parse_hlo_collectives(hlo_text: str) -> List[CollectiveOp]:
    """Extract every collective instruction from optimized-HLO text.

    Handles both replica-group encodings XLA prints (`{{0,1,...}}` lists
    and the iota form `[G,g]<=[N]`), tuple-shaped variadic collectives,
    and async `-start`/`-done` pairs (only `-start` is counted). Each op
    records its enclosing computation and whether that computation is
    (transitively) a while-loop body. (Computation splitting and loop
    attribution come from the shared analysis/hlo_text layer.)"""
    comp_lines = _split_computations(hlo_text)
    loop_comps = _loop_computations(comp_lines)

    ops: List[CollectiveOp] = []
    for computation, lines in comp_lines.items():
        instrs = [(line, m) for line in lines
                  for m in [_INSTR_RE.match(line)] if m]
        # Operands print as bare `%name` references; their shapes are
        # the defining instructions' (parameters included) in the same
        # computation.
        shape_of = {m.group("name"): m.group("shape") for _, m in instrs}
        for line, m in instrs:
            op = m.group("op")
            is_async = op.endswith("-start")
            kind = op[:-6] if is_async else op
            if kind not in COLLECTIVE_KINDS:
                continue
            out_bytes, out_shapes = _parse_shapes(m.group("shape"),
                                                  largest_only=is_async)
            # Operands: everything inside the call parens up to the
            # matching close. XLA prints them as bare `%name` references
            # (shape = the defining instruction's); HLO text printed with
            # operand shapes (`dtype[dims]{layout} %operand`) reads
            # directly.
            rest = line[m.end():]
            depth, i = 1, 0
            while i < len(rest) and depth:
                if rest[i] == "(":
                    depth += 1
                elif rest[i] == ")":
                    depth -= 1
                i += 1
            operands = rest[:i - 1]
            in_bytes, in_shapes = _parse_shapes(operands)
            if not in_shapes:
                in_bytes, in_shapes = _parse_shapes(" ".join(
                    shape_of.get(ref, "")
                    for ref in _OPERAND_RE.findall(operands)))
            attrs = rest[i:]

            group_size, num_groups = 1, 1
            gm = _IOTA_GROUPS_RE.search(attrs)
            if gm:
                num_groups, group_size = int(gm.group(1)), int(gm.group(2))
            else:
                gm = _LIST_GROUPS_RE.search(attrs)
                if gm:
                    groups = [g for g in gm.group(1)[1:-1].split("},{")]
                    num_groups = len(groups)
                    group_size = max(
                        len([r for r in g.split(",") if r != ""])
                        for g in groups)
            pairs = None
            pm = _PAIRS_RE.search(attrs)
            if pm:
                pairs = [tuple(int(x) for x in p.split(","))
                        for p in pm.group(1)[1:-1].split("},{")]
                group_size = max(group_size, len(pairs))
            om = _OPNAME_RE.search(attrs)
            ops.append(CollectiveOp(
                kind=kind, name=m.group("name"), computation=computation,
                out_bytes=out_bytes, in_bytes=in_bytes,
                out_shapes=out_shapes, in_shapes=in_shapes,
                group_size=group_size, num_groups=num_groups,
                source_target_pairs=pairs,
                op_name=om.group(1) if om else "",
                in_loop=computation in loop_comps))
    return ops


@dataclasses.dataclass
class CommAudit:
    """Structured report over one compiled program's collectives."""
    ops: List[CollectiveOp]
    hlo_text: str = ""

    def while_trip_counts(self) -> List[int]:
        return while_trip_counts(self.hlo_text)

    def of_kind(self, kind: str) -> List[CollectiveOp]:
        return [o for o in self.ops if o.kind == kind]

    def in_loops(self, kind: Optional[str] = None) -> List[CollectiveOp]:
        """Collectives inside while-loop computations (scan bodies) — they
        execute once per trip, so their static bytes must be multiplied by
        the analytic trip count."""
        return [o for o in self.ops if o.in_loop
                and (kind is None or o.kind == kind)]

    def total_wire(self, kind: Optional[str] = None) -> int:
        return sum(o.wire_bytes for o in self.ops
                   if kind is None or o.kind == kind)

    def summary(self) -> Dict[str, Dict[str, int]]:
        out: Dict[str, Dict[str, int]] = {}
        for o in self.ops:
            s = out.setdefault(o.kind, {"count": 0, "payload_bytes": 0,
                                        "wire_bytes": 0})
            s["count"] += 1
            s["payload_bytes"] += o.payload_bytes
            s["wire_bytes"] += o.wire_bytes
        return out

    def to_dict(self) -> Dict[str, Any]:
        return {"summary": self.summary(),
                "ops": [o.to_dict() for o in self.ops]}


def audit_text(hlo_text: str) -> CommAudit:
    return CommAudit(parse_hlo_collectives(hlo_text), hlo_text)


def audit_jit(fn, *args, **kwargs) -> CommAudit:
    """Audit a jitted callable on concrete (or ShapeDtypeStruct) args:
    lower → compile → parse. Compile-only; nothing executes."""
    import jax
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    compiled = fn.lower(*args, **kwargs).compile()
    return audit_text(compiled.as_text())


# --------------------------------------------------------------------- #
# The ZeRO-2 lowering probe + analytic wire model
# --------------------------------------------------------------------- #
_PROBE_CACHE: Dict[Tuple, str] = {}


def zero2_grad_sync_lowering(mesh, axis_name: str = "data",
                             dtype=None) -> str:
    """What a DECLARED dp-sharded gradient actually compiles to on this
    backend: ``"reduce-scatter"`` | ``"all-reduce"`` | ``"none"``.

    Compiles (never runs) a minimal replica of the engine's declarative
    ZeRO-2 pattern — batch sharded over ``axis_name``, grads constrained to
    a dp-sharded ``NamedSharding`` — and inspects which collective carries
    the cross-dp sync. "all-reduce" is the known GSPMD fallback (full
    all-reduce + slice): the gradient materializes unpartitioned and the
    wire bytes double vs the ZeRO schedule. Cached per (backend devices,
    axis, dtype) like tests/capability.py, so callers probe freely."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    dtype = dtype or jnp.float32
    n = int(mesh.shape[axis_name])
    if n <= 1:
        return "none"
    # The axis SIZE must be in the key: a dp=8 and a dp=4 x mp=2 mesh
    # enumerate the same device ids under the same axis name but compile
    # different probe programs.
    key = (tuple(d.id for d in mesh.devices.flat), axis_name, n,
           jnp.dtype(dtype).name)
    if key in _PROBE_CACHE:
        return _PROBE_CACHE[key]

    d = 2 * n
    w_sh = NamedSharding(mesh, P(axis_name))
    x_sh = NamedSharding(mesh, P(axis_name))

    def probe(w, x):
        g = jax.grad(lambda w_, x_: jnp.mean((x_ @ w_) ** 2))(w, x)
        return lax.with_sharding_constraint(g, w_sh)

    w = jax.ShapeDtypeStruct((d, d), dtype, sharding=NamedSharding(mesh, P()))
    x = jax.ShapeDtypeStruct((d, d), dtype, sharding=x_sh)
    try:
        audit = audit_jit(probe, w, x)
    except Exception:   # pragma: no cover - exotic backend
        _PROBE_CACHE[key] = "none"
        return "none"
    result = "none"
    if audit.of_kind("reduce-scatter"):
        result = "reduce-scatter"
    elif audit.of_kind("all-reduce"):
        result = "all-reduce"
    _PROBE_CACHE[key] = result
    return result


def moe_alltoall_wire_model(hidden: int, num_experts: int, top_k: int,
                            capacity_factor: float, ep: int,
                            n_moe_layers: int = 1, bytes_per_el: int = 4,
                            tokens_per_device: Optional[int] = None,
                            gas: int = 1) -> Dict[str, Any]:
    """Analytic per-device wire bytes of the MoE dispatch/combine
    all-to-alls (deepspeed_tpu/moe/layer.py) per optimizer step.

    Each MoE layer exchanges its ``[E, C, H]`` dispatch buffer over the
    ``expert`` axis FOUR times per micro-step — forward dispatch +
    combine, and their transposes in backward (the vjp of an all-to-all
    is an all-to-all) — each moving ``(ep-1)/ep`` of the buffer off-chip
    under the ring model (tools/comm_audit.py checks the compiled
    program against this to 5%).

    With ``tokens_per_device`` (T per micro-step) the figure is exact at
    the capacity rounding (C = ceil(cf·k·T/E)); without it only the
    T-free ``wire_bytes_per_token`` is reported (≈ 4·n·(ep-1)/ep·cf·k·
    H·bytes — the capacity ceil amortizes away). ep <= 1 prices to zero
    (no collective exists)."""
    out: Dict[str, Any] = {
        "ep": ep, "num_experts": num_experts, "top_k": top_k,
        "capacity_factor": capacity_factor, "n_moe_layers": n_moe_layers,
        "alltoalls_per_moe_layer_per_micro_step": 4,
        "bytes_per_el": int(bytes_per_el),
    }
    if ep <= 1:
        out.update({"wire_bytes_per_token": 0, "wire_bytes_per_step": 0})
        return out
    frac = (ep - 1) / ep
    import math as _math
    if _math.isinf(capacity_factor):
        per_token = 4 * n_moe_layers * frac * num_experts * hidden * \
            bytes_per_el
    else:
        per_token = 4 * n_moe_layers * frac * capacity_factor * top_k * \
            hidden * bytes_per_el
    out["wire_bytes_per_token"] = int(per_token)
    if tokens_per_device is not None:
        from ..moe.layer import expert_capacity
        c = expert_capacity(int(tokens_per_device), num_experts, top_k,
                            capacity_factor)
        buf = num_experts * c * hidden * bytes_per_el
        out["capacity"] = c
        out["dispatch_buffer_bytes"] = int(buf)
        out["wire_bytes_per_step"] = int(
            4 * n_moe_layers * int(gas) * ring_wire_bytes(
                "all-to-all", buf, ep))
    return out


def grad_sync_wire_model(params: Any, dp: int,
                         grad_bytes_per_el: int = 4,
                         zero3: bool = False,
                         param_bytes_per_el: Optional[int] = None,
                         gas: int = 1,
                         param_specs: Any = None,
                         mesh: Any = None,
                         moe: Optional[Dict[str, Any]] = None,
                         slices: int = 1,
                         dcn_compression: bool = False
                         ) -> Dict[str, Any]:
    """Analytic per-step gradient-sync wire bytes for a param tree under
    dp-way data parallelism, in both lowerings. Scatterable leaves follow
    zero/partition.py's rule (first dim >= dp and divisible); the rest are
    replicated and all-reduce in either mode (they are the small tail).

    ``zero3=True`` adds the stage-3 parameter-gather term: each sharded
    param crosses the wire twice more per micro-step — the forward
    all-gather and the backward re-gather (``jax.checkpoint`` around the
    gather / the layer scan's manual VJP re-gathers instead of saving
    the gathered tree) — at the COMPUTE dtype (``param_bytes_per_el``;
    the fp32 master shard is cast in flight, zero/stage3.gather_cast),
    each priced (g-1)/g · B by the ring model. With grad accumulation
    every micro-step repeats the whole schedule (the explicit path
    scatters into the sharded carry per micro-step too), the classic
    ZeRO-3 3x pattern: total = gas · (2 gathers + 1 fp32 grad
    reduce-scatter). ``param_specs`` overrides the sharded/replicated
    split with
    the engine's actual stage-3 spec tree (covered scanned leaves avoid
    the layer axis, so their divisibility differs from the plain rule);
    pass ``mesh`` with it so a dp+TP leaf is priced at its per-TP-rank
    slice (the dp collective moves 1/mp of the leaf per rank, and the
    dp gather reconstructs 1/mp per device, not the full leaf).

    ``moe``: kwargs for ``moe_alltoall_wire_model`` — when given, the
    output grows ``moe_alltoall_wire_bytes`` (the per-step priced
    dispatch/combine all-to-all term) and the full ``moe`` sub-record.
    The term is reported separately, NOT folded into the grad-sync
    figures: it is activation wire, and the engine sums the two for its
    per-step total.

    ``slices > 1``: the multi-slice HIERARCHICAL schedule
    (parallel/multislice.py) — the output grows the two-tier terms:

    - ``ici_wire_bytes``: the in-slice sync (reduce-scatter of
      scatterable + all-reduce of the replicated tail, over ``dp``) —
      identical to the single-slice reduce-scatter figure. Under
      ``zero3`` BOTH param gathers join this term (the axis-algebra
      planner binds them to `data`, an ICI axis on every
      factorization), per micro-step like the scatter;
    - ``dcn_payload_bytes``: the per-rank residual that crosses slices
      (the 1/dp shard + the replicated tail, f32);
    - ``dcn_wire_bytes``: its inter-slice ring all-reduce over
      ``slices`` — ONE per step (shards accumulate locally across
      micro-steps; only the accumulated residual crosses DCN);
    - ``dcn_wire_bytes_compressed``: the same hop in the 1-bit packed
      wire format (sign bits + per-chunk f32 scales,
      ops/onebit.comm_bytes) — what ``dcn_compression`` actually ships;
    - ``flat_dcn_link_bytes``: the comparator — a FLAT collective over
      the joint (slice, data) ring carries ~the full grad payload over
      every link including the DCN boundary links; hierarchy divides
      the DCN traffic by dp.

    The headline total ``hierarchical_wire_bytes`` = ici + dcn (the
    active dcn figure per ``dcn_compression``). With ``zero3`` the
    output also pins ``dcn_param_bytes: 0`` (zero param-sized bytes on
    the slow tier — the composition's claim) and carries the derived
    ``collective_plan`` (axis_algebra.plan_grad_sync) the audit and
    lint check the compiled program against.
    """
    import jax
    from .topology import DP_AXIS
    from ..runtime.zero.partition import _leaf_spec, spec_dp_dim

    leaves = jax.tree_util.tree_leaves(params)
    if param_specs is not None:
        spec_leaves = jax.tree_util.tree_structure(params).flatten_up_to(
            param_specs)
    else:
        spec_leaves = [None] * len(leaves)
    scatterable = replicated = 0
    scatterable_el = replicated_el = 0
    for leaf, sp in zip(leaves, spec_leaves):
        shape = getattr(leaf, "shape", None)
        if shape is None or getattr(leaf, "ndim", 0) < 1:
            continue
        nbytes = int(grad_bytes_per_el)
        nel = 1
        for s in shape:
            nbytes *= int(s)
            nel *= int(s)
        if sp is not None and mesh is not None:
            # dp+TP leaf: the dp collectives carry this TP rank's slice.
            for entry in sp:
                for ax in ((entry,) if isinstance(entry, str)
                           else (entry or ())):
                    if ax != DP_AXIS:
                        div = max(1, int(mesh.shape.get(ax, 1)))
                        nbytes //= div
                        nel //= div
        # The DP axis specifically: a leaf sharded only over a TP/model
        # axis never dp-scatters or dp-gathers (its dp grad sync is the
        # replicated all-reduce).
        sharded = spec_dp_dim(sp, DP_AXIS) is not None \
            if sp is not None \
            else any(e is not None for e in _leaf_spec(shape, dp, "data"))
        if sharded:
            scatterable += nbytes
            scatterable_el += nel
        else:
            replicated += nbytes
            replicated_el += nel
    repl_wire = ring_wire_bytes("all-reduce", replicated, dp)
    out = {
        "dp": dp,
        "grad_bytes": scatterable + replicated,
        "scatterable_bytes": scatterable,
        "replicated_bytes": replicated,
        "reduce_scatter_wire_bytes":
            ring_wire_bytes("reduce-scatter", scatterable, dp) + repl_wire,
        "all_reduce_wire_bytes":
            ring_wire_bytes("all-reduce", scatterable, dp) + repl_wire,
    }
    if zero3:
        pbytes = int(param_bytes_per_el or grad_bytes_per_el)
        gather_payload = scatterable_el * pbytes
        one_gather = ring_wire_bytes("all-gather", gather_payload, dp)
        out.update({
            "param_gather_payload_bytes": gather_payload,
            "param_gather_wire_bytes": 2 * int(gas) * one_gather,
            "param_gathers_per_step": 2 * int(gas),
            # Per STEP on the explicit path: every micro-step re-gathers
            # (fwd + bwd) and scatters its grads into the sharded carry.
            "zero3_wire_bytes":
                int(gas) * (out["reduce_scatter_wire_bytes"]
                            + 2 * one_gather),
        })
    if slices > 1:
        from .axis_algebra import MeshFactorization, plan_grad_sync
        from .multislice import dcn_comm_bytes
        fact = MeshFactorization.from_sizes(slice=slices, data=dp)
        plan = plan_grad_sync(fact, zero3=zero3,
                              dcn_compression=dcn_compression)
        # Per-rank residual after the in-slice reduce: the 1/dp shard of
        # every scatterable leaf + the replicated tail, f32. Stage-3
        # changes NOTHING here — its grads land on the same 1/dp shards
        # (gather_cast's transpose IS the in-slice reduce-scatter).
        dcn_el = scatterable_el // dp + replicated_el
        dcn_payload = dcn_el * 4
        dcn_wire = ring_wire_bytes("all-reduce", dcn_payload, slices)
        dcn_payload_c = dcn_comm_bytes(dcn_el, compressed=True,
                                       num_slices=slices)
        dcn_wire_c = ring_wire_bytes("all-reduce", dcn_payload_c, slices)
        active_dcn = dcn_wire_c if dcn_compression else dcn_wire
        # The in-slice (per-micro-step) tier: the grad reduce-scatter,
        # plus — under stage 3 — both param gathers, which the planner
        # places on `data`/ICI (param bytes NEVER ride DCN; the flat
        # comparator below shows what a joint-axis schedule would ship).
        ici = out["reduce_scatter_wire_bytes"]
        flat_link = scatterable + replicated
        if zero3:
            assert plan.gather is not None and plan.gather.tier == "ici"
            gather_payload = out["param_gather_payload_bytes"]
            ici += 2 * ring_wire_bytes("all-gather", gather_payload, dp)
            flat_link += 2 * gather_payload
        out.update({
            "slices": slices,
            "dcn_compression": bool(dcn_compression),
            "ici_wire_bytes": int(ici),
            "dcn_payload_bytes": int(dcn_payload),
            "dcn_wire_bytes": int(dcn_wire),
            "dcn_wire_bytes_compressed": int(dcn_wire_c),
            # A flat joint-(slice, data) ring pushes ~the full payload
            # over EVERY link, DCN boundary links included — under
            # stage 3 that payload includes BOTH param gathers per
            # micro-step, the figure the hierarchy zeroes out.
            "flat_dcn_link_bytes": int(flat_link),
            "dcn_param_bytes": 0,
            "hierarchical_wire_bytes": int(ici + active_dcn),
            "collective_plan": plan.to_meta(),
        })
    if moe is not None:
        m = moe_alltoall_wire_model(**moe)
        out["moe"] = m
        out["moe_alltoall_wire_bytes"] = int(
            m.get("wire_bytes_per_step") or 0)
    return out

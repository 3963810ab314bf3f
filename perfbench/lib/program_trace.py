"""The program's OWN spans and scopes, read from a traced run's
``.xplane.pb``: device self time by ``jax.named_scope`` path, the host
spans ``Telemetry.span`` opens (with their args), and the idle gaps
named by the innermost program span.

Where the scope path sits in a TPU trace (looked at by hand, PR 24): an
``XLA Ops`` event carries only times; its event METADATA carries the
stat ``tf_op`` = the HLO instruction's ``op_name`` plus ``:``, e.g.
``jit(train_step)/fwd_bwd/transpose(jvp())/while/body/closed_call/
checkpoint/rematted_computation/attn/dot_general:``.  A fusion has ONE
``tf_op``, its root instruction's, so a fusion that spans two scopes is
filed under its root's.  ``jax.profiler.ProfileData`` hands out an
event's own stats and not its metadata's, so the file is read here by a
small protobuf wire reader (``read_xspace``; field numbers from
tsl/profiler/protobuf/xplane.proto); everything after that works on plain
tuples and dicts, checked on hand-made ones and on one small recorded
chip trace (``perfbench/tests``).  The self-time rule, the split by
program and the gap labelling are ``perfbench/lib/xplane``'s, imported.

A program without scopes or spans (the parent of PR 24) gives empty
tables here, and every metric that reads them gives ``None``.
"""
import collections
import glob
import json
import os
import re
import statistics
import struct
import sys

from perfbench.lib import xplane

# The names are the program's contract (docs/tutorials/telemetry.md).
SCOPES = ("fwd_bwd", "grad_sync", "health_tap", "optimizer", "flatten",
          "norm", "kernel", "unflatten", "embed", "attn", "mlp", "lm_head",
          "kv_write", "attend", "sample", "cow_copy")
SPANS = ("train_batch", "data_prep", "step_dispatch", "offload_step",
         "step_log", "admit", "prefill", "prefill_plan", "prefill_chunk",
         "prefill_fetch", "decode", "decode_tables", "decode_dispatch",
         "decode_fetch", "decode_advance", "emit", "serve_idle")
REMAT = "rematted_computation"
BACKWARD = "transpose("


# ------------------------------------------------------------------ #
# Reading the file
# ------------------------------------------------------------------ #
def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind == 1:
            val, i = buf[i:i + 8], i + 8
        elif kind == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names):
    """One XStat -> (name, value).  A ``ref_value`` names another stat
    metadata entry whose name is the string."""
    name = value = None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif f == 6:
            value = bytes(v)
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf):
    key = val = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf, keep_metadata_stats):
    name, lines, emeta, smeta = "", [], [], []
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            emeta.append(v)
        elif f == 5:
            smeta.append(v)
    stat_names = {}
    for entry in smeta:
        key, val = _map_entry(entry)
        stat_names[key] = next(
            (bytes(v).decode() for f, v in _fields(val) if f == 2), "")
    metadata = {}
    for entry in emeta:
        key, val = _map_entry(entry)
        mname, stats = "", {}
        for f, v in _fields(val):
            if f == 2:
                mname = bytes(v).decode("utf-8", "replace")
            elif f == 5:
                sname, sval = _stat(v, stat_names)
                if sname in keep_metadata_stats:
                    stats[sname] = sval
        metadata[key] = (mname, stats)
    out_lines = {}
    for lbuf in lines:
        lname, t0_ns, events = "", 0, []
        for f, v in _fields(lbuf):
            if f == 2:
                lname = bytes(v).decode()
            elif f == 3:
                t0_ns = _signed(v)
            elif f == 4:
                events.append(v)
        rows = out_lines.setdefault(lname, [])
        for ebuf in events:
            mid = off_ps = dur_ps = 0
            stats = None
            for f, v in _fields(ebuf):
                if f == 1:
                    mid = v
                elif f == 2:
                    off_ps = _signed(v)
                elif f == 3:
                    dur_ps = _signed(v)
                elif f == 4:
                    stats = stats or []
                    stats.append(v)
            rows.append((mid, t0_ns + off_ps / 1e3, dur_ps / 1e3, stats))
    return name, {"lines": out_lines, "metadata": metadata,
                  "stat_names": stat_names}


def read_xspace(path: str, keep_metadata_stats=("tf_op",)):
    """{plane name: {"lines": {line name: [(metadata id, start_ns,
    duration_ns, raw stats or None)]}, "metadata": {id: (name, {stat:
    value})}, "stat_names": {id: name}}}.  Times are on the line's clock
    (``timestamp_ns`` + offset), the one ``ProfileData`` reports."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    keep = frozenset(keep_metadata_stats)
    return dict(_plane(v, keep) for f, v in _fields(data) if f == 1)


# ------------------------------------------------------------------ #
# Scopes
# ------------------------------------------------------------------ #
_INNER = re.compile(r"^(?:[\w.-]+\()*([\w.-]*)\)*$")


def scope_of(tf_op: str):
    """``tf_op`` -> (scope path as a tuple of SCOPES names in order,
    backward?, recomputed?).  JAX wraps a scope name in the transforms
    applied under it (``transpose(jvp(attn))``); a repeat of the scope
    before it (``fwd_bwd/transpose(fwd_bwd)``) is one scope."""
    op = (tf_op or "").split(";", 1)[0].rstrip(":")
    path = []
    for part in op.split("/"):
        m = _INNER.match(part)
        if m and m.group(1) in SCOPES and path[-1:] != [m.group(1)]:
            path.append(m.group(1))
    return tuple(path), BACKWARD in op, REMAT in op


def instruction_self_ns(plane: dict) -> dict:
    """{(program, metadata id): self ns} over one device plane's ``XLA
    Ops`` line; the program is the ``XLA Modules`` execution an op starts
    in, without its fingerprint."""
    ops = plane["lines"].get(xplane.OPS_LINE, [])
    meta = plane["metadata"]
    modules = [(meta.get(m, ("",))[0], s, d)
               for m, s, d, _ in plane["lines"].get(xplane.MODULES_LINE, [])]
    by_program = xplane.split_by_module(
        [(str(m), s, d) for m, s, d, _ in ops], modules)
    return {(program, int(mid)): ns for program, events in by_program.items()
            for mid, ns in xplane.self_time_by_name(events).items()}


def device_self_seconds(plane: dict, self_ns: dict = None) -> dict:
    """{(program, scope path, backward, recomputed): self seconds}
    (``self_ns``: ``instruction_self_ns(plane)`` where already made)."""
    out = collections.defaultdict(float)
    for (program, mid), ns in (self_ns or instruction_self_ns(plane)).items():
        tf_op = plane["metadata"].get(mid, ("", {}))[1].get("tf_op", "")
        out[(program,) + scope_of(tf_op)] += ns / 1e9
    return dict(out)


def unscoped_ops(plane: dict, self_ns: dict = None, top: int = 8) -> list:
    """[(instruction name, self seconds)] of the device operations that
    carry no scope, most time first: what the scopes cannot see."""
    out = collections.defaultdict(float)
    for (_, mid), ns in (self_ns or instruction_self_ns(plane)).items():
        name, stats = plane["metadata"].get(mid, ("", {}))
        if not scope_of(stats.get("tf_op", ""))[0]:
            out[xplane.base_name(name)] += ns / 1e9
    return sorted(out.items(), key=lambda kv: -kv[1])[:top]


# ------------------------------------------------------------------ #
# Host spans
# ------------------------------------------------------------------ #
def host_spans(planes: dict, names=SPANS) -> dict:
    """{span name: [(start_ns, duration_ns, args)]} in time order, from
    every plane that is not a device's."""
    out = {}
    for pname, plane in planes.items():
        if xplane.DEVICE_PLANE.match(pname):
            continue
        meta, stat_names = plane["metadata"], plane["stat_names"]
        for events in plane["lines"].values():
            for mid, start, dur, stats in events:
                name = meta.get(mid, ("",))[0]
                if name in names:
                    args = dict(_stat(s, stat_names) for s in stats or ())
                    out.setdefault(name, []).append((start, dur, args))
    for rows in out.values():
        rows.sort(key=lambda r: r[0])
    return out


def inside(rows, outer):
    """The rows (start, dur, ...) that start inside ``outer``."""
    return [r for r in rows if outer[0] <= r[0] < outer[0] + outer[1]]


def union_s(rows) -> float:
    return xplane.union_ns([("", r[0], r[1]) for r in rows]) / 1e9


# ------------------------------------------------------------------ #
# One traced run
# ------------------------------------------------------------------ #
def reduce(path: str) -> dict:
    """Everything the metrics read, from one ``.xplane.pb``: device 0's
    self seconds by (program, scope path, backward, recomputed), its
    unscoped operations, executions by program (counted, and as whole
    executions), the host spans, and the idle gaps of device 0 named by
    the innermost program span."""
    planes = read_xspace(path)
    dev = sorted((int(m.group(1)), p) for n, p in planes.items()
                 if (m := xplane.DEVICE_PLANE.match(n))
                 and p["lines"].get(xplane.OPS_LINE))
    spans = host_spans(planes)
    out = {"scoped": {}, "unscoped_ops": [], "programs": {},
           "whole_executions": {}, "spans": spans, "gaps": [], "window_s": 0.0}
    if dev:
        plane = dev[0][1]
        ops = [("", s, d) for _, s, d, _ in plane["lines"][xplane.OPS_LINE]]
        self_ns = instruction_self_ns(plane)
        out["scoped"] = device_self_seconds(plane, self_ns)
        out["unscoped_ops"] = unscoped_ops(plane, self_ns)
        durations = collections.defaultdict(list)
        for mid, _, d, _ in plane["lines"].get(xplane.MODULES_LINE, []):
            durations[xplane.module_name(
                plane["metadata"].get(mid, ("",))[0])].append(d)
        for name, ds in durations.items():
            out["programs"][name] = len(ds)
            # The window's edge cuts an execution short: count it as the
            # part of a whole one (the median) that was traced.
            out["whole_executions"][name] = sum(ds) / statistics.median(ds)
        out["window_s"] = (max(s + d for _, s, d in ops)
                           - min(s for _, s, _ in ops)) / 1e9
        flat = [(n, s, d) for n, rows in spans.items() for s, d, _ in rows]
        out["gaps"] = [(label, ns / 1e9) for label, ns in xplane.label_gaps(
            xplane.busy_intervals(ops), flat, "no_program_span")]
    elif spans:
        every = [r for rows in spans.values() for r in rows]
        out["window_s"] = (max(r[0] + r[1] for r in every)
                           - min(r[0] for r in every)) / 1e9
    return out


def seconds(scoped: dict, *, program: str = "", scope: str = "",
            backward=None, recomputed=None) -> float:
    """Sum of ``scoped`` over the keys whose program name contains
    ``program``, whose path holds ``scope`` (any scoped path for
    ``"*"``, every key for ``""``), and whose flags match."""
    total = 0.0
    for (prog, path, bwd, remat), s in scoped.items():
        if program not in prog:
            continue
        if scope == "*" and not path or scope not in ("", "*") \
                and scope not in path:
            continue
        if backward is not None and (bwd or remat) != backward:
            continue
        if recomputed is not None and remat != recomputed:
            continue
        total += s
    return total


def train_split_ms(tr: dict, steps: int) -> dict:
    """Per traced step, in ms: forward (under ``fwd_bwd``, neither
    transposed nor recomputed), backward (transposed or recomputed; the
    recompute also apart), optimizer, its buffer assembly (``flatten`` +
    ``unflatten``), kernel and norm, and the share of device self time
    that carries a scope.  ``{}`` where nothing is scoped."""
    sc = tr["scoped"]
    if not steps or not seconds(sc, scope="*"):
        return {}
    k = 1e3 / steps
    return {
        "fwd": k * seconds(sc, scope="fwd_bwd", backward=False),
        "bwd": k * seconds(sc, scope="fwd_bwd", backward=True),
        "recompute": k * seconds(sc, scope="fwd_bwd", recomputed=True),
        "optimizer": k * seconds(sc, scope="optimizer"),
        "assembly": k * (seconds(sc, scope="flatten")
                         + seconds(sc, scope="unflatten")),
        "kernel": k * seconds(sc, scope="kernel"),
        "norm": k * seconds(sc, scope="norm"),
        "coverage": 100.0 * seconds(sc, scope="*") / seconds(sc),
    }


def serve_split(tr: dict) -> dict:
    """The serve cell's numbers: ``kv_write`` device ms per whole
    ``decode_step`` execution and the scoped share of device time (both
    ``None`` where nothing is scoped), and from the host spans the share
    of the window inside ``prefill``, the host ms per ``decode`` that is
    not the wait in ``decode_fetch``, and the largest ``late_ms``."""
    sc, spans = tr["scoped"], tr["spans"]
    out = {}
    execs = sum(n for name, n in tr["whole_executions"].items()
                if "decode_step" in name)
    if seconds(sc, scope="*") and execs:
        out["kv_write_ms_per_iter"] = 1e3 * seconds(
            sc, program="decode_step", scope="kv_write") / execs
        out["coverage"] = 100.0 * seconds(sc, scope="*") / seconds(sc)
    if spans.get("prefill") and tr["window_s"]:
        out["prefill_stall_share"] = 100.0 * union_s(spans["prefill"]) \
            / tr["window_s"]
    decodes = spans.get("decode", [])
    if decodes:
        host = 0.0
        for d in decodes:
            host += sum(union_s(inside(spans.get(n, []), d)) for n in (
                "decode_tables", "decode_dispatch", "decode_advance"))
        # The emit and the admits that follow a decode, up to the next.
        starts = [d[0] for d in decodes[1:]] + [float("inf")]
        for d, nxt in zip(decodes, starts):
            after = (d[0] + d[1], nxt - d[0] - d[1])
            host += sum(union_s(inside(spans.get(n, []), after))
                        for n in ("emit", "admit"))
        out["host_ms_per_iter"] = 1e3 * host / len(decodes)
    late = [a.get("late_ms") for _, _, a in spans.get("admit", [])
            if a.get("late_ms") is not None]
    if late:
        out["arrival_late_max_ms"] = float(max(late))
    return out


def gap_totals(tr: dict) -> dict:
    totals = collections.defaultdict(float)
    for label, s in tr["gaps"]:
        totals[label] += s
    return dict(sorted(totals.items(), key=lambda kv: -kv[1]))


# ------------------------------------------------------------------ #
# The traced run of THIS process
# ------------------------------------------------------------------ #
_CACHE = {}


def _this_cell():
    argv = sys.argv
    for i, a in enumerate(argv):
        if a == "--workload" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--workload="):
            return a.split("=", 1)[1]
    return None


def _process_started() -> float:
    try:
        return os.stat(f"/proc/{os.getpid()}").st_mtime
    except OSError:
        return 0.0


def current(record) -> dict:
    """The reduced trace of this process's own ``--trace 1`` run, read
    once: the newest ``.xplane.pb`` under
    ``perfbench/.out/trace/<the --workload on sys.argv>/`` (cells are
    rehearsed in parallel, so "the newest file" of any cell will not
    do), refused when older than the process.  ``None`` when the run was
    not traced or left no such file.  The first read prints one line
    with the whole split."""
    if not (record or {}).get("trace"):
        return None
    if "trace" not in _CACHE:
        _CACHE["trace"] = None
        cell = _this_cell()
        found = sorted(glob.glob(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".out", "trace", cell or "", "plugins", "profile", "*",
            "*.xplane.pb")), key=os.path.getmtime)
        if cell and found and \
                os.path.getmtime(found[-1]) >= _process_started() - 1.0:
            tr = _CACHE["trace"] = reduce(found[-1])
            steps = record.get("steps_traced") or 0
            by_scope = collections.defaultdict(float)
            for (prog, path, bwd, remat), s in tr["scoped"].items():
                by_scope[f"{xplane.module_name(prog)}:{'/'.join(path)}"
                         f"{':bwd' if bwd or remat else ''}"
                         f"{':recompute' if remat else ''}"] += s
            print(json.dumps({
                "phase": "program_trace", "file": found[-1],
                "window_s": tr["window_s"], "programs": tr["programs"],
                "whole_executions": tr["whole_executions"],
                "train_split_ms": train_split_ms(tr, steps),
                "serve_split": serve_split(tr),
                "device_s_by_scope": dict(sorted(
                    by_scope.items(), key=lambda kv: -kv[1])[:40]),
                "unscoped_ops_s": tr["unscoped_ops"],
                "gap_s_by_program_span": gap_totals(tr),
                "spans_found": {n: len(r) for n, r in tr["spans"].items()},
            }), flush=True)
    return _CACHE["trace"]


def train_metric(record, key: str):
    """``train_split_ms``'s ``key`` for this process's traced run, or
    ``None`` (not a traced train run, or nothing scoped)."""
    tr = current(record)
    if tr is None or record.get("kind") != "train":
        return None
    return train_split_ms(tr, record.get("steps_traced") or 0).get(key)


def serve_metric(record, key: str):
    """``serve_split``'s ``key`` for this process's traced run, or
    ``None`` (not a traced serve run, or no such span or scope)."""
    tr = current(record)
    if tr is None or record.get("kind") != "serve":
        return None
    return serve_split(tr).get(key)

"""From a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file; everything after that works
on plain ``(name, start_ns, duration_ns)`` tuples, so the arithmetic can
be checked on a hand-made trace (``perfbench/tests``).

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose line
``XLA Ops`` holds one event per executed HLO instruction (a ``while`` or
``conditional`` contains its body's events on the same line) and whose
line ``XLA Modules`` holds one event per executed program.  Host threads
are lines of the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation``
spans appear there by name.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)"
    r"(-start|-done)?(\.\d+)*$")


# ------------------------------------------------------------------ #
# Reading
# ------------------------------------------------------------------ #
def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_planes(path: str) -> dict:
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}.
    Lines of one plane that share a name are concatenated."""
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return planes


# ------------------------------------------------------------------ #
# Arithmetic on events
# ------------------------------------------------------------------ #
def union_ns(events) -> float:
    """Total length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if s + d <= end:
            continue
        total += s + d - max(s, end)
        end = s + d
    return total


def busy_intervals(events) -> list:
    """Merged [start, end] intervals of the events, in order."""
    out = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return out


def self_time_by_name(events) -> dict:
    """{event name without its trailing .N: ns of self time}.  An event
    that lies inside another on the same line (a loop body's ops inside
    their ``while``) is taken out of the outer one's time, so the sum
    over names is the union and a container does not count its
    children twice."""
    out, stack = {}, []          # stack of [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([base_name(name), s + d, d])
    close(float("inf"))
    return out


def module_name(event_name: str) -> str:
    """``jit_decode_step(1234567)`` -> ``jit_decode_step``."""
    return re.sub(r"\(.*$", "", event_name)


def split_by_module(ops, modules) -> dict:
    """{program name: [op events that start inside one of its
    executions]}; ops outside every execution go under ``""``.
    ``modules`` are the ``XLA Modules`` events (name, start, dur); a
    name loses its ``(fingerprint)`` suffix."""
    import bisect
    mods = sorted(modules, key=lambda e: e[1])
    starts = [m[1] for m in mods]
    out = {}
    for ev in ops:
        i = bisect.bisect_right(starts, ev[1]) - 1
        inside = i >= 0 and ev[1] < mods[i][1] + mods[i][2]
        key = module_name(mods[i][0]) if inside else ""
        out.setdefault(key, []).append(ev)
    return out


def base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``%_pattn_kernel.2`` ->
    ``_pattn_kernel``.  A TPU trace names an op by its whole HLO line
    (``_fused_adam_kernel.1 = (bf16[...]) custom-call(...)``): the
    instruction's name is what stands before `` = ``."""
    return re.sub(r"(\.\d+)+$", "", name.split(" = ", 1)[0].lstrip("%"))


def label_gaps(intervals, spans, default: str) -> list:
    """Idle gaps between consecutive busy intervals, each named by the
    innermost host span (name, start, dur) that covers its midpoint:
    [(label, gap_ns)], longest first."""
    spans = sorted(spans, key=lambda e: e[2])        # innermost = shortest
    out = []
    for (_, a_end), (b_start, _) in zip(intervals, intervals[1:]):
        mid = (a_end + b_start) / 2
        label = next((n for n, s, d in spans if s <= mid <= s + d), default)
        out.append((label, b_start - a_end))
    return sorted(out, key=lambda g: -g[1])


# ------------------------------------------------------------------ #
# The reduction the runners use
# ------------------------------------------------------------------ #
def _cpu_rehearsal_planes(planes: dict) -> list:
    """The CPU backend has no device plane: its XLA ops run on host
    threads (lines ``tf_XLA...`` of ``/host:CPU``).  Only the benchmark's
    own CPU rehearsal reads them, to walk the same code; never a cell."""
    ops = [e for name, evs in planes.get("/host:CPU", {}).items()
           if name.startswith("tf_XLA") for e in evs
           if not e[0].startswith("ThreadpoolListener")]
    return [(0, {OPS_LINE: ops})] if ops else []


def reduce_trace(trace_dir: str, span_names, default_span: str,
                 n_devices: int, cpu_rehearsal: bool = False) -> dict:
    """Busy and window seconds (mean over the device planes used), and
    for device 0: self seconds by op name, collective seconds, program
    executions by module name, and the idle gaps by host span."""
    planes = read_planes(find_xplane(trace_dir))
    dev = sorted((int(m.group(1)), lines) for name, lines in planes.items()
                 if (m := DEVICE_PLANE.match(name)))[:n_devices]
    dev = [(i, lines) for i, lines in dev if lines.get(OPS_LINE)]
    if not dev and cpu_rehearsal:
        dev = _cpu_rehearsal_planes(planes)
    if not dev:
        raise RuntimeError("the trace holds no device operation "
                           f"(planes: {sorted(planes)})")
    busy, window = [], []
    for _, lines in dev:
        ops = lines[OPS_LINE]
        busy.append(union_ns(ops))
        window.append(max(s + d for _, s, d in ops) - min(s for _, s, _ in ops))
    ops0 = dev[0][1][OPS_LINE]
    by_name = self_time_by_name(ops0)
    by_module = {
        mod: {k: v / 1e9 for k, v in self_time_by_name(evs).items()}
        for mod, evs in split_by_module(
            ops0, dev[0][1].get(MODULES_LINE, [])).items()}
    host = [e for lines in (v for k, v in planes.items()
                            if not DEVICE_PLANE.match(k))
            for evs in lines.values() for e in evs if e[0] in span_names]
    gaps = label_gaps(busy_intervals(ops0), host, default_span)
    modules = {}
    for name, _, _ in dev[0][1].get(MODULES_LINE, []):
        key = module_name(name)
        modules[key] = modules.get(key, 0) + 1
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": sum(window) / len(window) / 1e9,
        "op_seconds": {k: v / 1e9 for k, v in by_name.items()},
        "op_seconds_by_module": by_module,
        "collective_s": sum(v for k, v in by_name.items()
                            if COLLECTIVE.match(k)) / 1e9,
        "modules": modules,
        "gaps": [(label, ns / 1e9) for label, ns in gaps],
        "host_spans_found": sorted({e[0] for e in host}),
        "device_planes": [i for i, _ in dev],
    }


def breakdown(reduced: dict) -> dict:
    """The contract's ``breakdown``: the ten device operations with the
    most self time, and the idle gaps: the five longest singly, then the
    totals by host span."""
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
    totals = {}
    for label, s in reduced["gaps"]:
        totals[label + ":total"] = totals.get(label + ":total", 0.0) + s
    gaps = [list(g) for g in reduced["gaps"][:5]] + \
        sorted(([k, v] for k, v in totals.items()), key=lambda kv: -kv[1])[:5]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps}

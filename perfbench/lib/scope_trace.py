"""Device self time under named scopes ``program_trace.SCOPES`` does not
list (the expert layer's ``moe`` > ``router`` / ``dispatch`` / ``experts`` /
``combine`` / ``shared``, the latent attention's ``latent_proj``), from the
same traced run and with the same reading of the file
(``program_trace.read_xspace`` / ``instruction_self_ns``); and the sums of
a host span's numeric args over the traced window.  A program that has no
such scope or span arg gives 0 / ``None``: the metric is then left out.
"""
import collections
import glob
import os

from perfbench.lib import program_trace, xplane

SCOPES = program_trace.SCOPES + ("latent_proj", "moe", "router", "dispatch",
                                 "experts", "combine", "shared")
_CACHE = {}


def _path(tf_op: str):
    op = (tf_op or "").split(";", 1)[0].rstrip(":")
    path = []
    for part in op.split("/"):
        m = program_trace._INNER.match(part)
        if m and m.group(1) in SCOPES and path[-1:] != [m.group(1)]:
            path.append(m.group(1))
    return tuple(path)


def scoped_seconds(record) -> dict:
    """{(program, scope path): device-0 self seconds} of this process's
    traced run ({} where there is none)."""
    tr = program_trace.current(record)
    if tr is None:
        return {}
    if "scoped" not in _CACHE:
        out = collections.defaultdict(float)
        # The file ``program_trace.current`` has just read and vetted.
        found = sorted(glob.glob(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".out", "trace", program_trace._this_cell() or "", "plugins",
            "profile", "*", "*.xplane.pb")), key=os.path.getmtime)
        planes = program_trace.read_xspace(found[-1]) if found else {}
        dev = sorted((int(m.group(1)), p) for n, p in planes.items()
                     if (m := xplane.DEVICE_PLANE.match(n))
                     and p["lines"].get(xplane.OPS_LINE))
        if dev:
            plane = dev[0][1]
            for (program, mid), ns in \
                    program_trace.instruction_self_ns(plane).items():
                tf_op = plane["metadata"].get(mid, ("", {}))[1].get(
                    "tf_op", "")
                out[(program, _path(tf_op))] += ns / 1e9
        _CACHE["scoped"] = dict(out)
    return _CACHE["scoped"]


def seconds(record, *, program: str = "", scope: str = "") -> float:
    """Sum over the keys whose program holds ``program`` and whose path
    holds ``scope`` (any scoped path for "*", every key for "")."""
    total = 0.0
    for (prog, path), s in scoped_seconds(record).items():
        if program not in prog:
            continue
        if scope == "*" and not path or scope not in ("", "*") \
                and scope not in path:
            continue
        total += s
    return total


def decode_executions(record) -> float:
    tr = program_trace.current(record)
    if tr is None:
        return 0.0
    return sum(n for name, n in tr["whole_executions"].items()
               if "decode_step" in name)


def span_arg_sum(record, span: str, arg: str):
    """(sum of ``arg`` over the traced ``span``s that carry it, how many
    did), or (None, 0)."""
    tr = program_trace.current(record)
    rows = [a[arg] for _, _, a in (tr or {"spans": {}})["spans"].get(span, [])
            if isinstance(a.get(arg), (int, float))]
    return (float(sum(rows)), len(rows)) if rows else (None, 0)


def kernel_seconds(record, kernel: str, program: str = "decode_step"):
    """(device self seconds of ``kernel`` inside ``program``'s executions,
    executions traced) from the runner's reduced trace."""
    tr = (record or {}).get("trace")
    if not tr:
        return 0.0, 0
    secs = execs = 0
    for module, ops in tr["op_seconds_by_module"].items():
        if program in module:
            secs += ops.get(kernel, 0.0)
            execs += tr["modules"].get(module, 0)
    return secs, execs

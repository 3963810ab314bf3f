"""Device self time under ONE named scope of the program, whatever the
scope is called: the retention family's ``state_update``,
``retention_chunk``, ``state_copy`` are in neither ``program_trace.SCOPES``
nor ``scope_trace.SCOPES`` (lists a later PR may not edit).  Same traced
run, same reading of the file (``program_trace.read_xspace`` /
``instruction_self_ns``) as ``lib/scope_trace.py``.  A program that has no
such scope gives 0: the metric is then left out.
"""
import collections
import glob
import os

from perfbench.lib import program_trace, xplane

_CACHE = {}


def _names(tf_op: str) -> frozenset:
    """Every scope-like part of an instruction's ``op_name`` path, without
    the transforms JAX wraps a scope in."""
    op = (tf_op or "").split(";", 1)[0].rstrip(":")
    out = set()
    for part in op.split("/"):
        m = program_trace._INNER.match(part)
        if m and m.group(1):
            out.add(m.group(1))
    return frozenset(out)


def _by_names(record) -> dict:
    """{(program, names on the path): device-0 self seconds} of this
    process's traced run ({} where there is none)."""
    if program_trace.current(record) is None:
        return {}
    if "by_names" not in _CACHE:
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
                out[(program, _names(tf_op))] += ns / 1e9
        _CACHE["by_names"] = dict(out)
    return _CACHE["by_names"]


def seconds(record, *, program: str = "", scope: str = "") -> float:
    """Device self seconds of the programs whose name holds ``program``,
    under ``scope`` (all of them for "")."""
    return sum(s for (prog, names), s in _by_names(record).items()
               if program in prog and (not scope or scope in names))

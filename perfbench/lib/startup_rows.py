"""Where a start's seconds went, from the program's own start-up ledger
(``deepspeed_tpu.monitor.startup``): the eight ``setup_*`` metrics.

The ledger's clock is the process's age; ``setup_s`` is counted from
``run.py``'s first line, tens of ms after the process began, so the rows
are cut at age ``setup_s``: a row counts if it BEGAN before it (whole:
the window's ``serve()`` call and the references some runners build after
the window begin later).  Seconds of rows that overlap (a build inside a
``serve()`` call, inside ``warm_prefill_widths``) are counted once: every
sum of intervals here is the measure of their union.

``parts(record)`` computes all eight once a record, prints ONE
``{"phase": "startup", ...}`` line (the builds by program, the totals of
ALL builds, ``own`` or not, and the builds that began after the cut), and
returns None on a program without the recorder.
"""
import importlib
import json

BUILT_IN_PROGRAM = ("before_program", "package_import", "engine_init",
                    "warm_prefill_widths", "engine_traffic")
LOADS = ("compile_cache", "kept_executable")


def measure(intervals):
    """Seconds the union of ``intervals`` [(start, end)] covers."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def _span(row):
    return (row["start_s"], row["end_s"])


def _inside(intervals, outer):
    """Seconds of ``intervals``' union that lie inside ``outer``'s."""
    both = measure(intervals + outer)
    return measure(intervals) + measure(outer) - both


def split(rows, setup_s):
    """The eight metrics of ``rows`` (the ledger's) cut at ``setup_s``."""
    rows = [r for r in rows if r["start_s"] < setup_s]
    kind = {}
    for r in rows:
        kind.setdefault(r["kind"], []).append(r)
    builds = kind.get("program_build", [])
    own = [r for r in builds if r["own"]]
    traffic = [_span(r) for r in kind.get("engine_traffic", [])]
    inside = [_span(r) for r in rows
              if r["kind"] in BUILT_IN_PROGRAM] + [_span(r) for r in own]
    return {
        "setup_before_program_s": measure(
            [_span(r) for r in kind.get("before_program", [])]),
        "setup_program_init_s": measure(
            [_span(r) for k in ("package_import", "engine_init")
             for r in kind.get(k, [])]),
        "setup_program_trace_lower_s": sum(
            r["trace_s"] + r["lower_s"] for r in own),
        "setup_program_compile_s": sum(
            r["backend_s"] for r in own if r["source"] == "compiled"),
        "setup_program_cache_load_s": sum(
            r["backend_s"] for r in own if r["source"] in LOADS),
        "setup_programs_built": len(own),
        "setup_engine_traffic_s": measure(traffic) - _inside(
            [_span(r) for r in builds], traffic),
        "setup_outside_program_s": setup_s - measure(inside),
    }


def by_program(builds):
    out = {}
    for r in builds:
        p = out.setdefault(r["program"] + (
            f"@{r['width']}" if "width" in r else ""), {
            "n": 0, "own": r["own"], "trace_s": 0.0, "lower_s": 0.0,
            "backend_s": 0.0, "source": {}})
        p["n"] += 1
        p["source"][r["source"]] = p["source"].get(r["source"], 0) + 1
        for part in ("trace_s", "lower_s", "backend_s"):
            p[part] += r[part]
    return out


def parts(record):
    """The eight metrics of this process's start (None: the program has
    no recorder); the ``startup`` line is printed at the first call."""
    if "_startup_parts" in record:
        return record["_startup_parts"]
    try:
        startup = importlib.import_module("deepspeed_tpu.monitor.startup")
    except ImportError:
        record["_startup_parts"] = None
        return None
    setup_s = float(record["end_to_end"]["setup_s"])
    snap = startup.snapshot()
    values = split(snap["rows"], setup_s)
    builds = [r for r in snap["rows"] if r["kind"] == "program_build"]
    before = [r for r in builds if r["start_s"] < setup_s]
    print(json.dumps({
        "phase": "startup", "clock": snap["clock"], "cut_s": setup_s,
        "first_useful_s": snap["first_useful_s"], "parts": values,
        # 0: the package was imported before ``jax.devices()`` ran, whose
        # seconds are then in ``setup_outside_program_s``
        "backend_up_at_import": next(
            (r.get("backend_up") for r in snap["rows"]
             if r["kind"] == "package_import"), None),
        "all_builds": {
            "n": len(before),
            "trace_lower_s": sum(r["trace_s"] + r["lower_s"]
                                 for r in before),
            "compile_s": sum(r["backend_s"] for r in before
                             if r["source"] == "compiled"),
            "cache_load_s": sum(r["backend_s"] for r in before
                                if r["source"] in LOADS)},
        "by_program": by_program(before),
        "spans": {k: v for k, v in snap["by_kind"].items()
                  if k not in ("program_build", "engine_traffic")},
        "builds_after_cut": [
            {"program": r["program"], "own": r["own"],
             "start_s": r["start_s"], "source": r["source"],
             "seconds": r["end_s"] - r["start_s"]}
            for r in builds if r["start_s"] >= setup_s],
        "dropped": snap["dropped"]}), flush=True)
    record["_startup_parts"] = values
    return values


def read(record, name):
    values = parts(record)
    return None if values is None else values[name]

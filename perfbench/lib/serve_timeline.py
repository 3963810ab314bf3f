"""The serving loop's timeline in a traced window: every stream's
inter-token interval and what filled it, from the args the program puts
on its ``emit`` and ``prefill`` spans, and the two stretches of idle
device that lie inside no host span's own time: after a ``decode_step``
execution's last operation until the ``decode_fetch`` that waited for it
returns (the copy back and the thread's wake-up), and from the start of a
``decode_dispatch`` to the execution's first operation.

An ``emit`` span is one row of ``ServingAggregator``'s timeline
(``deepspeed_tpu/monitor/serving.py``): ``gap_ms`` is the time since the
emission before, ``continuing`` the streams that waited all of it (a
stream admitted inside the interval waited less and is left out here;
``snapshot()["itl_ms"]`` has its first interval too), ``stall_ms`` the
part of it in other requests' prefill and copies.  A program without
these args (the parent of PR 36) gives ``None`` everywhere.

Host spans and device operations lie on one clock in an ``.xplane.pb``
(``lib/program_trace.py``'s gap labels rest on the same fact).
"""
import bisect
import json
import os
import statistics
import time

from perfbench.lib import program_trace, xplane

DECODE_PROGRAM = "decode_step"


# ------------------------------------------------------------------ #
# From span args
# ------------------------------------------------------------------ #
def intervals(spans: dict) -> list:
    """[(interval ms, streams that waited it, stall ms)] of the window's
    ``emit`` spans that carry the args and that a stream waited."""
    out = []
    for _, _, a in spans.get("emit", []):
        if a.get("gap_ms") is None:
            continue
        waited = a.get("continuing", a.get("streams"))
        if waited:
            out.append((float(a["gap_ms"]), int(waited),
                        float(a.get("stall_ms") or 0.0)))
    return out


def weighted_percentile(pairs, q: float) -> float:
    """Nearest rank over each value repeated by its whole weight."""
    pairs = sorted(pairs)
    total = sum(w for _, w in pairs)
    k = int(round(q / 100.0 * (total - 1)))
    for value, w in pairs:
        k -= w
        if k < 0:
            return value
    return pairs[-1][0]


def itl_split_ms(spans: dict) -> dict:
    """The window's mean interval and its parts, in ms, weighted as the
    percentiles are: ``stall`` (``stall_ms``), ``host`` (``host_ms``) and
    ``decode_wait`` (the rest: ``decode_dispatch`` + ``decode_fetch``).
    ``{}`` without the args."""
    rows = [(float(a["gap_ms"]), float(a.get("stall_ms") or 0.0),
             float(a.get("host_ms") or 0.0),
             int(a.get("continuing", a.get("streams")) or 0))
            for _, _, a in spans.get("emit", [])
            if a.get("gap_ms") is not None]
    n = sum(w for *_, w in rows)
    if not n:
        return {}
    mean, stall, host = (sum(r[i] * r[3] for r in rows) / n
                         for i in range(3))
    return {"mean": mean, "decode_wait": mean - stall - host,
            "stall": stall, "host": host, "n": n}


def itl_percentile_ms(spans: dict, q: float):
    rows = intervals(spans)
    if not rows:
        return None
    return weighted_percentile([(g, w) for g, w, _ in rows], q)


def itl_stall_share(spans: dict):
    """Percent of all streams' inter-token time spent in other requests'
    prefill and copies."""
    rows = intervals(spans)
    waited = sum(g * w for g, w, _ in rows)
    if not waited:
        return None
    return 100.0 * sum(s * w for _, w, s in rows) / waited


def prefill_row_fill(spans: dict):
    """Percent: rows the window's prefills needed (prompt - cached) over
    the rows their chunk programs computed."""
    need = done = 0
    for _, _, a in spans.get("prefill", []):
        if a.get("rows_computed"):
            need += int(a.get("prompt_tokens", 0)) \
                - int(a.get("cached_tokens", 0))
            done += int(a["rows_computed"])
    return 100.0 * need / done if done else None


# ------------------------------------------------------------------ #
# From the device's line and the spans together
# ------------------------------------------------------------------ #
def executions(lines: dict, program: str = DECODE_PROGRAM) -> list:
    """[(first op start, last op end)] in ns of a device plane's whole
    executions of ``program``, in order: the operations that start
    inside each ``XLA Modules`` event of that name.  ``lines`` is the
    plane as ``xplane.read_planes`` gives it."""
    ops = sorted((s, s + d) for _, s, d in lines.get(xplane.OPS_LINE, []))
    starts = [s for s, _ in ops]
    out = []
    for name, s, d in lines.get(xplane.MODULES_LINE, []):
        if program not in xplane.module_name(name):
            continue
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, s + d)
        if i < j:
            out.append((ops[i][0], max(e for _, e in ops[i:j])))
    return sorted(out)


def fetch_tails_ms(execs: list, spans: dict) -> list:
    """Per ``decode_fetch`` span that waited for an execution (one ended
    inside it, or before it with nothing since): the time from the
    execution's last operation (or the span's start, if later) to the
    span's end."""
    ends = [e for _, e in execs]
    out, last = [], -1
    for start, dur, _ in spans.get("decode_fetch", []):
        i = bisect.bisect_right(ends, start + dur) - 1
        if i <= last:        # no execution of its own inside the window
            continue
        last = i
        out.append((start + dur - max(ends[i], start)) / 1e6)
    return out


def launches_ms(execs: list, busy: list, spans: dict) -> list:
    """Per ``decode_dispatch`` span at whose start the device was idle:
    the time from the span's start to the first operation of the
    execution it launched (the first to begin after it, before the next
    dispatch)."""
    firsts = [s for s, _ in execs]
    busy_starts = [a for a, _ in busy]
    rows = spans.get("decode_dispatch", [])
    nexts = [r[0] for r in rows[1:]] + [float("inf")]
    out = []
    for (start, _, _), nxt in zip(rows, nexts):
        k = bisect.bisect_right(busy_starts, start) - 1
        if k >= 0 and busy[k][1] > start:
            continue         # still busy: the launch hides behind work
        i = bisect.bisect_left(firsts, start)
        if i < len(firsts) and firsts[i] < nxt:
            out.append((firsts[i] - start) / 1e6)
    return out


def idle_by_span(busy: list, spans: dict) -> dict:
    """{host span name: seconds the device was idle inside it}: every
    gap between two busy intervals, cut at the host spans' edges, each
    piece filed under the innermost span that covers it
    (``no_program_span`` outside them all).  The sum is the window's idle
    time exactly."""
    flat = sorted((s, s + d, n) for n, rows in spans.items()
                  for s, d, _ in rows)
    starts = [f[0] for f in flat]
    out = {}
    for (_, a), (b, _) in zip(busy, busy[1:]):
        t = a
        while t < b:
            k = bisect.bisect_right(starts, t)
            cover = next((flat[j] for j in range(k - 1, max(k - 65, -1), -1)
                          if flat[j][1] > t), None)
            end = min(b, cover[1]) if cover else b
            if k < len(flat):
                end = min(end, flat[k][0])
            name = cover[2] if cover else "no_program_span"
            out[name] = out.get(name, 0.0) + (end - t) / 1e9
            t = end
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


# ------------------------------------------------------------------ #
# One traced run
# ------------------------------------------------------------------ #
def from_spans(spans: dict) -> dict:
    """The four metrics the span args alone give (``None`` without the
    args) and, for the record, p90 / p95 beside the p99 (which a window
    of a hundred rows reads off its worst one or two), the number of
    intervals, the mean interval's split and the spans' median
    durations."""
    return {"itl_p50_ms": itl_percentile_ms(spans, 50),
            "itl_p99_ms": itl_percentile_ms(spans, 99),
            "itl_stall_share": itl_stall_share(spans),
            "prefill_row_fill": prefill_row_fill(spans),
            "itl_p90_ms": itl_percentile_ms(spans, 90),
            "itl_p95_ms": itl_percentile_ms(spans, 95),
            "intervals": len(intervals(spans)),
            "itl_split_ms": itl_split_ms(spans),
            "span_median_ms": {
                n: statistics.median(d for _, d, _ in rows) / 1e6
                for n, rows in spans.items()}}


def from_device(path: str, spans: dict) -> dict:
    """The two metrics that need device 0's lines beside the spans
    (``None`` without either) and, for the record, the device's idle
    seconds by the host span they lie in.  The lines are read as the
    runner reads them for ``busy_s`` (``xplane.read_planes``: whole
    nanoseconds)."""
    out = {"idle_fetch_tail_ms_per_iter": None,
           "idle_launch_ms_per_iter": None}
    dev = sorted((int(m.group(1)), lines)
                 for n, lines in xplane.read_planes(path).items()
                 if (m := xplane.DEVICE_PLANE.match(n))
                 and lines.get(xplane.OPS_LINE))
    if not dev:
        return out
    lines = dev[0][1]
    execs = executions(lines)
    busy = xplane.busy_intervals(lines[xplane.OPS_LINE])
    tails = fetch_tails_ms(execs, spans)
    launches = launches_ms(execs, busy, spans)
    if tails:
        out["idle_fetch_tail_ms_per_iter"] = statistics.median(tails)
    if launches:
        out["idle_launch_ms_per_iter"] = statistics.median(launches)
    out["fetch_tails"], out["launches"] = len(tails), len(launches)
    out["idle_s_by_span"] = idle_by_span(busy, spans)
    out["idle_s"] = sum(out["idle_s_by_span"].values())
    return out


def reduce(path: str) -> dict:
    """Everything above from one ``.xplane.pb`` on its own (a recorded
    file, a test)."""
    spans = program_trace.host_spans(program_trace.read_xspace(path))
    return {**from_spans(spans), **from_device(path, spans)}


# ------------------------------------------------------------------ #
# The traced run of THIS process
# ------------------------------------------------------------------ #
_CACHE = {}
FROM_DEVICE = ("idle_fetch_tail_ms_per_iter", "idle_launch_ms_per_iter")


def metric(record, key: str):
    """``key`` for this process's own ``--trace 1`` serve run, or
    ``None``.  The spans are the ones ``program_trace.current`` has read
    (and vetted the file of); only the two ``FROM_DEVICE`` keys open the
    file again, for device 0's lines.  The first read of either kind
    prints one line with all it found."""
    tr = program_trace.current(record)
    if tr is None or record.get("kind") != "serve":
        return None
    part = "device" if key in FROM_DEVICE else "spans"
    if part not in _CACHE:
        t0 = time.perf_counter()
        if part == "spans":
            _CACHE[part] = from_spans(tr["spans"])
        else:
            # The file ``current`` has read, under the cell's name as
            # ``scope_trace`` / ``retention_trace`` find it.
            _CACHE[part] = from_device(xplane.find_xplane(os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                ".out", "trace", program_trace._this_cell())), tr["spans"])
        print(json.dumps({"phase": f"serve_timeline_{part}", **_CACHE[part],
                          "read_s": time.perf_counter() - t0}), flush=True)
    return _CACHE[part].get(key)

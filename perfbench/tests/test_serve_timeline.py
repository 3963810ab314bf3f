"""perfbench/lib/serve_timeline.py: the interval, fill, fetch-tail and
launch arithmetic on hand-made spans and device lines with a known
answer, and the whole reduction on one small recorded chip trace
(``data/toy_serve_timeline.xplane.pb``: half a second of the rehearsal's
two-layer toy served on one v5e chip by this benchmark's own serve runner,
on the program as PR 36 leaves it; the ``/host:metadata`` plane taken out
to keep it small) and on a recorded trace of a program without the args
(``data/toy_train_scoped.xplane.pb``)."""
import json
import os
import sys

import pytest

from perfbench.lib import program_trace as pt
from perfbench.lib import serve_timeline as st
from perfbench.lib import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
SERVED = os.path.join(HERE, "data", "toy_serve_timeline.xplane.pb")
NO_ARGS = os.path.join(HERE, "data", "toy_train_scoped.xplane.pb")
MS = 1e6        # ns


def _emit(gap, streams, continuing, stall):
    return (0.0, 0.1 * MS, {"row": 0, "streams": streams,
                            "continuing": continuing, "gap_ms": gap,
                            "stall_ms": stall, "host_ms": 1.0})


def test_intervals_are_weighted_by_the_streams_that_waited():
    # 10 ms waited by 3 streams, 30 ms (20 of it another's prefill) by 1,
    # and the first emission after an idle wait, which nobody waited
    spans = {"emit": [_emit(10.0, 3, 3, 0.0), _emit(30.0, 2, 1, 20.0),
                      _emit(900.0, 2, 0, 0.0), _emit(10.0, 2, 2, 0.0)]}
    assert st.intervals(spans) == [(10.0, 3, 0.0), (30.0, 1, 20.0),
                                   (10.0, 2, 0.0)]
    # six intervals: 10 x 5, 30 x 1
    assert st.itl_percentile_ms(spans, 50) == 10.0
    assert st.itl_percentile_ms(spans, 99) == 30.0
    assert st.itl_percentile_ms(spans, 80) == 10.0
    assert st.itl_stall_share(spans) == pytest.approx(100 * 20 / 80)
    # the mean interval's parts: 80 ms over six intervals, 20 of them
    # stall, 1 ms of host each
    assert st.itl_split_ms(spans) == pytest.approx(
        {"mean": 80 / 6, "stall": 20 / 6, "host": 1.0,
         "decode_wait": 80 / 6 - 20 / 6 - 1.0, "n": 6})
    # without ``continuing`` every live stream counts
    for _, _, a in spans["emit"]:
        del a["continuing"]
    assert [w for _, w, _ in st.intervals(spans)] == [3, 2, 2, 2]


def test_weighted_percentile_is_nearest_rank_over_repeats():
    pairs = [(5.0, 2), (1.0, 3), (9.0, 1)]
    flat = sorted([5.0] * 2 + [1.0] * 3 + [9.0])
    for q in (0, 25, 50, 75, 95, 100):
        assert st.weighted_percentile(pairs, q) == \
            flat[int(round(q / 100 * (len(flat) - 1)))]


def test_a_program_without_the_args_reads_none():
    spans = {"emit": [(0.0, MS, {"finished": "3"})],
             "prefill": [(0.0, MS, {"prompt_tokens": 100,
                                    "cached_tokens": 0, "chunks": 1})]}
    assert st.intervals(spans) == [] and st.itl_split_ms(spans) == {}
    assert st.itl_percentile_ms(spans, 50) is None
    assert st.itl_stall_share(spans) is None
    assert st.prefill_row_fill(spans) is None
    assert st.prefill_row_fill({}) is None


def test_prefill_row_fill_is_needed_over_computed():
    spans = {"prefill": [
        (0.0, MS, {"prompt_tokens": 8300, "cached_tokens": 8192,
                   "chunks": 1, "rows_computed": 512}),
        (0.0, MS, {"prompt_tokens": 700, "cached_tokens": 0,
                   "chunks": 2, "rows_computed": 1024})]}
    assert st.prefill_row_fill(spans) == pytest.approx(
        100 * (108 + 700) / 1536)


def _device(ops, modules):
    """A device plane's lines, as ``xplane.read_planes`` gives them, from
    (start, dur) operations and (name, start, dur) program executions, in
    ms."""
    return {xplane.OPS_LINE: [("%fusion.1 = ...", s * MS, d * MS)
                              for s, d in ops],
            xplane.MODULES_LINE: [(name, s * MS, d * MS)
                                  for name, s, d in modules]}


def test_fetch_tail_and_launch_on_a_hand_made_iteration():
    # iteration A: dispatch at 0 finds the device idle; the execution's
    # operations run 0.4-5.0; the fetch (1.0-5.7) returns 0.7 after them.
    # a prefill execution 6.0-9.0 (not a decode_step).
    # iteration B: dispatch at 8.5 while the prefill still runs (no
    # launch counted); execution 9.0-14.0; the fetch starts at 14.5, after
    # the device finished: all of its 0.3 is tail.
    # iteration C: dispatch at 15.0, idle; execution starts 15.2 and the
    # window cuts it: no fetch.
    plane = _device(
        ops=[(0.4, 2.0), (2.4, 2.6), (6.0, 3.0), (9.0, 5.0), (15.2, 1.0)],
        modules=[("jit_decode_step(11)", 0.4, 4.6),
                 ("jit_prefill_step(7)", 6.0, 3.0),
                 ("jit_decode_step(11)", 9.0, 5.0),
                 ("jit_decode_step(11)", 15.2, 1.0)])
    spans = {"decode_dispatch": [(0.0, 0.3 * MS, {}), (8.5 * MS, 0.3 * MS, {}),
                                 (15.0 * MS, 0.3 * MS, {})],
             "decode_fetch": [(1.0 * MS, 4.7 * MS, {}),
                              (14.5 * MS, 0.3 * MS, {})]}
    execs = st.executions(plane)
    assert execs == [(0.4 * MS, 5.0 * MS), (9.0 * MS, 14.0 * MS),
                     (15.2 * MS, 16.2 * MS)]
    assert st.fetch_tails_ms(execs, spans) == pytest.approx([0.7, 0.3])
    busy = xplane.busy_intervals(plane[xplane.OPS_LINE])
    assert st.launches_ms(execs, busy, spans) == pytest.approx([0.4, 0.2])
    # idle by span: 0.4 before A's first operation is outside the busy
    # range; 5.0-6.0 = 0.7 in A's fetch + 0.3 outside every span; 14.0-15.2
    # = 0.5 outside, 0.3 in B's fetch, 0.2 outside, 0.2 in C's dispatch
    idle = st.idle_by_span(busy, spans)
    assert idle == pytest.approx({"decode_fetch": 1.0e-3,
                                  "no_program_span": 1.0e-3,
                                  "decode_dispatch": 0.2e-3})
    assert sum(idle.values()) == pytest.approx(
        (16.2 - 0.4 - sum(d for _, d in [(0.4, 4.6), (6.0, 3.0), (9.0, 5.0),
                                         (15.2, 1.0)])) * 1e-3)
    # a fetch whose execution the window's start cut off waits for none
    early = {"decode_fetch": [(0.0, 0.2 * MS, {})] + spans["decode_fetch"]}
    assert st.fetch_tails_ms(execs, early) == pytest.approx([0.7, 0.3])


def test_the_recorded_trace_without_the_args_reads_none_and_does_not_raise():
    out = st.reduce(NO_ARGS)
    assert out["intervals"] == 0
    for key in ("itl_p50_ms", "itl_p99_ms", "itl_stall_share",
                "prefill_row_fill", "idle_fetch_tail_ms_per_iter",
                "idle_launch_ms_per_iter"):
        assert out[key] is None, key


def test_metric_is_none_off_a_traced_serve_run():
    assert st.metric(None, "itl_p50_ms") is None
    assert st.metric({"trace": None, "kind": "serve"}, "itl_p50_ms") is None
    assert st.metric({"trace": {"busy_s": 1}, "kind": "train"},
                     "itl_p50_ms") is None


@pytest.fixture(scope="module")
def served():
    return st.reduce(SERVED), pt.reduce(SERVED)


def test_metric_reads_currents_spans_and_opens_the_file_for_two(
        served, monkeypatch, capsys):
    out, tr = served
    monkeypatch.setattr(pt, "_CACHE", {"trace": tr})     # current() has read
    monkeypatch.setattr(st, "_CACHE", {})
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "a.cell"])
    opened = []

    def find(trace_dir):
        opened.append(trace_dir)
        return SERVED
    monkeypatch.setattr(xplane, "find_xplane", find)
    record = {"trace": {"busy_s": 1.0}, "kind": "serve"}
    for key in ("itl_p50_ms", "itl_p99_ms", "itl_stall_share",
                "prefill_row_fill"):
        assert st.metric(record, key) == out[key] is not None
    assert opened == []              # the span args need no second read
    for key in st.FROM_DEVICE:
        assert st.metric(record, key) == out[key] is not None
    assert len(opened) == 1 and opened[0].endswith(
        os.path.join("perfbench", ".out", "trace", "a.cell"))
    phases = [json.loads(line)["phase"]
              for line in capsys.readouterr().out.splitlines()]
    assert phases == ["serve_timeline_spans", "serve_timeline_device"]


def test_the_recorded_serve_trace_reads_all_six(served):
    out, tr = served
    emits = tr["spans"]["emit"]
    assert emits and all({"row", "streams", "continuing", "gap_ms",
                          "stall_ms", "host_ms"} <= set(a)
                         for _, _, a in emits)
    rows = [a["row"] for _, _, a in emits]
    assert rows == list(range(rows[0], rows[0] + len(rows)))
    assert 0 < out["intervals"] <= len(emits)
    # an interval is the distance between two emissions on the
    # profiler's clock too (the program reads its own clock just inside
    # the span): to a tenth of a millisecond
    for (s0, _, _), (s1, _, a) in zip(emits, emits[1:]):
        assert a["gap_ms"] == pytest.approx((s1 - s0) / MS, abs=0.1)
    assert 0 < out["itl_p50_ms"] <= out["itl_p99_ms"]
    assert 0 <= out["itl_stall_share"] < 100
    assert 0 < out["prefill_row_fill"] <= 100
    # the toy's iteration is a fraction of a millisecond of device work:
    # both idle stretches are there and shorter than an interval
    assert 0 < out["idle_fetch_tail_ms_per_iter"] < out["itl_p50_ms"]
    assert 0 < out["idle_launch_ms_per_iter"] < out["itl_p50_ms"]
    assert out["fetch_tails"] >= len(tr["spans"]["decode_fetch"]) - 2
    assert out["launches"] > 0
    # the idle seconds by span are the device's idle seconds
    ops = xplane.read_planes(SERVED)["/device:TPU:0"][xplane.OPS_LINE]
    assert out["idle_s"] == pytest.approx(
        (max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)
         - xplane.union_ns(ops)) / 1e9, rel=1e-9)
    # (whole nanoseconds, as the runner's ``busy_s``: within a nanosecond
    # an operation of ``program_trace``'s picosecond reading)
    assert out["idle_s"] == pytest.approx(
        tr["window_s"] - xplane.union_ns(
            [("", s, d) for _, s, d, _ in pt.read_xspace(SERVED)[
                "/device:TPU:0"]["lines"][xplane.OPS_LINE]]) / 1e9,
        abs=2e-9 * len(ops))
    assert out["idle_s_by_span"]["decode_fetch"] > 0

"""The serving loop's one timeline (monitor/serving.py): rows of the
iterations, every stream's inter-token interval read from them, what
filled it, and the worst stalls — on a clock the test drives.

The fake engine below advances the clock itself (nothing else does: the
scheduler only reads it), laps where the real engine does, and keeps its
OWN record of every token's time by request, which the rows have to
reproduce.  No test here waits on the wall clock.
"""
import logging
import types

import numpy as np
import pytest

from deepspeed_tpu.inference import scheduler as scheduler_mod
from deepspeed_tpu.inference.scheduler import (ContinuousBatchingScheduler,
                                               Request)
from deepspeed_tpu.monitor import serving as serving_mod
from deepspeed_tpu.monitor.request_trace import RequestTrace
from deepspeed_tpu.monitor.serving import (COL, COLUMNS, GAP_PARTS,
                                           ServingAggregator, _pcts,
                                           percentile, weighted_percentile)


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class _Span:
    def __init__(self, log, name, args):
        self.args = dict(args)
        log.append((name, self.args))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        self.args.update(args)


class _Tel:
    enabled = False
    recompile_count = 0

    def __init__(self):
        self.spans = []

    def span(self, name, **args):
        return _Span(self.spans, name, args)

    def heartbeat(self):
        pass


class FakeEngine:
    """Scheduler-facing surface of ``InferenceEngine`` over a driven
    clock: each operation takes a fixed time, filed in the timeline where
    the real engine files it.  ``stall`` = {iteration: (part, seconds)}
    adds seconds to one part of one iteration.  ``ahead``: the real
    engine's order — a call dispatches the next iteration and only then
    fetches the one in flight — where the plain fake is synchronous."""
    max_len, dp = 10_000, 1
    COST = {"tables_s": 0.0004, "dispatch_s": 0.0011, "fetch_s": 0.0080,
            "advance_s": 0.0006, "prefill_s": 0.0200, "copy_s": 0.0003}

    def __init__(self, clock, slots=4, chunk=0, spec_k=0, stall=None,
                 ahead=False):
        self.clock, self.max_slots = clock, slots
        self.ahead, self.flight = ahead, None
        self.prefill_chunk, self.spec_k = chunk, spec_k
        self.spec_enabled = spec_k > 0
        self.telemetry = _Tel()
        self.serving = ServingAggregator(slots, clock=clock)
        self.active = np.zeros(slots, bool)
        self.slot_rid = {}
        self.lengths = np.zeros(slots, int)
        self.token_times = {}        # rid -> [time of each token]
        self.iterations = 0
        self.stall = stall or {}

    # -- admission -- #
    def select_slot(self, prompt, max_new_tokens=0, exclude_groups=()):
        free = np.flatnonzero(~self.active)
        if not len(free) or 0 in exclude_groups:
            return None
        return int(free[0])

    def group_of(self, slot):
        return 0

    def _prefill(self, slot, prompt, rid):
        self.clock.t += self.COST["copy_s"]
        self.serving.lap("prefill_s")       # as _copy_blocks does
        self.serving.lap("copy_s")
        self.clock.t += self.COST["prefill_s"]
        self.slot_rid[slot] = rid
        self.token_times[rid] = [self.clock.t]

    def prefill(self, prompt, slot, temperature=0.0, max_new_tokens=0,
                rid=None):
        self.serving.lap("admit_s")
        self._prefill(slot, prompt, rid)
        self.serving.note_prefill_pass(1, len(prompt), 64)
        return 7, None

    def prefill_many(self, admissions, temperature=0.0, rids=None):
        self.serving.lap("admit_s")
        for (slot, prompt, _), rid in zip(admissions, rids):
            self._prefill(slot, prompt, rid)
        self.serving.note_prefill_pass(
            len(admissions), sum(len(p) for _, p, _ in admissions),
            64 * len(admissions))
        return [(7, None)] * len(admissions)

    def activate_slot(self, slot, n, tok):
        self.active[slot] = True
        self.lengths[slot] = n

    def release_slot(self, slot):
        self.active[slot] = False

    def context_len(self, slot):
        return int(self.lengths[slot])

    def complete_request(self, rid, ttft_s, tpot_s, **kw):
        self.serving.note_request(ttft_s, tpot_s, kw["new_tokens"])

    # -- decode -- #
    def _iterate(self, counts):
        lap = self.serving.lap
        t0 = lap("other_s")
        extra_part, extra = self.stall.get(self.iterations, ("", 0.0))
        for part in ("tables_s", "dispatch_s", "fetch_s", "advance_s"):
            self.clock.t += self.COST[part] \
                + (extra if part == extra_part else 0.0)
            now = lap(part)
        n = int(self.active.sum())
        self.iterations += 1
        self.serving.note_iteration(
            n, now - t0, cache_bytes=1000 * n + 17 * self.iterations,
            context_tokens=int(self.lengths[self.active].sum()),
            emitted_tokens=int(counts[self.active].sum()))
        for slot in np.flatnonzero(self.active):
            self.lengths[slot] += counts[slot]
            self.token_times[self.slot_rid[slot]] += \
                [self.clock.t] * int(counts[slot])

    def decode_once(self, temperature=0.0, continuing=()):
        if self.ahead:
            return self._ahead(list(continuing))
        # synchronous: the iteration it fetches is the one it dispatched
        self._iterate(np.ones(self.max_slots, int))
        return np.full(self.max_slots, 5, np.int32), self.active.copy()

    def _ahead(self, continuing):
        lap = self.serving.lap
        t0 = lap("other_s")
        due, self.flight = self.flight, None
        if continuing:
            for part in ("tables_s", "dispatch_s"):
                self.clock.t += self.COST[part]
                lap(part)
            mask = np.zeros(self.max_slots, bool)
            mask[continuing] = True
            self.flight = (mask, t0, int(due is not None))
        if due is None:
            return None, None
        mask, t0, ahead = due
        for part in ("fetch_s", "advance_s"):
            self.clock.t += self.COST[part]
            now = lap(part)
        self.iterations += 1
        self.serving.note_iteration(
            int(mask.sum()), now - t0,
            cache_bytes=1000 * int(mask.sum()) + 17 * self.iterations,
            context_tokens=int(self.lengths[mask].sum()), ahead=ahead)
        for slot in np.flatnonzero(mask):
            self.lengths[slot] += 1
            self.token_times[self.slot_rid[slot]].append(self.clock.t)
        return np.full(self.max_slots, 5, np.int32), mask

    def decode_discard(self):
        self.flight = None

    def spec_decode_once(self, temperature=0.0):
        k = self.spec_k
        n_new = 1 + (np.arange(self.max_slots) + self.iterations) % (k + 1)
        self._iterate(n_new)
        return np.full((self.max_slots, k + 1), 5, np.int32), n_new


def _requests(n, gap_s=0.013, new=(6, 17)):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=np.arange(10 + i, dtype=np.int32),
                    max_new_tokens=int(rng.integers(*new)),
                    arrival_s=i * gap_s) for i in range(n)]


def _serve(engine, reqs, **kw):
    """Serve on the engine's driven clock: the scheduler's idle wait (all
    it asks of ``time``) moves that clock and waits for nothing."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(scheduler_mod, "time",
                   types.SimpleNamespace(sleep=engine.clock.sleep))
        return ContinuousBatchingScheduler(engine, **kw).serve(reqs)


MODES = [pytest.param(dict(chunk=0), id="plain"),
         pytest.param(dict(chunk=64), id="plain-batched-prefill"),
         pytest.param(dict(chunk=64, spec_k=3), id="speculative"),
         pytest.param(dict(chunk=64, ahead=True), id="ahead")]


@pytest.fixture(params=MODES)
def run(request, monkeypatch):
    # as in a profiler session: the spans' args are recorded
    monkeypatch.setattr(scheduler_mod, "spans_recorded", lambda tel: True)
    eng = FakeEngine(Clock(), **request.param)
    reqs = _requests(12)
    return eng, reqs, _serve(eng, reqs)


def test_token_times_equal_the_engines_own_recording(run):
    eng, reqs, report = run
    assert report["completed"] == len(reqs)
    for r in reqs:
        own = eng.token_times[r.rid]
        assert len(own) >= len(r.out_tokens) >= 2   # (a budget may cut a row)
        # 1..k+1 tokens of one row arrive together: one delivery
        np.testing.assert_array_equal(r.token_times(), np.unique(own))
        assert r.row_last - r.row_first + 2 == len(r.token_times())


@pytest.mark.parametrize("ahead", [False, True])
def test_requests_cut_by_the_windows_end_keep_their_rows(ahead):
    eng = FakeEngine(Clock(), ahead=ahead)
    reqs = _requests(6, new=(40, 50))
    report = _serve(eng, reqs, max_wall_s=0.2)
    assert report["unfinished"] == 2 and report["completed"] == 0
    cut = [r for r in reqs if r.t_first is not None
           and len(r.out_tokens) < r.max_new_tokens]
    assert len(cut) == 4            # every slot held one
    for r in cut:
        assert r.row_last == eng.serving.rows - 1
        np.testing.assert_array_equal(r.token_times(),
                                      eng.token_times[r.rid])
    never = [r for r in reqs if r.t_first is None]
    assert never and all(len(r.token_times()) == 0 for r in never)


def test_a_request_admitted_between_rows_starts_from_its_first_token(run):
    eng, reqs, _ = run
    agg = eng.serving
    late = [r for r in reqs if r.row_first > 0]
    assert late
    for r in late:
        t = r.token_times()
        row = agg._rows[r.row_first]
        assert t[0] == r.t_first and t[1] == row[COL["t_emit"]]
        # shorter than the interval the continuing streams waited (than
        # that and the one before, where its first row is the one after
        # the next: an iteration without it was in flight)
        before = agg._rows[r.row_first - 1][COL["gap_s"]] if eng.ahead else 0
        assert 0 < t[1] - t[0] < row[COL["gap_s"]] + before
        assert row[COL["admitted"]] >= 1


def test_the_rows_hold_every_interval_of_every_stream(run):
    eng, reqs, report = run
    itl = eng.serving.intervals()
    times = [r.token_times() for r in reqs]
    assert itl["n"] == sum(len(t) - 1 for t in times) == report["itl_ms"]["n"]
    assert itl["total_s"] == pytest.approx(
        sum(t[-1] - t[0] for t in times), abs=1e-9)
    every = np.sort(np.concatenate([np.diff(t) for t in times]))
    assert report["itl_ms"]["max"] == pytest.approx(every[-1] * 1e3, abs=1e-3)
    # continuing streams' intervals are exact; an admitted stream's first
    # interval enters as its row's mean first interval
    assert report["itl_ms"]["p50"] == pytest.approx(
        percentile(every.tolist(), 50) * 1e3, abs=1e-3)


def test_the_splits_parts_sum_to_the_mean_interval(run):
    eng, _, report = run
    split = report["itl_split_ms"]
    assert set(split) == {"decode_wait", "stall", "host"}
    assert sum(split.values()) == pytest.approx(report["itl_ms"]["mean"],
                                                abs=1e-9)
    assert all(v > 0 for v in split.values())
    # a row's parts are its interval, nothing left over
    table = eng.serving._table()
    parts = table[:, [COL[c] for c in GAP_PARTS]].sum(axis=1)
    np.testing.assert_allclose(parts, table[:, COL["gap_s"]], atol=1e-9)
    # decode wait is what the engine spent in dispatch + fetch
    assert split["decode_wait"] == pytest.approx(
        1e3 * (FakeEngine.COST["dispatch_s"] + FakeEngine.COST["fetch_s"]),
        abs=1e-6)
    assert 0 < report["itl_stalled_share"] < 1
    assert report["prefill_row_fill"] == pytest.approx(
        sum(10 + i for i in range(12)) / (64 * 12), abs=1e-4)
    assert report["stalls"] == []


def test_emit_span_args_are_the_rows(run):
    eng, _, report = run
    emits = [a for n, a in eng.telemetry.spans if n == "emit"]
    table = eng.serving._table()
    assert [a["row"] for a in emits] == list(range(len(table)))
    for a, row in zip(emits, table):
        assert a["continuing"] == row[COL["continuing"]]
        if a["row"]:                 # nobody waited for the first row
            assert a["streams"] == \
                row[COL["continuing"]] + row[COL["admitted"]]
        assert a["gap_ms"] == pytest.approx(row[COL["gap_s"]] * 1e3, abs=1e-4)
        assert a["stall_ms"] == pytest.approx(
            (row[COL["prefill_s"]] + row[COL["copy_s"]]) * 1e3, abs=1e-4)
        assert a["gap_ms"] - a["stall_ms"] - a["host_ms"] == pytest.approx(
            (row[COL["dispatch_s"]] + row[COL["fetch_s"]]) * 1e3, abs=1e-3)


def test_emit_args_are_built_only_for_a_span_something_records(monkeypatch):
    eng = FakeEngine(Clock())        # no profiler session, no trace file
    monkeypatch.setattr(eng.serving, "emit_args",
                        lambda *_: pytest.fail("args built for nobody"))
    report = _serve(eng, _requests(3))
    assert report["itl_ms"]["n"] > 0
    assert all(a == {} for n, a in eng.telemetry.spans if n == "emit")


def test_request_trace_ticks_carry_the_row():
    eng = FakeEngine(Clock())
    trace = RequestTrace(clock=eng.clock)
    reqs = _requests(3)
    # keep the records: complete() drains them
    seen = {}
    orig = trace.tick

    def tick(rid, *a, **kw):
        seen.setdefault(rid, []).append(kw["row"])
        return orig(rid, *a, **kw)
    trace.tick = tick
    _serve(eng, reqs, trace=trace)
    for r in reqs:
        assert seen[r.rid] == list(range(r.row_first, r.row_last + 1))
    tr = RequestTrace()
    tr.enqueue(1, t=0.0)
    tr.tick(1, 2, 1, t=0.5, row=41)
    tr.tick(1, 2, 1, t=0.6)
    assert [m.get("row") for m in tr._live[1].ticks] == [41, None]


# ------------------------------------------------------------------ #
# The keys snapshot() had before the rows
# ------------------------------------------------------------------ #
def _parents_keys(calls, max_slots):
    """``snapshot()``'s per-iteration keys as the lists of the parent
    commit computed them, from the same ``note_iteration`` calls."""
    occ = [a / max_slots for a, *_ in calls]
    ms = [d * 1e3 for _, d, *_ in calls]
    fed = [(cb, ct) for _, _, cb, ct in calls if cb is not None and ct]
    out = {"occupancy_mean": round(sum(occ) / len(occ), 4),
           "occupancy_p50": round(percentile(sorted(occ), 50), 4),
           "decode_step_ms": _pcts(ms)}
    if fed:
        out["hbm_bytes_per_token"] = _pcts([cb / ct for cb, ct in fed])
        out["cache_bytes_p95"] = int(percentile(
            sorted(cb for cb, _ in fed), 95))
    return out


@pytest.mark.parametrize("fed", [True, False], ids=["cache-fed", "bare"])
def test_snapshots_old_keys_equal_the_parents_on_a_recorded_run(fed):
    rng = np.random.default_rng(5)
    calls = [(int(rng.integers(0, 9)), float(rng.uniform(0.004, 0.03)),
              int(rng.integers(10 ** 6, 10 ** 10)) if fed else None,
              int(rng.integers(0, 5000)) if fed else None)
             for _ in range(300)]
    agg = ServingAggregator(8)
    for a, d, cb, ct in calls:
        agg.note_iteration(a, d, cache_bytes=cb, context_tokens=ct)
    snap = agg.snapshot(wall_s=1.0)
    want = _parents_keys(calls, 8)
    assert {k: snap.get(k) for k in want} == want
    assert ("hbm_bytes_per_token" in snap) == fed
    assert snap["iterations"] == 300
    assert snap["decode_tokens"] == sum(a for a, *_ in calls)


def test_attend_keys_equal_the_per_slot_loop():
    """``_attend_work`` is one expression over the live lengths; the
    per-slot loop it replaced is the reference (integers: equal)."""
    from deepspeed_tpu.inference.engine import InferenceEngine

    class Spec:
        block_size, blocks_per_group, num_groups, num_layers = 16, 40, 2, 3
        reach, per_stream = None, False

    def shell(cost):
        eng = object.__new__(InferenceEngine)
        eng.cache_spec, eng.cache_specs, eng.max_slots = Spec, (Spec,), 12
        eng.lengths = np.array([0, 1, 15, 16, 17, 200, 33, 64, 5, 0, 9, 640])
        eng.active = eng.lengths % 2 == 1
        eng._attend_cost = cost
        return eng

    def per_key(context=None, pool_blocks=None, spec=None):  # a K/V pool
        keys = pool_blocks * 16 if pool_blocks is not None \
            else -(-max(1, context) // 16) * 16
        return 4 * 20 * 64 * keys * 3, 2 * keys * 20 * 64 * 2 * 3

    def constant(context=None, pool_blocks=None, spec=None):  # a state
        return 123_456, 7_890

    for cost in (per_key, constant):
        eng = shell(cost)
        live = [cost(context=max(1, int(c)))
                for c in eng.lengths[eng.active]]
        pool = cost(pool_blocks=40)
        for k in (1, 4):
            assert eng._attend_work(k) == (
                sum(f for f, _ in live) * k, pool[0] * k * 12,
                sum(b for _, b in live), pool[1] * 2)
    agg = ServingAggregator(12)
    agg.note_attend(*shell(per_key)._attend_work(1), 6)
    snap = agg.snapshot(wall_s=1.0)
    assert snap["attend"]["flops_per_token"]["kernel"] > 0
    assert snap["attend_work_ratio"] > 1.0


# ------------------------------------------------------------------ #
# Stalls
# ------------------------------------------------------------------ #
def _logged(fn):
    """Messages of the repo's logger (it does not propagate)."""
    records = []

    class H(logging.Handler):
        def emit(self, r):
            records.append(r.getMessage())
    lg, h = logging.getLogger("deepspeed_tpu"), H()
    lg.addHandler(h)
    try:
        fn()
    finally:
        lg.removeHandler(h)
    return records


@pytest.mark.parametrize("part,name", [
    ("fetch_s", "decode_fetch"), ("dispatch_s", "decode_dispatch"),
    ("tables_s", "decode_tables")])
def test_a_stall_through_the_clock_is_named_by_its_column(part, name):
    eng = FakeEngine(Clock(), stall={9: (part, 2.0)})
    reqs = _requests(6, new=(30, 40))
    out = {}
    msgs = _logged(lambda: out.update(_serve(eng, reqs)))
    (stall,) = out["stalls"]
    assert stall["row"] == 9 and stall["in"] == name
    assert stall["gap_ms"] == pytest.approx(2000 + 1e3 * sum(
        FakeEngine.COST[p] for p in ("tables_s", "dispatch_s", "fetch_s",
                                     "advance_s")), abs=1.0)
    assert stall["in_ms"] == pytest.approx(2000, abs=1.0)
    t_row = eng.serving._rows[9, COL["t_emit"]]
    assert stall["at_s"] == pytest.approx(t_row - 100.0, abs=1e-3)
    assert out["itl_ms"]["max"] == stall["gap_ms"]
    (line,) = [m for m in msgs if "stalled interval" in m]
    assert name in line and "row 9" in line


def test_a_stall_between_spans_and_the_cap_of_eight():
    clock = Clock()
    eng = FakeEngine(clock)
    reqs = _requests(4, gap_s=0.0, new=(60, 61))
    orig = eng.decode_once

    def slow(temperature=0.0, **kw):
        if eng.iterations % 5 == 4:      # the scheduler's own thread stood
            clock.t += 0.3 + 0.01 * eng.iterations
        return orig(temperature, **kw)
    eng.decode_once = slow
    report = _serve(eng, reqs)
    assert len(report["stalls"]) == 8
    assert {s["in"] for s in report["stalls"]} == {"between_spans"}
    # eleven stood (rows 4, 9, .. 54): the eight longest, in order of time
    assert [s["row"] for s in report["stalls"]] == list(range(19, 55, 5))


def test_a_run_without_a_stall_logs_nothing():
    eng = FakeEngine(Clock())
    out = {}
    msgs = _logged(lambda: out.update(_serve(eng, _requests(6))))
    assert out["stalls"] == [] and not [m for m in msgs if "stall" in m]
    # an idle wait ahead of a late arrival is nobody's interval
    eng = FakeEngine(Clock())
    late = _requests(3) + [Request(rid=9, prompt=np.arange(8, dtype=np.int32),
                                   max_new_tokens=5, arrival_s=30.0)]
    msgs = _logged(lambda: out.update(_serve(eng, late, idle_sleep_s=0.5)))
    assert out["completed"] == 4 and out["stalls"] == []
    assert not [m for m in msgs if "stall" in m]
    assert any(n == "serve_idle" for n, _ in eng.telemetry.spans)


def test_stalls_are_the_latest_serves_own():
    eng = FakeEngine(Clock(), stall={5: ("fetch_s", 1.0)})
    first = _serve(eng, _requests(4, new=(20, 21)))
    assert [s["row"] for s in first["stalls"]] == [5]
    again = _serve(eng, [Request(rid=50 + r.rid, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens)
                         for r in _requests(4, new=(20, 21))])
    assert again["stalls"] == []
    assert again["itl_ms"]["n"] > first["itl_ms"]["n"]   # rows: both serves


# ------------------------------------------------------------------ #
# The ring
# ------------------------------------------------------------------ #
def test_the_ring_wraps_without_losing_live_requests(monkeypatch):
    monkeypatch.setattr(serving_mod, "RING", 16)
    eng = FakeEngine(Clock(), slots=3)
    long = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                   max_new_tokens=41)
    short = [Request(rid=1 + i, prompt=np.arange(9, dtype=np.int32),
                     max_new_tokens=6, arrival_s=0.07 * i)
             for i in range(7)]
    report = _serve(eng, [long] + short)
    agg = eng.serving
    assert agg.rows == 40 and len(agg._table()) == 16
    assert report["iterations"] == 40 and report["completed"] == 8
    # the long request: its last 16 deliveries, in order, to the last one
    t = long.token_times()
    own = eng.token_times[0]
    np.testing.assert_array_equal(t, [own[0]] + own[-16:])
    # a request whose rows are all still held loses nothing
    last = short[-1]
    assert last.row_first >= agg.rows - 16
    np.testing.assert_array_equal(last.token_times(),
                                  eng.token_times[last.rid])
    # the figures are those of the rows held
    table = agg._table()
    np.testing.assert_array_equal(np.diff(table[:, COL["t_emit"]]) > 0, True)
    assert report["itl_ms"]["n"] == int(
        table[:, COL["continuing"]].sum() + table[:, COL["admitted"]].sum())
    assert report["itl_ms"]["n"] >= 16
    assert sum(report["itl_split_ms"].values()) == pytest.approx(
        report["itl_ms"]["mean"], abs=1e-9)
    assert report["decode_step_ms"]["n"] == 16


def test_an_engine_driven_without_a_scheduler_still_gets_its_rows():
    clock = Clock()
    eng = FakeEngine(clock, slots=2)
    eng._prefill(0, np.arange(5), rid=0)
    eng.activate_slot(0, 5, 7)
    for _ in range(4):
        clock.t += 0.002             # the caller's own work between steps
        eng.decode_once()
    agg = eng.serving
    table = agg._table()
    assert agg.rows == len(table) == 4
    # nobody said who waited: the rows carry no stream's interval
    assert table[:, COL["continuing"]].tolist() == [0, 0, 0, 0]
    assert table[:, COL["occupancy"]].tolist() == [0.5] * 4
    np.testing.assert_array_equal(table[:, COL["t_emit"]],
                                  np.unique(eng.token_times[0])[1:])
    np.testing.assert_allclose(np.diff(table[:, COL["t_emit"]]),
                               table[1:, COL["gap_s"]], atol=1e-12)
    parts = table[:, [COL[c] for c in GAP_PARTS]].sum(axis=1)
    np.testing.assert_allclose(parts, table[:, COL["gap_s"]], atol=1e-9)
    assert table[1:, COL["other_s"]] == pytest.approx(0.002)
    snap = agg.snapshot(wall_s=1.0)
    assert snap["decode_step_ms"]["n"] == 4
    assert not {"itl_ms", "itl_split_ms", "itl_stalled_share",
                "stalls"} & set(snap)


class _Replica(FakeEngine):
    """What ``ReplicaRouter`` asks of an engine beside the scheduler's."""
    replica = ""

    @property
    def active_slots(self):
        return int(self.active.sum())

    def prefix_match_tokens(self, prompt):
        return 0


@pytest.mark.parametrize("spec_k", [0, 3], ids=["plain", "spec"])
def test_the_router_feeds_each_replicas_timeline_as_the_scheduler_does(
        spec_k):
    from deepspeed_tpu.inference.router import ReplicaRouter
    clock = Clock()                  # one thread, one clock, two replicas
    engines = [_Replica(clock, slots=2, chunk=8, spec_k=spec_k)
               for _ in range(2)]
    reqs = [Request(rid=i, prompt=np.arange(10 + i, dtype=np.int32),
                    max_new_tokens=5 + 3 * i) for i in range(7)]
    report = ReplicaRouter(engines).serve(reqs)
    assert report["completed"] == 7 and report["unfinished"] == 0
    intervals = [0, 0]
    for rec, req in zip(report["requests"], reqs):
        eng = engines[rec["replica"]]
        assert req.timeline is eng.serving
        # every delivery, the first token's included, on the rows' clock
        np.testing.assert_array_equal(
            req.token_times(), np.unique(eng.token_times[req.rid]))
        intervals[rec["replica"]] += len(req.token_times()) - 1
    for eng, snap, n in zip(engines, report["replicas"], intervals):
        assert snap["itl_ms"]["n"] == n > 0
        assert sum(snap["itl_split_ms"].values()) == pytest.approx(
            snap["itl_ms"]["mean"], abs=1e-9)
        table = eng.serving._table()
        # streams admitted while others decoded wait from their own first
        # token, not the whole interval
        assert table[1:, COL["admitted"]].sum() > 0
        # the other replica's turn is time between this one's spans
        assert table[1:, COL["other_s"]].max() > FakeEngine.COST["fetch_s"]
    assert report["itl_ms"]["n"] == sum(intervals)


def test_merged_pools_the_rows():
    a = FakeEngine(Clock(), slots=4)
    b = FakeEngine(Clock(), slots=2)
    ra = _serve(a, _requests(5))
    rb = _serve(b, _requests(3))
    both = ServingAggregator.merged([a.serving, b.serving]).snapshot(
        wall_s=1.0)
    assert both["itl_ms"]["n"] == ra["itl_ms"]["n"] + rb["itl_ms"]["n"]
    assert both["decode_step_ms"]["n"] == \
        ra["decode_step_ms"]["n"] + rb["decode_step_ms"]["n"]
    assert both["stalls"] == []
    assert sum(both["itl_split_ms"].values()) == pytest.approx(
        both["itl_ms"]["mean"], abs=1e-9)


def test_weighted_percentile_is_percentile_over_repeats():
    rng = np.random.default_rng(2)
    v = rng.uniform(0, 1, 50)
    w = rng.integers(0, 6, 50).astype(float)
    flat = sorted(np.repeat(v, w.astype(int)).tolist())
    for q in (0, 50, 95, 99, 100):
        assert weighted_percentile(v, w, q) == percentile(flat, q)
    assert weighted_percentile(v, np.zeros(50), 50) == 0.0
    assert len(COLUMNS) == len(set(COLUMNS)) and set(GAP_PARTS) < set(COLUMNS)

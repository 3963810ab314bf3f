"""The training timeline (``monitor/training.py``): one row a
``train_batch`` call, steps seen complete without a sync, a stall logged
once with where the thread stood and whether the device's queue drained.

The clock is injected and the losses are fakes whose ``is_ready()`` is
scripted and whose every fetching method raises: what the rows hold is
checked by hand, and nothing in the engine's call may wait for a step.
"""
import time

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import (GPT2_CONFIGS, gpt2_init,
                                       gpt2_loss_fn)
from deepspeed_tpu.monitor import serving, training
from deepspeed_tpu.monitor.serving import (STALL_FLOOR_S, STALL_TIMES_MEDIAN,
                                           STALLS_KEPT, ServingAggregator,
                                           stall_limit, stall_rows)
from deepspeed_tpu.monitor.training import COL, COLUMNS, TrainingTimeline
from deepspeed_tpu.parallel.topology import build_mesh

CFG = GPT2_CONFIGS["gpt2-tiny"]


class Clock:
    def __init__(self, t=100.0):
        self.t = t
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return self.t

    def advance(self, s):
        self.t += s


class Future:
    """A loss still on the device: ready when the script says so, and
    any way of waiting for it or fetching it fails the test."""

    def __init__(self, ready=False):
        self.ready = ready
        self.polls = 0

    def is_ready(self):
        self.polls += 1
        return self.ready

    def _synced(self, *a, **k):
        raise AssertionError("the timeline waited for a step")

    block_until_ready = __float__ = __array__ = __int__ = __bool__ = \
        item = tolist = copy_to_host_async = _synced


def call(tm, clock, step, outside=0.0, data=0.0, dispatch=0.0, log=0.0,
         save=0.0, loss=None, built=0):
    """One ``train_batch`` as the engine drives the timeline."""
    clock.advance(outside)
    tm.enter(step)
    clock.advance(data)
    tm.lap("data_s")
    clock.advance(dispatch)
    loss = Future() if loss is None else loss
    tm.dispatched(loss, built)
    clock.advance(log)
    tm.leave(save)
    return loss


def column(tm, name):
    return tm.table()[:, COL[name]].tolist()


@pytest.fixture
def tm():
    clock = Clock()
    return TrainingTimeline(clock=clock), clock


# --------------------------------------------------------------------- #
# rows
# --------------------------------------------------------------------- #
PARTS = [(0.0, 0.5, 2.0, 0.25), (0.125, 0.0625, 0.5, 0.03125),
         (1.0, 0.25, 0.125, 0.5), (0.0, 0.0, 0.75, 0.0)]


def test_a_row_a_call_and_its_columns(tm):
    tm, clock = tm
    assert tm.rows == 0 and tm.snapshot() == {"steps": 0,
                                              "built_after_first": 0}
    for step, (out, d, x, l) in enumerate(PARTS, start=7):
        call(tm, clock, step, out, d, x, l)
    assert tm.rows == len(PARTS)
    t = tm.table()
    assert t.shape == (len(PARTS), len(COLUMNS))
    assert column(tm, "step") == [7, 8, 9, 10]
    assert column(tm, "data_s") == [p[1] for p in PARTS]
    assert column(tm, "dispatch_s") == [p[2] for p in PARTS]
    assert column(tm, "log_s") == [p[3] for p in PARTS]
    # the first row has no call before it
    assert column(tm, "outside_s") == [0.0] + [p[0] for p in PARTS[1:]]
    assert column(tm, "gap_s")[0] == 0.0
    assert column(tm, "t_enter")[0] == 100.0


@pytest.mark.parametrize("i", range(1, len(PARTS)))
def test_gap_is_the_call_before_and_the_time_outside(tm, i):
    tm, clock = tm
    for step, (out, d, x, l) in enumerate(PARTS):
        call(tm, clock, step, out, d, x, l)
    t = tm.table()
    before, this = t[i - 1], t[i]
    assert this[COL["gap_s"]] == before[COL["data_s"]] + \
        before[COL["dispatch_s"]] + before[COL["log_s"]] + \
        this[COL["outside_s"]]
    assert this[COL["gap_s"]] == this[COL["t_enter"]] - before[COL["t_enter"]]


def test_four_clock_reads_a_call(tm):
    tm, clock = tm
    call(tm, clock, 0)
    before = clock.reads
    call(tm, clock, 1)
    assert clock.reads - before == 4


def test_wall_s_is_entry_to_the_latest_lap(tm):
    tm, clock = tm
    clock.advance(3.0)
    tm.enter(0)
    clock.advance(0.5)
    tm.lap("data_s")
    assert tm.wall_s == 0.5
    clock.advance(0.25)
    tm.dispatched(Future())
    assert tm.wall_s == 0.75
    clock.advance(9.0)                   # not read: no lap
    assert tm.wall_s == 0.75


def test_span_args_are_the_row_just_written(tm):
    tm, clock = tm
    a, b = Future(), Future()
    call(tm, clock, 0, 0.0, 0.5, 2.0, 0.25, loss=a, built=1)
    a.ready = True
    call(tm, clock, 1, 0.125, 0.0625, 0.5, 0.03125, loss=b)
    args = tm.span_args()
    assert args == {"row": 1, "gap_ms": 2875.0, "outside_ms": 125.0,
                    "host_ms": 593.75, "data_ms": 62.5, "dispatch_ms": 500.0,
                    "log_ms": 31.25, "in_flight": 0, "completed": 1,
                    "built": 0}
    assert all(isinstance(v, (int, float)) for v in args.values())


def test_a_call_that_raised_leaves_no_half_row(tm):
    tm, clock = tm
    call(tm, clock, 0, 0.0, 0.5, 0.5, 0.5)
    tm.enter(1)
    clock.advance(4.0)
    tm.lap("data_s")                     # ... and the dispatch raised
    clock.advance(1.0)
    call(tm, clock, 1, 0.0, 0.25, 0.25, 0.25)
    assert tm.rows == 2
    assert column(tm, "data_s") == [0.5, 0.25]


def test_the_ring_keeps_the_latest_rows(monkeypatch):
    monkeypatch.setattr(training, "RING", 8)
    clock = Clock()
    tm = TrainingTimeline(clock=clock)
    losses = [call(tm, clock, s, 0.5, 0.5, 0.5, 0.5) for s in range(20)]
    assert tm.rows == 20 and len(tm.table()) == 8
    assert column(tm, "step") == list(range(12, 20))
    assert tm.table(3)[:, COL["step"]].tolist() == [17, 18, 19]
    # a step the ring has dropped is seen complete without a write
    for f in losses:
        f.ready = True
    call(tm, clock, 20, 0.5)
    assert column(tm, "step") == list(range(13, 21))
    assert all(t > 0 for t in column(tm, "t_complete")[:-1])
    assert tm.snapshot()["steps"] == 21


# --------------------------------------------------------------------- #
# completion without a sync
# --------------------------------------------------------------------- #
def test_in_flight_completed_and_t_complete_by_hand(tm):
    tm, clock = tm
    f = [Future() for _ in range(6)]
    # calls of 1 s: entry at 100, 102, 104, ...; exit a second later
    call(tm, clock, 0, 0.0, 0.25, 0.5, 0.25, loss=f[0])   # 100 .. 101
    call(tm, clock, 1, 1.0, 0.25, 0.5, 0.25, loss=f[1])   # 102 .. 103
    f[0].ready = True                   # seen at the next entry, 104
    call(tm, clock, 2, 1.0, 0.25, 0.5, 0.25, loss=f[2])   # 104 .. 105
    f[2].ready = True                   # not the oldest: f[1] holds it
    call(tm, clock, 3, 1.0, 0.25, 0.5, 0.25, loss=f[3])   # 106 .. 107
    # f[1] ends while call 4 runs: seen at its EXIT, 109, with f[2]
    clock.advance(1.0)
    tm.enter(4)
    f[1].ready = True
    clock.advance(0.25)
    tm.lap("data_s")
    clock.advance(0.5)
    tm.dispatched(f[4])
    clock.advance(0.25)
    tm.leave()
    f[3].ready = True
    call(tm, clock, 5, 1.0, 0.25, 0.5, 0.25, loss=f[5])   # 110 .. 111
    # steps in flight when each went out: what no poll had seen complete
    assert column(tm, "in_flight") == [0, 1, 1, 2, 3, 1]
    # seen complete since the entry before (that call's exit, this entry)
    assert column(tm, "completed") == [0, 0, 1, 0, 0, 3]
    assert column(tm, "t_complete") == [104.0, 109.0, 109.0, 110.0, 0.0, 0.0]
    # the oldest unready step is the only one polled further
    assert f[5].polls == 0 and f[4].polls >= 1


def test_a_host_value_is_complete_at_once(tm):
    tm, clock = tm
    call(tm, clock, 0, 0.0, 0.25, 0.5, 0.25, loss=1.5)
    call(tm, clock, 1, 1.0, 0.25, 0.5, 0.25, loss=np.float32(2.0))
    call(tm, clock, 2, 1.0, 0.25, 0.5, 0.25, loss=1.0)
    assert column(tm, "in_flight") == [0, 0, 0]
    # each is seen at its own call's exit: counted by the row after
    assert column(tm, "completed") == [0, 1, 1]
    assert column(tm, "t_complete") == [101.0, 103.0, 105.0]


def test_a_real_array_is_seen_complete_without_a_fetch(tm):
    tm, clock = tm
    loss = jax.block_until_ready(jax.numpy.ones(()) * 3)
    call(tm, clock, 0, loss=loss)
    call(tm, clock, 1, 1.0, loss=loss)
    assert column(tm, "completed") == [0, 1]


# --------------------------------------------------------------------- #
# stalls
# --------------------------------------------------------------------- #
def steady(tm, clock, steps, depth, first=0):
    """``steps`` calls of 10 ms + 90 ms outside with ``depth`` steps in
    flight: each entry finds the oldest complete, as a loop that waits
    for its losses ``depth`` steps late does.  Returns the futures."""
    flying = list(tm._flying)
    for s in range(first, first + steps):
        if len(flying) >= depth:
            flying.pop(0)[1].ready = True
        f = call(tm, clock, s, 0.09, 0.002, 0.007, 0.001, built=int(s == 0))
        flying.append((s, f))
    return [f for _, f in flying]


@pytest.mark.parametrize("complete,verdict", [
    (4, "host"),         # the queue drained while the thread was away
    (0, "device"),       # nothing completed: the device stood
    (2, "both"),         # neither
])
def test_the_three_verdicts(tm, complete, verdict):
    tm, clock = tm
    flying = steady(tm, clock, 40, depth=4)
    assert len(flying) == 4 and tm.snapshot()["stalls"] == []
    for f in flying[:complete]:
        f.ready = True
    call(tm, clock, 40, outside=3.0)
    snap = tm.snapshot()
    assert len(snap["stalls"]) == 1
    st = snap["stalls"][0]
    assert st["row"] == 40 and st["step"] == 39
    assert st["where"] == "outside"
    assert st["gap_s"] == pytest.approx(3.01)
    assert st["median_s"] == pytest.approx(0.1)
    # steps 36..39: the three in flight when 39 went out, and 39 itself
    assert st["in_flight_before"] == 4
    assert st["completed_during"] == complete
    assert st["verdict"] == verdict
    assert st["device_lost_s"] == pytest.approx(3.01 - complete * 0.1)
    assert st["t"] == pytest.approx(0.09 + 39 * 0.1, abs=1e-3)
    assert snap["stall_s_total"] == pytest.approx(3.01 - 0.1)


@pytest.mark.parametrize("where,parts,save,before", [
    # the thread stood BEFORE the step went out: only the earlier steps
    # were the device's to work on meanwhile
    ("data_prep", (2.0, 0.007, 0.001), 0.0, 3),
    ("step_dispatch", (0.002, 2.0, 0.001), 0.0, 3),
    ("step_log", (0.002, 0.007, 2.0), 0.0, 4),
    ("checkpoint_save", (0.002, 0.007, 2.0), 1.5, 4),
])
def test_where_the_thread_stood(tm, where, parts, save, before):
    tm, clock = tm
    flying = steady(tm, clock, 40, depth=4)
    flying[0].ready = True
    call(tm, clock, 40, 0.09, *parts, save=save)
    assert tm.snapshot()["stalls"] == []      # the next row closes it
    for f in flying[1:]:
        f.ready = True
    call(tm, clock, 41, 0.09, 0.002, 0.007, 0.001)
    (st,) = tm.snapshot()["stalls"]
    assert st["row"] == 41 and st["step"] == 40
    assert st["where"] == where
    assert st["in_flight_before"] == before
    assert st["completed_during"] == 3 and st["verdict"] == (
        "host" if before == 3 else "both")


def test_a_stall_is_logged_once_by_the_row_that_closes_it(tm, monkeypatch):
    tm, clock = tm
    lines = []
    monkeypatch.setattr(training.logger, "warning",
                        lambda msg, *a: lines.append(msg % a))
    flying = steady(tm, clock, 40, depth=4)
    assert lines == []
    for f in flying:
        f.ready = True
    call(tm, clock, 40, outside=3.0)
    assert len(lines) == 1 and tm.stalls_logged == 1
    assert "row 40 (from step 39" in lines[0]
    assert "in outside" in lines[0] and ": host (" in lines[0]
    assert "4 step(s) in flight before, 4 seen complete" in lines[0]
    steady(tm, clock, 40, depth=4, first=41)
    tm.snapshot()
    tm.snapshot()
    assert len(lines) == 1
    assert [s["row"] for s in tm.snapshot()["stalls"]] == [40]


def test_the_first_build_is_start_up_and_a_later_one_is_named(tm,
                                                               monkeypatch):
    tm, clock = tm
    lines = []
    monkeypatch.setattr(training.logger, "warning",
                        lambda msg, *a: lines.append(msg % a))
    call(tm, clock, 0, 0.0, 0.002, 30.0, 0.001, built=1)    # compiles
    steady(tm, clock, 40, depth=1, first=1)
    assert tm.snapshot()["stalls"] == [] and lines == []
    assert tm.snapshot()["built_after_first"] == 0
    call(tm, clock, 41, 0.09, 0.002, 30.0, 0.001, built=1)  # another shape
    call(tm, clock, 42, 0.09, 0.002, 0.007, 0.001)
    snap = tm.snapshot()
    assert snap["built_after_first"] == 1
    (st,) = snap["stalls"]
    assert (st["step"], st["where"], st["built"]) == (41, "step_dispatch", 1)
    assert len(lines) == 1 and "step_dispatch (1 program(s) built)" in lines[0]


def test_the_first_wait_of_a_loop_that_filled_its_queue_is_no_stall(
        tm, monkeypatch):
    """A loop dispatches sixteen steps a millisecond apart and only then
    waits for a loss, a step's time: the median of ALL intervals so far
    is the host's dispatch, and the rule is held against the intervals
    by whose end a step was seen complete."""
    tm, clock = tm
    lines = []
    monkeypatch.setattr(training.logger, "warning",
                        lambda msg, *a: lines.append(msg % a))
    call(tm, clock, 0, 0.0, 0.002, 3.0, 0.001, built=1, loss=1.0)
    f = [call(tm, clock, s, 0.0005, 0.0001, 0.0008, 0.0001)
         for s in range(1, 17)]                       # the fill
    assert tm.snapshot()["stalls"] == []              # nothing to hold to
    for s in range(17, 40):                           # 0.3 s a step
        f.pop(0).ready = True
        f.append(call(tm, clock, s, 0.299, 0.0001, 0.0008, 0.0001))
    assert lines == [] and tm.snapshot()["stalls"] == []
    assert tm.snapshot()["gap_ms"]["p50"] == pytest.approx(300.0)
    f.pop(0).ready = True
    call(tm, clock, 40, outside=3.5)                  # and a real one
    assert len(lines) == 1 and "median is 300.0" in lines[0]
    (st,) = tm.snapshot()["stalls"]
    assert st["median_s"] == pytest.approx(0.3) and st["verdict"] == "both"


def test_an_interval_under_the_floor_is_no_stall(tm):
    tm, clock = tm
    steady(tm, clock, 40, depth=2)              # median 0.1 s
    call(tm, clock, 40, outside=0.9)            # 9 medians
    assert tm.snapshot()["stalls"] == []
    fast = TrainingTimeline(clock=clock)
    for s in range(40):
        call(fast, clock, s, 0.001, 0.0, 0.001, 0.0, loss=1.0)
    call(fast, clock, 40, outside=0.2)          # 100 medians, under 0.25 s
    assert fast.snapshot()["stalls"] == []
    call(fast, clock, 41, outside=0.3)
    assert [s["row"] for s in fast.snapshot()["stalls"]] == [41]


def test_the_longest_stalls_are_kept_in_order_of_time(tm):
    tm, clock = tm
    steady(tm, clock, 20, depth=1)
    lengths = [1.0 + 0.1 * ((7 * i) % 11) for i in range(STALLS_KEPT + 3)]
    for i, s in enumerate(lengths):
        call(tm, clock, 20 + 2 * i, outside=s, loss=1.0)
        call(tm, clock, 21 + 2 * i, outside=0.09, data=0.01, loss=1.0)
    snap = tm.snapshot()
    kept = snap["stalls"]
    assert len(kept) == STALLS_KEPT
    assert [s["row"] for s in kept] == sorted(s["row"] for s in kept)
    # (an interval is the call before, 10 ms, and the time outside)
    assert sorted(round(s["gap_s"], 6) for s in kept) == sorted(
        round(s + 0.01, 6) for s in sorted(lengths)[-STALLS_KEPT:])
    # every stalled interval counts in the total, kept or not
    assert snap["stall_s_total"] == pytest.approx(
        sum(lengths) + len(lengths) * (0.01 - kept[0]["median_s"]))


# --------------------------------------------------------------------- #
# one rule for both loops
# --------------------------------------------------------------------- #
def _rule_before(gap):
    """``ServingAggregator.stalls``' selection as it stood inline."""
    limit = max(STALL_TIMES_MEDIAN * float(np.median(gap)), STALL_FLOOR_S)
    worst = np.flatnonzero(gap > limit)
    return np.sort(worst[np.argsort(-gap[worst])][:STALLS_KEPT])


@pytest.mark.parametrize("seed", range(6))
def test_the_shared_rule_selects_what_serving_selected(seed):
    rng = np.random.default_rng(seed)
    gap = rng.uniform(0.01, 0.04, 400)
    gap[rng.choice(400, 3 * seed, replace=False)] = rng.uniform(
        0.2, 3.0, 3 * seed)
    assert stall_rows(gap).tolist() == _rule_before(gap).tolist()
    assert stall_limit(gap) == max(STALL_TIMES_MEDIAN * np.median(gap),
                                   STALL_FLOOR_S)
    assert len(stall_rows(gap)) <= STALLS_KEPT


def test_the_rule_on_nothing():
    assert stall_rows(np.zeros(0)).tolist() == []
    assert stall_limit(np.zeros(0)) == STALL_FLOOR_S


def test_serving_stalls_read_as_before():
    clock = Clock()
    agg = ServingAggregator(4, clock=clock)
    agg.note_serve_start()
    for i in range(30):
        clock.advance(3.0 if i == 17 else 0.02)
        agg.lap("fetch_s")
        agg.note_iteration(2, 0.02)
        agg.note_emit(2)
    table = agg._table()
    keep = table[:, serving.COL["continuing"]] > 0
    rows = np.flatnonzero(keep)[_rule_before(
        table[keep][:, serving.COL["gap_s"]])]
    stalls = agg.snapshot()["stalls"]
    assert [s["row"] for s in stalls] == rows.tolist() == [17]
    assert stalls[0]["in"] == "decode_fetch"
    assert stalls[0]["gap_ms"] == pytest.approx(3000.0)
    assert set(stalls[0]) == {"row", "at_s", "gap_ms", "in", "in_ms"}


# --------------------------------------------------------------------- #
# the summary
# --------------------------------------------------------------------- #
def test_snapshot_keys_and_figures(tm):
    tm, clock = tm
    steady(tm, clock, 50, depth=3)
    snap = tm.snapshot()
    assert set(snap) == {"steps", "gap_ms", "host_ms", "outside_ms",
                         "in_flight", "built_after_first", "stalls",
                         "stall_s_total"}
    assert snap["steps"] == 50
    assert set(snap["gap_ms"]) == {"p50", "p95", "p99", "max"}
    assert snap["gap_ms"]["p50"] == pytest.approx(100.0)
    assert set(snap["host_ms"]) == {"mean", "p99", "data", "dispatch", "log"}
    assert snap["host_ms"]["mean"] == pytest.approx(10.0)
    assert snap["host_ms"]["data"] == pytest.approx(2.0)
    assert snap["host_ms"]["dispatch"] == pytest.approx(7.0)
    assert snap["host_ms"]["log"] == pytest.approx(1.0)
    assert snap["outside_ms"] == {"mean": pytest.approx(90.0)}
    # (the first call, which built the step, is start-up and left out)
    assert snap["in_flight"] == {"mean": pytest.approx(1.98, abs=1e-3),
                                 "min": 1}
    assert snap["stalls"] == [] and snap["stall_s_total"] == 0.0


def test_the_call_that_built_the_step_is_left_out_of_the_means(tm):
    tm, clock = tm
    call(tm, clock, 0, 0.0, 0.002, 30.0, 0.001, built=1, loss=1.0)
    assert set(tm.snapshot()) == {"steps", "built_after_first"}
    for s in range(1, 11):
        call(tm, clock, s, 0.09, 0.002, 0.007, 0.001, loss=1.0)
    snap = tm.snapshot()
    assert snap["steps"] == 11
    assert snap["host_ms"]["mean"] == pytest.approx(10.0)
    assert snap["host_ms"]["p99"] == pytest.approx(10.0)
    assert snap["gap_ms"]["max"] == pytest.approx(100.0)
    # ... and a first call that built nothing (a timeline begun later) counts
    late = TrainingTimeline(clock=clock)
    call(late, clock, 50, 0.0, 0.002, 0.5, 0.001, loss=1.0)
    call(late, clock, 51, 0.09, 0.002, 0.007, 0.001, loss=1.0)
    assert late.snapshot()["host_ms"]["dispatch"] == pytest.approx(253.5)


# --------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def engine():
    ds = {"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
          "gradient_accumulation_steps": 1, "gradient_clipping": 1.0,
          "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
          "steps_per_print": 10 ** 9}
    eng, _, _, _ = deepspeed_tpu.initialize(
        config=ds, model=gpt2_loss_fn(CFG),
        model_params=gpt2_init(jax.random.PRNGKey(1), CFG),
        mesh=build_mesh(devices=jax.devices()[:1]))
    return eng


def _batch(seed=0, seq=33):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, (2, seq)).astype(np.int32)


def test_the_engine_has_a_timeline_with_telemetry_off(engine):
    assert not engine.telemetry.enabled
    assert isinstance(engine.timeline, TrainingTimeline)
    assert engine.timeline.snapshot()["steps"] == engine.timeline.rows


def test_built_is_one_on_the_first_call_and_on_a_retrace(engine):
    tm = engine.timeline = TrainingTimeline()
    first = engine._train_step_fn is None
    for i in range(3):
        engine.train_batch(_batch(i))
    engine.train_batch(_batch(3, seq=17))        # another shape: a retrace
    for i in range(2):
        engine.train_batch(_batch(i))            # the first shape again
    assert column(tm, "built") == [int(first), 0, 0, 1, 0, 0]
    assert tm.snapshot()["built_after_first"] == 1
    assert column(tm, "step") == list(range(engine.global_steps - 6,
                                            engine.global_steps))
    host = tm.table()[:, [COL["data_s"], COL["dispatch_s"], COL["log_s"]]]
    assert (host > 0).all()
    gaps = tm.table()[1:, COL["gap_s"]]
    assert np.allclose(gaps, host[:-1].sum(axis=1)
                       + tm.table()[1:, COL["outside_s"]], atol=1e-12)


def test_the_engine_never_syncs_for_its_rows(engine, monkeypatch):
    """The losses the timeline holds are fakes that fail on any fetch:
    ``train_batch`` polls ``is_ready`` and nothing else."""
    engine.train_batch(_batch())                 # built before
    tm = engine.timeline = TrainingTimeline()
    real = engine._dispatch_step
    fakes = []

    def dispatch(micro_batches):
        metrics = dict(real(micro_batches))
        fakes.append(Future())
        metrics["loss"] = fakes[-1]
        return metrics
    monkeypatch.setattr(engine, "_dispatch_step", dispatch)
    for i in range(5):
        assert engine.train_batch(_batch(i)) is fakes[i]
    assert column(tm, "in_flight") == [0, 1, 2, 3, 4]
    assert column(tm, "completed") == [0] * 5
    assert fakes[0].polls == 9 and fakes[1].polls == 0
    fakes[0].ready = fakes[1].ready = fakes[3].ready = True
    engine.train_batch(_batch())
    assert column(tm, "in_flight")[-1] == 3
    assert column(tm, "completed")[-1] == 2
    assert tm.table()[:2, COL["t_complete"]].tolist() == \
        [tm.table()[5, COL["t_enter"]]] * 2
    assert engine.timeline.snapshot()["in_flight"]["min"] == 0


def test_a_save_inside_the_call_is_its_save_s(engine, monkeypatch):
    tm = engine.timeline = TrainingTimeline()
    engine.train_batch(_batch())

    def save():
        with engine.telemetry.span("checkpoint_save", tag="auto"):
            time.sleep(0.02)
    monkeypatch.setattr(engine, "_maybe_auto_save", save)
    before = engine.telemetry.checkpoint_exposed_s
    engine.train_batch(_batch())
    row = tm.table()[-1]
    assert row[COL["save_s"]] >= 0.02
    assert row[COL["save_s"]] == pytest.approx(
        engine.telemetry.checkpoint_exposed_s - before)
    assert row[COL["log_s"]] >= row[COL["save_s"]]
    assert tm.table()[0, COL["save_s"]] == 0.0


def test_the_record_and_the_beat_take_the_rows_seconds(tmp_path):
    """``wall_ms`` of the telemetry record is the row's entry-to-dispatch
    seconds: one measurement, read twice."""
    ds = {"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
          "steps_per_print": 10 ** 9,
          "telemetry": {"enabled": True, "output_path": str(tmp_path),
                        "report_steps": 10 ** 6}}
    eng, _, _, _ = deepspeed_tpu.initialize(
        config=ds, model=gpt2_loss_fn(CFG),
        model_params=gpt2_init(jax.random.PRNGKey(1), CFG),
        mesh=build_mesh(devices=jax.devices()[:1]))
    for i in range(3):
        eng.train_batch(_batch(i))
    t = eng.timeline.table()
    walls = [host["wall_ms"] for _, _, _, host in eng.telemetry._ring]
    assert walls == pytest.approx(
        ((t[:, COL["data_s"]] + t[:, COL["dispatch_s"]]) * 1e3).tolist())
    eng.telemetry.close()

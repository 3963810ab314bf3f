"""perfbench/layer_metrics/serve_lookahead_share.py: 100 x the ``decode``
spans' ``ahead`` over their count, on hand-made spans with a known answer,
on one small recorded chip trace of a program that runs its decode loop an
iteration ahead (``data/toy_serve_lookahead.xplane.pb``: a tenth of a
second of the rehearsal's two-layer toy served on one v5e chip by this
benchmark's own serve runner, on the program as PR 37 leaves it; the
``/host:metadata`` plane taken out), and ``None`` on recorded traces of
programs whose spans carry no such arg (PR 36's serve, a train run)."""
import importlib.util
import os

import pytest

from perfbench.lib import program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))
AHEAD = os.path.join(HERE, "data", "toy_serve_lookahead.xplane.pb")
NO_ARG = os.path.join(HERE, "data", "toy_serve_timeline.xplane.pb")
TRAIN = os.path.join(HERE, "data", "toy_train_scoped.xplane.pb")
RECORD = {"trace": {"busy_s": 1.0}, "kind": "serve"}


@pytest.fixture(scope="module")
def read():
    path = os.path.join(os.path.dirname(HERE), "layer_metrics",
                        "serve_lookahead_share.py")
    spec = importlib.util.spec_from_file_location("lookahead_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _current(monkeypatch, tr):
    monkeypatch.setattr(pt, "_CACHE", {"trace": tr})     # current() has read


def test_share_is_the_spans_ahead_over_their_count(read, monkeypatch):
    # a stretch of four iterations: the first span only dispatches, the
    # last only fetches; then a stretch of one
    ahead = [0, 1, 1, 1, 0, 0, 0]
    _current(monkeypatch, {"spans": {"decode": [
        (i * 1e6, 0.5e6, {"iteration": i, "active": 2, "ahead": a,
                          "dropped": 0}) for i, a in enumerate(ahead)]}})
    assert read(RECORD) == pytest.approx(100 * 3 / 7)
    # spans without the arg are not counted; none with it reads None
    _current(monkeypatch, {"spans": {"decode": [
        (0.0, 1e6, {"iteration": 0, "active": 2}),
        (2e6, 1e6, {"iteration": 1, "active": 2, "ahead": 1})]}})
    assert read(RECORD) == 100.0
    _current(monkeypatch, {"spans": {"decode": [
        (0.0, 1e6, {"iteration": 0, "active": 2})]}})
    assert read(RECORD) is None
    _current(monkeypatch, {"spans": {}})
    assert read(RECORD) is None


def test_none_off_a_traced_run(read):
    assert read(None) is None
    assert read({"trace": None, "kind": "serve"}) is None


@pytest.mark.parametrize("path", [NO_ARG, TRAIN])
def test_recorded_traces_without_the_arg_read_none(read, monkeypatch, path):
    _current(monkeypatch, pt.reduce(path))
    assert read(RECORD) is None


def test_the_recorded_lookahead_trace_reads_its_share(read, monkeypatch):
    tr = pt.reduce(AHEAD)
    _current(monkeypatch, tr)
    decodes = [a for _, _, a in tr["spans"]["decode"]]
    assert len(decodes) > 20
    assert all(a["ahead"] in (0, 1) and a["dropped"] == 0 for a in decodes)
    share = read(RECORD)
    assert share == pytest.approx(
        100.0 * sum(a["ahead"] for a in decodes) / len(decodes))
    # the toy never runs empty inside the window: every dispatch but a
    # stretch's first goes out ahead
    assert 80.0 < share <= 100.0
    # a span that dispatched describes that iteration; in the window's
    # steady state each span also fetched one: as many fetches as
    # dispatches, give or take the window's two edges
    sp = tr["spans"]
    assert abs(len(sp["decode_dispatch"]) - len(sp["decode_fetch"])) <= 1
    # ahead means what it says on the profiler's clock too: the dispatch
    # of a span lies before the fetch of the same span
    for (d0, dd, a) in sp["decode"]:
        inside = [s for s, _, _ in sp["decode_dispatch"]
                  if d0 <= s <= d0 + dd]
        fetch = [s for s, _, _ in sp["decode_fetch"] if d0 <= s <= d0 + dd]
        if a["ahead"] and inside and fetch:
            assert inside[0] < fetch[0]

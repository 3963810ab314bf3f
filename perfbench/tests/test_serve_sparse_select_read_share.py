"""perfbench/layer_metrics/serve_sparse_select_read_share.py: the ``decode``
spans' ``ck_blocks_read`` over their ``sparse_blocks_in_reach``, in percent,
on hand-made spans with a known answer, and ``None`` for a window without a
``decode`` span, for spans of a program that does not count what its
selection gathers (the parent's; the recorded chip trace of the toy serve,
``data/toy_serve_timeline.xplane.pb``, has no sparse layer at all), a train
run's trace and a run that was not traced."""
import importlib.util
import os

import pytest

from perfbench.lib import program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))
SERVE = os.path.join(HERE, "data", "toy_serve_timeline.xplane.pb")
TRAIN = os.path.join(HERE, "data", "toy_train_scoped.xplane.pb")
RECORD = {"trace": {"busy_s": 1.0}, "kind": "serve"}


@pytest.fixture(scope="module")
def metric():
    path = os.path.join(os.path.dirname(HERE), "layer_metrics",
                        "serve_sparse_select_read_share.py")
    spec = importlib.util.spec_from_file_location("select_read_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _decode(t, **args):
    return (t * 1e6, 0.5e6, {"iteration": t, "active": 256, **args})


def test_blocks_gathered_over_blocks_in_reach(metric, monkeypatch):
    # every stream's table width, 256 streams x 2,072 slots x 2 layers x 2
    # heads, over ~1,115 blocks in reach a stream: the per-stream arm
    each = 256 * 2072 * 4
    reach = 256 * 1115 * 4
    spans = {"decode": [_decode(i, ck_blocks_read=each,
                                sparse_blocks_in_reach=reach,
                                sparse_blocks_read=256 * 64 * 4)
                        for i in range(3)]}
    monkeypatch.setattr(pt, "_CACHE", {"trace": {"spans": spans}})
    assert metric.read(RECORD) == pytest.approx(100 * 2072 / 1115)
    # 11 tiles of 32 streams: a table row once a tile, 32 own slots a stream
    once = 11 * (2072 + 32 * 32) * 4
    spans = {"decode": [_decode(0, ck_blocks_read=once,
                                sparse_blocks_in_reach=reach),
                        _decode(1, ck_blocks_read=each,
                                sparse_blocks_in_reach=reach),
                        # a span that fetched nothing carries no counter
                        _decode(2)]}
    monkeypatch.setattr(pt, "_CACHE", {"trace": {"spans": spans}})
    assert metric.read(RECORD) == pytest.approx(
        100 * (once + each) / (2 * reach))
    assert 100 * once / reach < 15


@pytest.mark.parametrize("spans", [
    {}, {"decode": []}, {"prefill": [(0.0, 1e6, {"slots": 1})]},
    # a program that does not count what its selection gathers
    {"decode": [_decode(0, sparse_blocks_in_reach=99, sparse_blocks_read=9)]},
    # nothing in reach: no stream was live
    {"decode": [_decode(0, ck_blocks_read=0, sparse_blocks_in_reach=0)]},
])
def test_none_where_no_span_carries_the_counters(metric, monkeypatch, spans):
    monkeypatch.setattr(pt, "_CACHE", {"trace": {"spans": spans}})
    assert metric.read(RECORD) is None


def test_none_for_a_run_that_was_not_traced(metric, monkeypatch):
    monkeypatch.setattr(pt, "_CACHE", {})
    assert metric.read({"kind": "serve"}) is None
    assert metric.read(None) is None


@pytest.mark.parametrize("path", [SERVE, TRAIN])
def test_none_on_recorded_chip_traces(metric, monkeypatch, path):
    monkeypatch.setattr(pt, "_CACHE", {"trace": pt.reduce(path)})
    assert metric.read(RECORD) is None

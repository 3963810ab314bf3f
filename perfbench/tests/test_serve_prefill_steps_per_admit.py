"""perfbench/layer_metrics/serve_prefill_steps_per_admit.py: the ``prefill``
spans' ``chunks`` over their ``slots``, on hand-made spans with a known
answer, on the recorded chip trace of the toy serve
(``data/toy_serve_timeline.xplane.pb``: its spans carry both args), and
``None`` for a window without a ``prefill`` span, a span that never got as
far as its plan, a train run's trace and a run that was not traced."""
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
                        "serve_prefill_steps_per_admit.py")
    spec = importlib.util.spec_from_file_location("steps_per_admit", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _prefill(t, **args):
    return (t * 1e6, 0.5e6, {"prompt_tokens": 600, "rids": "1", **args})


def test_chunks_over_slots_on_hand_made_spans(metric, monkeypatch):
    # three turns cut at their snapshot's boundary (two programs each), one
    # long one (three), and a batch of two groups' admissions in one pass
    # of two programs
    spans = {"prefill": [_prefill(0, slots=1, chunks=2),
                         _prefill(1, slots=1, chunks=2),
                         _prefill(2, slots=1, chunks=2),
                         _prefill(3, slots=1, chunks=3),
                         _prefill(4, slots=2, chunks=2)],
             "decode": [(5e6, 1e6, {"iteration": 0})]}
    assert metric.per_admit(spans) == pytest.approx(11 / 6)
    monkeypatch.setattr(pt, "_CACHE", {"trace": {"spans": spans}})
    assert metric.read(RECORD) == pytest.approx(11 / 6)
    # every turn one program
    one = {"prefill": [_prefill(i, slots=1, chunks=1) for i in range(5)]}
    assert metric.per_admit(one) == 1.0


@pytest.mark.parametrize("spans", [
    {}, {"prefill": []}, {"decode": [(0.0, 1e6, {"iteration": 0})]},
    # a span that raised before its plan carries neither arg
    {"prefill": [(0.0, 1e6, {"slots": 1, "prompt_tokens": 9})]},
])
def test_none_without_a_planned_prefill_span(metric, monkeypatch, spans):
    assert metric.per_admit(spans) is None
    monkeypatch.setattr(pt, "_CACHE", {"trace": {"spans": spans}})
    assert metric.read(RECORD) is None


def test_none_for_a_run_that_was_not_traced(metric, monkeypatch):
    monkeypatch.setattr(pt, "_CACHE", {})
    assert metric.read({"kind": "serve"}) is None
    assert metric.read(None) is None


def test_on_recorded_chip_traces(metric):
    spans = pt.reduce(SERVE)["spans"]
    rows = [a for _, _, a in spans["prefill"]]
    assert rows and all(a["chunks"] >= 1 and a["slots"] == 1 for a in rows)
    assert metric.per_admit(spans) == pytest.approx(
        sum(a["chunks"] for a in rows) / len(rows))
    assert metric.per_admit(pt.reduce(TRAIN)["spans"]) is None

"""perfbench/layer_metrics/serve_chain_walks_per_admit.py: the ``prefill``
spans' ``chain_walks`` over their ``slots``, on hand-made spans with a known
answer, and ``None`` for a window without a ``prefill`` span, for spans of a
program that does not count its walks (the recorded chip trace of the toy
serve, ``data/toy_serve_timeline.xplane.pb``, predates the arg), a train
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
                        "serve_chain_walks_per_admit.py")
    spec = importlib.util.spec_from_file_location("walks_per_admit", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _prefill(t, **args):
    return (t * 1e6, 0.5e6, {"prompt_tokens": 600, "rids": "1", "chunks": 1,
                             **args})


def test_walks_over_slots_on_hand_made_spans(metric, monkeypatch):
    # a program that walks for every question: nine an admission, more for
    # a head refused first; a batch of two groups' admissions
    spans = {"prefill": [_prefill(0, slots=1, chain_walks=9),
                         _prefill(1, slots=1, chain_walks=13),
                         _prefill(2, slots=2, chain_walks=18)],
             "decode": [(5e6, 1e6, {"iteration": 0})]}
    assert metric.per_admit(spans) == pytest.approx(40 / 4)
    monkeypatch.setattr(pt, "_CACHE", {"trace": {"spans": spans}})
    assert metric.read(RECORD) == pytest.approx(10.0)
    # a request's chain kept with it
    one = {"prefill": [_prefill(i, slots=1, chain_walks=1)
                       for i in range(5)]}
    assert metric.per_admit(one) == 1.0


@pytest.mark.parametrize("spans", [
    {}, {"prefill": []}, {"decode": [(0.0, 1e6, {"iteration": 0})]},
    # a program that does not count its walks
    {"prefill": [(0.0, 1e6, {"slots": 1, "prompt_tokens": 9, "chunks": 1})]},
])
def test_none_where_no_span_carries_the_arg(metric, monkeypatch, spans):
    assert metric.per_admit(spans) is None
    monkeypatch.setattr(pt, "_CACHE", {"trace": {"spans": spans}})
    assert metric.read(RECORD) is None


def test_none_for_a_run_that_was_not_traced(metric, monkeypatch):
    monkeypatch.setattr(pt, "_CACHE", {})
    assert metric.read({"kind": "serve"}) is None
    assert metric.read(None) is None


def test_on_recorded_chip_traces(metric):
    spans = pt.reduce(SERVE)["spans"]
    assert spans["prefill"]
    assert metric.per_admit(spans) is None      # recorded before the arg
    assert metric.per_admit(pt.reduce(TRAIN)["spans"]) is None

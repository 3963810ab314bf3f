"""perfbench/layer_metrics/train_host_ms_per_step.py and
train_data_prep_ms_per_step.py: the program's ``train_batch`` spans'
``host_ms`` / ``data_ms`` over the spans that carry them, on hand-made span
tables with a known answer — the arg present; the runner's own arg-less
``train_batch`` wrapper beside the program's; the arg absent (the parent's
program) -> ``None``; a reading of 0.0 is a reading — on the recorded chip
trace of a program from before the args (``data/toy_train_scoped.xplane.pb``
-> ``None``), and off a traced run."""
import importlib.util
import os

import pytest

from perfbench.lib import program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN = os.path.join(HERE, "data", "toy_train_scoped.xplane.pb")
RECORD = {"trace": {"busy_s": 1.0}, "kind": "train", "steps_traced": 5}
READERS = {"train_host_ms_per_step": "host_ms",
           "train_data_prep_ms_per_step": "data_ms"}


def _reader(name):
    path = os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _current(monkeypatch, spans):
    monkeypatch.setattr(pt, "_CACHE", {"trace": {"spans": spans}})


def _program(i, **args):
    """The engine's span of step ``i``: a StepTraceAnnotation with the
    row's args."""
    return (i * 1e8 + 1e4, 2e6, {"step_num": i, "_r": 1, "row": i,
                                 "gap_ms": 100.0, "in_flight": i, **args})


def _wrapper(i):
    """The runner's own annotation round the call: same name, no args."""
    return (i * 1e8, 2.1e6, {})


@pytest.mark.parametrize("name,arg", sorted(READERS.items()))
def test_mean_of_the_arg_over_the_programs_spans(monkeypatch, name, arg):
    read = _reader(name)
    values = [0.5, 0.25, 1.0, 0.75, 2.5]
    other = "data_ms" if arg == "host_ms" else "host_ms"
    _current(monkeypatch, {"train_batch": [
        _program(i, **{arg: v, other: 77.0}) for i, v in enumerate(values)]})
    assert read(RECORD) == pytest.approx(sum(values) / 5)


@pytest.mark.parametrize("name,arg", sorted(READERS.items()))
def test_the_runners_wrapper_is_not_counted(monkeypatch, name, arg):
    read = _reader(name)
    values = [0.5, 0.25, 1.0, 0.75, 2.5]
    rows = []
    for i, v in enumerate(values):
        rows += [_wrapper(i), _program(i, **{arg: v})]
    _current(monkeypatch, {"train_batch": rows, "data_prep": [
        (i * 1e8 + 2e4, 1e5, {"step": i}) for i in range(5)]})
    assert read(RECORD) == pytest.approx(sum(values) / 5)   # five, not ten


@pytest.mark.parametrize("name,arg", sorted(READERS.items()))
def test_a_program_without_the_arg_reads_none(monkeypatch, name, arg):
    read = _reader(name)
    # the parent's spans: the step number and nothing else
    _current(monkeypatch, {"train_batch": [
        r for i in range(5) for r in (_wrapper(i), (i * 1e8 + 1e4, 2e6, {
            "step_num": i, "_r": 1}))]})
    assert read(RECORD) is None
    _current(monkeypatch, {"train_batch": []})
    assert read(RECORD) is None
    _current(monkeypatch, {})
    assert read(RECORD) is None


@pytest.mark.parametrize("name,arg", sorted(READERS.items()))
def test_zero_is_a_reading(monkeypatch, name, arg):
    read = _reader(name)
    _current(monkeypatch, {"train_batch": [
        _program(i, **{arg: 0.0}) for i in range(5)]})
    value = read(RECORD)
    assert value == 0.0 and isinstance(value, float)
    # an integer the profiler stored counts as well
    _current(monkeypatch, {"train_batch": [
        _program(i, **{arg: 2}) for i in range(5)]})
    assert read(RECORD) == 2.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_off_a_traced_run(name):
    read = _reader(name)
    assert read(None) is None
    assert read({"trace": None, "kind": "train"}) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_recorded_trace_from_before_the_args_reads_none(monkeypatch,
                                                          name):
    tr = pt.reduce(TRAIN)
    assert tr["spans"].get("train_batch")       # the spans are there
    monkeypatch.setattr(pt, "_CACHE", {"trace": tr})
    assert _reader(name)(RECORD) is None

"""CPU rehearsal of the two train cells' toys (``train.tiny`` on one host
device, ``train.tiny.dp4`` on four) through ``perfbench/run.py --trace 1``:
the result line HOLDS ``train_host_ms_per_step`` and
``train_data_prep_ms_per_step`` as floats, read from the program's own
``train_batch`` spans of the traced window (``traced_steps`` of them, the
runner's arg-less wrapper of the same name left out)."""
import json

import pytest

from test_rehearsal import CELLS, _run, bench_json  # noqa: F401

NEW = ("train_host_ms_per_step", "train_data_prep_ms_per_step")


@pytest.mark.parametrize("cell,chips", [(n, c) for n, _, c in CELLS
                                        if n.startswith("train.")])
def test_the_traced_result_holds_both_metrics(bench_json, cell, chips):  # noqa: F811
    bench, path = bench_json
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert cell in listed[name]["workloads"]
        assert listed[name]["source"] == "program_span"
        assert listed[name]["moves"] == "train_tokens_per_s"
    out = _run(path, cell, 1, chips)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()
             if l.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True
    for name in NEW:
        assert name in last["metrics"], sorted(last["metrics"])
        m = last["metrics"][name]
        assert isinstance(m["value"], float) and m["unit"] == "ms"
        assert 0.0 <= m["value"] < 60e3
    # entry to the end of data_prep is a part of the call
    assert last["metrics"]["train_data_prep_ms_per_step"]["value"] <= \
        last["metrics"]["train_host_ms_per_step"]["value"]
    # the window holds the runner's wrapper AND the program's span a step
    found = next(l for l in lines if l.get("phase") == "program_trace")
    traced = json.load(open(bench["paths"][0] + "/traffic/pretrain-s128.json")
                       )["traced_steps"]
    assert found["spans_found"]["train_batch"] == 2 * traced
    assert found["spans_found"]["data_prep"] == traced

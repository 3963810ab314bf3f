"""CPU rehearsal of the ``docqa`` runner (``perfbench/runners/docqa.py``)
at a toy ``deepseek_v3`` configuration: the set-up that serves the
documents, the reference comparison, the window, the new per-layer
readers.  ``test_rehearsal.py``'s twin for the kind this file's PR added
(that file's cells are fixed lists); the toy is never a cell."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
CELL = "serve.deepseek-tiny.docqa"
REAL = "serve.gigachat3.1-702b-a36b.docqa-over"


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b["paths"] = [REH]
    b["configs"] = [{"name": "deepseek-tiny", "source": "none", "reduced": [],
                     "file": os.path.join(REH, "configs",
                                          "deepseek-tiny.json"),
                     "why": "toy"}]
    b["workloads"] = [{"name": CELL, "config": "deepseek-tiny",
                       "traffic": "docqa-tiny-over", "chips": 1,
                       "why": "rehearsal"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL in m["workloads"] else []
    path = tmp_path_factory.mktemp("reh") / "BENCHMARK.json"
    path.write_text(json.dumps(b))
    return b, str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_is_the_result(bench_json, trace):
    bench, path = bench_json
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--benchmark-json", path, "--workload", CELL, "--seed",
         str(2 ** 31 + 7), "--seconds", "3", "--trace", str(trace),
         "--rehearse-on-cpu"], cwd=ROOT, env=env, text=True,
        capture_output=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()
             if l.startswith("{")]
    last = lines[-1]
    serve = next(l for l in lines if l.get("phase") == "serve")
    assert last["correct"] is True and last["failed"] == 0, serve
    assert last["attempted"] > 0
    assert serve["kv"]["documents_whole"] == 3
    assert serve["prefix"]["hit_rate"] > 0.5 if "prefix" in serve else True
    counters = serve["snapshot"]["model_counters"]
    assert 0 < counters["moe_held_pair_share"] < 1
    if trace:
        names = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [])}
        assert set(last["metrics"]) <= names
        for want in ("serve_moe_held_pair_share",
                     "serve_moe_held_load_max_over_mean",
                     "serve_occupancy", "serve_prefix_hit_rate"):
            assert want in last["metrics"], sorted(last["metrics"])
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}

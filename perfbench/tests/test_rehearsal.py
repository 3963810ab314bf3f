"""CPU rehearsal of ``perfbench/run.py`` at a tiny configuration: both
kinds of runner, four host devices for ``chips: 4``, the last line's keys,
and the refusal to measure without a TPU.  The toy is never a cell."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
CELLS = [("train.tiny", "pretrain-s128", 1), ("train.tiny.dp4", "pretrain-s128", 4),
         ("serve.tiny.over", "chat-tiny-over", 1)]


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    """The repo's BENCHMARK.json with its configurations and cells swapped
    for the toy's; the metric lists are the real ones."""
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    kinds = {m["name"]: m["workloads"][0].split(".")[0]
             for m in b["end_to_end"] + b["per_layer"] if "workloads" in m}
    b["paths"] = [REH]
    b["configs"] = [{"name": "gpt2-tiny", "source": "none", "reduced": [],
                     "file": os.path.join(REH, "configs", "gpt2-tiny.json"),
                     "why": "toy"}]
    b["workloads"] = [{"name": n, "config": "gpt2-tiny", "traffic": t,
                       "chips": c, "why": "rehearsal"} for n, t, c in CELLS]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [n for n, _, _ in CELLS
                              if n.split(".")[0] == kinds[m["name"]]]
    path = tmp_path_factory.mktemp("reh") / "BENCHMARK.json"
    path.write_text(json.dumps(b))
    return b, str(path)


def _run(bench_path, cell, trace, chips, extra=("--rehearse-on-cpu",)):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--benchmark-json", bench_path, "--workload", cell,
         "--seed", str(2 ** 31 + 5), "--seconds", "2", "--trace", str(trace),
         *extra], cwd=ROOT, env=env, text=True, capture_output=True,
        timeout=600)


@pytest.mark.parametrize("cell,chips", [(n, c) for n, _, c in CELLS])
@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_is_the_result(bench_json, cell, chips, trace):
    bench, path = bench_json
    out = _run(path, cell, trace, chips)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    dev = last["device"]
    assert dev["platform"] == "cpu" and dev["count"] == chips
    assert "memory_peak_bytes" in dev
    listed = bench["per_layer"] if trace else bench["end_to_end"]
    allowed = {m["name"] for m in listed
               if "workloads" not in m or cell in m["workloads"]}
    assert set(last["metrics"]) <= allowed
    for m in last["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] >= dev["busy_s"]
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
        moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
        e2e = {m["name"] for m in bench["end_to_end"]
               if "workloads" not in m or cell in m["workloads"]}
        assert all(moves[name] in e2e for name in last["metrics"])
    else:
        assert set(last["metrics"]) == allowed and "setup_s" in allowed


def test_no_tpu_no_result(bench_json):
    _, path = bench_json
    out = _run(path, "train.tiny", 0, 1, extra=())
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout

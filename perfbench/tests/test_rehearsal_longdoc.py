"""CPU rehearsal of the ``longdoc`` runner (``perfbench/runners/longdoc.py``)
at a toy ``minicpm_sala`` configuration: the reference comparison across
``dense_len`` (logits, Lightning states a head, pooled keys, the sets the
engine's own steps chose) and its controls, the emitted tokens of two
requests of the window teacher-forced through the reference, the set-up that serves the documents, the
window, the new per-layer readers; and ``lib/minicpm_sala_costs.py``.
``test_rehearsal.py``'s twin for the kind this file's PR added; the toy is
never a cell."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
CELL = "serve.minicpm-sala-tiny.longdoc"
REAL = "serve.minicpm-sala.longdoc-over"
sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b["paths"] = [REH]
    b["configs"] = [{"name": "minicpm-sala-tiny", "source": "none",
                     "reduced": [], "why": "toy", "file": os.path.join(
                         REH, "configs", "minicpm-sala-tiny.json")}]
    b["workloads"] = [{"name": CELL, "config": "minicpm-sala-tiny",
                       "traffic": "longdoc-tiny-over", "chips": 1,
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
         str(2 ** 31 + 7), "--seconds", "4", "--trace", str(trace),
         "--rehearse-on-cpu"], cwd=ROOT, env=env, text=True,
        capture_output=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()
             if l.startswith("{")]
    last = lines[-1]
    serve = next(l for l in lines if l.get("phase") == "serve")
    assert all(serve["agree"].values()), (serve["agree"], serve["summary"])
    # float32 toy: the sets the engine's own steps chose (returned behind
    # the logits) are the reference's, set for set; so is the side pass
    assert len(serve["selection_checks"]) == 8       # 2 x (prompt + its hit)
    assert all(row[1] > 0 and row[2] == 0
               for row in serve["selection_checks"]
               + serve["facts"]["side_pass"])
    assert serve["facts"]["flip_gap_max"] == 0.0
    # two requests served inside the window, every emitted token the
    # reference's own greedy one; the control's are not
    checked = serve["served_tokens_checked"]
    assert len(checked) == 2 and {c[1] for c in checked} <= {0, 1}
    assert all(c[3] >= 4 and c[4] < 1e-3 for c in checked), checked
    assert serve["served_tokens_control"] > 100 * max(c[4] for c in checked) \
        and serve["served_tokens_control"] > 0.01
    assert serve["summary"]["logit_max"] < 1e-3
    assert serve["summary"]["head_max"] < 1e-4
    assert serve["summary"]["pooled_max"] < 1e-5
    fails = serve["controls_fail"]
    assert set(fails) == {"dense", "no_forced", "top_less", "stale_ck",
                          "decay_shift", "bf16_state", "no_gates",
                          "served_tokens.no_gates"}
    # each control fails the rule that is ITS: the selection's by sets, the
    # pooled keys' by rows (the limits on logits and heads are sized for the
    # published widths; the toy's bf16 state moves a head by less)
    c = serve["controls"]
    assert not c["no_forced"]["passes"]["selection"]
    assert not c["top_less"]["passes"]["selection"]
    assert not c["dense"]["passes"]["selection"]
    assert not c["stale_ck"]["passes"]["pooled"]
    assert not c["decay_shift"]["passes"]["heads"]
    assert c["bf16_state"]["head_max"] > 10 * serve["summary"]["head_max"]
    assert not c["bf16_state"]["passes"]["low_bits"]
    assert c["bf16_state"]["low_bits"] == 0.0
    assert min(serve["facts"]["low_bits"]) > 0.9
    assert c["no_gates"]["logit_max"] > 100 * serve["summary"]["logit_max"]
    assert serve["resumed"], serve["facts"]
    assert serve["kv"]["documents_cached"] == 2
    assert serve["kv"]["shared_cached_after"] == 2
    assert last["failed"] == 0 and last["attempted"] > 0
    assert serve["compiles_window"] == 0
    counters = serve["snapshot"]["model_counters"]
    assert 0 < counters["sparse_read_share"] < 1
    assert counters["ck_rows_scored"] > 0
    names = set(last["metrics"])
    if trace:
        # the CPU trace has no device plane: scope and kernel readers
        # return None there; the counters' reader reads the spans
        assert "serve_sparse_read_share" in names, names
        assert 0 < last["metrics"]["serve_sparse_read_share"]["value"] < 100
    else:
        assert {"serve_tokens_per_s", "setup_s"} <= names


def test_the_costs_are_the_issues_bytes():
    from perfbench.lib import minicpm_sala_costs as costs
    sizes = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "minicpm-sala.json")))
    assert costs.attend_block_bytes(sizes) == 32768
    assert costs.lightning_layers(sizes) == 6 and costs.sparse_layers(sizes) == 2
    # 256 streams: 6 layers x 4 MB in and out a stream
    assert costs.state_update_bytes(sizes, 256) == 256 * 6 * 2 * 2 ** 21
    # 256 streams x 2 layers x 2 heads x 64 blocks of 32 KB
    assert costs.attend_bytes(sizes, 256 * 2 * 2 * 64) == 2 ** 31
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share = costs.roofline_share(
        costs.state_update_flops(sizes, 256),
        costs.state_update_bytes(sizes, 256), 0.01, peaks)
    assert 70 < share < 90

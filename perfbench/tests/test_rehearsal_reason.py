"""CPU rehearsal of the ``reason`` runner (``perfbench/runners/reason.py``)
at a toy ``xing4_0`` configuration (the ``deepseek_v3`` family on four
residual streams): the reference comparison with its three controls, the
backlog and the open loop, the teacher-forced check of a long reply, the
new per-layer readers.  ``test_rehearsal_docqa.py``'s twin for the kind
this file's PR added; the toy is never a cell."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
CELL = "serve.xing-tiny.reason"
REAL = "serve.xing4.0-29b-a4b.reason-over"


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b["paths"] = [REH]
    b["configs"] = [{"name": "xing-tiny", "source": "none", "reduced": [],
                     "file": os.path.join(REH, "configs", "xing-tiny.json"),
                     "why": "toy"}]
    b["workloads"] = [{"name": CELL, "config": "xing-tiny",
                       "traffic": "reason-tiny-over", "chips": 1,
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
         str(2 ** 31 + 7), "--seconds", "6", "--trace", str(trace),
         "--rehearse-on-cpu"], cwd=ROOT, env=env, text=True,
        capture_output=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()
             if l.startswith("{")]
    last = lines[-1]
    serve = next(l for l in lines if l.get("phase") == "serve")
    traffic = next(l for l in lines if l.get("phase") == "traffic")
    assert last["correct"] is True and last["failed"] == 0, serve
    assert last["attempted"] >= 8 and traffic["backlog"] == 8
    # the served path agrees with the reference under the runner's rule and
    # each of the three controls is told apart from it
    assert serve["logits_agree"] and len(serve["logit_checks"]) == 42
    assert serve["controls_fail"] == {"8bit": True, "res_identity": True,
                                      "sinkhorn_once": True}
    assert len(serve["served_tokens_checked"]) == 2
    rid, plen, n, worst, share, _ = serve["served_tokens_checked"][0]
    assert n >= 64 and plen + n > 74           # the long reply
    assert worst <= serve["limits"]["token_gap_max"]
    assert share <= serve["limits"]["token_share"]
    counters = serve["snapshot"]["model_counters"]
    assert counters["moe_held_pair_share"] == 1.0     # every expert held
    assert 0 < counters["hc_res_err_max"] < 0.5
    if trace:
        names = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [])}
        assert {"serve_hc_ms_per_iter", "serve_lm_head_ms_per_iter"} <= names
        assert set(last["metrics"]) <= names
        for want in ("serve_moe_held_pair_share", "serve_occupancy",
                     "serve_prefix_hit_rate", "serve_decode_iter_ms"):
            assert want in last["metrics"], sorted(last["metrics"])
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_the_backlog_is_due_at_zero_and_every_seed_offers_the_same_work():
    from perfbench.lib import reason_traffic
    spec = json.load(open(os.path.join(ROOT, "perfbench", "traffic",
                                       "reason-over.json")))
    a = reason_traffic.requests(spec, 1, 51.0, 1000)
    b = reason_traffic.requests(spec, 2 ** 31 + 9, 51.0, 1000)
    n_open = int(round(spec["rate_rps"] * 51.0))
    assert len(a) == len(b) == spec["backlog"] + n_open
    for items in (a, b):
        at = np.array([r["arrival_s"] for r in items])
        assert (at[:spec["backlog"]] == 0).all() and (np.diff(at) >= 0).all()
        assert at[-1] < 51.0 and at[spec["backlog"] + 1] > 0
        assert max(len(r["prompt"]) + r["max_new_tokens"]
                   for r in items) <= spec["max_total"]
        assert abs(sum(r["shared"] >= 0 for r in items) - len(items) / 2) <= 1
    # the same stratified multisets, dealt in another order (a prompt
    # behind a system prompt is at least that long, so the pairing moves a
    # few of them)
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        x, y = sorted(map(key, a)), sorted(map(key, b))
        assert np.median(x) == np.median(y) and x[-1] == y[-1]
        assert abs(sum(x) - sum(y)) < 0.02 * sum(x)
    # every eighth arrival of the open loop is due at the same time
    k = spec["backlog"]
    assert [r["arrival_s"] for r in a[k::8]] == pytest.approx(
        [r["arrival_s"] for r in b[k::8]])
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]

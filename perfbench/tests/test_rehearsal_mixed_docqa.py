"""CPU rehearsal of the ``mixed_docqa`` runner
(``perfbench/runners/mixed_docqa.py``) at a toy ``afmoe`` configuration:
the set-up that serves the documents, the reference comparison and its
controls, the window, the new per-layer readers.  ``test_rehearsal.py``'s
twin for the kind this file's PR added; the toy is never a cell."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
CELL = "serve.afmoe-tiny.mixed-docqa"
REAL = "serve.trinity-mini.mixed-docqa-over"
sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b["paths"] = [REH]
    b["configs"] = [{"name": "afmoe-tiny", "source": "none", "reduced": [],
                     "file": os.path.join(REH, "configs", "afmoe-tiny.json"),
                     "why": "toy"}]
    b["workloads"] = [{"name": CELL, "config": "afmoe-tiny",
                       "traffic": "mixed-docqa-tiny-over", "chips": 1,
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
    # The toy's 8-bit control need not fail (64-wide products round
    # little); everything else of ``correct`` holds at the toy too.
    fails = serve["controls_fail"]
    assert fails["window_off"] and fails["rotary_on_full"], serve["controls"]
    assert serve["logits_agree"], serve["logit_checks"]
    assert last["correct"] is all(fails.values()) and last["failed"] == 0
    assert last["attempted"] > 0
    assert serve["kv"]["documents_whole"] == 3
    facts = serve["facts"]
    assert facts["doc0_resumed_at"] == facts["doc0_full_blocks"] > 0
    assert facts["doc0_cached_by_class"] == {
        "full": facts["doc0_full_blocks"], "window": 32}
    assert facts["window_blocks_returned_by_slid_prompt"]["window"] > 0
    classes = serve["kv"]["classes"]
    assert classes["window"]["returned_in_window"] > 0
    assert classes["full"]["returned_in_window"] == 0
    counters = serve["snapshot"]["model_counters"]
    assert counters["moe_held_pair_share"] == 1.0
    assert set(serve["snapshot"]["cache_classes"]) == {"full", "window"}
    if trace:
        names = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [])}
        assert set(last["metrics"]) <= names
        for want in ("serve_moe_held_pair_share",
                     "serve_moe_held_load_max_over_mean",
                     "serve_window_pool_live_share",
                     "serve_full_pool_live_share", "serve_kv_live_share",
                     "serve_occupancy", "serve_prefix_hit_rate"):
            assert want in last["metrics"], sorted(last["metrics"])
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_the_mix_is_the_same_multiset_for_every_seed():
    from perfbench.lib import mixed_traffic
    spec = json.load(open(os.path.join(ROOT, "perfbench", "traffic",
                                       "mixed-docqa-over.json")))
    spec = dict(spec, rate_rps=8.0,
                documents=dict(spec["documents"], min=96, max=192))

    def shape(seed):
        docs = mixed_traffic.documents(spec, seed, 1000)
        reqs = mixed_traffic.requests(spec, seed, 20.0, 1000, docs)
        return docs, reqs
    (docs_a, a), (docs_b, b) = shape(3), shape(2 ** 31 + 11)
    assert [len(d) for d in docs_a] == [len(d) for d in docs_b]
    assert len(a) == len(b) == 160

    def multiset(reqs, docs):
        """The marginals: document choices, question lengths, short prompt
        lengths, reply lengths (how they pair is the seed's)."""
        own = [len(r["prompt"]) - (len(docs[r["shared"]])
                                   if r["shared"] >= 0 else 0) for r in reqs]
        return (sorted(r["shared"] for r in reqs),
                sorted(n for n, r in zip(own, reqs) if r["shared"] >= 0),
                sorted(n for n, r in zip(own, reqs) if r["shared"] < 0),
                sorted(r["max_new_tokens"] for r in reqs))
    assert multiset(a, docs_a) == multiset(b, docs_b)
    assert [r["shared"] for r in a] != [r["shared"] for r in b]
    # half long, and every eight consecutive requests hold four of each
    longs = np.array([r["shared"] >= 0 for r in a])
    assert longs.sum() == 80
    assert all(longs[i:i + 8].sum() == 4 for i in range(0, 160, 8))
    short = [len(r["prompt"]) for r in a if r["shared"] < 0]
    assert min(short) >= 32 and max(short) <= 2048
    assert all(r["arrival_s"] < 20.0 for r in a)


def test_costs_count_rows_in_reach_and_experts_with_rows():
    from perfbench.lib import afmoe_costs
    sizes = json.load(open(os.path.join(ROOT, "perfbench", "configs",
                                        "trinity-mini.json")))
    assert afmoe_costs.kv_row_bytes(sizes) == 2048
    assert afmoe_costs.attend_bytes(sizes, 1000) == 2_048_000
    assert afmoe_costs.attend_flops(sizes, 1000) == 1000 * 32 * 4 * 128
    assert afmoe_costs.expert_gemm_flops(sizes, 1024) \
        == 1024 * 6 * 2048 * 1024
    assert afmoe_costs.expert_gemm_bytes(sizes, 512, 1024) \
        == 512 * 3 * 2048 * 1024 * 2 + 1024 * 2 * 2048 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share = afmoe_costs.roofline_share(0.0, 819e9 * 1e-3, 2e-3, peaks)
    assert abs(share - 50.0) < 1e-9

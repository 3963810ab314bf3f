"""CPU rehearsal of the ``docqa_state`` runner
(``perfbench/runners/docqa_state.py``) at a toy ``brumby`` configuration:
the set-up that serves the documents and leaves their snapshots, the
reference comparison through the snapshot-hit path, the window, the new
per-layer readers.  ``test_rehearsal_docqa.py``'s twin for the kind this
file's PR added; the toy is never a cell."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
CELL = "serve.brumby-tiny.docqa-long"
REAL = "serve.brumby-14b-base.docqa-long-over"


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b["paths"] = [REH]
    b["configs"] = [{"name": "brumby-tiny", "source": "none", "reduced": [],
                     "file": os.path.join(REH, "configs", "brumby-tiny.json"),
                     "why": "toy"}]
    b["workloads"] = [{"name": CELL, "config": "brumby-tiny",
                       "traffic": "docqa-long-tiny-over", "chips": 1,
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
    assert serve["kv"]["reclaimed_in_window"] == 0
    state = serve["snapshot"]["state"]
    assert state["snapshot_hits"] > 0 and state["resumed_tokens"] > 0
    # Every question resumed from its document's snapshot.
    assert serve["snapshot"]["prefix"]["hit_rate"] > 0.5
    doc0 = [r for r in serve["logit_checks"] if r[0].startswith("doc0")]
    assert doc0 and all(resumed > 0 for _, resumed, _ in doc0)
    assert [r[0] for r in doc0] == ["doc0.prefill", "doc0.decode",
                                    "doc0.reply"]
    # The page after the reply, held to the reference's recurrence: the
    # served state passes, and both controls fail their comparisons.
    assert serve["state_agrees"] and len(serve["state_checks"]) == 2
    assert all(r[2] <= serve["limits"]["normaliser_rtol"] < r[4]
               for r in serve["state_checks"])
    assert serve["controls_fail"] == {"logits_8bit_reference": True,
                                      "state_bf16_reference": True}
    if trace:
        names = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [])}
        assert set(last["metrics"]) <= names
        for want in ("serve_occupancy", "serve_prefix_hit_rate",
                     "serve_kv_live_share", "serve_decode_iter_ms"):
            assert want in last["metrics"], sorted(last["metrics"])
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_retention_costs_count_the_distinct_pairs():
    from perfbench.lib import retention_costs
    sizes = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "brumby-14b-base.json")))
    assert retention_costs.feature_width(sizes) == 8256
    assert retention_costs.state_bytes(sizes) == 8 * 8256 * 129 * 4
    # 32 live streams, 4 layers: 8.72 GB read and written.
    moved = retention_costs.state_update_bytes(sizes, 32)
    assert abs(moved / 1e9 - 8.72) < 0.01
    share = retention_costs.roofline_share(
        retention_costs.state_update_flops(sizes, 32), moved, 0.0213,
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert 49.0 < share < 51.0          # bandwidth-bound: 10.65 ms floor


def test_configuration_keeps_every_published_key():
    cat = {"attention_bias": False, "head_dim": 128, "hidden_act": "silu",
           "hidden_size": 5120, "intermediate_size": 17408,
           "max_position_embeddings": 32768, "max_window_layers": 40,
           "model_type": "brumby", "num_attention_heads": 40,
           "num_hidden_layers": 40, "num_key_value_heads": 8,
           "rms_norm_eps": 1e-06, "rope_scaling": None,
           "rope_theta": 1000000, "sliding_window": None,
           "tie_word_embeddings": False, "use_sliding_window": False,
           "vocab_size": 151936}
    sizes = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "brumby-14b-base.json")))
    changed = {k for k, v in cat.items() if sizes.get(k, "absent") != v}
    assert changed == set(sizes["reduced"]) == {"num_hidden_layers"}

"""CPU rehearsal of the ``chat_state`` runner
(``perfbench/runners/chat_state.py``) at a toy ``falcon_h1`` configuration:
the set-up that serves the system prompts, the reference comparison over
both kinds of cache in one layer and its controls, the window, the new
per-layer readers; and ``lib/ssm_costs.py``.  ``test_rehearsal.py``'s twin
for the kind this file's PR added; the toy is never a cell."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
CELL = "serve.falcon-h1-tiny.chat-short"
REAL = "serve.falcon-h1-34b.chat-short-over"
sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b["paths"] = [REH]
    b["configs"] = [{"name": "falcon-h1-tiny", "source": "none",
                     "reduced": [], "why": "toy", "file": os.path.join(
                         REH, "configs", "falcon-h1-tiny.json")}]
    b["workloads"] = [{"name": CELL, "config": "falcon-h1-tiny",
                       "traffic": "chat-short-tiny-over", "chips": 1,
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
    # The toy's state arithmetic reads float32 rounding noise on the CPU,
    # and every control fails its rule at the toy too.
    fails = serve["controls_fail"]
    assert set(fails) == {
        "bf16_state.carried", "bf16_state.chunks", "e4m3_steps",
        "state_zeroed.logits", "state_zeroed.pages",
        "no_ssm.logits", "d_zero.logits", "unit_ssm_multipliers.logits"}
    # (LOGIT_ATOL is sized for the published widths' logits: the toy's lost
    # state moves its logits by less; the pages see it)
    assert all(v for k, v in fails.items() if k != "state_zeroed.logits"), \
        serve["controls"]
    assert serve["state_agrees"] and serve["facts"]["state_carried"] < 1e-6
    assert serve["facts"]["state_carried_bf16"] > 1e-3
    # the long prompt's four chunk programs: three carried states
    assert len(serve["facts"]["chunks_carried"]) == 3
    assert max(serve["facts"]["chunks_carried"]) < 1e-5
    assert min(serve["facts"]["chunks_carried_bf16"]) > 1e-3
    # the program's steps against the reference's own: bf16 activations
    assert len(serve["facts"]["steps"]) == 4
    assert max(serve["facts"]["steps"]) < 2 ** -6 \
        < min(serve["facts"]["steps_e4m3"])
    assert serve["logits_agree"], serve["summary"]
    assert serve["pages_agree"], serve["summary"]
    assert last["correct"] is all(fails.values()) and last["failed"] == 0
    assert last["attempted"] > 0
    assert serve["kv"]["system_prompts_cached"] == 2
    facts = serve["facts"]
    assert facts["resumed_at"] == facts["boundary"] == 16
    assert facts["cached_by_class"] == {"full": 16, "state": 16}
    assert len(serve["served_tokens_checked"]) == 2
    window = serve["window"]
    assert window["admissions"] > 4
    assert window["snapshot_hits"] > 0
    assert window["prefix_lost_to_kind_tokens"] is not None
    assert set(serve["snapshot"]["cache_classes"]) == {"full", "state"}
    if trace:
        names = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [])}
        assert set(last["metrics"]) <= names
        for want in ("serve_prefix_kind_loss", "serve_snapshots_per_admit",
                     "serve_full_pool_live_share", "serve_kv_live_share",
                     "serve_occupancy", "serve_prefix_hit_rate",
                     "serve_prefill_steps_per_admit"):
            assert want in last["metrics"], sorted(last["metrics"])
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_the_system_prompts_come_back_out_of_the_requests():
    from perfbench.lib import reason_traffic
    from perfbench.runners import chat_state
    spec = json.load(open(os.path.join(ROOT, "perfbench", "traffic",
                                       "chat-short-over.json")))
    items = reason_traffic.requests(spec, 2 ** 31 + 5, 10.0, 261120)
    system = chat_state.system_prompts(items, 512)
    assert len(system) == 8 and all(len(p) == 512 for p in system)
    for r in items:
        if r["shared"] >= 0:
            assert (r["prompt"][:512] == system[r["shared"]]).all()
            assert len(r["prompt"]) >= 512 + 32
        assert len(r["prompt"]) + r["max_new_tokens"] <= 3072
    assert sum(r["arrival_s"] == 0.0 for r in items) >= 128
    assert max(int(r["prompt"].max()) for r in items) < 261120


def test_the_costs_of_the_two_forms_at_the_published_widths():
    from perfbench.lib import ssm_costs
    sizes = json.load(open(os.path.join(ROOT, "perfbench", "configs",
                                        "falcon-h1-34b.json")))
    assert ssm_costs.state_bytes(sizes) == 32 * 256 * 128 * 4 == 4194304
    # 128 live streams, 4 layers: 4.29 GB read and written an iteration
    assert ssm_costs.state_update_bytes(sizes, 128) == 2 * 128 * 4 * 4194304
    assert ssm_costs.state_update_flops(sizes, 128) \
        == 5 * 128 * 4 * 32 * 256 * 128
    # bound by bandwidth: 0.625 operations a byte
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share = ssm_costs.roofline_share(
        ssm_costs.state_update_flops(sizes, 128),
        ssm_costs.state_update_bytes(sizes, 128), 6.6e-3, peaks)
    assert 79.0 < share < 80.0
    # the scan: 4.78 MFLOP a token and layer at sub-chunks of 64 (5.37 at
    # the published 128), beside the layer's 860
    assert ssm_costs.chunk_scan_flops(sizes, 1, 64) / 4 == 4784128
    assert ssm_costs.chunk_scan_flops(sizes, 1, 128) / 4 == 5373952

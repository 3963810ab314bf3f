"""CPU rehearsal of the ``paste`` runner (``perfbench/runners/paste.py``)
at a toy ``smallthinker`` configuration that KEEPS 7 query heads a K/V
head, the ``0 1 1 1`` layouts over 8 layers, top-3 of 8 experts and a
window shorter than the prompts: the set-up that serves the system prompts
and the four groups, the window, the reference comparison after it and its
controls, every new per-layer reader.  ``test_rehearsal.py``'s twin for the
kind this file's PR added; the toy is never a cell."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
CELL = "serve.smallthinker-tiny.paste"
REAL = "serve.smallthinker-21b-a3b.paste-over"
NEW = ("serve_prefill_ms_per_chunk", "serve_prefill_moe_ms_per_chunk",
       "serve_prefill_window_attend_ms_per_chunk",
       "serve_prefill_full_attend_ms_per_chunk",
       "serve_route_ahead_ms_per_iter",
       "serve_window_blocks_returned_per_admit",
       "serve_scope_coverage.paste")
sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b["paths"] = [REH]
    b["configs"] = [{"name": "smallthinker-tiny", "source": "none",
                     "reduced": [], "why": "toy", "file": os.path.join(
                         REH, "configs", "smallthinker-tiny.json")}]
    b["workloads"] = [{"name": CELL, "config": "smallthinker-tiny",
                       "traffic": "paste-tiny-over", "chips": 1,
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
    assert serve["logits_agree"], serve["logit_summary"]
    assert set(serve["controls_fail"]) == {
        "e4m3", "window_off", "rotary_on_full", "router_reads_post_norm",
        "silu_for_relu", "softmax_over_all"}
    assert all(serve["controls_fail"].values()), serve["controls"]
    assert last["correct"] and last["failed"] == 0 and last["attempted"] > 0
    assert serve["kv"]["system_prompts_cached"] == 2
    facts = serve["facts"]
    assert facts["shared"]["resumed_at"] == facts["system_prompt_blocks"] == 16
    assert facts["shared"]["cached_by_class"] == {"full": 16, "window": 16}
    for group in ("slid", "long"):          # the ring turned DURING prefill
        assert facts[group]["returned_by_prefill"]["window"] > 0
        assert facts[group]["returned_by_prefill"]["full"] == 0
    assert [k for _, k, *_ in serve["served_tokens_checked"]] == [
        "past_window", "behind_system_prompt"]
    classes = serve["kv"]["classes"]
    assert classes["window"]["returned_in_window"] > 0
    assert classes["full"]["returned_in_window"] == 0
    snap = serve["snapshot"]
    assert snap["prefill_window_blocks_returned"] > 0
    assert snap["cached_tokens_full"] >= snap["cached_tokens_window"] > 0
    assert snap["model_counters"]["moe_held_pair_share"] == 1.0
    if trace:
        names = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [])}
        assert set(last["metrics"]) <= names
        for want in ("serve_window_blocks_returned_per_admit",
                     "serve_moe_held_pair_share",
                     "serve_moe_held_load_max_over_mean",
                     "serve_window_pool_live_share",
                     "serve_full_pool_live_share", "serve_kv_live_share",
                     "serve_occupancy", "serve_prefix_hit_rate"):
            assert want in last["metrics"], sorted(last["metrics"])
        assert last["metrics"]["serve_window_blocks_returned_per_admit"][
            "value"] > 0
        # The CPU backend has no device plane: the readers of named scopes
        # and kernels find nothing to read and leave their metric out (what
        # they do on a program without the family); the chip's traced run
        # is where they read (PERF.md section 5, cell 11).
        assert not [m for m in last["metrics"]
                    if "roofline" in m or "_per_chunk" in m
                    or m.startswith("serve_scope_coverage")]
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_the_cells_traffic_is_the_issues():
    from perfbench.lib import reason_traffic
    spec = json.load(open(os.path.join(ROOT, "perfbench", "traffic",
                                       "paste-over.json")))
    assert spec["kind"] == "paste" and spec["backlog"] == 64
    a, b = (reason_traffic.requests(spec, seed, 51.0, 151936)
            for seed in (3, 2 ** 31 + 11))
    n = 64 + round(spec["rate_rps"] * 51.0)
    assert len(a) == len(b) == n

    def multiset(reqs):
        """(a prompt behind a system prompt is at least 512 + 1,024 long:
        which of the shortest twelfth are raised to that is the seed's)"""
        return (sorted(n for n in (len(r["prompt"]) for r in reqs)
                       if n > 1536),
                sorted(r["max_new_tokens"] for r in reqs),
                sorted(r["shared"] for r in reqs))
    assert multiset(a) == multiset(b)
    assert abs(sum(len(r["prompt"]) for r in a)
               - sum(len(r["prompt"]) for r in b)) < 0.005 * sum(
                   len(r["prompt"]) for r in a)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    lens = np.array([len(r["prompt"]) for r in a])
    assert lens.min() >= 1024 and lens.max() <= 15360
    assert abs(np.median(lens) - 4096) < 300
    assert 0.4 < (lens > 4096).mean() < 0.6       # half outgrow the window
    assert sum(r["arrival_s"] == 0.0 for r in a) >= 64
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 16384 for r in a)
    assert abs(sum(r["shared"] >= 0 for r in a) - n / 2) <= 1
    assert {r["shared"] for r in a} == {-1, 0, 1, 2, 3}
    assert all(r["prompt"].max() < 151936 for r in a[:8])


def test_costs_count_rows_in_reach_and_experts_with_rows():
    from perfbench.lib import smallthinker_costs as costs
    sizes = json.load(open(os.path.join(ROOT, "perfbench", "configs",
                                        "smallthinker-21b-a3b.json")))
    assert set(costs.KEYS) <= set(sizes)
    assert costs.kv_row_bytes(sizes) == 2048
    assert costs.attend_bytes(sizes, 1000) == 2_048_000
    assert costs.attend_flops(sizes, 1000) == 1000 * 28 * 4 * 128
    assert costs.expert_gemm_flops(sizes, 384) == 384 * 6 * 2560 * 768
    assert costs.expert_gemm_bytes(sizes, 512, 384) \
        == 512 * 3 * 2560 * 768 * 2 + 384 * 2 * 2560 * 2
    assert costs.expert_cells(sizes) == 512
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert abs(costs.roofline_share(0.0, 819e9 * 1e-3, 2e-3, peaks)
               - 50.0) < 1e-9
    # a mean chunk program: two admissions of 3 and 1 chunks, the last
    # chunks 100 and 40 rows with 12 and 90 experts unhit
    spans = [(0, 1, {"chunks": 3, "slots": 1, "moe_held_pairs": 100 * 48,
                     "moe_held_empty": 12}),
             (2, 1, {"chunks": 1, "slots": 1, "moe_held_pairs": 40 * 48,
                     "moe_held_empty": 90}),
             (3, 1, {"chunks": 2})]                 # no counters: skipped
    pairs, with_rows = costs.prefill_expert_load(sizes, spans, 512)
    assert pairs == (2 * 512 + 100 + 40) * 48 / 4
    assert with_rows == (4 * 512 - 12 - 90) / 4
    assert costs.prefill_expert_load(sizes, [], 512) is None


def test_readers_leave_a_program_without_the_family_out():
    """On the parent (no such spans, counters or kernel) every new reader
    returns None and raises nothing."""
    from perfbench.run import load_module
    record = {"kind": "serve", "trace": None, "snapshot": {}, "summary": {},
              "peaks": None}
    for name in NEW + ("moe_reglu_gemm_roofline.decode",
                       "moe_reglu_gemm_roofline.prefill",
                       "gqa_paged_attend_roofline.g7"):
        read = load_module(os.path.join(
            ROOT, "perfbench", "layer_metrics", name + ".py")).read
        assert read(record) is None, name

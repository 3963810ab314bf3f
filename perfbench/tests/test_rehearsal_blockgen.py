"""CPU rehearsal of the ``blockgen`` runner (``perfbench/runners/blockgen.py``)
at a toy ``sdar_moe`` configuration (generation by diffusion over blocks):
the backlog and the open loop of fixed budgets, the in-window record of
whole blocks' passes held to the float32 reference after the window, its two
controls, the new per-layer readers — and the SAME run with the reference
given another ``block_length``, which must come out as not correct.
``test_rehearsal_reason.py``'s twin for the kind this file's PR added; the
toy is never a cell."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
CELL = "serve.sdar-tiny.blockgen"
REAL = "serve.sdar-30b-a3b-chat.blockgen-over"
NEW = {"serve_denoise_passes_per_block", "serve_unmask_ms_per_iter",
       "serve_block_gap_p50_ms", "serve_block_gap_p99_ms",
       "gqa_paged_attend_roofline.b4", "moe_all_experts_gemm_roofline.sdar",
       "serve_scope_coverage.sdar"}


def bench_json(tmp_path, config_file):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b["paths"] = [REH]
    b["configs"] = [{"name": "sdar-tiny", "source": "none", "reduced": [],
                     "file": config_file, "why": "toy"}]
    b["workloads"] = [{"name": CELL, "config": "sdar-tiny",
                       "traffic": "blockgen-tiny-over", "chips": 1,
                       "why": "rehearsal"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL in m["workloads"] else []
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(b))
    return b, str(path)


def run_cell(path, trace):
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
    return lines[-1], next(l for l in lines if l.get("phase") == "serve"), \
        next(l for l in lines if l.get("phase") == "traffic")


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_is_the_result(tmp_path, trace):
    bench, path = bench_json(tmp_path, os.path.join(REH, "configs",
                                                    "sdar-tiny.json"))
    last, serve, traffic = run_cell(path, trace)
    assert last["correct"] is True and last["failed"] == 0, serve
    assert last["attempted"] >= 8 and traffic["backlog"] == 8
    assert traffic["block_length"] == 4 and traffic["denoising_steps"] == 2
    # four streams x two whole blocks x (two denoise passes + the commit) x
    # the block's four positions, one of them behind a prefix-cache hit
    assert serve["agree"] == {"logits": True, "rule": True, "passes": True}
    assert len(serve["recorded"]) == 4 and len(serve["logit_checks"]) == 96
    assert any(r["cached_tokens"] for r in serve["recorded"])
    assert all(len(r["starts"]) == 6 and r["starts"][3] == r["starts"][0] + 4
               for r in serve["recorded"])
    assert serve["controls_fail"] == {"8bit": True, "causal": True}
    assert serve["compiles_window"] == 0
    # tokens are committed reply tokens: budgets of the completed requests
    assert serve["output_tokens"] >= 16 * serve["completed"]
    counters = serve["snapshot"]["model_counters"]
    assert counters["moe_held_pair_share"] == 1.0     # every expert held
    assert 2.9 < counters["block_rows"] / 4 / counters["commits"] < 3.1
    assert serve["snapshot"]["block_gap_ms"]["n"] > 0
    if trace:
        names = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [])}
        assert NEW <= names and set(last["metrics"]) <= names
        for want in ("serve_denoise_passes_per_block", "serve_occupancy",
                     "serve_block_gap_p50_ms", "serve_block_gap_p99_ms",
                     "serve_lookahead_share", "serve_prefix_hit_rate",
                     "serve_moe_held_pair_share"):
            assert want in last["metrics"], sorted(last["metrics"])
        assert 2.9 < last["metrics"]["serve_denoise_passes_per_block"][
            "value"] < 3.1
        assert last["metrics"]["serve_lookahead_share"]["value"] > 90
        assert not {"serve_itl_p50_ms", "serve_itl_p99_ms"} \
            & set(last["metrics"])
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_the_comparison_can_fail(tmp_path):
    """The reference given blocks of 2 (the served path keeps its 4): the
    recorded logits no longer agree and the run is not correct, by the
    logits' rule alone."""
    sizes = json.load(open(os.path.join(REH, "configs", "sdar-tiny.json")))
    sizes["reference"] = {"block_length": 2}
    wrong = tmp_path / "sdar-tiny-wrong-reference.json"
    wrong.write_text(json.dumps(sizes))
    _, path = bench_json(tmp_path, str(wrong))
    last, serve, _ = run_cell(path, 0)
    assert last["correct"] is False
    assert serve["agree"] == {"logits": False, "rule": True, "passes": True}
    assert serve["summary_checks"]["logit_median"] \
        > serve["limits"]["median"]

"""CPU rehearsal of the ``longgen`` runner (``perfbench/runners/longgen.py``)
at a toy ``kimi_linear`` configuration: the set-up that serves the system
prompts and the documents, the reference comparison over both kinds of cache
and its controls, the window, the new per-layer readers; the generator
``lib/longgen_traffic.py`` and ``lib/kda_costs.py``.  ``test_rehearsal.py``'s
twin for the kind this file's PR added; the toy is never a cell."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
CELL = "serve.kimi-linear-tiny.longgen"
REAL = "serve.kimi-linear-48b-a3b.longgen-over"
sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b["paths"] = [REH]
    b["configs"] = [{"name": "kimi-linear-tiny", "source": "none",
                     "reduced": [], "why": "toy", "file": os.path.join(
                         REH, "configs", "kimi-linear-tiny.json")}]
    b["workloads"] = [{"name": CELL, "config": "kimi-linear-tiny",
                       "traffic": "longgen-tiny-over", "chips": 1,
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
    fails = serve["controls_fail"]
    assert set(fails) == {
        "bf16_state.carried", "bf16_state.chunks", "e4m3_steps",
        "state_zeroed.logits", "state_zeroed.pages", "no_delta.logits",
        "head_decay.logits", "unit_alpha.logits", "rotary_on.logits"}
    # The state's own arithmetic reads float32 rounding noise on the CPU and
    # its controls fail their rules at the toy too.  (The logit limits are
    # sized for the published widths' logits: what a wrong model moves at
    # the toy's is asserted in tests/test_kimi_linear_serving.py.)
    facts = serve["facts"]
    assert serve["state_agrees"], facts
    assert facts["state_carried"] < 1e-5 < 1e-3 < facts["state_carried_bf16"]
    assert len(facts["chunks_carried"]) >= 3
    assert max(facts["chunks_carried"]) < 1e-5
    assert min(facts["chunks_carried_bf16"]) > 1e-3
    assert max(facts["steps"]) < 2 ** -6 < min(facts["steps_e4m3"])
    for name in ("bf16_state.carried", "bf16_state.chunks", "e4m3_steps",
                 "state_zeroed.pages"):
        assert fails[name], serve["controls"]
    assert serve["pages_agree"], serve["summary"]
    assert last["failed"] == 0 and last["attempted"] > 0
    # the question resumed at the document's last block boundary, in both
    # kinds, and every shared prefix outlived the window
    assert facts["resumed_at"] == facts["boundary"] \
        == facts["doc_tokens"] // 4 * 4
    assert facts["cached_by_class"] == {"latent": facts["boundary"],
                                        "state": facts["boundary"]}
    assert serve["resumed"]
    assert serve["kv"]["shared_cached_before"] == 4
    assert serve["kv"]["shared_cached_after"] == 4
    window = serve["window"]
    assert window["admissions"] > 4 and window["snapshot_hits"] > 0
    assert set(serve["snapshot"]["cache_classes"]) == {"latent", "state"}
    assert serve["snapshot"]["model_counters"]["moe_held_pairs"] > 0
    if trace:
        names = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [])}
        assert set(last["metrics"]) <= names
        for want in ("serve_prefix_kind_loss", "serve_snapshots_per_admit",
                     "serve_kv_live_share", "serve_occupancy",
                     "serve_prefix_hit_rate", "serve_moe_held_pair_share",
                     "serve_prefill_steps_per_admit"):
            assert want in last["metrics"], sorted(last["metrics"])
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_every_seed_offers_the_same_work_in_another_order():
    from perfbench.lib import longgen_traffic
    spec = json.load(open(os.path.join(ROOT, "perfbench", "traffic",
                                       "longgen-over.json")))
    spec = dict(spec, rate_rps=6.0)
    made = []
    for seed in (2 ** 31 + 5, 11):
        docs = longgen_traffic.documents(spec, seed, 20480)
        system = longgen_traffic.system_prompts(spec, seed, 20480)
        items = longgen_traffic.requests(spec, seed, 10.0, 20480, docs,
                                         system)
        made.append(items)
        assert len(docs) == 8 and 16384 <= len(docs[0]) < len(docs[-1]) \
            <= 49152
        assert system.shape == (4, 512)
        assert len(items) == 256 + 60
        assert sum(r["arrival_s"] == 0.0 for r in items) >= 256
        long_ = [r for r in items if r["doc"] >= 0]
        assert len(long_) == round(0.125 * len(items))
        # one request in eight, all along the stream (the counts do not
        # divide evenly: a run of eight may hold a second)
        assert all(1 <= sum(r["doc"] >= 0 for r in items[i:i + 8]) <= 2
                   for i in range(0, len(items) - 16, 8))
        for r in items:
            if r["doc"] >= 0:
                d = docs[r["doc"]]
                assert (r["prompt"][:len(d)] == d).all() and r["shared"] < 0
                assert 32 <= len(r["prompt"]) - len(d) <= 256
            elif r["shared"] >= 0:
                assert (r["prompt"][:512] == system[r["shared"]]).all()
                assert len(r["prompt"]) >= 512 + 128
            assert len(r["prompt"]) + r["max_new_tokens"] <= 57600
            assert 1 <= r["max_new_tokens"] <= 8192
        plain = [r for r in items if r["doc"] < 0]
        assert abs(sum(r["shared"] >= 0 for r in plain)
                   - len(plain) / 2) <= 1
        assert max(int(r["prompt"].max()) for r in items) < 20480

    def multiset(items):
        return sorted((len(r["prompt"]), r["doc"], r["shared"])
                      for r in items)
    assert multiset(made[0]) == multiset(made[1])
    assert sorted(r["max_new_tokens"] for r in made[0]) \
        == sorted(r["max_new_tokens"] for r in made[1])
    assert [r["doc"] for r in made[0]] != [r["doc"] for r in made[1]]


def test_the_costs_of_the_two_forms_at_the_published_widths():
    from perfbench.lib import kda_costs
    sizes = {"num_heads": 32, "head_dim": 128, "layers_run": 6}
    assert kda_costs.state_bytes(sizes) == 32 * 128 * 128 * 4 == 2097152
    # 256 live streams, 6 layers: 6.44 GB read and written an iteration
    assert kda_costs.state_update_bytes(sizes, 256) == 2 * 256 * 6 * 2097152
    assert kda_costs.state_update_flops(sizes, 256) \
        == 7 * 256 * 6 * 32 * 128 * 128
    # bound by bandwidth: 0.875 operations a byte
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share = kda_costs.roofline_share(
        kda_costs.state_update_flops(sizes, 256),
        kda_costs.state_update_bytes(sizes, 256), 10e-3, peaks)
    assert 78.0 < share < 79.0


@pytest.mark.parametrize("program_has_the_keys", [True, False])
def test_the_expert_roofline_twin_counts_from_its_own_keys(
        program_has_the_keys, monkeypatch):
    """``moe_expert_gemm_roofline.kda`` takes the expert layers and the
    experts held from ``record["kda"]`` (``record["latent"]``'s
    ``num_hidden_layers`` is the LATENT layers here), and reads nothing on a
    record without them (the parent's)."""
    from perfbench import run as harness
    from perfbench.lib import scope_trace
    reader = harness.load_module(os.path.join(
        ROOT, "perfbench", "layer_metrics",
        "moe_expert_gemm_roofline.kda.py"))
    monkeypatch.setattr(scope_trace, "kernel_seconds",
                        lambda record, kernel: (0.3, 100))
    sums = {"moe_held_pairs": (100 * 2048.0, 100),
            "moe_held_empty": (100 * 2.0, 100)}
    monkeypatch.setattr(scope_trace, "span_arg_sum",
                        lambda record, span, arg: sums[arg])
    record = {"latent": {"hidden_size": 2304, "moe_intermediate_size": 1024,
                         "num_hidden_layers": 2},
              "kda": {"num_heads": 32, "head_dim": 128, "layers_run": 6},
              "peaks": {"bf16_flops_per_s": 197e12,
                        "hbm_bytes_per_s": 819e9}}
    if not program_has_the_keys:
        assert reader.read(record) is None
        return
    record["kda"].update(moe_layers=7, experts_held=16)
    # 7 x 16 - 2 experts' three matrices + 2,048 rows in and out, bf16
    bytes_ = (110 * 3 * 2304 * 1024 + 2048 * 2 * 2304) * 2
    assert 2048 * 6 * 2304 * 1024 / 197e12 < bytes_ / 819e9
    assert reader.read(record) == pytest.approx(
        100.0 * (bytes_ / 819e9) / 3e-3)

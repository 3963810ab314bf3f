"""CPU rehearsal of the ``sessions`` runner (``perfbench/runners/sessions.py``)
at a toy ``lfm2_moe`` configuration: the set-up that serves the histories,
the reference comparison over both kinds of cache and its controls, the
window of turns, the new per-layer readers; and the traffic generator's
promises.  ``test_rehearsal.py``'s twin for the kind this file's PR added;
the toy is never a cell."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
CELL = "serve.lfm2-tiny.sessions"
REAL = "serve.lfm2-24b-a2b.sessions-over"
sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b["paths"] = [REH]
    b["configs"] = [{"name": "lfm2-tiny", "source": "none", "reduced": [],
                     "file": os.path.join(REH, "configs", "lfm2-tiny.json"),
                     "why": "toy"}]
    b["workloads"] = [{"name": CELL, "config": "lfm2-tiny",
                       "traffic": "sessions-tiny-over", "chips": 1,
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
    # The toy's logit controls need not fail (64-wide products round
    # little, and LOGIT_ATOL is sized for the published widths' logits);
    # the lost state fails the page comparison at the toy too, and
    # everything else of ``correct`` holds.
    fails = serve["controls_fail"]
    assert set(fails) == {"state_zeroed.logits", "state_zeroed.pages",
                          "e4m3.logits", "e4m3.pages"}
    assert fails["state_zeroed.pages"], serve["controls"]
    assert serve["logits_agree"], serve["logit_checks"]
    assert serve["pages_agree"], serve["page_checks"]
    assert last["correct"] is all(fails.values()) and last["failed"] == 0
    assert last["attempted"] > 0
    assert serve["kv"]["histories_cached"] == 4
    facts = serve["facts"]
    assert facts["near_resumed_at"] == facts["turn_resumed_at"] \
        == facts["boundary"] > 0
    assert facts["near_cached_by_class"] == {
        "full": facts["boundary"], "conv": facts["boundary"]}
    window = serve["window"]
    assert window["admissions"] > 4           # later turns, not only first
    assert window["snapshot_hits"] == window["admissions"]
    assert 0 < window["snapshots_taken"] <= window["admissions"]
    assert window["prefix_lost_to_kind_tokens"] is not None
    assert set(serve["snapshot"]["cache_classes"]) == {"full", "conv"}
    assert serve["snapshot"]["state"]["resumed_tokens"] \
        == serve["snapshot"]["prefix"]["cached_tokens"] > 0
    counters = serve["snapshot"]["model_counters"]
    assert counters["moe_held_pair_share"] == 1.0
    if trace:
        names = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [])}
        assert set(last["metrics"]) <= names
        for want in ("serve_prefix_kind_loss", "serve_snapshots_per_admit",
                     "serve_moe_held_pair_share",
                     "serve_moe_held_load_max_over_mean",
                     "serve_full_pool_live_share", "serve_kv_live_share",
                     "serve_occupancy", "serve_prefix_hit_rate"):
            assert want in last["metrics"], sorted(last["metrics"])
        assert last["metrics"]["serve_prefix_kind_loss"]["value"] < 50.0
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def _spec(**kw):
    spec = json.load(open(os.path.join(ROOT, "perfbench", "traffic",
                                       "sessions-over.json")))
    return dict(spec, rate_rps=16.0,
                sessions=dict(spec["sessions"], count=8, min=96, max=320),
                **kw)


def test_every_seed_offers_the_same_multiset_in_balanced_rounds():
    from perfbench.lib import sessions_traffic
    spec = _spec()

    def shape(seed):
        hist = sessions_traffic.histories(spec, seed, 1000)
        return hist, sessions_traffic.requests(spec, seed, 20.0, 1000, hist)
    (hist_a, a), (hist_b, b) = shape(3), shape(2 ** 31 + 11)
    assert [len(h) for h in hist_a] == [len(h) for h in hist_b]
    assert len(hist_a) == 8 and len(hist_a[0]) < len(hist_a[-1])
    assert len(a) == len(b) == 320

    def added(reqs, hist):
        """What each turn adds: its message (the prompt less the turn
        before's prompt and reply) and the reply it asks for."""
        seen = {s: len(h) for s, h in enumerate(hist)}
        out = []
        for r in reqs:
            out.append(len(r["prompt"]) - seen[r["shared"]])
            seen[r["shared"]] = len(r["prompt"]) + r["max_new_tokens"]
        return sorted(out), sorted(r["max_new_tokens"] for r in reqs)
    assert added(a, hist_a) == added(b, hist_b)
    assert [r["shared"] for r in a] != [r["shared"] for r in b]
    np.testing.assert_allclose([r["arrival_s"] for r in a][::8],
                               [r["arrival_s"] for r in b][::8], rtol=1e-12)
    # every 8 consecutive requests hold each session once: turn k of every
    # session comes in round k
    for reqs in (a, b):
        order = np.array([r["shared"] for r in reqs]).reshape(-1, 8)
        assert (np.sort(order, axis=1) == np.arange(8)).all()
        assert [r["turn"] for r in reqs] == [i // 8 for i in range(320)]
    messages, replies = added(a, hist_a)
    assert min(messages) >= 32 and max(messages) <= 1024
    assert min(replies) >= 32 and max(replies) <= 512
    assert all(r["arrival_s"] < 20.0 for r in a)
    assert all(int(r["prompt"].max()) < 1000 for r in a)


def test_a_turn_extends_the_turn_before_by_its_offered_reply():
    from perfbench.lib import sessions_traffic
    spec = _spec()
    hist = sessions_traffic.histories(spec, 5, 1000)
    reqs = sessions_traffic.requests(spec, 5, 10.0, 1000, hist)
    last = {}
    for r in reqs:
        s, p = r["shared"], r["prompt"]
        if s in last:
            before = last[s]
            n = len(before["prompt"]) + before["max_new_tokens"]
            assert len(p) > n
            np.testing.assert_array_equal(p[:len(before["prompt"])],
                                          before["prompt"])
        else:
            np.testing.assert_array_equal(p[:len(hist[s])], hist[s])
        last[s] = r
    # the same seed gives the same inputs
    again = sessions_traffic.requests(spec, 5, 10.0, 1000, hist)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(reqs, again))


def test_max_total_is_held_or_refused():
    from perfbench.lib import sessions_traffic
    spec = _spec(max_total=4000)
    hist = sessions_traffic.histories(spec, 7, 1000)
    reqs = sessions_traffic.requests(spec, 7, 1.5, 1000, hist)
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 4000 for r in reqs)
    with pytest.raises(ValueError, match="outgrown"):
        sessions_traffic.requests(spec, 7, 20.0, 1000, hist)

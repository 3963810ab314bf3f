"""Request-scoped serving observability (PR-19 tentpole).

The load-bearing invariants:

1. **Contiguity** — every finished request's span timeline tiles
   [0, total_ms] exactly (queued → prefill → decode share boundary
   instants by construction), and ``queue_wait + service_ttft == ttft``
   to the microsecond, so a TTFT regression is attributable to queuing
   vs prefill from the record alone.
2. **Ledger identity** — each replica's serving goodput buckets
   (prefill / decode_useful / spec_wasted / admission_blocked / idle)
   sum to the serve wall within tolerance; a NEGATIVE residual (double
   attribution) flips ``consistent`` to False instead of being clamped.
3. **Explainability** — the router records every candidate's
   occupancy / queue-depth / prefix-affinity scores at route time, and
   the chosen replica maximizes the recorded score for EVERY decision.
4. **Honest accounting** — admission rejections are counted per request
   and surfaced (first rejection emits a structured event); zero
   completed requests is a reported condition in the report tool, never
   a crash.
"""
import json
import os
import sys

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import (ContinuousBatchingScheduler,
                                     InferenceEngine, ReplicaRouter,
                                     Request, shared_prefix_requests,
                                     synthetic_requests)
from deepspeed_tpu.models.gpt2 import GPT2_CONFIGS, gpt2_init
from deepspeed_tpu.monitor import (SERVING_BUCKETS, RequestTrace,
                                   ServingGoodputLedger, SLOTracker,
                                   validate_timeline)
from deepspeed_tpu.monitor.serving import ServingAggregator

CFG = GPT2_CONFIGS["gpt2-tiny"]

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.PRNGKey(1), CFG)


class _FakeTelemetry:
    enabled = True
    tracer = None

    def __init__(self):
        self.events = []

    def event(self, kind, payload):
        self.events.append((kind, dict(payload)))


# --------------------------------------------------------------------- #
# Serving goodput ledger
# --------------------------------------------------------------------- #
class TestServingGoodputLedger:
    def test_buckets_sum_to_wall_with_residual(self):
        led = ServingGoodputLedger(label="r0")
        led.note("prefill", 0.2)
        led.note("decode_useful", 0.5)
        led.note("spec_wasted", 0.1)
        led.note("idle", 0.15)
        s = led.snapshot(wall_s=1.0)
        assert s["label"] == "r0"
        total = sum(s[f"{b}_s"] for b in SERVING_BUCKETS) + s["other_s"]
        assert total == pytest.approx(1.0)
        assert s["other_s"] == pytest.approx(0.05)
        assert s["consistent"] and s["accounted_fraction"] == 1.0

    def test_double_attribution_flips_consistent(self):
        led = ServingGoodputLedger()
        led.note("prefill", 0.8)
        led.note("decode_useful", 0.8)      # 1.6s noted in a 1s wall
        s = led.snapshot(wall_s=1.0)
        assert s["other_s"] < 0, "negative residual surfaced, not clamped"
        assert not s["consistent"]

    def test_unknown_bucket_raises_and_nonpositive_ignored(self):
        led = ServingGoodputLedger()
        with pytest.raises(ValueError, match="bucket"):
            led.note("training", 1.0)
        led.note("idle", 0.0)
        led.note("idle", -5.0)
        assert led.noted_total() == 0.0

    def test_merged_sums_buckets_and_walls(self):
        a = ServingGoodputLedger(label="r0")
        b = ServingGoodputLedger(label="r1")
        a.note("prefill", 0.3)
        b.note("decode_useful", 0.6)
        m = ServingGoodputLedger.merged(
            [a.snapshot(wall_s=1.0), b.snapshot(wall_s=1.0)])
        assert m["wall_s"] == pytest.approx(2.0)
        assert m["prefill_s"] == pytest.approx(0.3)
        assert m["decode_useful_s"] == pytest.approx(0.6)
        assert m["consistent"]


# --------------------------------------------------------------------- #
# SLO tracker
# --------------------------------------------------------------------- #
class TestSLOTracker:
    def test_attainment_and_burn_rate(self):
        tr = SLOTracker(ttft_ms=100.0, tpot_ms=50.0, availability=0.9)
        assert tr.enabled
        assert tr.observe(0.05, 0.01)           # good
        assert not tr.observe(0.5, 0.01)        # ttft miss
        assert not tr.observe(0.05, 0.2)        # tpot miss
        tr.observe_failure()                    # aborted request
        s = tr.snapshot()
        assert s["total"] == 4 and s["good"] == 1
        assert s["ttft_misses"] == 1 and s["tpot_misses"] == 1
        assert s["attainment"] == pytest.approx(0.25)
        # burn = (1 - attainment) / (1 - availability) = 0.75 / 0.1
        assert s["burn_rate"] == pytest.approx(7.5)

    def test_unset_target_always_passes(self):
        tr = SLOTracker(ttft_ms=100.0)          # tpot unset
        assert tr.observe(0.05, 100.0)          # huge tpot: still good
        assert SLOTracker().enabled is False

    def test_window_prunes_old_outcomes(self):
        t = [0.0]
        tr = SLOTracker(ttft_ms=100.0, window_s=10.0, clock=lambda: t[0])
        tr.observe(1.0, None, t=0.0)            # miss, will age out
        t[0] = 100.0
        tr.observe(0.01, None, t=100.0)         # good, in window
        s = tr.snapshot(now=100.0)
        assert s["total"] == 2 and s["attainment"] == pytest.approx(0.5)
        assert s["window"]["n"] == 1
        assert s["window"]["attainment"] == pytest.approx(1.0)

    def test_merged_pools_trackers(self):
        a = SLOTracker(ttft_ms=100.0)
        b = SLOTracker(ttft_ms=100.0)
        a.observe(0.05, None)
        b.observe(0.5, None)
        m = SLOTracker.merged([a, b])
        assert m["total"] == 2 and m["good"] == 1
        assert m["attainment"] == pytest.approx(0.5)
        assert SLOTracker.merged([]) is None

    def test_validation(self):
        with pytest.raises(ValueError):
            SLOTracker(ttft_ms=100.0, availability=1.5)
        with pytest.raises(ValueError):
            SLOTracker(ttft_ms=100.0, window_s=0.0)


# --------------------------------------------------------------------- #
# Request trace (host-side unit: no engine, no device)
# --------------------------------------------------------------------- #
class TestRequestTrace:
    def test_lifecycle_timeline_is_contiguous(self):
        tr, tel = RequestTrace(), _FakeTelemetry()
        tr.enqueue(7, t=100.0)
        tr.route(7, 1, [{"replica": 0, "score": -1.0},
                        {"replica": 1, "score": 0.5}], t=100.001)
        assert tr.admit_reject(7, reason="reservation", t=100.002)
        assert not tr.admit_reject(7, reason="reservation", t=100.003)
        tr.admit(7, slot=2, t=100.01, replica="r1")
        tr.prefill(7, 0.02, tokens=16, chunks=2, cached_tokens=8)
        tr.first_token(7, t=100.03)
        tr.tick(7, 3, 1, t=100.05)
        tr.tick(7, 3, 4, proposed=4, accepted=3, t=100.09)
        tr.complete(7, t=100.09, telemetry=tel)
        kind, tl = tel.events[0]
        assert kind == "request_trace"
        assert validate_timeline(tl) == []
        assert tl["outcome"] == "complete"
        assert tl["replica"] == "r1" and tl["admission_attempts"] == 2
        assert [s["phase"] for s in tl["spans"]] == \
            ["queued", "prefill", "decode"]
        assert tl["queue_wait_ms"] + tl["service_ttft_ms"] == \
            pytest.approx(tl["ttft_ms"])
        # The decode span accumulated the per-tick marks.
        assert tl["spans"][2]["ticks"] == 2
        assert tl["spans"][2]["emitted"] == 5

    def test_abort_paths_still_tile(self):
        tr, tel = RequestTrace(), _FakeTelemetry()
        # Aborted after admit, before first token: prefill extends to
        # the end, no decode span, no gap.
        tr.enqueue(1, t=10.0)
        tr.admit(1, slot=0, t=10.01, replica="r0")
        tr.abort(1, "max_wall", t=10.05, telemetry=tel)
        tl = tel.events[0][1]
        assert tl["outcome"] == "abort" and tl["abort_reason"] == "max_wall"
        assert [s["phase"] for s in tl["spans"]] == ["queued", "prefill"]
        assert validate_timeline(tl) == []
        # Never admitted (starved in queue): one queued span.
        tr.enqueue(2, t=20.0)
        tr.abort(2, "starved", t=20.5, telemetry=tel)
        tl2 = tel.events[1][1]
        assert [s["phase"] for s in tl2["spans"]] == ["queued"]
        assert validate_timeline(tl2) == []

    @pytest.mark.parametrize("clock_s,wait_s", [(13.0, 1.5), (400.0, 12.0),
                                                (2500.0, 60.0)])
    def test_long_waits_do_not_read_as_gaps(self, clock_s, wait_s):
        """Under load the waits grow to seconds; offsets and durations
        are float differences of shared instants, so ``t + dur`` misses
        the next ``t`` by a few ulp. Exact equality flagged a quarter of
        such (correct) timelines; a hole of one microsecond is still
        found."""
        rng = np.random.default_rng(0)

        def tick(t):                     # perf_counter: whole ns / 1e9
            return int(t * 1e9) / 1e9
        inexact = 0
        for rid in range(300):
            tr = RequestTrace()
            t0 = tick(clock_s + 50 * rng.random())
            t_admit = tick(t0 + wait_s * rng.random())
            t_first = tick(t_admit + wait_s / 4 * rng.random())
            t_end = tick(t_first + wait_s / 3 * rng.random())
            tr.enqueue(rid, t=t0)
            tr.admit(rid, slot=0, t=t_admit)
            tr.first_token(rid, t=t_first)
            tl = tr.complete(rid, t=t_end)
            assert validate_timeline(tl) == [], tl
            a, b = tl["spans"][1], tl["spans"][2]
            inexact += a["t_ms"] + a["dur_ms"] != b["t_ms"]
            holed = json.loads(json.dumps(tl))
            holed["spans"][2]["t_ms"] += 1e-3
            assert any("gap" in p for p in validate_timeline(holed))
        # the case exists at loaded magnitudes (else this tests nothing)
        assert inexact > 0 or wait_s < 5

    def test_ring_caps_count_drops(self):
        tr, tel = RequestTrace(capacity=2, tick_capacity=3), \
            _FakeTelemetry()
        for rid in range(4):
            tr.enqueue(rid, t=float(rid))
        assert tr.summary()["records_dropped"] == 2
        tr.admit(0, slot=0, t=0.01, replica="r0")
        tr.first_token(0, t=0.02)
        for i in range(5):
            tr.tick(0, 1, 1, t=0.03 + i * 0.01)
        tr.complete(0, t=0.1, telemetry=tel)
        tl = tel.events[0][1]
        assert len(tl["ticks"]) == 3, "ring kept the newest tick marks"
        assert tl["ticks_dropped"] == 2
        assert tr.summary()["ticks_dropped"] == 2


# --------------------------------------------------------------------- #
# inference.slo config block
# --------------------------------------------------------------------- #
class TestInferenceSloConfig:
    def test_defaults_disabled(self):
        from deepspeed_tpu.runtime.config import InferenceConfig
        inf = InferenceConfig(None)
        assert inf.slo.ttft_ms == 0.0 and inf.slo.tpot_ms == 0.0
        assert inf.slo.availability == 0.99 and inf.slo.window_s == 60.0
        assert not inf.slo.enabled

    def test_block_parses_and_enables(self):
        from deepspeed_tpu.runtime.config import InferenceConfig
        inf = InferenceConfig({"inference": {
            "slo": {"ttft_ms": 250.0, "tpot_ms": 20,
                    "availability": 0.999, "window_s": 30}}})
        assert inf.slo.enabled
        assert inf.slo.ttft_ms == 250.0 and inf.slo.tpot_ms == 20.0
        assert inf.slo.availability == 0.999

    def test_invalid_values_raise(self):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                                  InferenceConfig)
        for bad in ({"ttft_ms": -1}, {"tpot_ms": True},
                    {"availability": 0.0}, {"availability": 1.0},
                    {"window_s": 0}, {"window_s": -2.0}):
            with pytest.raises(DeepSpeedConfigError):
                InferenceConfig({"inference": {"slo": bad}})
        with pytest.raises(DeepSpeedConfigError):
            InferenceConfig({"inference": {"slo": 5}})


# --------------------------------------------------------------------- #
# Aggregator: queue-wait split + admission accounting (satellites 1, 2)
# --------------------------------------------------------------------- #
class TestAggregatorSplitAndAdmission:
    def test_queue_wait_and_service_ttft_surface(self):
        agg = ServingAggregator(8, label="r0")
        for i in range(4):
            agg.note_request(0.030, 0.002, 8, queue_wait_s=0.010,
                             service_ttft_s=0.020,
                             admission_attempts=1 + i % 2)
        agg.note_reject()
        agg.note_reject()
        snap = agg.snapshot(wall_s=1.0)
        assert snap["queue_wait_ms"]["p50"] == pytest.approx(10.0)
        assert snap["service_ttft_ms"]["p50"] == pytest.approx(20.0)
        assert snap["queue_wait_ms"]["p50"] + \
            snap["service_ttft_ms"]["p50"] == \
            pytest.approx(snap["ttft_ms"]["p50"])
        assert snap["admission"]["reservations_rejected"] == 2
        assert snap["admission"]["attempts"]["p95"] == 2

    def test_merged_pools_split_and_rejections(self):
        a, b = ServingAggregator(8, label="r0"), \
            ServingAggregator(8, label="r1")
        a.note_request(0.03, None, 4, queue_wait_s=0.01,
                       service_ttft_s=0.02)
        b.note_request(0.05, None, 4, queue_wait_s=0.02,
                       service_ttft_s=0.03)
        a.note_reject()
        m = ServingAggregator.merged([a, b])
        snap = m.snapshot(wall_s=1.0)
        assert snap["queue_wait_ms"]["n"] == 2
        assert snap["admission"]["reservations_rejected"] == 1

    def test_ledger_and_slo_ride_the_snapshot(self):
        agg = ServingAggregator(8, label="r0")
        agg.ledger = ServingGoodputLedger(label="r0")
        agg.ledger.note("decode_useful", 0.4)
        agg.slo = SLOTracker(ttft_ms=100.0)
        agg.slo.observe(0.05, None)
        snap = agg.snapshot(wall_s=1.0)
        assert snap["ledger"]["decode_useful_s"] == pytest.approx(0.4)
        assert snap["ledger"]["wall_s"] == pytest.approx(1.0)
        assert snap["slo"]["attainment"] == 1.0
        # No slo attached -> section omitted (skip-never-fail).
        assert "slo" not in ServingAggregator(8).snapshot(wall_s=1.0)


# --------------------------------------------------------------------- #
# Router decision explainability (satellite 3)
# --------------------------------------------------------------------- #
class TestRoutingExplainability:
    def test_recorded_scores_explain_every_choice(self, params):
        """Skewed two-replica shared-prefix stream: after a first wave
        populates one replica's prefix cache, a second wave's routing
        decisions must (a) be argmax of the RECORDED candidate scores,
        decision by decision, and (b) show nonzero recorded prefix
        affinity."""
        engines = [InferenceEngine(CFG, params, config={
            "inference": {"max_slots": 8, "max_seq_len": 64,
                          "prefill_chunk": 8, "block_size": 16,
                          "replica": f"r{i}"}}) for i in range(2)]
        router = ReplicaRouter(engines, affinity_weight=1.0)
        wave1 = shared_prefix_requests(6, prefix_len=32, tail_len=(4, 8),
                                       max_new_tokens=4,
                                       vocab_size=CFG.vocab_size, seed=5)
        router.serve(wave1)
        # Second wave shares the same prefix: its blocks are resident
        # now, so route-time affinity scores must be nonzero.
        wave2 = shared_prefix_requests(6, prefix_len=32, tail_len=(4, 8),
                                       max_new_tokens=4,
                                       vocab_size=CFG.vocab_size, seed=5)
        for r in wave2:
            r.rid += 100
        router.serve(wave2)
        assert len(router.decisions) == 12
        for d in router.decisions:
            scores = [c["score"] for c in d["candidates"]]
            assert len(scores) == 2
            assert scores[d["chosen"]] == max(scores), \
                f"decision for rid={d['rid']} not explained by scores"
            for c in d["candidates"]:
                assert {"replica", "occupancy", "queue_depth",
                        "affinity_tokens"} <= set(c)
        wave2_decisions = [d for d in router.decisions
                           if d["rid"] >= 100]
        assert any(c["affinity_tokens"] > 0
                   for d in wave2_decisions for c in d["candidates"]), \
            "no recorded prefix affinity in the second wave"
        for e in engines:
            e.close()


# --------------------------------------------------------------------- #
# End-to-end: scheduler stream -> JSONL -> report (the acceptance gate)
# --------------------------------------------------------------------- #
class TestServingObservabilityStream:
    def test_traced_stream_jsonl_validates(self, tmp_path, params):
        """dp=8 shared-prefix stream under fail_on_recompile: every
        completed request's timeline re-validates from the JSONL alone,
        the ledger is consistent, the report's serving_slo section
        carries verdicts, and admission pressure is surfaced."""
        eng = InferenceEngine(CFG, params, config={
            "inference": {"max_slots": 8, "max_seq_len": 64,
                          "prefill_chunk": 8, "block_size": 16,
                          "spec_k": 4,
                          "slo": {"ttft_ms": 60000.0,
                                  "tpot_ms": 60000.0}},
            "telemetry": {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "obs", "report_steps": 10 ** 6,
                          "fail_on_recompile": True}})
        # 3x oversubscription (24 requests, 8 slots, saturation
        # arrivals): later requests queue, so queue_wait > 0 and
        # head-of-queue admission rejections occur and must be counted.
        reqs = shared_prefix_requests(24, prefix_len=24, tail_len=(4, 8),
                                      max_new_tokens=6,
                                      vocab_size=CFG.vocab_size, seed=7)
        report = eng.serve(reqs)
        assert report["completed"] == 24 and report["recompiles"] == 0
        # Ledger: buckets sum to the serve wall within tolerance.
        led = report["ledger"]
        assert led["consistent"], led
        total = sum(led[f"{b}_s"] for b in SERVING_BUCKETS) \
            + led["other_s"]
        assert total == pytest.approx(led["wall_s"], rel=1e-6)
        assert led["decode_useful_s"] > 0 and led["prefill_s"] > 0
        # SLO: loose targets -> full attainment, burn 0.
        assert report["slo"]["attainment"] == 1.0
        assert report["slo"]["burn_rate"] == 0.0
        # Queue split: oversubscribed saturation stream waits.
        assert report["queue_wait_ms"]["n"] == 24
        assert report["queue_wait_ms"]["p95"] > 0
        assert report["admission"]["reservations_rejected"] >= 0
        # Trace summary rode the report.
        assert report["trace"]["completed"] == 24
        assert report["trace"]["records_dropped"] == 0
        eng.close()

        # JSONL replay: timelines + events, with no engine state.
        events = []
        with open(tmp_path / "obs.jsonl") as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("kind") == "event":
                    events.append(rec)
        traces = [e for e in events if e["event"] == "request_trace"]
        assert len(traces) == 24
        for tl in traces:
            assert validate_timeline(tl) == [], \
                (tl["rid"], validate_timeline(tl))
        # First-rejection events (if any pool-gate rejections happened)
        # carry rid + reason + queue depth.
        for e in events:
            if e["event"] == "admission_rejected":
                assert {"rid", "reason", "queue_depth"} <= set(e)

        # Report tool: serving_slo section parses from the stream.
        sys.path.insert(0, TOOLS)
        from telemetry_report import summarize
        summary = summarize(str(tmp_path / "obs.jsonl"))
        ss = summary["serving_slo"]
        assert ss["available"]
        assert ss["ledger"]["consistent"]
        assert ss["slo"]["burn"]["default"]["verdict"] == "ok"
        assert ss["traces"]["recorded"] == 24
        assert ss["traces"]["contiguity_violations"] == 0
        worst = ss["traces"]["worst_ttft"]
        assert worst and worst[0]["spans"], "exemplars carry timelines"
        assert worst[0]["ttft_ms"] >= worst[-1]["ttft_ms"]
        srv = summary["serving"]
        assert srv["queue_wait_ms"]["n"] == 24
        assert srv["service_ttft_ms"]["n"] == 24

    def test_zero_completed_requests_report_null_slo(self, tmp_path):
        """Satellite 6 regression: a serving stream that completed
        nothing (all aborted/starved) must summarize with slo: null and
        a reason, not a crash."""
        stream = tmp_path / "empty.jsonl"
        recs = [
            {"kind": "meta", "mode": "serving", "ts": 1.0},
            {"kind": "report", "step": 1,
             "serving": {"replica": "r0", "completed": 0,
                         "ledger": {"wall_s": 1.0, "prefill_s": 0.0,
                                    "decode_useful_s": 0.0,
                                    "spec_wasted_s": 0.0,
                                    "admission_blocked_s": 0.9,
                                    "idle_s": 0.0, "other_s": 0.1,
                                    "accounted_fraction": 1.0,
                                    "consistent": True}}},
        ]
        with open(stream, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        sys.path.insert(0, TOOLS)
        from telemetry_report import summarize
        summary = summarize(str(stream))
        ss = summary["serving_slo"]
        assert ss["available"]
        assert ss["slo"] is None
        assert "no completed requests" in ss["slo_unavailable_reason"]
        assert ss["ledger"]["consistent"]
        assert summary["serving"]["completed"] == 0

    def test_fake_engine_scheduler_path_still_works(self):
        """The duck-typed fake-engine path (telemetry disabled) must not
        trip over the new tracing hooks — trace stays None, no new
        attribute is required of the engine."""
        import time as _time

        class _FakeTel:
            enabled = False
            recompile_count = 0

            def span(self, *a, **k):
                # what a disabled Telemetry returns: the bare annotation
                import jax
                return jax.profiler.TraceAnnotation(*a, **k)

        class _FakeEngine:
            max_slots, max_len, prefill_chunk = 2, 1000, 8
            telemetry = _FakeTel()

            def __init__(self):
                self.active = np.zeros(2, bool)
                self.serving = ServingAggregator(2)

            def group_of(self, slot):
                return 0                 # one dp group: one admission a batch

            def select_slot(self, prompt, max_new_tokens=0,
                            exclude_groups=None):
                free = np.flatnonzero(~self.active)
                return int(free[0]) if len(free) and not exclude_groups \
                    else None

            def prefill(self, prompt, slot, temperature=0.0, **kw):
                return 1, None

            def prefill_many(self, admissions, temperature=0.0, rids=None):
                return [self.prefill(prompt, slot, temperature)
                        for slot, prompt, _ in admissions]

            def activate_slot(self, slot, n, tok):
                self.active[slot] = True

            def release_slot(self, slot):
                self.active[slot] = False

            def context_len(self, slot):
                return 10

            def decode_once(self, temperature=0.0, continuing=()):
                # a synchronous engine: the iteration fetched is its own
                self.serving.note_iteration(int(self.active.sum()), 1e-4)
                _time.sleep(0.001)
                return np.ones(2, np.int32), self.active.copy()

            def complete_request(self, *a, **k):
                self.serving.note_request(0.01, None, 1)

        eng = _FakeEngine()
        reqs = synthetic_requests(4, prompt_len=(4, 4),
                                  max_new_tokens=3)
        sched = ContinuousBatchingScheduler(eng)
        assert sched.trace is None
        report = sched.serve(reqs)
        assert report["completed"] == 4
        assert "trace" not in report

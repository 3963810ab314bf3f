"""Trace-truth profiling: ingestion, reconciliation, and the honesty
machinery around them.

- **Classification**: HLO/kernel names land in the right measurement
  bucket (GEMM, Pallas family, ICI vs DCN collective, host transfer),
  with the documented precedences (collective beats a Pallas name
  match; ``sparse_flash`` is not shadowed by ``flash_attention``).
- **Decomposition**: the sweep line partitions covered time exactly
  under the bucket priority; buckets + idle + unattributed sum to the
  window wall (``explained_frac == 1.0``); runtime scaffold spans are
  dropped instead of double-covering real ops.
- **Perfetto validity**: TraceWriter's closed file is strict JSON, its
  pre-close file is the unterminated array form, lanes/pids are
  consistent, flow arrows are well-formed — and both forms round-trip
  through ``parse_trace_events`` with span counts preserved.
- **ProfilerWindow**: failed start/stop surface as structured
  ``profile_window`` events; a reused capture dir is refused, never
  silently overwritten.
- **Reconciliation**: measured-over-floor ratios, boundedness verdicts,
  and the seeded-divergence path — an injected host-sync stall is
  attributed to the ``host`` bucket and fires ``reconcile_divergence``.
- **Label ratchet** (tools/bench_gate.py): measured stays measured.
"""
import glob
import gzip
import importlib.util
import json
import os

import pytest

from deepspeed_tpu.monitor.cost_model import (BOUND_DCN, BOUND_HBM,
                                              BOUND_INTERCONNECT)
from deepspeed_tpu.monitor.profile_ingest import (BUCKET_PRIORITY,
                                                  classify_op,
                                                  ingest,
                                                  ingest_events,
                                                  ingest_from_telemetry,
                                                  parse_trace_events)
from deepspeed_tpu.monitor.reconcile import (DEFAULT_HOST_FRAC,
                                             divergence_events,
                                             reconcile)
from deepspeed_tpu.monitor.trace import _LANES, ProfilerWindow, TraceWriter
from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                          TelemetryProfileConfig)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ev(name, ts, dur, pid=1, tid=1, **args):
    """One complete trace event carrying an hlo_op arg (so its lane is
    recognized as a device lane)."""
    return {"name": name, "ph": "X", "pid": pid, "tid": tid,
            "ts": float(ts), "dur": float(dur),
            "args": dict({"hlo_op": name}, **args)}


# --------------------------------------------------------------------- #
# Classification
# --------------------------------------------------------------------- #
class TestClassifyOp:
    def test_gemm_ops(self):
        assert classify_op("dot.5")[0] == "gemm"
        assert classify_op("convolution.2")[0] == "gemm"
        # Fusions keep the root op identity through args["hlo_op"].
        assert classify_op("fusion.12", {"hlo_op": "dot.3"})[0] == "gemm"

    def test_collective_tiers(self):
        assert classify_op("all-reduce.1") == ("collective_ici", None)
        assert classify_op("reduce-scatter.4")[0] == "collective_ici"
        # A DCN axis name or dcn marker moves the op to the DCN tier.
        assert classify_op("all-reduce.1",
                           {"hlo_module": "dcn"})[0] == "collective_dcn"
        assert classify_op("all-gather.2 slice")[0] == "collective_dcn"

    def test_host_ops(self):
        assert classify_op("TfrtCpuBuffer::Await")[0] == "host"
        assert classify_op("infeed.1")[0] == "host"
        assert classify_op("copy-start.3")[0] == "host"

    def test_pallas_families(self):
        cases = {"_ln_fwd_kernel": "fused_ln",
                 "_gelu_bwd_kernel": "fused_gelu",
                 "_fwd_kernel": "flash_attention",
                 "_gg_kernel": "grouped_gemm",
                 "_pattn_kernel": "paged_attention",
                 "_pattn_chunk_kernel": "paged_attention",
                 "_fused_adam_kernel": "fused_update"}
        for name, family in cases.items():
            assert classify_op(name) == ("pallas", family), name

    def test_sparse_flash_not_shadowed(self):
        # _sfwd_kernel must hit sparse_flash, not flash_attention's
        # broader pattern (registry-order shadowing hazard).
        assert classify_op("_sfwd_kernel") == ("pallas", "sparse_flash")
        assert classify_op("_sdkv_kernel")[1] == "sparse_flash"

    def test_collective_beats_pallas_name(self):
        # An op that names both is wire time, not kernel time.
        assert classify_op("all_to_all_grouped_gemm")[0] == \
            "collective_ici"

    def test_unattributed_fallback(self):
        assert classify_op("transpose.7") == ("unattributed", None)


# --------------------------------------------------------------------- #
# Sweep-line decomposition
# --------------------------------------------------------------------- #
class TestDecomposition:
    def test_overlap_owned_by_higher_priority(self):
        # gemm [0,100), all-reduce [50,150): the overlap [50,100) is
        # wire time under the documented priority.
        out = ingest_events([_ev("dot.1", 0, 100),
                             _ev("all-reduce.1", 50, 100)])
        b = out["buckets_ms"]
        assert b["gemm"] == pytest.approx(0.050)
        assert b["collective_ici"] == pytest.approx(0.100)
        assert b["idle"] == pytest.approx(0.0)

    def test_buckets_plus_idle_sum_to_wall(self):
        out = ingest_events([_ev("dot.1", 0, 10),
                             _ev("all-reduce.2", 30, 20),
                             _ev("transpose.3", 90, 10)])
        sc = out["sum_check"]
        assert sc["explained_frac"] == pytest.approx(1.0)
        assert sc["decomposed_ms"] == pytest.approx(sc["wall_ms"])
        assert out["buckets_ms"]["idle"] == pytest.approx(0.060)

    def test_unattributed_is_never_clamped(self):
        out = ingest_events([_ev("mystery_op.9", 0, 50)])
        assert out["buckets_ms"]["unattributed"] == pytest.approx(0.050)
        assert out["sum_check"]["unattributed_ms"] == pytest.approx(0.050)

    def test_scaffold_spans_do_not_double_cover(self):
        # A runtime container span wrapping the whole program must not
        # count as busy time on top of the ops inside it.
        ev = [_ev("dot.1", 10, 20)]
        ev.append({"name": "ThunkExecutor::Execute", "ph": "X",
                   "pid": 1, "tid": 1, "ts": 0.0, "dur": 100.0})
        out = ingest_events(ev)
        assert out["buckets_ms"]["unattributed"] == pytest.approx(0.0)
        assert out["buckets_ms"]["gemm"] == pytest.approx(0.020)

    def test_per_step_division(self):
        out = ingest_events([_ev("dot.1", 0, 100)], n_steps=2)
        assert out["per_step_ms"]["gemm"] == pytest.approx(0.050)
        assert out["per_step_wall_ms"] == pytest.approx(out["wall_ms"] / 2)

    def test_pallas_family_attribution(self):
        out = ingest_events([_ev("_gg_kernel", 0, 40),
                             _ev("_pattn_kernel", 40, 10)])
        fams = out["pallas_families_ms"]
        assert fams["grouped_gemm"] == pytest.approx(0.040)
        assert fams["paged_attention"] == pytest.approx(0.010)
        assert out["buckets_ms"]["pallas"] == pytest.approx(0.050)

    def test_bucket_priority_is_total(self):
        assert set(BUCKET_PRIORITY) == {
            "collective_dcn", "collective_ici", "host", "pallas",
            "gemm", "unattributed"}


# --------------------------------------------------------------------- #
# Trace parsing forms + Perfetto validity
# --------------------------------------------------------------------- #
class TestParseForms:
    def test_dict_form(self):
        text = json.dumps({"traceEvents": [_ev("dot.1", 0, 1)]})
        assert len(parse_trace_events(text)) == 1

    def test_strict_array_form(self):
        assert len(parse_trace_events(json.dumps([_ev("a", 0, 1)]))) == 1

    def test_unterminated_array_form(self):
        text = "[\n" + json.dumps(_ev("a", 0, 1)) + ",\n" + \
            json.dumps(_ev("b", 1, 1)) + ",\n"
        assert len(parse_trace_events(text)) == 2

    def test_garbage_raises(self):
        with pytest.raises(json.JSONDecodeError):
            parse_trace_events("not json at all")


class TestTraceWriterPerfetto:
    def _write(self, path, close):
        tw = TraceWriter(path, is_writer=True)
        with tw.span("train_batch", step=1):
            pass
        tw.add_span("grad_sync", 0.001, 0.002)
        tw.add_span("optimizer_apply", 0.003, 0.001)
        tw.instant("nan_guard", {"step": 1})
        t = 0.004
        tw.flow("req", 7, "s", t, tid=0)
        tw.flow("req", 7, "t", t + 0.001, tid=1)
        tw.flow("req", 7, "f", t + 0.002, tid=2)
        tw.flush()
        if close:
            tw.close()
        return tw

    def test_closed_file_is_strict_json(self, tmp_path):
        path = str(tmp_path / "host.trace.json")
        tw = self._write(path, close=True)
        with open(tw.path) as f:
            doc = json.load(f)   # strict parse — no repair step
        assert isinstance(doc, list)
        # One pid throughout; span lanes follow the stable map.
        pids = {e["pid"] for e in doc}
        assert len(pids) == 1
        spans = [e for e in doc if e.get("ph") == "X"]
        by_name = {e["name"]: e for e in spans}
        assert by_name["grad_sync"]["tid"] == _LANES["grad_sync"]
        assert by_name["train_batch"]["tid"] == _LANES["train_batch"]
        # Flow arrows: s/t/f triple sharing one id; the finish binds to
        # the enclosing slice.
        flows = [e for e in doc if e.get("ph") in ("s", "t", "f")]
        assert [e["ph"] for e in flows] == ["s", "t", "f"]
        assert len({e["id"] for e in flows}) == 1
        assert flows[-1]["bp"] == "e"

    def test_preclose_file_is_unterminated_form(self, tmp_path):
        path = str(tmp_path / "host.trace.json")
        tw = self._write(path, close=False)
        with open(tw.path) as f:
            text = f.read()
        with pytest.raises(json.JSONDecodeError):
            json.loads(text)     # by design: crash-tolerant form
        assert len(parse_trace_events(text)) > 0
        tw.close()

    @pytest.mark.parametrize("close", [True, False])
    def test_round_trip_preserves_span_count(self, tmp_path, close):
        path = str(tmp_path / "host.trace.json")
        tw = self._write(path, close=close)
        with open(tw.path) as f:
            events = parse_trace_events(f.read())
        spans = [e for e in events if e.get("ph") == "X"]
        assert len(spans) == 3   # train_batch, grad_sync, optimizer_apply
        out = ingest_events(events)
        assert out["n_events"] == 3
        if not close:
            tw.close()


# --------------------------------------------------------------------- #
# ProfilerWindow: structured events + overwrite refusal
# --------------------------------------------------------------------- #
class TestProfilerWindow:
    def _window(self, tmp_path, start=4, n=2, sub="w"):
        events = []
        w = ProfilerWindow(start, n, str(tmp_path / sub),
                           on_event=lambda k, p: events.append((k, p)))
        return w, events

    def test_capture_dir_carries_step_range(self, tmp_path):
        w, _ = self._window(tmp_path, start=4, n=2)
        assert w.capture_dir.endswith("step_4_6")

    def test_failed_start_emits_structured_event(self, tmp_path):
        # out_dir is a FILE: the capture dir cannot be created.
        blocker = tmp_path / "blocked"
        blocker.write_text("x")
        events = []
        w = ProfilerWindow(4, 2, str(blocker),
                           on_event=lambda k, p: events.append((k, p)))
        w.tick(4)
        assert w.failed
        kind, p = events[-1]
        assert kind == "profile_window"
        assert p["phase"] == "start" and p["ok"] is False
        assert "reason" in p and p["start_step"] == 4
        # A failed window stays failed — no retry storm on later ticks.
        w.tick(5)
        assert len(events) == 1

    def test_failed_stop_emits_structured_event(self, tmp_path,
                                                monkeypatch):
        import jax
        w, events = self._window(tmp_path)
        w._active = True         # simulate an armed window

        def boom():
            raise RuntimeError("profiler backend gone")
        monkeypatch.setattr(jax.profiler, "stop_trace", boom)
        w.stop()
        kind, p = events[-1]
        assert p["phase"] == "stop" and p["ok"] is False
        assert "profiler backend gone" in p["reason"]
        assert w.failed

    def test_duplicate_capture_dir_refused(self, tmp_path):
        w1, _ = self._window(tmp_path, sub="shared")
        w1._claim_dir()
        w2, events = self._window(tmp_path, sub="shared")
        with pytest.raises(RuntimeError, match="duplicate"):
            w2._claim_dir()
        # Through tick(): the refusal surfaces as a failed-start event,
        # never a silent overwrite.
        w3, events3 = self._window(tmp_path, sub="shared")
        w3.tick(4)
        assert w3.failed
        assert events3[-1][1]["ok"] is False
        assert "duplicate" in events3[-1][1]["reason"]

    def test_nonempty_dir_on_disk_refused(self, tmp_path):
        w, _ = self._window(tmp_path, sub="prior")
        os.makedirs(w.capture_dir)
        with open(os.path.join(w.capture_dir, "old.trace.json"), "w") as f:
            f.write("[]")
        with pytest.raises(RuntimeError, match="not empty"):
            w._claim_dir()


# --------------------------------------------------------------------- #
# Reconciliation + the seeded divergence
# --------------------------------------------------------------------- #
def _cost_model(bound=BOUND_HBM, t_compute=1.0, t_hbm=2.0, t_comm=0.5,
                t_dcn=0.0):
    path = {"available": True, "t_compute_ms": t_compute,
            "t_hbm_ms": t_hbm, "t_comm_ms": t_comm, "t_dcn_ms": t_dcn,
            "floor_ms": max(t_compute, t_hbm) + t_comm + t_dcn,
            "bound": bound}
    return {"paths": {"train_step": path},
            "step": {"paths": {"train_step": 1}, "bound": bound}}


def _decomp(gemm=0.0, pallas=0.0, ici=0.0, dcn=0.0, host=0.0,
            unattributed=0.0, idle=0.0):
    per_step = {"gemm": gemm, "pallas": pallas, "collective_ici": ici,
                "collective_dcn": dcn, "host": host,
                "unattributed": unattributed, "idle": idle}
    return {"per_step_ms": per_step,
            "per_step_wall_ms": sum(per_step.values())}


class TestReconcile:
    def test_match_when_dominant_confirms_bound(self):
        r = reconcile(_decomp(gemm=4.0, ici=0.6), _cost_model(BOUND_HBM))
        assert r["verdict"] == "match"
        assert r["dominant_bucket"] == "gemm"
        assert r["predicted_bound"] == BOUND_HBM
        assert r["paths"]["train_step"]["verdict"] == "match"

    def test_mismatch_when_wire_dominates_a_compute_prediction(self):
        r = reconcile(_decomp(gemm=0.5, ici=6.0), _cost_model(BOUND_HBM))
        assert r["verdict"] == "mismatch"
        assert r["dominant_bucket"] == "collective_ici"

    def test_dcn_bucket_confirms_dcn_bound(self):
        r = reconcile(_decomp(dcn=5.0, gemm=1.0),
                      _cost_model(BOUND_DCN, t_dcn=2.0))
        assert r["verdict"] == "match"

    def test_measured_over_floor_ratio(self):
        # compute-side busy 6ms vs max(1,2)=2ms floor -> 3.0x.
        r = reconcile(_decomp(gemm=5.0, unattributed=1.0),
                      _cost_model(BOUND_HBM), threshold=10.0)
        comp = r["components"]["compute"]
        assert comp["measured_ms"] == pytest.approx(6.0)
        assert comp["floor_ms"] == pytest.approx(2.0)
        assert comp["measured_over_floor"] == pytest.approx(3.0)
        assert not comp["diverged"]

    def test_threshold_fires_divergence(self):
        r = reconcile(_decomp(ici=5.0, gemm=2.5),
                      _cost_model(BOUND_INTERCONNECT), threshold=3.0)
        assert r["components"]["collective_ici"]["diverged"]
        evs = divergence_events(r)
        assert evs and evs[0]["event"] == "reconcile_divergence"
        assert evs[0]["component"] == "collective_ici"

    def test_seeded_host_stall_fires_divergence(self):
        """The acceptance seed: an injected host-sync stall must land
        in the host bucket and fire reconcile_divergence — end to end
        through the real ingest path, not a hand-built decomposition."""
        events = [
            _ev("dot.1", 0, 2000),                       # 2ms compute
            # The stall: a blocking host wait for 8ms of a ~10ms step.
            _ev("TfrtCpuBuffer::Await", 2000, 8000),
        ]
        decomp = ingest_events(events, n_steps=1)
        assert decomp["per_step_ms"]["host"] == pytest.approx(8.0)
        r = reconcile(decomp, _cost_model(BOUND_HBM),
                      host_frac=DEFAULT_HOST_FRAC)
        host = r["components"]["host"]
        assert host["diverged"] and host["wall_frac"] > 0.5
        assert any(d["component"] == "host" for d in r["divergences"])
        assert any(e["event"] == "reconcile_divergence"
                   and e["component"] == "host"
                   for e in divergence_events(r))

    def test_unavailable_path_gets_unavailable_verdict(self):
        cm = _cost_model()
        cm["paths"]["eval_step"] = {"available": False}
        r = reconcile(_decomp(gemm=1.0), cm)
        assert r["paths"]["eval_step"]["verdict"] == "unavailable"


# --------------------------------------------------------------------- #
# telemetry.profile config block
# --------------------------------------------------------------------- #
class TestTelemetryProfileConfig:
    def test_defaults(self):
        c = TelemetryProfileConfig()
        assert c.start_step == -1 and c.window_steps == 2
        assert c.divergence_threshold == pytest.approx(3.0)
        assert c.host_frac == pytest.approx(0.10)

    def test_block_overrides(self):
        c = TelemetryProfileConfig({"start_step": 7, "window_steps": 3,
                                    "divergence_threshold": 1.5,
                                    "host_frac": 0.25,
                                    "out_dir": "/tmp/x"})
        assert (c.start_step, c.window_steps) == (7, 3)
        assert c.divergence_threshold == pytest.approx(1.5)
        assert c.out_dir == "/tmp/x"

    def test_legacy_flat_aliases(self):
        c = TelemetryProfileConfig(None, legacy_start=5, legacy_steps=4,
                                   legacy_dir="/tmp/legacy")
        assert (c.start_step, c.window_steps) == (5, 4)
        assert c.out_dir == "/tmp/legacy"

    def test_block_wins_over_legacy(self):
        c = TelemetryProfileConfig({"start_step": 9}, legacy_start=5)
        assert c.start_step == 9

    @pytest.mark.parametrize("bad", [
        {"start_step": "soon"},
        {"window_steps": 0},
        {"window_steps": True},
        {"divergence_threshold": -1.0},
        {"host_frac": "lots"},
    ])
    def test_validation_rejects(self, bad):
        with pytest.raises(DeepSpeedConfigError):
            TelemetryProfileConfig(bad)


# --------------------------------------------------------------------- #
# JSONL-only ingestion + the label ratchet
# --------------------------------------------------------------------- #
class TestIngestFromTelemetry:
    def _jsonl(self, tmp_path, trace_dir, ok=True, reason=None):
        rec = {"kind": "event", "event": "profile_window",
               "phase": "stop", "path": str(trace_dir),
               "start_step": 4, "stop_step": 6, "ok": ok, "step": 6,
               "ts": 0.0}
        if reason:
            rec["reason"] = reason
        path = tmp_path / "run.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps({"kind": "meta"}) + "\n")
            f.write(json.dumps(rec) + "\n")
        return str(path)

    def test_locates_and_ingests_from_jsonl_alone(self, tmp_path):
        trace_dir = tmp_path / "cap"
        os.makedirs(trace_dir)
        doc = {"traceEvents": [_ev("dot.1", 0, 100),
                               _ev("all-reduce.1", 100, 50)]}
        with gzip.open(trace_dir / "host.trace.json.gz", "wt") as f:
            f.write(json.dumps(doc))
        out = ingest_from_telemetry(self._jsonl(tmp_path, trace_dir))
        assert out["n_device_ops"] == 2
        assert out["steps"] == 2          # stop_step - start_step
        assert out["profile_window"]["path"] == str(trace_dir)

    def test_failed_window_reports_not_ingests(self, tmp_path):
        out = ingest_from_telemetry(self._jsonl(
            tmp_path, tmp_path / "nope", ok=False, reason="boom"))
        assert "error" in out and "boom" in out["error"]
        assert out["n_device_ops"] == 0

    def test_missing_window_is_an_error(self, tmp_path):
        path = tmp_path / "empty.jsonl"
        path.write_text(json.dumps({"kind": "meta"}) + "\n")
        assert "error" in ingest_from_telemetry(str(path))

    def test_ingest_empty_dir_is_an_error(self, tmp_path):
        out = ingest(str(tmp_path / "missing"))
        assert "error" in out and out["n_device_ops"] == 0


class TestLabelRatchet:
    @pytest.fixture(scope="class")
    def bg(self):
        return _load_tool("bench_gate")

    def _truth(self, **arts):
        return {"artifacts": {
            name: ({"label": label, "reconciliation": {"verdict": "match"}}
                   if reconciled else {"label": label})
            for name, (label, reconciled) in arts.items()}}

    def test_extract_labels_truth_doc(self, bg):
        labels = bg.extract_labels(self._truth(
            a=("measured", True), b=("cpu-structural", False)))
        assert labels == {"a": {"label": "measured", "reconciled": True},
                          "b": {"label": "cpu-structural",
                                "reconciled": False}}

    def test_extract_labels_single_artifact_doc(self, bg):
        labels = bg.extract_labels({"artifact": "X", "label": "measured"})
        assert labels == {"X": {"label": "measured", "reconciled": False}}

    def test_extract_labels_pre_truth_doc_is_none(self, bg):
        assert bg.extract_labels({"parsed": {"mfu": 0.4}}) is None

    def test_pre_truth_rounds_skip(self, bg):
        assert bg.label_ratchet({}, self._truth(a=("measured", True))) \
            is None

    def test_measured_stays_measured(self, bg):
        old = self._truth(a=("measured", True))
        assert bg.label_ratchet(old, self._truth(a=("measured", True))) \
            == []

    def test_downgrade_fails(self, bg):
        old = self._truth(a=("measured", False))
        fails = bg.label_ratchet(old, self._truth(a=("projected", False)))
        assert fails and "regressed" in fails[0]
        fails = bg.label_ratchet(
            old, self._truth(a=("cpu-structural", False)))
        assert fails

    def test_dropped_measured_artifact_fails(self, bg):
        old = self._truth(a=("measured", True))
        fails = bg.label_ratchet(old, self._truth(b=("measured", True)))
        assert fails and "dropped" in fails[0]

    def test_dropped_reconciliation_fails(self, bg):
        old = self._truth(a=("measured", True))
        fails = bg.label_ratchet(old, self._truth(a=("measured", False)))
        assert fails and "reconciliation" in fails[0]

    def test_upgrades_are_free(self, bg):
        old = self._truth(a=("projected", False),
                          b=("cpu-structural", False))
        assert bg.label_ratchet(old, self._truth(
            a=("measured", True), b=("measured", True))) == []

    def test_repo_truth_json_parses(self, bg):
        path = os.path.join(REPO, "TRUTH.json")
        with open(path) as f:
            truth = json.load(f)
        labels = bg.extract_labels(truth)
        assert labels, "TRUTH.json must carry extractable labels"
        for rec in labels.values():
            assert rec["label"] in ("projected", "cpu-structural",
                                    "measured")
        # On a CPU-built TRUTH.json there must be no measured labels.
        if truth.get("backend") != "tpu":
            assert all(r["label"] != "measured" for r in labels.values())
        # The ratchet against itself is clean.
        assert bg.label_ratchet(truth, truth) == []


# --------------------------------------------------------------------- #
# jax.profiler round trip on this box (one real capture)
# --------------------------------------------------------------------- #
class TestRealCaptureRoundTrip:
    def test_profiler_window_capture_ingests(self, tmp_path):
        """A real (tiny) jax.profiler window: arm, run two trivial
        device programs, stop, ingest from the capture dir."""
        import jax
        import jax.numpy as jnp
        events = []
        w = ProfilerWindow(0, 1, str(tmp_path / "cap"),
                           on_event=lambda k, p: events.append(p))
        w.tick(0)
        f = jax.jit(lambda x: (x @ x).sum())
        for _ in range(3):
            f(jnp.ones((64, 64))).block_until_ready()
        w.tick(1)
        assert [p["phase"] for p in events] == ["start", "stop"]
        assert all(p["ok"] for p in events)
        out = ingest(events[-1]["path"], n_steps=1)
        assert out.get("n_device_ops", 0) > 0
        assert out["sum_check"]["explained_frac"] == pytest.approx(
            1.0, abs=0.05)
        assert glob.glob(os.path.join(
            events[-1]["path"], "plugins", "profile", "*", "*"))


# --------------------------------------------------------------------- #
# The .xplane.pb front end, on recorded chip traces (one v5e; two steps
# of a two-layer toy): instruction names, kernel families and named
# scopes from the profiler's own file.
# --------------------------------------------------------------------- #
_DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "tests", "data")


def _capture(tmp_path, name):
    """A ``jax.profiler`` capture dir holding the recorded trace."""
    src = os.path.join(_DATA, name)
    if not os.path.exists(src):
        pytest.skip(f"no recorded trace {name} in this checkout")
    import shutil
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    shutil.copy(src, d / "host.xplane.pb")
    return str(tmp_path)


class TestXplaneFrontEnd:
    def test_instruction_name_rule(self):
        from deepspeed_tpu.monitor.xplane_reader import instruction_name
        assert instruction_name(
            "%_fused_adam_kernel.1 = (bf16[8,128]{1,0}) custom-call("
            "f32[1,8] %x), custom_call_target=\"tpu_custom_call\""
        ) == "_fused_adam_kernel.1"
        assert instruction_name("fusion.12") == "fusion.12"

    @pytest.mark.parametrize("tf_op,want", [
        ("jit(train_step)/fwd_bwd/transpose(jvp())/while/body/closed_call/"
         "checkpoint/rematted_computation/attn/dot_general:",
         (("fwd_bwd", "attn"), True, True)),
        ("jit(train_step)/fwd_bwd/transpose(fwd_bwd)/jvp(lm_head)/mul:",
         (("fwd_bwd", "lm_head"), True, False)),
        ("jit(decode_step)/while/body/attn/kv_write/scatter:",
         (("attn", "kv_write"), False, False)),
        ("jit(train_step)/jit(_threefry_split)/concatenate:",
         ((), False, False)),
    ])
    def test_scope_of(self, tf_op, want):
        from deepspeed_tpu.monitor.xplane_reader import scope_of
        assert scope_of(tf_op) == want

    def test_chip_trace_names_families_and_buckets(self, tmp_path):
        """PR 23's trace (no scopes yet): the kernel-family patterns
        match a CHIP trace once the instruction's name is cut out of its
        HLO line; device copies are not host transfers; buckets + idle
        still partition the window."""
        out = ingest(_capture(tmp_path, "tiny_train.xplane.pb"), n_steps=2)
        assert out["trace_files"] == [os.path.join(
            "plugins", "profile", "2026_01_01", "host.xplane.pb")]
        assert out["n_device_ops"] > 500 and out["n_device_lanes"] == 1
        assert set(out["pallas_families_ms"]) == {
            "flash_attention", "fused_gelu", "fused_ln", "fused_update"}
        assert all(v > 0 for v in out["pallas_families_ms"].values())
        b = out["buckets_ms"]
        assert b["pallas"] > 0 and b["gemm"] > 0 and b["host"] == 0.0
        assert out["sum_check"]["explained_frac"] == pytest.approx(1.0)
        assert {"op": "_fused_adam_kernel", "bucket": "pallas"}.items() <= \
            next(t for t in out["top_ops"]
                 if t["op"] == "_fused_adam_kernel").items()
        assert "scopes_ms" not in out          # nothing was scoped then

    def test_chip_trace_reports_time_by_scope(self, tmp_path):
        out = ingest(_capture(tmp_path, "toy_train_scoped.xplane.pb"),
                     n_steps=2)
        scopes = out["scopes_ms"]
        for key in ("fwd_bwd/attn", "fwd_bwd/mlp", "fwd_bwd/attn:bwd",
                    "fwd_bwd/mlp:bwd", "fwd_bwd/lm_head",
                    "optimizer/flatten", "optimizer/kernel",
                    "optimizer/unflatten"):
            assert scopes.get(key, 0) > 0, (key, sorted(scopes))
        # self times by scope are the device's busy time, per step
        busy = sum(v for k, v in out["per_step_ms"].items() if k != "idle")
        assert sum(scopes.values()) == pytest.approx(busy, rel=0.01)
        # the kernel scope holds the fused optimizer's kernel time
        assert scopes["optimizer/kernel"] * 2 >= \
            out["pallas_families_ms"]["fused_update"] * 0.5

    def test_device_events_carry_the_scope(self, tmp_path):
        from deepspeed_tpu.monitor.xplane_reader import device_op_events
        path = os.path.join(_DATA, "toy_train_scoped.xplane.pb")
        if not os.path.exists(path):
            pytest.skip("no recorded trace in this checkout")
        ops = [e for e in device_op_events(path) if e["ph"] == "X"]
        adam = [e for e in ops if e["name"].startswith("_fused_adam_kernel")]
        assert adam and all(e["args"]["scope"] == "optimizer/kernel"
                            for e in adam)
        assert any(e["args"]["recomputed"] for e in ops)
        assert any(e["args"]["backward"] and not e["args"]["recomputed"]
                   for e in ops)

    def test_capture_without_a_tpu_plane_is_read_from_its_json(
            self, tmp_path):
        """The CPU backend's capture has no device plane: the xplane is
        passed over and the Chrome-trace JSON ingested as before."""
        d = tmp_path / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        with gzip.open(d / "host.trace.json.gz", "wt") as f:
            json.dump({"traceEvents": [
                _ev("dot.1", 0, 10, args={"hlo_op": "dot.1"})]}, f)
        out = ingest(str(tmp_path))
        assert out["trace_files"] == [os.path.join(
            "plugins", "profile", "t", "host.trace.json.gz")]
        assert out["buckets_ms"]["gemm"] > 0

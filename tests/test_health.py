"""Training health monitor (monitor/health.py + flight.py): anomaly
provenance, hang watchdog, crash flight recorder, per-host telemetry
shards + aggregation, and the truncated-segment verdict.

Acceptance gates from the PR issue:
- an induced-NaN fp16 run on the dp=8 mesh emits an anomaly event
  naming the FIRST non-finite gradient leaf and its layer;
- a SIGTERM'd run leaves a parseable FLIGHT.json with the last-N step
  records and the unsettled goodput window;
- an induced stall fires the watchdog with an all-thread stack dump;
- the health layer adds ZERO hot-path device syncs (enabled-vs-disabled
  ``device_sync_count`` fence assertion).
"""
import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import deepspeed_tpu.utils.timer as timer_mod
from deepspeed_tpu.monitor import (EwmaDetector, FlightRecorder,
                                   HangWatchdog, JsonlSink, TapSpec,
                                   Telemetry, TraceWriter, leaf_sq_taps,
                                   resolve_writer, shard_path)
from deepspeed_tpu.monitor.health import HealthMonitor
from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                          DeepSpeedConfigError)
from deepspeed_tpu.runtime.engine import DeepSpeedEngine

from simple_model import (simple_model_params, simple_loss_fn, random_batch,
                          base_config)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_engine(tmp_path, tel_knobs=None, **cfg_overrides):
    cfg = base_config(**cfg_overrides)
    tel = {"enabled": True, "output_path": str(tmp_path), "job_name": "run"}
    tel.update(tel_knobs or {})
    cfg["telemetry"] = tel
    params = simple_model_params(jax.random.PRNGKey(0))
    return DeepSpeedEngine(model=simple_loss_fn, model_params=params,
                           config=cfg)


def read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def run_jsonl(tmp_path, job="run"):
    return read_jsonl(os.path.join(str(tmp_path), f"{job}.jsonl"))


# --------------------------------------------------------------------- #
# Config surface
# --------------------------------------------------------------------- #
class TestHealthConfig:
    def test_defaults(self):
        cfg = DeepSpeedConfig(base_config(telemetry={"enabled": True}))
        h = cfg.telemetry_config.health
        assert h.enabled and h.grad_taps and h.flight_recorder
        assert not h.watchdog            # daemon thread is opt-in
        assert not cfg.telemetry_config.per_host_shards

    def test_knobs_parse(self):
        cfg = DeepSpeedConfig(base_config(telemetry={
            "enabled": True, "per_host_shards": True,
            "health": {"z_threshold": 4.0, "ewma_alpha": 0.2,
                       "warmup_steps": 5, "watchdog": True,
                       "watchdog_factor": 3.0, "watchdog_min_s": 1.5,
                       "flight_window": 16, "grad_taps": False}}))
        h = cfg.telemetry_config.health
        assert h.z_threshold == 4.0 and h.ewma_alpha == 0.2
        assert h.warmup_steps == 5 and h.watchdog
        assert h.watchdog_factor == 3.0 and h.watchdog_min_s == 1.5
        assert h.flight_window == 16 and not h.grad_taps
        assert cfg.telemetry_config.per_host_shards

    @pytest.mark.parametrize("bad", [
        {"z_threshold": 0}, {"ewma_alpha": 0.0}, {"ewma_alpha": 1.5},
        {"warmup_steps": -1}, {"watchdog_factor": -2},
        {"watchdog_min_s": 0}, {"flight_window": 0},
        {"enabled": "yes"}])
    def test_invalid_raises(self, bad):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig(base_config(telemetry={"enabled": True,
                                                   "health": bad}))

    def test_per_host_type_checked(self):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig(base_config(
                telemetry={"enabled": True, "per_host_shards": "all"}))


# --------------------------------------------------------------------- #
# EWMA z-score detector
# --------------------------------------------------------------------- #
class TestEwmaDetector:
    def test_warmup_never_fires(self):
        det = EwmaDetector(alpha=0.3, z_threshold=3.0, warmup=10)
        assert all(det.update(v) is None
                   for v in [1.0, 100.0, -50.0, 1.0, 2.0])

    def test_spike_fires_and_absorbs(self):
        det = EwmaDetector(alpha=0.2, z_threshold=4.0, warmup=5)
        rng = np.random.default_rng(0)
        for _ in range(50):
            assert det.update(2.0 + 0.05 * rng.standard_normal()) is None
        z = det.update(10.0)
        assert z is not None and z > 4.0
        # The baseline absorbs the shift instead of firing forever.
        fired = sum(det.update(10.0 + 0.05 * rng.standard_normal())
                    is not None for _ in range(50))
        assert fired < 10

    def test_constant_series_no_division_blowup(self):
        det = EwmaDetector(alpha=0.2, z_threshold=6.0, warmup=3)
        for _ in range(20):
            assert det.update(1.0) is None
        # A genuine jump off the flat baseline SHOULD fire.
        assert det.update(2.0) is not None

    def test_nonfinite_skipped(self):
        det = EwmaDetector(warmup=0)
        assert det.update(float("nan")) is None
        assert det.update(float("inf")) is None
        assert det.n == 0


# --------------------------------------------------------------------- #
# Tap spec + in-graph taps
# --------------------------------------------------------------------- #
class TestTaps:
    def test_spec_layers_and_paths(self):
        tree = {"block0": {"w": np.ones((2, 2)), "b": np.ones(2)},
                "head": np.ones(3)}
        spec = TapSpec.from_tree(tree)
        assert spec.num_leaves == 3
        assert set(spec.layer_names) == {"block0", "head"}
        assert any("w" in p for p in spec.leaf_paths)
        for i in range(spec.num_leaves):
            assert spec.layer_of(i) in spec.layer_names

    def test_leaf_sq_values_and_provenance(self):
        tree = {"a": np.array([1.0, 2.0], np.float32),
                "b": np.array([np.nan, 1.0], np.float32),
                "c": np.array([3.0], np.float32)}
        spec = TapSpec.from_tree(tree)
        sq = np.asarray(leaf_sq_taps(tree))
        assert sq.shape == (3,)
        assert sq[0] == pytest.approx(5.0)
        assert not np.isfinite(sq[1])
        mon = HealthMonitor(spec=spec)
        prov = mon._provenance(sq)
        assert "b" in prov["first_nonfinite_leaf"]
        assert prov["first_nonfinite_layer"] == "b"
        assert prov["nonfinite_leaves"] == 1
        assert prov["layer_grad_norms"]["b"] == "non-finite"
        assert prov["layer_grad_norms"]["a"] == pytest.approx(
            np.sqrt(5.0), abs=1e-5)

    def test_monitor_counts_and_spikes(self):
        mon = HealthMonitor(z_threshold=4.0, ewma_alpha=0.2,
                            warmup_steps=5)
        for i in range(30):
            assert mon.check_step(i, {"loss": 1.0 + 0.001 * (i % 3),
                                      "grad_norm": 0.5}) == []
        evs = mon.check_step(30, {"loss": 50.0, "grad_norm": 0.5})
        assert [e["anomaly"] for e in evs] == ["loss_spike"]
        evs = mon.check_step(31, {"loss": float("nan"),
                                  "grad_norm": float("inf"),
                                  "overflow": True})
        kinds = {e["anomaly"] for e in evs}
        assert kinds == {"nonfinite_loss", "nonfinite_grad"}
        assert mon.summary()["total"] == 3
        # -1.0 is the "norm not computed" sentinel, never an anomaly.
        assert mon.check_step(32, {"loss": 50.0, "grad_norm": -1.0}) == []


# --------------------------------------------------------------------- #
# Shared writer resolver (the deduplicated is_writer guard)
# --------------------------------------------------------------------- #
class TestWriterResolver:
    def test_explicit_override_wins(self):
        assert resolve_writer(False, rank=0)[0] is False
        assert resolve_writer(True, rank=5)[0] is True

    def test_rank_policy(self):
        assert resolve_writer(None, per_host=False, rank=0, world=4)[0]
        assert not resolve_writer(None, per_host=False, rank=3, world=4)[0]
        assert resolve_writer(None, per_host=True, rank=3, world=4)[0]

    def test_shard_path(self):
        assert shard_path("/runs/job.jsonl", 0) == "/runs/job.jsonl"
        assert shard_path("/runs/job.jsonl", 3) == "/runs/job.rank3.jsonl"
        assert shard_path("/t/trace.json", 2) == "/t/trace.rank2.json"

    def test_sink_per_host_shard_file(self, tmp_path):
        sink = JsonlSink(str(tmp_path), "job", per_host=True, rank=2,
                         world=4)
        sink.write({"kind": "step", "step": 1})
        sink.close()
        assert os.path.exists(tmp_path / "job.rank2.jsonl")
        recs = read_jsonl(tmp_path / "job.rank2.jsonl")
        assert recs[0]["step"] == 1

    def test_sink_nonwriter_drop_unchanged_without_per_host(self, tmp_path):
        sink = JsonlSink(str(tmp_path), "job", per_host=False, rank=2,
                         world=4)
        sink.write({"kind": "step", "step": 1})
        sink.close()
        assert not list(tmp_path.glob("*.jsonl"))

    def test_trace_writer_same_resolver(self, tmp_path):
        tw = TraceWriter(str(tmp_path / "trace.json"), per_host=True,
                         rank=1, world=2)
        with tw.span("x"):
            pass
        tw.close()
        assert os.path.exists(tmp_path / "trace.rank1.json")
        tw2 = TraceWriter(str(tmp_path / "t2.json"), rank=1, world=2)
        assert not tw2.is_writer


# --------------------------------------------------------------------- #
# Engine acceptance: induced-NaN provenance on the dp=8 mesh
# --------------------------------------------------------------------- #
class TestNanProvenance:
    def test_fp16_nan_names_leaf_and_layer(self, tmp_path):
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 50},
                             fp16={"enabled": True,
                                   "initial_scale_power": 4})
        x, y = random_batch(n=16)
        for _ in range(3):
            engine.train_batch(batch=(x, y))
        bad = x.copy()
        bad[0, 0] = np.nan
        engine.train_batch(batch=(bad, y))
        engine.train_batch(batch=(x, y))
        engine.telemetry.close()
        recs = run_jsonl(tmp_path)
        anomalies = [r for r in recs if r.get("event") == "anomaly"]
        grads = [a for a in anomalies
                 if a["anomaly"] == "nonfinite_grad"]
        assert grads, f"no nonfinite_grad anomaly in {anomalies}"
        ev = grads[0]
        leaf_names = {"w1", "b1", "w2", "b2"}
        assert any(n in ev["first_nonfinite_leaf"] for n in leaf_names)
        assert ev["first_nonfinite_layer"] in leaf_names
        assert ev["anomaly_step"] == 4
        assert ev["overflow"] is True
        assert ev["nonfinite_leaves"] >= 1
        # Per-step JSONL keeps its scalar shape: the tap never lands in
        # the step records.
        for s in (r for r in recs if r["kind"] == "step"):
            assert "health_leaf_sq" not in s
        # The flight recorder carries the anomaly summary.
        flight = json.load(open(tmp_path / "FLIGHT.json"))
        assert flight["anomalies"]["counts"]["nonfinite_grad"] >= 1

    def test_trio_path_taps(self, tmp_path):
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 50})
        x, y = random_batch(n=16)
        for _ in range(2):
            loss = engine.forward((x, y))
            engine.backward(loss)
            engine.step()
        bad = x.copy()
        bad[:, :] = np.inf
        loss = engine.forward((bad, y))
        engine.backward(loss)
        engine.step()
        engine.telemetry.close()
        recs = run_jsonl(tmp_path)
        anomalies = [r for r in recs if r.get("event") == "anomaly"
                     and r.get("first_nonfinite_leaf")]
        assert anomalies, "trio apply path produced no provenance"

    def test_tap_norms_are_unscaled_under_fp16(self, tmp_path):
        """The tap rides loss-SCALED grads in-graph but must report
        true magnitudes: sqrt(sum(leaf_sq)) == the step's (unscaled)
        grad_norm, even at a 2^12 loss scale."""
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 50},
                             fp16={"enabled": True,
                                   "initial_scale_power": 12})
        captured = []
        health = engine.telemetry.health
        orig = health.check_step
        health.check_step = lambda step, rec, leaf_sq=None: (
            captured.append((dict(rec), np.asarray(leaf_sq))),
            orig(step, rec, leaf_sq))[1]
        x, y = random_batch(n=16)
        for _ in range(3):
            engine.train_batch(batch=(x, y))
        engine.telemetry.close()
        rec, leaf_sq = captured[-1]
        assert rec["grad_norm"] == pytest.approx(
            float(np.sqrt(leaf_sq.sum())), rel=1e-3)

    def test_fp32_noclip_nan_still_detected(self, tmp_path):
        """fp32 without clipping computes no grad norm and has no
        overflow vote — the per-leaf tap is the ONLY detector, and a
        poisoned step must still fire (found driving a saturating-tanh
        model: inf input -> finite loss, NaN grads, silent poisoning)."""
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 50})
        x, y = random_batch(n=16)
        engine.train_batch(batch=(x, y))
        bad = x.copy()
        bad[0, 0] = np.inf      # tanh saturates: loss stays finite
        engine.train_batch(batch=(bad, y))
        engine.telemetry.close()
        recs = run_jsonl(tmp_path)
        grads = [r for r in recs if r.get("event") == "anomaly"
                 and r["anomaly"] == "nonfinite_grad"]
        assert grads and grads[0]["overflow"] is False
        assert grads[0]["first_nonfinite_leaf"]

    def test_taps_off_knob(self, tmp_path):
        engine = make_engine(
            tmp_path, tel_knobs={"health": {"grad_taps": False}})
        assert engine._health_tap_fn is None
        x, y = random_batch(n=16)
        engine.train_batch(batch=(x, y))
        engine.telemetry.close()


# --------------------------------------------------------------------- #
# Hang watchdog
# --------------------------------------------------------------------- #
class TestWatchdog:
    def test_unit_fire_and_rearm(self, tmp_path):
        fired = []
        wd = HangWatchdog(factor=2.0, min_timeout_s=0.2, poll_s=0.05,
                          on_fire=fired.append, dump_dir=str(tmp_path),
                          memory_sampler=lambda: None)
        wd.start()
        try:
            wd.pending("train_step")
            for _ in range(3):
                wd.beat(0.01)
                time.sleep(0.02)
            time.sleep(0.5)           # induced stall
            assert wd.fires == 1      # once per stall, not per poll
            ev = fired[0]
            assert ev["pending_fn"] == "train_step"
            assert ev["phase"] == "steady"
            assert ev["elapsed_s"] >= 0.2     # (reported to the millisecond)
            dump = open(ev["stack_dump_path"]).read()
            assert "Thread" in dump and "watchdog" in dump
            wd.beat(0.01)             # re-arm
            time.sleep(0.5)
            assert wd.fires == 2
        finally:
            wd.stop()

    def test_timeout_scales_with_p95(self):
        wd = HangWatchdog(factor=5.0, min_timeout_s=0.1)
        assert wd.timeout_s() == pytest.approx(0.1)
        for _ in range(20):
            wd.beat(1.0)
        assert wd.timeout_s() == pytest.approx(5.0)

    def test_engine_stall_fires_with_thread_dump(self, tmp_path):
        engine = make_engine(tmp_path, tel_knobs={
            "report_steps": 50,
            "health": {"watchdog": True, "watchdog_min_s": 0.3,
                       "watchdog_factor": 2.0}})
        batch = random_batch(n=16)
        for _ in range(3):
            engine.train_batch(batch=batch)
        time.sleep(1.0)               # the induced stall
        engine.telemetry.close()
        recs = run_jsonl(tmp_path)
        fires = [r for r in recs if r.get("event") == "watchdog"]
        assert fires, "stall did not fire the watchdog"
        ev = fires[-1]
        assert ev["pending_fn"] == "train_step"
        assert os.path.exists(ev["stack_dump_path"])
        assert "Thread" in open(ev["stack_dump_path"]).read()
        flight = json.load(open(tmp_path / "FLIGHT.json"))
        assert flight["watchdog_fires"] >= 1

    def test_instrumented_fn_keeps_raw_unwrapped(self, tmp_path):
        engine = make_engine(tmp_path, tel_knobs={
            "health": {"watchdog": True, "watchdog_min_s": 60.0}})
        batch = random_batch(n=16)
        engine.train_batch(batch=batch)
        raw = engine._train_step_fn.__wrapped__
        # One unwrap must reach the raw jitted fn the sentinel
        # registered (flops profiler / hlo audit contract) — not the
        # intermediate sentinel wrapper.
        assert raw is engine.telemetry.sentinel._fns["train_step"]["fn"]
        engine.telemetry.close()


# --------------------------------------------------------------------- #
# Flight recorder
# --------------------------------------------------------------------- #
class TestFlightRecorder:
    def test_clean_close_artifact(self, tmp_path):
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 3})
        batch = random_batch(n=16)
        for _ in range(7):
            engine.train_batch(batch=batch)
        engine.telemetry.close()
        flight = json.load(open(tmp_path / "FLIGHT.json"))
        assert flight["reason"] == "close"
        assert flight["closed_clean"] is True
        assert [s["step"] for s in flight["last_steps"]] == \
            list(range(1, 8))
        assert flight["final_step"] == 7
        assert flight["last_report"]["kind"] == "report"
        assert "goodput_totals" in flight
        assert flight["snapshot"]["env"]["jax"]
        assert flight["snapshot"]["dp"] == 8

    def test_window_bounds_last_steps(self, tmp_path):
        engine = make_engine(tmp_path, tel_knobs={
            "report_steps": 2, "health": {"flight_window": 4}})
        batch = random_batch(n=16)
        for _ in range(10):
            engine.train_batch(batch=batch)
        engine.telemetry.close()
        flight = json.load(open(tmp_path / "FLIGHT.json"))
        assert [s["step"] for s in flight["last_steps"]] == [7, 8, 9, 10]

    def test_close_reentrancy_from_signal_handler(self, tmp_path):
        """Satellite gate: Telemetry.close() must be safe when a signal
        handler lands on top of the atexit-driven close."""
        engine = make_engine(tmp_path)
        batch = random_batch(n=16)
        engine.train_batch(batch=batch)
        tl = engine.telemetry
        calls = []
        orig_drain = tl.drain

        def draining(extra=None):
            # Simulate the signal arriving MID-close: re-enter close().
            calls.append(1)
            if len(calls) == 1:
                tl.close()
            return orig_drain(extra)

        tl.drain = draining
        tl.close()
        assert len(calls) == 1        # the re-entrant close was a no-op
        tl.close()                    # idempotent afterwards too
        recs = run_jsonl(tmp_path)
        assert [r["kind"] for r in recs].count("final") == 1

    def test_in_process_sigterm_chain(self, tmp_path):
        """SIGTERM with a prior handler installed: ours persists, closes
        telemetry, chains, and restores."""
        seen = []
        prev = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
        try:
            engine = make_engine(tmp_path, tel_knobs={"report_steps": 50})
            batch = random_batch(n=16)
            for _ in range(4):
                engine.train_batch(batch=batch)
            os.kill(os.getpid(), signal.SIGTERM)
            assert seen == [signal.SIGTERM]   # chained to prior handler
            flight = json.load(open(tmp_path / "FLIGHT.json"))
            assert flight["reason"] == "SIGTERM"
            assert flight["closed_clean"] is True   # close ran in-handler
            assert len(flight["last_steps"]) == 4
            assert flight["at_signal"]["undrained_steps"] == [1, 2, 3, 4]
            gp = flight["goodput_unsettled"]
            assert gp["open_window_s"] > 0 and gp["windows_closed"] == 0
            assert engine.telemetry._closed
            # Handler restored itself: ours is gone.
            assert signal.getsignal(signal.SIGTERM) not in \
                (signal.SIG_DFL,)
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_stale_chained_recorder_does_not_clobber(self, tmp_path):
        """Two engines sharing an output dir: the CLOSED engine's
        handler stays linked in the live engine's signal chain — a
        stale invocation must pass the signal through without
        overwriting the live run's FLIGHT.json."""
        seen = []
        prev = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
        try:
            eng_a = make_engine(tmp_path, tel_knobs={"report_steps": 50})
            batch = random_batch(n=16)
            eng_a.train_batch(batch=batch)
            eng_b = make_engine(tmp_path, tel_knobs={"report_steps": 50})
            for _ in range(3):
                eng_b.train_batch(batch=batch)
            eng_a.telemetry.close()   # A's handler is now a stale link
            os.kill(os.getpid(), signal.SIGTERM)
            assert seen == [signal.SIGTERM]
            flight = json.load(open(tmp_path / "FLIGHT.json"))
            # B's signal-time artifact survived; A (1 step, closed)
            # did not overwrite it.
            assert flight["reason"] == "SIGTERM"
            assert len(flight["last_steps"]) == 3
        finally:
            signal.signal(signal.SIGTERM, prev)

    @pytest.mark.slow
    def test_subprocess_sigterm_mid_run(self, tmp_path):
        """The acceptance gate end to end: a real process killed mid-run
        dies BY SIGTERM and leaves a parseable FLIGHT.json."""
        script = tmp_path / "child.py"
        script.write_text(f"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {str(os.path.join(REPO, 'tests'))!r})
sys.path.insert(0, {REPO!r})
from simple_model import (simple_model_params, simple_loss_fn,
                          random_batch, base_config)
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
out = {str(tmp_path)!r}
cfg = base_config(telemetry={{"enabled": True, "output_path": out,
                             "job_name": "run", "report_steps": 1000}})
eng = DeepSpeedEngine(model=simple_loss_fn,
                      model_params=simple_model_params(
                          jax.random.PRNGKey(0)), config=cfg)
batch = random_batch(n=16)
for i in range(2000):
    eng.train_batch(batch=batch)
    if i == 4:
        open(os.path.join(out, "READY"), "w").write("1")
    time.sleep(0.05)
""")
        proc = subprocess.Popen([sys.executable, str(script)],
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        try:
            t0 = time.time()
            ready = str(tmp_path / "READY")
            while not os.path.exists(ready):
                time.sleep(0.1)
                assert proc.poll() is None, "child died before READY"
                assert time.time() - t0 < 180, "child never became ready"
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=60)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert rc == -signal.SIGTERM     # true termination signal
        flight = json.load(open(tmp_path / "FLIGHT.json"))
        assert flight["reason"] == "SIGTERM"
        assert len(flight["last_steps"]) >= 5
        assert flight["goodput_unsettled"]["open_window_s"] > 0
        assert flight["at_signal"]["undrained_steps"]
        recs = run_jsonl(tmp_path)
        assert [r["kind"] for r in recs][-1] == "final"


# --------------------------------------------------------------------- #
# Per-host shards + aggregation + truncation (tools/telemetry_report.py)
# --------------------------------------------------------------------- #
def _write_stream(path, rank, losses, wall_ms, last_step=None,
                  final=True):
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "meta", "process_index": rank,
                            "process_count": 2, "emits_final": True,
                            "health_enabled": True}) + "\n")
        for i, loss in enumerate(losses, start=1):
            if last_step is not None and i > last_step:
                break
            f.write(json.dumps({"kind": "step", "step": i, "loss": loss,
                                "wall_ms": wall_ms}) + "\n")
        f.write(json.dumps({"kind": "report", "records": len(losses)})
                + "\n")
        if final:
            f.write(json.dumps({"kind": "final", "step": len(losses)})
                    + "\n")


class TestMultiHostReport:
    def test_engine_per_host_shard_and_aggregation(self, tmp_path,
                                                   monkeypatch):
        """A rank-1 engine (identity faked via DS_PROC_INDEX) writes its
        own shard instead of dropping records; the report aggregates it
        against the primary."""
        rep = load_tool("telemetry_report")
        # Primary (rank 0 of a faked 2-process world, like a real pod).
        monkeypatch.setenv("DS_PROC_INDEX", "0")
        monkeypatch.setenv("DS_PROC_COUNT", "2")
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 3})
        batch = random_batch(n=16)
        for _ in range(6):
            engine.train_batch(batch=batch)
        engine.telemetry.close()
        # Rank 1: same run shape through the faked identity.
        monkeypatch.setenv("DS_PROC_INDEX", "1")
        engine1 = make_engine(tmp_path, tel_knobs={
            "report_steps": 3, "per_host_shards": True})
        for _ in range(6):
            engine1.train_batch(batch=batch)
        engine1.telemetry.close()
        monkeypatch.delenv("DS_PROC_INDEX")
        shard = tmp_path / "run.rank1.jsonl"
        assert shard.exists()
        assert len([r for r in read_jsonl(shard)
                    if r["kind"] == "step"]) == 6
        summary = rep.summarize(str(tmp_path / "run.jsonl"))
        hosts = summary["health"]["hosts"]
        assert hosts["available"] and hosts["n_hosts"] == 2
        assert {e["rank"] for e in hosts["per_host"]} == {0, 1}
        assert hosts["step_count_desync"] is False
        # Identical data + seed on both "hosts" -> identical loss hash.
        assert hosts["loss_desync"] is False

    def test_explicit_flight_path_shards_per_rank(self, tmp_path,
                                                  monkeypatch):
        """per_host + an explicit flight_path: ranks must not share one
        FLIGHT.json (the last handler would clobber the primary's
        postmortem)."""
        monkeypatch.setenv("DS_PROC_INDEX", "1")
        monkeypatch.setenv("DS_PROC_COUNT", "2")
        fp = str(tmp_path / "FL.json")
        engine = make_engine(tmp_path, tel_knobs={
            "per_host_shards": True, "health": {"flight_path": fp}})
        assert engine.telemetry.flight.path == str(tmp_path /
                                                   "FL.rank1.json")
        engine.telemetry.close()

    def test_stale_flight_artifact_not_attributed(self, tmp_path):
        """A segment that never armed a flight recorder must not adopt
        a previous run's FLIGHT.json sitting in the same directory."""
        rep = load_tool("telemetry_report")
        (tmp_path / "FLIGHT.json").write_text(json.dumps(
            {"reason": "SIGTERM", "last_steps": []}))
        _write_stream(tmp_path / "clean.jsonl", 0, [1.0], wall_ms=5.0)
        fr = rep.summarize(str(tmp_path / "clean.jsonl"))["health"][
            "flight_recorder"]
        assert fr == {"present": False}

    def test_nonwriter_without_per_host_still_drops(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("DS_PROC_INDEX", "1")
        monkeypatch.setenv("DS_PROC_COUNT", "2")
        engine = make_engine(tmp_path)
        engine.train_batch(batch=random_batch(n=16))
        engine.telemetry.close()
        assert not list(tmp_path.glob("*.jsonl"))

    def test_straggler_and_desync_detection(self, tmp_path):
        rep = load_tool("telemetry_report")
        losses = [1.0, 0.9, 0.8, 0.7]
        _write_stream(tmp_path / "job.jsonl", 0, losses, wall_ms=10.0)
        # Rank 1: 2x slower, diverged losses, stopped one step early.
        _write_stream(tmp_path / "job.rank1.jsonl", 1,
                      [1.0, 0.9, 0.85, 0.7], wall_ms=20.0, last_step=3,
                      final=False)
        summary = rep.summarize(str(tmp_path / "job.jsonl"))
        hosts = summary["health"]["hosts"]
        assert hosts["n_hosts"] == 2
        assert hosts["straggler_skew_rel"] == pytest.approx(1.0)
        assert hosts["slowest_rank"] == 1
        assert hosts["step_count_desync"] is True
        assert hosts["loss_desync"] is True

    def test_stale_shards_excluded(self, tmp_path):
        """Orphaned rank files from a previous (larger-world) run must
        not fabricate desync verdicts against a relaunch."""
        rep = load_tool("telemetry_report")
        losses = [1.0, 0.9]
        _write_stream(tmp_path / "job.jsonl", 0, losses, wall_ms=10.0)
        # process_count in the stream meta is 2: rank 5 is topology from
        # a dead, larger run.
        _write_stream(tmp_path / "job.rank5.jsonl", 5,
                      [2.0, 1.5, 1.1], wall_ms=99.0, final=False)
        hosts = rep.summarize(str(tmp_path / "job.jsonl"))["health"][
            "hosts"]
        assert hosts["available"] is False and hosts["n_hosts"] == 1
        assert hosts["stale_shards"][0]["rank"] == 5

    def test_truncated_verdict(self, tmp_path):
        rep = load_tool("telemetry_report")
        _write_stream(tmp_path / "ok.jsonl", 0, [1.0, 0.9], wall_ms=5.0)
        assert rep.summarize(str(tmp_path / "ok.jsonl"))["truncated"] \
            is False
        _write_stream(tmp_path / "cut.jsonl", 0, [1.0, 0.9], wall_ms=5.0,
                      final=False)
        cut = rep.summarize(str(tmp_path / "cut.jsonl"))
        assert cut["truncated"] is True
        assert cut["goodput"].get("truncated") is True
        assert cut["health"]["truncated"] is True

    def test_pre_marker_stream_unknown_not_false_verdict(self, tmp_path):
        rep = load_tool("telemetry_report")
        with open(tmp_path / "old.jsonl", "w") as f:
            f.write(json.dumps({"kind": "meta"}) + "\n")
            f.write(json.dumps({"kind": "step", "step": 1, "loss": 1.0,
                                "wall_ms": 5.0}) + "\n")
        assert rep.summarize(str(tmp_path / "old.jsonl"))["truncated"] \
            is None

    def test_engine_run_reports_health_section(self, tmp_path):
        rep = load_tool("telemetry_report")
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 50},
                             fp16={"enabled": True,
                                   "initial_scale_power": 4})
        x, y = random_batch(n=16)
        for _ in range(3):
            engine.train_batch(batch=(x, y))
        bad = x.copy()
        bad[0, 0] = np.nan
        engine.train_batch(batch=(bad, y))
        engine.train_batch(batch=(x, y))   # drain happens later, at close
        engine.telemetry.close()
        summary = rep.summarize(str(tmp_path / "run.jsonl"))
        h = summary["health"]
        assert h["available"]
        assert h["anomalies"]["nonfinite"] >= 1
        # Skipped-overflow NaN is routine fp16 mechanics, not the
        # gate-failing class.
        assert h["anomalies"]["nonfinite_unskipped"] == 0
        ev = h["anomalies"]["events"][0]
        assert ev["first_nonfinite_leaf"]
        # The listed step is the anomaly's OWN step, not the drain-time
        # counter (drain ran at close, step 5).
        assert ev["step"] == 4
        assert h["flight_recorder"]["present"]
        assert h["flight_recorder"]["reason"] == "close"
        assert summary["truncated"] is False


# --------------------------------------------------------------------- #
# bench_gate health validation
# --------------------------------------------------------------------- #
class TestBenchGateHealth:
    def _telemetry_doc(self, **health_over):
        h = {"available": True, "watchdog_fires": 0,
             "anomalies": {"total": 0, "nonfinite": 0,
                           "nonfinite_unskipped": 0},
             "truncated": False}
        h.update(health_over)
        return {"mfu": {"window_mfu": 0.5}, "goodput":
                {"goodput_fraction": 0.9}, "health": h,
                "truncated": h["truncated"]}

    def _gate(self, tmp_path, old, new):
        bg = load_tool("bench_gate")
        po, pn = tmp_path / "old.json", tmp_path / "new.json"
        po.write_text(json.dumps(old))
        pn.write_text(json.dumps(new))
        return bg.gate(str(po), str(pn), 0.10, 0.05)

    def test_healthy_round_passes(self, tmp_path):
        assert self._gate(tmp_path, self._telemetry_doc(),
                          self._telemetry_doc()) == 0

    def test_watchdog_fire_fails(self, tmp_path):
        assert self._gate(tmp_path, self._telemetry_doc(),
                          self._telemetry_doc(watchdog_fires=2)) == 1

    def test_unskipped_nonfinite_anomaly_fails(self, tmp_path):
        bad = self._telemetry_doc(
            anomalies={"total": 1, "nonfinite": 1,
                       "nonfinite_unskipped": 1})
        assert self._gate(tmp_path, self._telemetry_doc(), bad) == 1

    def test_overflow_skipped_nonfinite_passes(self, tmp_path):
        # Routine fp16 loss-scale backoff: the overflow vote skipped the
        # update, so the anomaly is signal, not a gate failure.
        ok = self._telemetry_doc(
            anomalies={"total": 2, "nonfinite": 2,
                       "nonfinite_unskipped": 0})
        assert self._gate(tmp_path, self._telemetry_doc(), ok) == 0

    def test_truncated_fails(self, tmp_path):
        assert self._gate(tmp_path, self._telemetry_doc(),
                          self._telemetry_doc(truncated=True)) == 1

    def test_pre_health_round_skips(self, tmp_path):
        old = {"mfu": {"window_mfu": 0.5},
               "goodput": {"goodput_fraction": 0.9}}
        assert self._gate(tmp_path, old, dict(old)) == 0

    def test_spike_anomalies_do_not_fail(self, tmp_path):
        # Spikes are signal, not defects: only non-finite events gate.
        doc = self._telemetry_doc(anomalies={"total": 3, "nonfinite": 0})
        assert self._gate(tmp_path, self._telemetry_doc(), doc) == 0


# --------------------------------------------------------------------- #
# The zero-added-syncs fence (enabled-vs-disabled device_sync_count)
# --------------------------------------------------------------------- #
class TestHealthFence:
    def _run(self, tmp_path, telemetry: bool):
        cfg = base_config(fp16={"enabled": True,
                                "initial_scale_power": 4})
        if telemetry:
            cfg["telemetry"] = {"enabled": True,
                                "output_path": str(tmp_path),
                                "job_name": "fence", "report_steps": 4}
        engine = DeepSpeedEngine(
            model=simple_loss_fn,
            model_params=simple_model_params(jax.random.PRNGKey(0)),
            config=cfg)
        x, y = random_batch(n=16)
        bad = x.copy()
        bad[0, 0] = np.nan
        engine.train_batch(batch=(x, y))    # compiles outside the fence
        before = timer_mod.device_sync_count()
        for _ in range(6):
            engine.train_batch(batch=(x, y))
        engine.train_batch(batch=(bad, y))
        delta = timer_mod.device_sync_count() - before
        engine.telemetry.close()
        return delta

    def test_health_adds_no_hot_path_syncs(self, tmp_path):
        off = self._run(tmp_path / "off", telemetry=False)
        on = self._run(tmp_path / "on", telemetry=True)
        assert on == off, (
            f"health-enabled run issued {on} device-sync fences vs "
            f"{off} disabled — the zero-added-syncs contract broke")

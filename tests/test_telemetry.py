"""Unified telemetry subsystem (monitor/): ring-buffered per-step JSONL
records, Chrome-trace spans, the recompile sentinel, memory watermarks,
and the zero-added-hot-path-syncs design rule (asserted via the
instrumented fence counter, not trusted).

Acceptance gates from the PR issue:
- the recompile sentinel catches an induced retrace (shape-changing batch
  after warmup) and can raise under fail_on_recompile;
- a telemetry-enabled dp=8 run produces a JSONL + Chrome-trace pair that
  tools/telemetry_report.py turns into TELEMETRY.json whose step-time,
  wire-bytes, and memory fields check out against the hlo_audit wire
  model and memory_stats() ground truth;
- telemetry-enabled runs add no per-step device fences.
"""
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.utils.timer as timer_mod
from deepspeed_tpu.monitor import (GOODPUT_BUCKETS, JsonlSink,
                                   MemoryWatermark, RecompileError,
                                   RecompileSentinel,
                                   analytic_state_bytes,
                                   device_memory_stats)
from deepspeed_tpu.monitor.recompile import signature_delta
from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                          DeepSpeedConfigError)
from deepspeed_tpu.runtime.engine import DeepSpeedEngine

from simple_model import (simple_model_params, simple_loss_fn, random_batch,
                          base_config)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_report_tool():
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(REPO, "tools",
                                         "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def telemetry_config(tmp_path, **knobs):
    tel = {"enabled": True, "output_path": str(tmp_path), "job_name": "run"}
    tel.update(knobs)
    return tel


def make_engine(tmp_path, seed=0, tel_knobs=None, **cfg_overrides):
    cfg = base_config(**cfg_overrides)
    cfg["telemetry"] = telemetry_config(tmp_path, **(tel_knobs or {}))
    params = simple_model_params(jax.random.PRNGKey(seed))
    return DeepSpeedEngine(model=simple_loss_fn, model_params=params,
                           config=cfg)


def read_jsonl(tmp_path, job="run"):
    with open(os.path.join(str(tmp_path), f"{job}.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


# --------------------------------------------------------------------- #
# Config surface
# --------------------------------------------------------------------- #
class TestTelemetryConfig:
    def test_defaults_off(self):
        cfg = DeepSpeedConfig(base_config())
        assert not cfg.telemetry_config.enabled

    def test_knobs_parse(self):
        cfg = DeepSpeedConfig(base_config(telemetry={
            "enabled": True, "output_path": "/tmp/x", "job_name": "j",
            "report_steps": 7, "buffer_size": 32,
            "trace_path": "/tmp/t.json", "fail_on_recompile": True,
            "recompile_warmup_calls": 3, "watermark_ratio": 1.5}))
        t = cfg.telemetry_config
        assert t.enabled and t.report_steps == 7 and t.buffer_size == 32
        assert t.trace_path == "/tmp/t.json" and t.fail_on_recompile
        assert t.recompile_warmup_calls == 3 and t.watermark_ratio == 1.5

    def test_tensorboard_alias(self):
        """A tensorboard-only config gets an enabled telemetry sink with
        the tensorboard block's output_path/job_name."""
        cfg = DeepSpeedConfig(base_config(tensorboard={
            "enabled": True, "output_path": "/tmp/tb", "job_name": "tb_job"}))
        t = cfg.telemetry_config
        assert t.enabled and t.tensorboard
        assert t.output_path == "/tmp/tb" and t.job_name == "tb_job"

    def test_explicit_telemetry_wins_over_alias(self):
        cfg = DeepSpeedConfig(base_config(
            tensorboard={"enabled": True, "job_name": "tb"},
            telemetry={"enabled": False}))
        assert not cfg.telemetry_config.enabled

    @pytest.mark.parametrize("bad", [
        {"buffer_size": 0}, {"buffer_size": "big"}, {"report_steps": -1},
        {"recompile_warmup_calls": -2}, {"watermark_ratio": 0}])
    def test_invalid_raises(self, bad):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig(base_config(telemetry=bad))


# --------------------------------------------------------------------- #
# Ring buffer -> JSONL
# --------------------------------------------------------------------- #
class TestStepRecords:
    def test_records_drain_at_boundaries(self, tmp_path):
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 5})
        batch = random_batch(n=16)
        for _ in range(11):
            engine.train_batch(batch=batch)
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        kinds = [r["kind"] for r in recs]
        assert kinds[0] == "meta"
        steps = [r for r in recs if r["kind"] == "step"]
        reports = [r for r in recs if r["kind"] == "report"]
        assert [s["step"] for s in steps] == list(range(1, 12))
        assert len(reports) == 3      # step 5, step 10, close()
        for s in steps:
            assert s["wall_ms"] > 0
            assert isinstance(s["loss"], float)
            assert isinstance(s["lr"], float)
            assert isinstance(s["loss_scale"], float)
            assert isinstance(s["overflow"], bool)
            assert s["wire_bytes"] == recs[0]["wire_bytes_per_step"]
        assert reports[0]["skipped_steps"] == 0

    def test_ring_overflow_is_reported(self, tmp_path):
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 8,
                                                  "buffer_size": 3})
        batch = random_batch(n=16)
        for _ in range(8):
            engine.train_batch(batch=batch)
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        steps = [r for r in recs if r["kind"] == "step"]
        report = next(r for r in recs if r["kind"] == "report")
        # Ring kept the newest 3 of 8; the drop count is explicit.
        assert [s["step"] for s in steps] == [6, 7, 8]
        assert report["dropped_records"] == 5

    def test_disabled_is_inert(self, tmp_path):
        cfg = base_config()
        cfg["telemetry"] = {"enabled": False,
                            "output_path": str(tmp_path)}
        engine = DeepSpeedEngine(
            model=simple_loss_fn,
            model_params=simple_model_params(jax.random.PRNGKey(0)),
            config=cfg)
        engine.train_batch(batch=random_batch(n=16))
        engine.telemetry.close()
        assert not os.path.exists(os.path.join(str(tmp_path), "run.jsonl"))
        assert engine.telemetry.sentinel is None


# --------------------------------------------------------------------- #
# Recompile sentinel
# --------------------------------------------------------------------- #
class TestRecompileSentinel:
    def test_steady_state_is_clean(self, tmp_path):
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 10 ** 9})
        batch = random_batch(n=16)
        for _ in range(6):
            engine.train_batch(batch=batch)
        assert engine.telemetry.recompile_count == 0

    def test_induced_retrace_is_caught(self, tmp_path):
        """The acceptance gate: a shape-changing batch after warmup is a
        structured recompile event naming the function and the
        abstract-signature delta."""
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 10 ** 9})
        for _ in range(4):
            engine.train_batch(batch=random_batch(n=16))
        engine.train_batch(batch=random_batch(n=32))   # induced retrace
        assert engine.telemetry.recompile_count == 1
        event = engine.telemetry.sentinel.events[-1]
        assert event["fn"] == "train_step"
        delta = " ".join(event["signature_delta"])
        assert "16" in delta and "32" in delta
        engine.telemetry.close()
        jsonl_events = [r for r in read_jsonl(tmp_path)
                        if r["kind"] == "event" and r["event"] == "recompile"]
        assert len(jsonl_events) == 1
        assert jsonl_events[0]["fn"] == "train_step"

    def test_fail_on_recompile_raises(self, tmp_path):
        engine = make_engine(tmp_path,
                             tel_knobs={"fail_on_recompile": True,
                                        "report_steps": 10 ** 9})
        for _ in range(4):
            engine.train_batch(batch=random_batch(n=16))
        with pytest.raises(RecompileError, match="train_step"):
            engine.train_batch(batch=random_batch(n=32))
        # The raise is deferred past the donated-state assignment: a
        # caller that catches it must still hold a USABLE engine (e.g.
        # to checkpoint before dying), not deleted buffers.
        assert float(jax.device_get(engine.state.loss_scale)) == 1.0
        engine.train_batch(batch=random_batch(n=32))   # now cached: fine

    def test_sentinel_standalone(self):
        sent = RecompileSentinel(warmup_calls=1)
        fn = sent.instrument("f", jax.jit(lambda x: x + 1))
        fn(jnp.ones(3))                  # cold compile: warmup
        fn(jnp.ones(3))                  # cache hit
        assert sent.recompile_count == 0
        fn(jnp.ones(4))                  # retrace
        assert sent.recompile_count == 1
        assert "float32[3]" in " ".join(sent.events[0]["signature_delta"])
        assert "float32[4]" in " ".join(sent.events[0]["signature_delta"])

    def test_signature_delta_no_change(self):
        sig = (("a", "float32[3]"),)
        assert "no abstract-signature change" in \
            signature_delta(sig, sig)[0]


# --------------------------------------------------------------------- #
# Zero added hot-path device fences (tier-1 gate)
# --------------------------------------------------------------------- #
class TestNoAddedSyncs:
    def _syncs_per_run(self, tmp_path, enabled, n=5):
        cfg = base_config()
        cfg["telemetry"] = {"enabled": enabled,
                            "output_path": str(tmp_path),
                            "job_name": f"sync_{enabled}",
                            # trace spans on: they must cost no fences
                            "trace_path": os.path.join(
                                str(tmp_path), f"trace_{enabled}.json"),
                            "report_steps": 10 ** 9}
        engine = DeepSpeedEngine(
            model=simple_loss_fn,
            model_params=simple_model_params(jax.random.PRNGKey(0)),
            config=cfg)
        batch = random_batch(n=16)
        engine.train_batch(batch=batch)       # compile
        before = timer_mod.device_sync_count()
        for _ in range(n):
            engine.train_batch(batch=batch)
        return timer_mod.device_sync_count() - before

    def test_telemetry_adds_no_per_step_fences(self, tmp_path):
        disabled = self._syncs_per_run(tmp_path, False)
        enabled = self._syncs_per_run(tmp_path, True)
        assert enabled == disabled, (
            f"telemetry-enabled run issued {enabled} device fences vs "
            f"{disabled} disabled — the hot path must not fence")


# --------------------------------------------------------------------- #
# Memory watermarks
# --------------------------------------------------------------------- #
class TestMemoryWatermark:
    def test_analytic_bytes_respects_sharding(self, mesh8):
        from jax.sharding import NamedSharding, PartitionSpec as P
        x = jax.device_put(jnp.zeros((16, 4), jnp.float32),
                           NamedSharding(mesh8, P("data")))
        r = jax.device_put(jnp.zeros((16, 4), jnp.float32),
                           NamedSharding(mesh8, P()))
        assert analytic_state_bytes({"x": x}) == 16 * 4 * 4 // 8
        assert analytic_state_bytes({"r": r}) == 16 * 4 * 4
        assert analytic_state_bytes({"x": x, "r": r}) == \
            16 * 4 * 4 + 16 * 4 * 4 // 8

    def test_engine_zero2_analytic_smaller_than_replicated(self, tmp_path):
        engine = make_engine(tmp_path, **{
            "zero_optimization": {"stage": 2}})
        analytic = engine.telemetry.meta["analytic_state_bytes"]
        full = sum(np.prod(l.shape) * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(engine.state)
                   if hasattr(l, "shape"))
        assert 0 < analytic < full   # moments are dp-sharded

    def test_watermark_event_fires_and_clears(self):
        fake = {"num_devices": 2, "per_device": [],
                "bytes_in_use_max": 100, "bytes_in_use_sum": 150,
                "peak_bytes_in_use_max": 100, "peak_bytes_in_use_sum": 150,
                "bytes_limit_max": 1000, "bytes_limit_sum": 2000}
        wm = MemoryWatermark(analytic_bytes=40, ratio=2.0, slack_bytes=10,
                             sampler=lambda: dict(fake))
        stats, event = wm.check()      # threshold = 40*2+10 = 90 < 100
        assert stats is not None and event is not None
        assert event["peak_bytes_in_use_max"] == 100
        assert event["threshold_bytes"] == 90
        assert event["ratio"] == 2.5
        fake["peak_bytes_in_use_max"] = 80
        stats, event = wm.check()
        assert stats is not None and event is None
        assert len(wm.events) == 1

    def test_unavailable_backend_is_graceful(self):
        wm = MemoryWatermark(analytic_bytes=40, sampler=lambda: None)
        assert wm.check() == (None, None)

    def test_engine_drain_writes_watermark_event(self, tmp_path):
        engine = make_engine(tmp_path,
                             tel_knobs={"report_steps": 2,
                                        "watermark_slack_bytes": 0})
        analytic = engine.telemetry.watermark.analytic_bytes
        engine.telemetry.watermark.sampler = lambda: {
            "num_devices": 1, "per_device": [],
            "bytes_in_use_max": analytic, "bytes_in_use_sum": analytic,
            "peak_bytes_in_use_max": analytic * 100,
            "peak_bytes_in_use_sum": analytic * 100,
            "bytes_limit_max": 0, "bytes_limit_sum": 0}
        batch = random_batch(n=16)
        engine.train_batch(batch=batch)
        engine.train_batch(batch=batch)    # drain boundary
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        events = [r for r in recs if r["kind"] == "event"
                  and r["event"] == "memory_watermark"]
        assert events and events[0]["analytic_state_bytes"] == analytic
        report = next(r for r in recs if r["kind"] == "report")
        assert report["memory"]["peak_bytes_in_use_max"] == analytic * 100

    def test_see_memory_usage_uses_shared_sampler(self, monkeypatch,
                                                  capsys):
        import deepspeed_tpu.runtime.utils as rutils
        from deepspeed_tpu.utils.logging import logger
        msgs = []
        monkeypatch.setattr(logger, "info", lambda m: msgs.append(m))
        monkeypatch.setattr(
            "deepspeed_tpu.monitor.memory.device_memory_stats",
            lambda: {"num_devices": 8,
                     "bytes_in_use_max": 2 ** 30, "bytes_in_use_sum":
                     8 * 2 ** 30, "peak_bytes_in_use_max": 2 ** 31,
                     "peak_bytes_in_use_sum": 8 * 2 ** 31,
                     "bytes_limit_max": 16 * 2 ** 30,
                     "bytes_limit_sum": 0, "per_device": []})
        rutils.see_memory_usage("tag")
        assert msgs and "8 device(s)" in msgs[0]
        assert "max=1.00GB" in msgs[0] and "sum=8.00GB" in msgs[0]

    def test_device_memory_stats_matches_backend(self):
        """Sampler truth vs the backend: on backends with no
        memory_stats() (CPU) it must be None; where stats exist the
        aggregates must bound the per-device values."""
        raw = jax.local_devices()[0].memory_stats()
        stats = device_memory_stats()
        if raw is None:
            assert stats is None
        else:
            assert stats["bytes_in_use_max"] >= raw.get("bytes_in_use", 0)
            assert stats["bytes_in_use_sum"] >= stats["bytes_in_use_max"]


# --------------------------------------------------------------------- #
# JSONL sink resource story (the old _Monitor bugs)
# --------------------------------------------------------------------- #
class TestJsonlSink:
    def test_non_writer_process_opens_nothing(self, tmp_path):
        sink = JsonlSink(str(tmp_path), "job", is_writer=False)
        sink.write({"kind": "step", "step": 1})
        sink.close()
        assert not os.path.exists(os.path.join(str(tmp_path), "job.jsonl"))

    def test_writer_process_and_idempotent_close(self, tmp_path):
        sink = JsonlSink(str(tmp_path), "job", is_writer=True)
        sink.write({"kind": "step", "step": 1})
        sink.close()
        sink.close()                      # double close is safe
        sink.write({"kind": "step", "step": 2})   # post-close is a no-op
        recs = read_jsonl(tmp_path, job="job")
        assert len(recs) == 1 and recs[0]["step"] == 1


# --------------------------------------------------------------------- #
# Honesty regressions (from review)
# --------------------------------------------------------------------- #
class TestWireHonesty:
    def test_sparse_engine_wire_excludes_csr_leaves(self, tmp_path):
        """Sparse embedding grads travel the data-dependent CSR exchange;
        pricing them at the dense wire model would overstate wire by
        orders of magnitude."""
        import jax.numpy as jnp

        def loss_fn(params, batch, rng):
            x, y = batch
            h = jnp.tanh(params["embed"][y] @ params["w"])
            return jnp.mean(h * x[:, :4])

        params = {
            "embed": jax.random.normal(jax.random.PRNGKey(0), (64, 8)),
            "w": jax.random.normal(jax.random.PRNGKey(1), (8, 4)),
        }
        cfg = base_config(sparse_gradients=True)
        cfg["telemetry"] = telemetry_config(tmp_path)
        engine = DeepSpeedEngine(model=loss_fn, model_params=params,
                                 config=cfg)
        assert engine._sparse_mask is not None and engine.dp_size == 8
        from deepspeed_tpu.parallel import hlo_audit
        dense_only = hlo_audit.grad_sync_wire_model([params["w"]], 8)
        full = hlo_audit.grad_sync_wire_model(params, 8)
        assert engine._wire_bytes == dense_only["all_reduce_wire_bytes"]
        assert engine._wire_bytes < full["all_reduce_wire_bytes"]
        assert "CSR" in engine._wire_detail
        assert engine.telemetry.meta["wire_bytes_per_step"] == \
            engine._wire_bytes

    def test_report_tool_summarizes_latest_run_only(self, tmp_path):
        """The sink appends; the report must not conflate runs."""
        for run in range(2):
            engine = make_engine(tmp_path, tel_knobs={"report_steps": 2})
            batch = random_batch(n=16)
            for _ in range(2 + run * 2):
                engine.train_batch(batch=batch)
            engine.telemetry.close()
        tool = load_report_tool()
        summary = tool.summarize(os.path.join(str(tmp_path), "run.jsonl"))
        assert summary["steps_recorded"] == 4     # second run only

    def test_trio_wall_covers_forward(self, tmp_path):
        """fwd/bwd/step path: wall_ms spans the whole accumulation
        window, not just the optimizer apply."""
        import time as _time
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 1})
        batch = random_batch(n=16)
        engine.forward(batch)
        t_mid = _time.perf_counter()
        _time.sleep(0.05)          # forward->step gap must be included
        engine.backward()
        engine.step()
        assert engine._trio_t0 is None
        engine.telemetry.close()
        step = next(r for r in read_jsonl(tmp_path) if r["kind"] == "step")
        assert step["wall_ms"] >= 50.0

    def test_non_writer_process_collects_nothing(self, tmp_path):
        from deepspeed_tpu.monitor import Telemetry
        cfg = DeepSpeedConfig(base_config(telemetry=telemetry_config(
            tmp_path))).telemetry_config
        tl = Telemetry(cfg, default_report_steps=1, is_writer=False)
        tl.record_step(1, {"loss": 1.0})
        assert len(tl._ring) == 0
        tl.drain()                       # no fetch, no write, no crash
        tl.close()
        assert not os.path.exists(os.path.join(str(tmp_path), "run.jsonl"))


# --------------------------------------------------------------------- #
# Resource/lifetime regressions (from review)
# --------------------------------------------------------------------- #
class TestLifetime:
    def test_closed_telemetry_releases_engine(self, tmp_path):
        """atexit keeps the Telemetry alive; a closed one must not pin
        the engine's device state (weakref step_provider + unregister)."""
        import gc
        import weakref
        engine = make_engine(tmp_path)
        engine.train_batch(batch=random_batch(n=16))
        engine.telemetry.close()
        ref = weakref.ref(engine)
        del engine
        gc.collect()
        assert ref() is None

    def test_trace_writer_incremental_flush(self, tmp_path):
        from deepspeed_tpu.monitor import TraceWriter
        import time as _time
        path = os.path.join(str(tmp_path), "t.json")
        tw = TraceWriter(path, is_writer=True)
        t = _time.perf_counter()
        tw.add_span("a", t, 0.001)
        tw.flush()
        assert tw._events == []          # buffer cleared, not rewritten
        tw.add_span("b", t, 0.001)
        tw.close()
        evs = json.load(open(path))
        # The first event lays the file's ts 0 on the wall clock.
        assert [e["name"] for e in evs[:3]] == ["clock_sync", "a", "b"]
        assert evs[0]["ts"] == 0
        assert abs(evs[0]["args"]["unix_ns"] - _time.time_ns()) < 60e9

    def test_trace_writer_non_writer_buffers_nothing(self, tmp_path):
        from deepspeed_tpu.monitor import TraceWriter
        import time as _time
        path = os.path.join(str(tmp_path), "t.json")
        tw = TraceWriter(path, is_writer=False)
        tw.add_span("a", _time.perf_counter(), 0.001)
        tw.instant("b")
        assert tw._events == []
        tw.close()
        assert not os.path.exists(path)

    def test_profiler_window_resume_mid_window(self, monkeypatch):
        from deepspeed_tpu.monitor import ProfilerWindow
        calls = []
        import jax
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: calls.append(("start", d)))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.append(("stop",)))
        w = ProfilerWindow(start_step=500, num_steps=5, out_dir="/tmp/x")
        w.tick(503)        # checkpoint resume landed mid-window
        assert calls and calls[0][0] == "start"
        w.tick(505)
        assert calls[-1] == ("stop",)


# --------------------------------------------------------------------- #
# Offload path: timings surfaced in record + log line (satellite)
# --------------------------------------------------------------------- #
class TestOffloadTelemetry:
    def make_offload_engine(self, tmp_path, overlap):
        from deepspeed_tpu.parallel.topology import build_mesh
        cfg = base_config(**{
            "train_batch_size": 4,
            "zero_optimization": {"stage": 2, "cpu_offload": True,
                                  "overlap_comm": overlap},
            "steps_per_print": 1})
        cfg["telemetry"] = telemetry_config(
            tmp_path, report_steps=1,
            trace_path=os.path.join(str(tmp_path), "trace.json"))
        return DeepSpeedEngine(
            model=simple_loss_fn,
            model_params=simple_model_params(jax.random.PRNGKey(0)),
            config=cfg, mesh=build_mesh(devices=jax.devices()[:1]))

    @pytest.mark.parametrize("overlap", [False, True])
    def test_offload_record_and_log_line(self, tmp_path, overlap,
                                         monkeypatch):
        import deepspeed_tpu.runtime.engine as engine_mod
        lines = []
        monkeypatch.setattr(engine_mod, "log_dist",
                            lambda msg, ranks=None: lines.append(msg))
        engine = self.make_offload_engine(tmp_path, overlap)
        engine.train_batch(batch=random_batch(n=4))
        engine.telemetry.close()
        # steps_per_print line surfaces the offload breakdown
        step_lines = [l for l in lines if l.startswith("step=")]
        assert step_lines and "offload[" in step_lines[-1]
        assert "overlap=" in step_lines[-1]
        # the step record carries the phase timings + overlap_fraction
        recs = read_jsonl(tmp_path)
        step = next(r for r in recs if r["kind"] == "step")
        off = step["offload"]
        assert off["overlapped"] == overlap
        assert {"d2h_ms", "host_norm_ms", "host_step_ms",
                "overlap_fraction", "num_buckets"} <= set(off)
        # per-bucket spans synthesized from the fenced timings
        trace = json.load(open(os.path.join(str(tmp_path), "trace.json")))
        names = {ev["name"] for ev in trace}
        assert any(n.startswith("offload_adam") for n in names)


# --------------------------------------------------------------------- #
# End-to-end acceptance: dp=8 run -> JSONL + trace -> TELEMETRY.json
# --------------------------------------------------------------------- #
class TestEndToEndReport:
    def test_dp8_run_report_validates(self, tmp_path, mesh8):
        trace_path = os.path.join(str(tmp_path), "trace.json")
        cfg = base_config(**{
            "zero_optimization": {"stage": 2},
            "steps_per_print": 4})
        cfg["telemetry"] = telemetry_config(tmp_path, report_steps=4,
                                            trace_path=trace_path)
        engine = DeepSpeedEngine(
            model=simple_loss_fn,
            model_params=simple_model_params(jax.random.PRNGKey(0)),
            config=cfg, mesh=mesh8)
        assert engine.dp_size == 8
        batch = random_batch(n=16)
        for _ in range(12):
            engine.train_batch(batch=batch)
        engine.save_checkpoint(str(tmp_path / "ckpt"))
        engine.load_checkpoint(str(tmp_path / "ckpt"))
        engine.telemetry.close()

        # --- wire bytes: validated against the hlo_audit wire model --- #
        from deepspeed_tpu.parallel import hlo_audit
        model = hlo_audit.grad_sync_wire_model(engine.state.params, 8)
        mode = engine._grad_sync_mode
        declared = hlo_audit.zero2_grad_sync_lowering(engine.mesh, "data")
        if mode == "allreduce" or (mode == "declarative"
                                   and declared == "all-reduce"):
            expected_wire = model["all_reduce_wire_bytes"]
        else:
            expected_wire = model["reduce_scatter_wire_bytes"]

        report_tool = load_report_tool()
        jsonl = os.path.join(str(tmp_path), "run.jsonl")
        out = str(tmp_path / "TELEMETRY.json")
        assert report_tool.main([jsonl, "-o", out]) == 0
        summary = json.load(open(out))

        assert summary["steps_recorded"] == 12
        assert summary["dropped_records"] == 0
        st = summary["step_time_ms"]
        assert st["n"] == 12 and 0 < st["p50"] <= st["p95"]
        assert summary["wire_bytes_per_step"] == expected_wire
        assert summary["wire_bytes_consistent"]
        assert summary["recompiles"]["count"] == 0
        # throughput window closed (steps_per_print=4 over 12 steps)
        assert summary["throughput"]["window_valid"]
        assert summary["throughput"]["samples_per_sec"] > 0
        # memory vs memory_stats() ground truth: on this backend (CPU)
        # stats are unavailable and the report must say so; on a real
        # TPU the same field carries the peak/analytic comparison.
        ground_truth = jax.local_devices()[0].memory_stats()
        if ground_truth is None:
            assert summary["memory"]["available"] is False
        else:   # pragma: no cover - device-backend runs
            assert summary["memory"]["peak_bytes_in_use_max"] >= \
                ground_truth.get("peak_bytes_in_use", 0)
        assert summary["memory"]["analytic_state_bytes"] == \
            engine.telemetry.meta["analytic_state_bytes"]
        assert summary["meta"]["dp"] == 8
        assert summary["skipped_steps"] == 0

        # --- roofline cost model: one cost_model record, per-path
        # verdicts validated against the wire model --- #
        recs = read_jsonl(tmp_path)
        cms = [r for r in recs if r["kind"] == "cost_model"]
        assert len(cms) == 1
        cm = cms[0]
        train = cm["paths"]["train_step"]
        assert train["available"]
        assert train["bound"] in ("compute", "hbm", "interconnect")
        # comm priced from the PR-3 wire model at the RESOLVED lowering.
        assert train["comm_bytes"] == expected_wire
        assert train["analytic_flops"] > 0
        assert cm["step"]["floor_ms"] > 0
        assert cm["chip"]["assumed"]   # CPU mesh: v5e peaks, flagged

        # --- per-step MFU + fenced window MFU --- #
        step_recs = [r for r in recs if r["kind"] == "step"]
        assert all(0 < s["mfu"] < 1 for s in step_recs)
        report_recs = [r for r in recs if r["kind"] == "report"]
        assert any(0 < r.get("window_mfu", 0) < 1 for r in report_recs)

        # --- goodput ledger: every settled window sums to its wall
        # within 1% and is consistent; the post-step checkpoint wall
        # lands in the close-drain window --- #
        gp_windows = [r["goodput"] for r in report_recs
                      if isinstance(r.get("goodput"), dict)]
        assert gp_windows
        for w in gp_windows:
            total = sum(w[f"{b}_s"] for b in GOODPUT_BUCKETS)
            assert abs(total - w["window_s"]) <= 0.01 * w["window_s"] \
                + 1e-9
            assert w["consistent"]
        assert sum(w["checkpoint_s"] for w in gp_windows) > 0
        # cold-start compile wall is attributed, not hidden
        assert sum(w["recompile_s"] for w in gp_windows) > 0

        # --- TELEMETRY.json grew the three sections --- #
        assert summary["mfu"]["available"]
        assert summary["mfu"]["peak_assumed"]
        assert 0 < summary["mfu"]["window_mfu"] < 1
        assert summary["roofline"]["available"]
        assert summary["roofline"]["step_bound"] in (
            "compute", "hbm", "interconnect")
        assert summary["roofline"]["paths"]["train_step"]["bound"] == \
            train["bound"]
        assert summary["roofline"]["measured_p50_over_floor"] > 0
        assert summary["goodput"]["available"]
        assert summary["goodput"]["consistent"]
        assert summary["goodput"]["accounted_fraction"] == \
            pytest.approx(1.0, abs=0.01)
        assert summary["goodput"]["windows"] == len(gp_windows)

        # --- Chrome-trace pair: valid JSON (array form, terminated at
        # close) with the expected spans --- #
        trace = json.load(open(trace_path))
        assert isinstance(trace, list)
        names = {ev["name"] for ev in trace}
        assert {"train_batch", "data_prep", "step_dispatch",
                "checkpoint_save", "checkpoint_load"} <= names
        for ev in trace:
            assert ev["ph"] in ("X", "i")
            assert ev["ts"] >= 0

    def test_trained_loss_still_falls(self, tmp_path):
        """Telemetry must not perturb training itself."""
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 3})
        batch = random_batch(n=16)
        losses = [float(engine.train_batch(batch=batch))
                  for _ in range(15)]
        assert losses[-1] < losses[0] * 0.8


# --------------------------------------------------------------------- #
# Goodput ledger wired through the engine
# --------------------------------------------------------------------- #
class SlowDataset:
    """Indexable dataset whose item access sleeps — the injected data
    stall the goodput ledger must see."""

    def __init__(self, n=64, dim=8, delay_s=0.002):
        rng = np.random.default_rng(0)
        self.x = rng.normal(size=(n, dim)).astype(np.float32)
        self.y = (self.x.sum(axis=1) > 0).astype(np.int32)
        self.delay_s = delay_s

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        time.sleep(self.delay_s)
        return self.x[i], self.y[i]


def _assert_sums_to_wall(window):
    """The acceptance identity: buckets sum to window wall within 1%."""
    total = sum(window[f"{b}_s"] for b in GOODPUT_BUCKETS)
    assert abs(total - window["window_s"]) <= \
        0.01 * window["window_s"] + 1e-9
    assert window["consistent"]


class TestGoodputEngine:
    def test_slow_dataset_stall_lands_in_ledger(self, tmp_path):
        delay = 0.002
        cfg = base_config()
        cfg["telemetry"] = telemetry_config(tmp_path, report_steps=5)
        engine = DeepSpeedEngine(
            model=simple_loss_fn,
            model_params=simple_model_params(jax.random.PRNGKey(0)),
            config=cfg, training_data=SlowDataset(delay_s=delay))
        for _ in range(5):
            engine.train_batch()
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        w = next(r["goodput"] for r in recs if r["kind"] == "report")
        # 5 steps x 16 samples x injected sleep: sleep() only ever
        # overshoots, so the stall floor is exact.
        expected = 5 * 16 * delay
        assert w["data_stall_s"] >= expected
        assert w["data_stall_s"] < w["window_s"]
        assert w["useful_compute_s"] >= 0
        _assert_sums_to_wall(w)
        assert w["accounted_fraction"] == pytest.approx(1.0)
        # the loader-local counter sees the same stall (dataset access
        # + collate happen inside the loader's __next__)
        assert engine.training_dataloader.cumulative_fetch_wait_s() >= \
            expected

    def test_recompile_wall_attributed(self, tmp_path):
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 3})
        for _ in range(3):
            engine.train_batch(batch=random_batch(n=16))  # cold compile
        for _ in range(3):
            engine.train_batch(batch=random_batch(n=32))  # induced retrace
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        gps = [r["goodput"] for r in recs if r["kind"] == "report"]
        assert len(gps) >= 2
        assert gps[0]["recompile_s"] > 0    # cold start is real lost wall
        assert gps[1]["recompile_s"] > 0    # the retrace window
        for w in gps:
            _assert_sums_to_wall(w)
        # ledger windows partition the sentinel's cumulative compile wall
        total = sum(g["recompile_s"] for g in gps)
        assert total == pytest.approx(
            engine.telemetry.sentinel.compile_wall_s, rel=1e-3, abs=1e-5)

    def test_overflow_skipped_steps_attributed(self, tmp_path):
        engine = make_engine(
            tmp_path, tel_knobs={"report_steps": 4},
            fp16={"enabled": True, "initial_scale_power": 8,
                  "hysteresis": 1})
        x, y = random_batch(n=16)
        bad = (np.full_like(x, np.nan), y)
        for batch in [(x, y), bad, bad, (x, y)]:
            engine.train_batch(batch=batch)
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        steps = [r for r in recs if r["kind"] == "step"]
        assert [s["overflow"] for s in steps] == [False, True, True, False]
        w = next(r["goodput"] for r in recs if r["kind"] == "report")
        # overflow-skipped wall == exactly the overflow steps' wall
        # (work executed, result discarded — not useful compute)
        expected = sum(s["wall_ms"] for s in steps if s["overflow"]) / 1e3
        assert w["overflow_skipped_s"] == pytest.approx(
            expected, rel=1e-3, abs=1e-6)
        assert w["overflow_skipped_s"] > 0
        _assert_sums_to_wall(w)

    def test_first_step_overflow_during_cold_compile(self, tmp_path):
        """The first step both cold-compiles AND overflows: the compile
        wall (inside that step's wall) must land in recompile, not be
        double-counted against the overflow bucket — the window stays
        consistent and useful_compute non-negative."""
        engine = make_engine(
            tmp_path, tel_knobs={"report_steps": 3},
            fp16={"enabled": True, "initial_scale_power": 8,
                  "hysteresis": 1})
        x, y = random_batch(n=16)
        bad = (np.full_like(x, np.nan), y)
        for batch in [bad, (x, y), (x, y)]:
            engine.train_batch(batch=batch)
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        steps = [r for r in recs if r["kind"] == "step"]
        assert steps[0]["overflow"] and not steps[1]["overflow"]
        w = next(r["goodput"] for r in recs if r["kind"] == "report")
        assert w["recompile_s"] > 0
        assert w["overflow_skipped_s"] >= 0
        assert w["useful_compute_s"] >= 0
        _assert_sums_to_wall(w)

    def test_trailing_checkpoint_settles_at_close(self, tmp_path):
        """A checkpoint saved after the last report boundary must not
        vanish: close() settles the ledger even with an empty ring."""
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 2})
        for _ in range(2):
            engine.train_batch(batch=random_batch(n=16))  # drains at 2
        engine.save_checkpoint(str(tmp_path / "ckpt"))
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        gps = [r["goodput"] for r in recs if r["kind"] == "report"]
        assert len(gps) == 2            # boundary + close settlement
        assert gps[-1]["steps"] == 0
        assert gps[-1]["checkpoint_s"] > 0
        _assert_sums_to_wall(gps[-1])


# --------------------------------------------------------------------- #
# Roofline cost model wired through the engine
# --------------------------------------------------------------------- #
class TestCostModelEngine:
    def test_disabled_knob_writes_no_record(self, tmp_path):
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 2,
                                                  "cost_model": False})
        for _ in range(2):
            engine.train_batch(batch=random_batch(n=16))
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        assert not [r for r in recs if r["kind"] == "cost_model"]
        assert all("mfu" not in r for r in recs if r["kind"] == "step")

    def test_build_failure_degrades_to_event(self, tmp_path, monkeypatch):
        """Observability must never kill training: a cost-model build
        crash becomes a structured event and the run continues."""
        import deepspeed_tpu.monitor.cost_model as cm_mod

        def boom(*a, **k):
            raise RuntimeError("synthetic cost-model failure")

        monkeypatch.setattr(cm_mod, "build_cost_model", boom)
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 2})
        losses = [float(engine.train_batch(batch=random_batch(n=16)))
                  for _ in range(4)]
        assert all(np.isfinite(losses))
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        evs = [r for r in recs if r["kind"] == "event"
               and r["event"] == "cost_model_error"]
        assert len(evs) == 1            # built once, failed once
        assert "synthetic cost-model failure" in evs[0]["error"]
        assert not [r for r in recs if r["kind"] == "cost_model"]

    def test_offload_path_priced(self, tmp_path):
        engine = TestOffloadTelemetry().make_offload_engine(
            tmp_path, overlap=False)
        engine.train_batch(batch=random_batch(n=4))   # report_steps=1
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        cm = next(r for r in recs if r["kind"] == "cost_model")
        assert cm["step"]["paths"] == {"offload_grad_step": 1.0}
        p = cm["paths"]["offload_grad_step"]
        assert p["available"] and p["analytic_flops"] > 0
        assert cm["step"]["missing_paths"] == []

    def test_trio_path_priced_with_gas_weighting(self, tmp_path):
        """forward/backward/step trio: grad_step priced gas x, the apply
        once — the fused step total reconciles both programs."""
        cfg = base_config(train_batch_size=16,
                          gradient_accumulation_steps=2)
        cfg["telemetry"] = telemetry_config(tmp_path, report_steps=1)
        engine = DeepSpeedEngine(
            model=simple_loss_fn,
            model_params=simple_model_params(jax.random.PRNGKey(0)),
            config=cfg)
        x, y = random_batch(n=16)
        for mb in [(x[:8], y[:8]), (x[8:], y[8:])]:
            loss = engine.forward(mb)
            engine.backward(loss)
            engine.step()
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        cm = next(r for r in recs if r["kind"] == "cost_model")
        assert cm["step"]["paths"] == {"grad_step": 2.0, "apply_grads": 1.0}
        assert cm["paths"]["grad_step"]["available"]
        assert cm["paths"]["apply_grads"]["available"]
        assert cm["step"]["missing_paths"] == []
        # fused flops: gas x grad program + 1 x apply program
        expected = 2 * cm["paths"]["grad_step"]["analytic_flops"] + \
            cm["paths"]["apply_grads"]["analytic_flops"]
        assert cm["step"]["flops_per_step"] == pytest.approx(expected)

    def test_build_adds_no_device_fences(self, tmp_path):
        """The cost-model build is host-side AOT work: re-lowering every
        registered path must issue ZERO device fences — asserted with
        the instrumented counter, not trusted."""
        engine = make_engine(tmp_path, tel_knobs={"report_steps": 10 ** 9})
        engine.train_batch(batch=random_batch(n=16))
        before = timer_mod.device_sync_count()
        engine._maybe_build_cost_model()
        assert engine.telemetry.cost_model_payload is not None
        assert timer_mod.device_sync_count() == before

    def test_wire_bytes_priced_on_grad_path(self, tmp_path, mesh8):
        """The cost model prices the PR-3 wire model's resolved bytes on
        the grad-computing path — interconnect ceiling is wire-model
        ground truth, not a guess."""
        cfg = base_config(**{"zero_optimization": {"stage": 2}})
        cfg["telemetry"] = telemetry_config(tmp_path, report_steps=2)
        engine = DeepSpeedEngine(
            model=simple_loss_fn,
            model_params=simple_model_params(jax.random.PRNGKey(0)),
            config=cfg, mesh=mesh8)
        for _ in range(2):
            engine.train_batch(batch=random_batch(n=16))
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        cm = next(r for r in recs if r["kind"] == "cost_model")
        meta = next(r for r in recs if r["kind"] == "meta")
        assert cm["paths"]["train_step"]["comm_bytes"] == \
            meta["wire_bytes_per_step"]
        assert cm["n_devices"] == 8


# --------------------------------------------------------------------- #
# Pipeline engine: per-stage cost attribution
# --------------------------------------------------------------------- #
class TestPipelineCostModel:
    def test_per_stage_attribution(self, tmp_path):
        from deepspeed_tpu.parallel.topology import build_mesh
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
        from deepspeed_tpu.runtime.pipe.module import PipelineModule

        def block(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        L, D = 4, 8
        params = {
            f"layer_{i}": {
                "w": jax.random.normal(jax.random.PRNGKey(i), (D, D)) * 0.3,
                "b": jnp.zeros((D,))}
            for i in range(L)}
        module = PipelineModule(
            [block] * L, num_stages=2,
            loss_fn=lambda x, labels: jnp.mean(
                (x.sum(axis=(-1, -2)) - labels) ** 2),
            partition_method="uniform")
        spec = module.to_pipe_spec(params)
        cfg = {"train_batch_size": 4, "train_micro_batch_size_per_gpu": 2,
               "gradient_accumulation_steps": 2,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
               "steps_per_print": 10 ** 9,
               "telemetry": telemetry_config(tmp_path, report_steps=1)}
        rng = np.random.default_rng(0)
        x = rng.normal(size=(4, 4, D)).astype(np.float32)
        y = x.sum(axis=(-1, -2))
        # pp=2 x dp=1
        mesh_pp = build_mesh(pp=2, devices=jax.devices()[:2])
        engine = PipelineEngine(model=spec, config=cfg, mesh=mesh_pp)
        engine.train_batch((x, y))
        engine.telemetry.close()
        recs = read_jsonl(tmp_path)
        cm = next(r for r in recs if r["kind"] == "cost_model")
        pipe = cm["pipeline"]
        assert pipe["stages"] == 2 and pipe["layers"] == L
        # uniform SPMD split: per-stage flops sum back to the analytic
        # total of the whole pipelined step program
        assert len(pipe["flops_per_stage"]) == 2
        assert sum(pipe["flops_per_stage"]) == pytest.approx(
            cm["paths"]["train_step"]["analytic_flops"])
        assert pipe["schedule"] in ("gpipe", "1f1b")
        assert pipe["micro_batches"] >= 1
        # module-level breakdown from the same jaxpr walk
        assert pipe["top_modules"]
        assert all(m["flops"] >= 0 for m in pipe["top_modules"])

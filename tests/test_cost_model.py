"""Roofline cost model (monitor/cost_model.py), the shared chip-peak
table (monitor/peaks.py), and the goodput ledger (monitor/goodput.py).

Tier-1 correctness gates from the PR issue:

- the jaxpr-walk flops profiler and XLA's ``Compiled.cost_analysis()``
  must agree on a STRAIGHT-LINE gpt2 block within a documented tolerance
  (cross-validating both counters: drift in the per-primitive table
  fails here);
- on a scanned program XLA undercounts by the trip count (the scan body
  is costed once) and the cost model must detect and correct it;
- the ledger's buckets must sum to the window wall-clock within 1%, and
  double-attribution must be SURFACED (consistent=False), not clamped.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
from deepspeed_tpu.monitor.cost_model import (BOUND_COMPUTE, BOUND_HBM,
                                              BOUND_INTERCONNECT,
                                              abstract_args_of,
                                              analytic_flops,
                                              build_cost_model, mfu,
                                              path_cost, roofline,
                                              xla_cost_analysis)
from deepspeed_tpu.monitor.goodput import (BUCKETS, GoodputLedger,
                                           extract_step_info)
from deepspeed_tpu.monitor.peaks import (TPU_HBM_GBS, TPU_ICI_GBS,
                                         TPU_PEAK_TFLOPS, ChipPeaks,
                                         chip_peak_tflops, peaks_for_kind)
from deepspeed_tpu.monitor.recompile import RecompileSentinel


# --------------------------------------------------------------------- #
# Shared peak table
# --------------------------------------------------------------------- #
class TestPeakTable:
    def test_every_generation_fully_specified(self):
        assert set(TPU_PEAK_TFLOPS) == set(TPU_HBM_GBS) == set(TPU_ICI_GBS)
        for table in (TPU_PEAK_TFLOPS, TPU_HBM_GBS, TPU_ICI_GBS):
            assert all(v > 0 for v in table.values())

    @pytest.mark.parametrize("kind,gen", [
        ("TPU v4", "v4"), ("TPU v5e", "v5e"), ("TPU v5p", "v5p"),
        ("TPU v6e", "v6e"),
        # device_kind as the hardware reports it
        ("TPU v5 lite", "v5e"), ("TPU v5", "v5p"), ("TPU v6 lite", "v6e"),
        ("v5e", "v5e")])                 # bare generation key
    def test_kind_resolution(self, kind, gen):
        pk = peaks_for_kind(kind)
        assert pk.name == gen and not pk.assumed
        assert pk.bf16_tflops == TPU_PEAK_TFLOPS[gen]
        assert pk.hbm_gbs == TPU_HBM_GBS[gen]
        assert pk.ici_gbs == TPU_ICI_GBS[gen]

    def test_non_tpu_kind_is_assumed_v5e(self):
        for kind in ("cpu", "", "NVIDIA H100", None):
            pk = peaks_for_kind(kind or "")
            assert pk.name == "v5e" and pk.assumed

    @pytest.mark.parametrize("kind", ["TPU v9", "TPU7x", "tpu v5 ultra"])
    def test_unknown_tpu_kind_raises(self, kind):
        """A real chip with no row is an error, never a default."""
        with pytest.raises(KeyError, match="no peak row"):
            peaks_for_kind(kind)

    def test_the_described_chip_is_a_measured_v5e_row(self):
        pk = peaks_for_kind("TPU v5 lite")
        assert (pk.name, pk.assumed) == ("v5e", False)
        assert (pk.bf16_tflops, pk.hbm_gbs) == (197.0, 819.0)

    def test_unit_conversions(self):
        pk = peaks_for_kind("TPU v4")
        assert pk.flops_per_sec == pk.bf16_tflops * 1e12
        assert pk.hbm_bytes_per_sec == pk.hbm_gbs * 1e9
        assert pk.ici_bytes_per_sec == pk.ici_gbs * 1e9

    def test_bench_reexports_the_shared_table(self):
        """bench.py's historical API now IS the shared table — one source
        of truth for every MFU denominator."""
        assert bench.TPU_PEAK_TFLOPS is TPU_PEAK_TFLOPS
        assert bench.chip_peak_tflops is chip_peak_tflops
        # ...and off-TPU there is no peak to divide by: no utilisation
        # is ever printed against the assumed row.
        with pytest.raises(RuntimeError, match="ASSUMED"):
            chip_peak_tflops()


# --------------------------------------------------------------------- #
# Roofline + MFU math
# --------------------------------------------------------------------- #
PEAKS = ChipPeaks(name="v5e", bf16_tflops=200.0, hbm_gbs=1000.0,
                  ici_gbs=100.0)


class TestRoofline:
    def test_compute_bound(self):
        # 1e12 flops / 200 TF = 5 ms; 1e6 bytes HBM = 1 us; no comm.
        r = roofline(1e12, 1e6, 0.0, PEAKS)
        assert r["bound"] == BOUND_COMPUTE
        assert r["floor_ms"] == pytest.approx(5.0)
        assert r["floor_ms"] == max(r["t_compute_ms"], r["t_hbm_ms"],
                                    r["t_comm_ms"])

    def test_hbm_bound(self):
        # 1e9 bytes / 1000 GB/s = 1 ms; 1e9 flops = 5 us.
        r = roofline(1e9, 1e9, 0.0, PEAKS)
        assert r["bound"] == BOUND_HBM
        assert r["floor_ms"] == pytest.approx(1.0)

    def test_interconnect_bound(self):
        # 1e9 wire bytes / 100 GB/s = 10 ms.
        r = roofline(1e9, 1e6, 1e9, PEAKS)
        assert r["bound"] == BOUND_INTERCONNECT
        assert r["floor_ms"] == pytest.approx(10.0)

    def test_operational_intensity(self):
        r = roofline(2e9, 1e9, 0.0, PEAKS)
        assert r["intensity_flops_per_byte"] == pytest.approx(2.0)
        assert r["machine_balance_flops_per_byte"] == pytest.approx(
            PEAKS.flops_per_sec / PEAKS.hbm_bytes_per_sec)


class TestMfu:
    def test_formula(self):
        # 8 devices, 1.6e9 total flops, 1 ms step: 2e11 flops/s/device
        # over a 2e14 peak = 1e-3.
        assert mfu(1.6e9, 1e-3, 8, PEAKS) == pytest.approx(1e-3)
        # perfect utilisation pins at 1.0: one step exactly at peak.
        assert mfu(8 * 2e14, 1.0, 8, PEAKS) == pytest.approx(1.0)

    def test_degenerate_inputs(self):
        assert mfu(1e12, 0.0, 8, PEAKS) == 0.0
        assert mfu(1e12, 1.0, 0, PEAKS) == 0.0


# --------------------------------------------------------------------- #
# Tier-1 gate: analytic profiler vs XLA cost analysis on the gpt2 block
# --------------------------------------------------------------------- #
def _gpt2_fixture(scan_layers, num_layers=2):
    from deepspeed_tpu.models import GPT2_CONFIGS
    from deepspeed_tpu.models.gpt2 import gpt2_apply, gpt2_init
    cfg = dataclasses.replace(
        GPT2_CONFIGS["gpt2-tiny"], scan_layers=scan_layers,
        num_layers=num_layers, hidden_dropout=0.0, attn_dropout=0.0)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 64), dtype=jnp.int32)
    fn = jax.jit(lambda p, t: gpt2_apply(p, t, cfg))
    return fn, (params, tokens)


class TestFlopsCrossValidation:
    # Documented tolerance: the analytic jaxpr-walk count follows the
    # model-flops convention (2mnk matmuls + elementwise), while XLA's
    # optimized-HLO count also prices transcendentals (softmax exp,
    # layernorm rsqrt, gelu tanh) — so XLA sits a few percent ABOVE the
    # analytic figure on this block (measured ~5% here). 10% catches
    # per-primitive-table drift without flaking on XLA version noise.
    TOLERANCE = 0.10

    def test_gpt2_block_straight_line_agreement(self):
        fn, args = _gpt2_fixture(scan_layers=False)
        a_args, a_kwargs = abstract_args_of(args, {})
        analytic = analytic_flops(fn, a_args, a_kwargs)
        xla = xla_cost_analysis(fn, a_args, a_kwargs)
        assert analytic and analytic > 0
        assert xla is not None and xla["flops"] > 0
        assert xla["bytes_accessed"] > 0
        ratio = analytic / xla["flops"]
        assert abs(ratio - 1.0) <= self.TOLERANCE, (
            f"flops counters drifted: analytic={analytic} "
            f"xla={xla['flops']} ratio={ratio:.4f}")

    def test_scan_undercount_detected_and_corrected(self):
        """XLA costs a scan body ONCE; the analytic walk multiplies by
        the trip count. path_cost must detect the ratio and scale the
        HBM bytes by the same factor."""
        fn, args = _gpt2_fixture(scan_layers=True, num_layers=4)
        a_args, a_kwargs = abstract_args_of(args, {})
        p = path_cost("train", fn, a_args, a_kwargs, comm_bytes=0.0,
                      n_devices=1, peaks=PEAKS)
        assert p["available"]
        # 4 scanned layers dominate: analytic/XLA sits well above the
        # 1.5 detection threshold and below the layer count (embedding +
        # head run outside the scan).
        assert 1.5 < p["scan_scale"] <= 4.0
        # scan_scale is rounded for the record; the bytes use the exact
        # ratio — compare loosely.
        assert p["hbm_bytes_per_device"] == pytest.approx(
            p["xla_bytes_per_device"] * p["scan_scale"], rel=1e-3)
        # flops estimate is the analytic (scan-aware) one.
        assert p["flops_per_device"] == pytest.approx(p["analytic_flops"])


# --------------------------------------------------------------------- #
# path_cost / build_cost_model plumbing
# --------------------------------------------------------------------- #
class TestBuildCostModel:
    def _sentinel_with_matmul(self):
        sentinel = RecompileSentinel(warmup_calls=1)
        fn = jax.jit(lambda a, b: a @ b)
        wrapped = sentinel.instrument("mm_step", fn)
        a = jnp.ones((64, 64), jnp.float32)
        wrapped(a, a)   # compile -> registry records the signature
        return sentinel

    def test_sentinel_registry_feeds_the_model(self):
        sentinel = self._sentinel_with_matmul()
        st = sentinel._fns["mm_step"]
        assert st["fn"] is not None and st["abstract_args"] is not None
        out = build_cost_model(sentinel, comm_bytes_by_path={"mm_step": 512},
                               step_paths={"mm_step": 1.0}, n_devices=1,
                               peaks=PEAKS)
        p = out["paths"]["mm_step"]
        assert p["available"]
        # 64x64x64 matmul: 2mnk = 524288 flops.
        assert p["analytic_flops"] == 2 * 64 ** 3
        assert p["comm_bytes"] == 512
        assert p["bound"] in (BOUND_COMPUTE, BOUND_HBM, BOUND_INTERCONNECT)
        step = out["step"]
        assert step["flops_per_step"] == pytest.approx(2 * 64 ** 3)
        assert step["missing_paths"] == []
        assert out["chip"]["name"] == "v5e"

    def test_step_fusion_weights_and_missing(self):
        """gas-style weighting: a path invoked k times contributes k x
        flops and k x floor; unregistered paths are surfaced."""
        sentinel = self._sentinel_with_matmul()
        out1 = build_cost_model(sentinel, {}, {"mm_step": 1.0}, 1,
                                peaks=PEAKS)
        out3 = build_cost_model(sentinel, {},
                                {"mm_step": 3.0, "ghost": 1.0}, 1,
                                peaks=PEAKS)
        assert out3["step"]["flops_per_step"] == pytest.approx(
            3 * out1["step"]["flops_per_step"])
        assert out3["step"]["floor_ms"] == pytest.approx(
            3 * out1["step"]["floor_ms"], rel=1e-6)
        assert out3["step"]["missing_paths"] == ["ghost"]

    def test_extra_paths(self):
        """Paths outside the sentinel registry (e.g. an eval fn) can be
        priced via extra_paths."""
        sentinel = RecompileSentinel()
        fn = jax.jit(lambda a: a * 2.0)
        a_args, a_kwargs = abstract_args_of(
            (jnp.ones((8, 8), jnp.float32),), {})
        out = build_cost_model(sentinel, {}, {"scale": 1.0}, 1,
                               peaks=PEAKS,
                               extra_paths={"scale": (fn, a_args, a_kwargs)})
        assert out["paths"]["scale"]["available"]

    def test_abstract_leaf_survives_donation(self):
        """abstract_args_of mirrors shapes/dtypes as ShapeDtypeStructs —
        usable after the live buffers are donated/deleted."""
        x = jnp.ones((4, 2), jnp.bfloat16)
        a_args, _ = abstract_args_of((x, 3), {})
        x.delete()
        leaf = a_args[0]
        assert leaf.shape == (4, 2) and leaf.dtype == jnp.bfloat16
        assert a_args[1] == 3   # non-array leaves pass through


# --------------------------------------------------------------------- #
# Goodput ledger
# --------------------------------------------------------------------- #
class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class TestGoodputLedger:
    def test_window_settlement_math(self):
        clk = FakeClock(10.0)
        led = GoodputLedger(clock=clk)
        led.note("data_stall", 0.1)
        led.note("recompile", 0.05)
        led.note("checkpoint", 0.2)
        steps = [(0.5, False, 0.0), (0.2, True, 0.0), (0.4, False, 0.1)]
        clk.t = 12.0
        w = led.close_window(steps)
        assert w["window_s"] == pytest.approx(2.0)
        assert w["steps"] == 3
        # useful = non-overflow step wall (0.9) minus in-step stalls
        # (0.1 + 0.05) minus exposed offload host time (0.1).
        assert w["useful_compute_s"] == pytest.approx(0.65)
        assert w["data_stall_s"] == pytest.approx(0.1)
        assert w["recompile_s"] == pytest.approx(0.05)
        assert w["overflow_skipped_s"] == pytest.approx(0.2)
        assert w["checkpoint_s"] == pytest.approx(0.2)
        assert w["offload_exposed_s"] == pytest.approx(0.1)
        assert w["other_s"] == pytest.approx(2.0 - 1.3)
        # The acceptance identity: buckets sum to window wall within 1%.
        total = sum(w[f"{b}_s"] for b in BUCKETS)
        assert total == pytest.approx(w["window_s"], rel=0.01)
        assert w["accounted_fraction"] == pytest.approx(1.0)
        assert w["consistent"]

    def test_stall_inside_overflow_step_reattributed(self):
        """A step can both cold-compile AND overflow (high initial loss
        scale): the compile wall is inside the overflow step's wall, so
        it must move OUT of the overflow bucket — counted once, under
        recompile — and the window must stay consistent."""
        clk = FakeClock(0.0)
        led = GoodputLedger(clock=clk)
        led.note("recompile", 0.8)
        clk.t = 2.0
        w = led.close_window([(1.0, True, 0.0)])   # the only step overflowed
        assert w["recompile_s"] == pytest.approx(0.8)
        assert w["overflow_skipped_s"] == pytest.approx(0.2)
        assert w["useful_compute_s"] == 0.0
        assert w["other_s"] == pytest.approx(1.0)
        assert w["consistent"]

    def test_spill_beyond_overflow_wall_is_surfaced(self):
        """Measured stalls exceeding ALL step wall is genuine
        double-attribution: overflow goes negative and consistent flips
        — surfaced, never clamped."""
        clk = FakeClock(0.0)
        led = GoodputLedger(clock=clk)
        led.note("recompile", 0.9)
        clk.t = 2.0
        w = led.close_window([(0.5, True, 0.0)])
        assert w["overflow_skipped_s"] < 0
        assert not w["consistent"]

    def test_double_attribution_is_surfaced_not_clamped(self):
        """Steps claiming more wall than the window exists -> negative
        residual -> consistent=False. The ledger never invents time."""
        clk = FakeClock(0.0)
        led = GoodputLedger(clock=clk)
        clk.t = 1.0
        w = led.close_window([(2.0, False, 0.0)])
        assert w["other_s"] < 0
        assert not w["consistent"]

    def test_windows_are_contiguous(self):
        clk = FakeClock(0.0)
        led = GoodputLedger(clock=clk)
        clk.t = 2.0
        w1 = led.close_window([])
        clk.t = 3.5
        w2 = led.close_window([])
        assert w1["window_s"] == pytest.approx(2.0)
        assert w2["window_s"] == pytest.approx(1.5)   # opened at t=2.0
        s = led.summary()
        assert s["windows"] == 2
        assert s["total_window_s"] == pytest.approx(3.5)

    def test_noted_buckets_reset_per_window(self):
        clk = FakeClock(0.0)
        led = GoodputLedger(clock=clk)
        led.note("data_stall", 0.5)
        clk.t = 1.0
        assert led.close_window([])["data_stall_s"] == pytest.approx(0.5)
        clk.t = 2.0
        assert led.close_window([])["data_stall_s"] == 0.0

    def test_summary_goodput_fraction(self):
        clk = FakeClock(0.0)
        led = GoodputLedger(clock=clk)
        clk.t = 1.0
        led.close_window([(0.6, False, 0.0)])
        s = led.summary()
        assert s["goodput_fraction"] == pytest.approx(0.6)

    def test_extract_step_info(self):
        assert extract_step_info({"wall_ms": 500.0, "overflow": False}) \
            == (0.5, False, 0.0)
        rec = {"wall_ms": 1000.0, "overflow": True,
               "offload": {"wall_ms": 1000.0, "device_step_ms": 400.0}}
        wall, ovf, exposed = extract_step_info(rec)
        assert wall == 1.0 and ovf
        assert exposed == pytest.approx(0.6)
        # missing device timing -> no exposed attribution (not negative)
        assert extract_step_info(
            {"wall_ms": 10.0, "offload": {"wall_ms": 10.0}})[2] == 0.0


# --------------------------------------------------------------------- #
# Bench gate (tools/bench_gate.py)
# --------------------------------------------------------------------- #
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_bench_gate():
    spec = importlib.util.spec_from_file_location(
        "bench_gate", os.path.join(REPO, "tools", "bench_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchGate:
    def _write(self, tmp_path, name, doc):
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        return str(p)

    def test_extract_metrics_all_shapes(self):
        bg = load_bench_gate()
        none_srv = {"serve_tps": None, "ttft_p95": None,
                    "kernel_speedup": None, "tile_speedup": None,
                    "zero3_overlap": None,
                    "health": None, "hbm_per_token": None,
                    "accept_rate": None, "moe_drop": None,
                    "dcn_bytes": None, "ckpt_share": None,
                    "ckpt_every": None, "attend_ratio": None,
                    "z3_dcn_bytes": None, "z3_dcn_param": None,
                    "slo_attainment": None, "ledger_consistent": None}
        # driver round file wrapping a bench record
        m = bg.extract_metrics({"n": 6, "parsed": {"mfu": 0.55}})
        assert m == {"mfu": 0.55, "goodput": None, **none_srv}
        # raw bench record
        assert bg.extract_metrics({"mfu": 0.5})["mfu"] == 0.5
        # TELEMETRY.json: fenced window figure wins
        m = bg.extract_metrics({
            "mfu": {"window_mfu": 0.4, "per_step_p50": 0.3},
            "goodput": {"goodput_fraction": 0.9}})
        assert m == {"mfu": 0.4, "goodput": 0.9, **none_srv}
        # SERVE_BENCH.json / serving-mode TELEMETRY.json
        m = bg.extract_metrics({"serving": {
            "tokens_per_s": 85.3, "ttft_ms": {"p50": 10.0, "p95": 20.0}}})
        assert m["serve_tps"] == 85.3 and m["ttft_p95"] == 20.0
        # pre-MFU / pre-serving round: nothing extractable
        assert bg.extract_metrics({"parsed": {"value": 100.0}}) == \
            {"mfu": None, "goodput": None, **none_srv}

    def test_gate_serving_rounds(self, tmp_path):
        """Serving tokens/s drop and TTFT p95 rise gate; pre-serving
        rounds skip, never fail."""
        bg = load_bench_gate()
        old = self._write(tmp_path, "old.json", {"serving": {
            "tokens_per_s": 100.0, "ttft_ms": {"p95": 100.0}}})
        ok = self._write(tmp_path, "ok.json", {"serving": {
            "tokens_per_s": 95.0, "ttft_ms": {"p95": 110.0}}})
        slow = self._write(tmp_path, "slow.json", {"serving": {
            "tokens_per_s": 80.0, "ttft_ms": {"p95": 100.0}}})
        laggy = self._write(tmp_path, "laggy.json", {"serving": {
            "tokens_per_s": 100.0, "ttft_ms": {"p95": 200.0}}})
        pre = self._write(tmp_path, "pre.json", {"mfu": 0.5})
        assert bg.main([old, ok]) == 0
        assert bg.main([old, slow]) == 1
        assert bg.main([old, laggy]) == 1
        assert bg.main([pre, old]) == 0        # pre-serving round skips

    def test_gate_checkpoint_exposed_share(self, tmp_path):
        """Resilience rounds gate the checkpoint-EXPOSED goodput share
        (new side, absolute ceiling); pre-resilience rounds skip, never
        fail. Both carrier shapes parse: RESILIENCE_BENCH.json's
        top-level record and a TELEMETRY.json goodput sub-dict."""
        bg = load_bench_gate()
        m = bg.extract_metrics({"checkpoint": {
            "snapshot_every": 50, "exposed_share": 0.008,
            "exposed_s": 0.01}})
        assert m["ckpt_share"] == 0.008 and m["ckpt_every"] == 50
        m = bg.extract_metrics({"goodput": {
            "goodput_fraction": 0.96,
            "checkpoint": {"exposed_share": 0.01, "exposed_s": 0.02,
                           "snapshot_every": 50}}})
        assert m["ckpt_share"] == 0.01
        # A non-checkpointing run (zero exposed wall) carries no gateable
        # share — it must skip, not trivially pass forever.
        m = bg.extract_metrics({"goodput": {
            "goodput_fraction": 0.96,
            "checkpoint": {"exposed_share": 0.0, "exposed_s": 0.0}}})
        assert m["ckpt_share"] is None
        old = self._write(tmp_path, "old.json", {"mfu": 0.5})
        ok = self._write(tmp_path, "ck_ok.json", {"checkpoint": {
            "snapshot_every": 50, "exposed_share": 0.008,
            "exposed_s": 0.01}})
        bad = self._write(tmp_path, "ck_bad.json", {"checkpoint": {
            "snapshot_every": 50, "exposed_share": 0.12,
            "exposed_s": 0.5}})
        assert bg.main([old, ok]) == 0
        assert bg.main([old, bad]) == 1
        assert bg.main([ok, old]) == 0         # pre-resilience new side

    def test_extract_paged_serving_fields(self):
        bg = load_bench_gate()
        m = bg.extract_metrics({"serving": {
            "tokens_per_s": 900.0,
            "ttft_ms": {"p95": 50.0},
            "hbm_bytes_per_token": {"p50": 1200.0, "p95": 1400.0},
            "spec": {"proposed": 100, "accepted": 80,
                     "acceptance_rate": 0.8}}})
        assert m["hbm_per_token"] == 1200.0
        assert m["accept_rate"] == 0.8
        # Slot-major serving record: paged fields absent -> None.
        m = bg.extract_metrics({"serving": {"tokens_per_s": 50.0}})
        assert m["hbm_per_token"] is None and m["accept_rate"] is None

    def test_gate_hbm_bytes_per_token(self, tmp_path):
        """HBM/token regresses on a RISE; pre-paging rounds skip."""
        bg = load_bench_gate()
        old = self._write(tmp_path, "old.json", {"serving": {
            "hbm_bytes_per_token": {"p50": 1000.0}}})
        ok = self._write(tmp_path, "ok.json", {"serving": {
            "hbm_bytes_per_token": {"p50": 1100.0}}})
        fat = self._write(tmp_path, "fat.json", {"serving": {
            "hbm_bytes_per_token": {"p50": 1300.0}}})
        pre = self._write(tmp_path, "pre.json", {"serving": {
            "tokens_per_s": 50.0}})
        assert bg.main([old, ok]) == 0
        assert bg.main([old, fat]) == 1
        assert bg.main([old, fat, "--hbm-rise", "0.5"]) == 0
        assert bg.main([pre, old]) == 0        # pre-paging round skips
        assert bg.main([old, pre]) == 0

    def test_gate_spec_acceptance(self, tmp_path):
        """Acceptance gates on the new-side floor and on a relative
        drop vs the previous round; pre-spec rounds skip."""
        bg = load_bench_gate()

        def srv(rate):
            return {"serving": {"spec": {"acceptance_rate": rate}}}

        old = self._write(tmp_path, "old.json", srv(0.8))
        ok = self._write(tmp_path, "ok.json", srv(0.75))
        collapsed = self._write(tmp_path, "collapsed.json", srv(0.02))
        dropped = self._write(tmp_path, "dropped.json", srv(0.5))
        pre = self._write(tmp_path, "pre.json", {"serving": {
            "tokens_per_s": 50.0}})
        assert bg.main([old, ok]) == 0
        assert bg.main([old, collapsed]) == 1      # under the floor
        assert bg.main([old, dropped]) == 1        # >10% rel drop
        assert bg.main([pre, ok]) == 0             # floor-only check
        assert bg.main([old, pre]) == 0            # pre-spec skips

    def test_gate_passes_within_threshold(self, tmp_path):
        bg = load_bench_gate()
        old = self._write(tmp_path, "old.json", {"mfu": 0.50})
        new = self._write(tmp_path, "new.json", {"mfu": 0.47})
        assert bg.main([old, new, "--mfu-drop", "0.10"]) == 0

    def test_gate_fails_on_mfu_regression(self, tmp_path):
        bg = load_bench_gate()
        old = self._write(tmp_path, "old.json", {"mfu": 0.50})
        new = self._write(tmp_path, "new.json", {"mfu": 0.40})
        assert bg.main([old, new, "--mfu-drop", "0.10"]) == 1

    def test_gate_tile_speedup(self, tmp_path):
        """--tile-drop gates kernels.tile_speedup;
        pre-autotune rounds skip, never fail."""
        bg = load_bench_gate()
        old = self._write(tmp_path, "old.json",
                          {"kernels": {"tile_speedup": 1.20}})
        bad = self._write(tmp_path, "bad.json",
                          {"kernels": {"tile_speedup": 1.00}})
        ok = self._write(tmp_path, "ok.json",
                         {"kernels": {"tile_speedup": 1.15}})
        pre = self._write(tmp_path, "pre.json", {"mfu": 0.5})
        assert bg.extract_metrics(
            {"kernels": {"tile_speedup": 1.2}})["tile_speedup"] == 1.2
        assert bg.main([old, ok, "--tile-drop", "0.10"]) == 0
        assert bg.main([old, bad, "--tile-drop", "0.10"]) == 1
        assert bg.main([old, bad, "--tile-drop", "0.20"]) == 0
        # Pre-autotune rounds on either side: skipped, never failed.
        assert bg.main([pre, pre]) == 0

    def test_gate_attend_work_ratio(self, tmp_path):
        """--attend-drop gates serving.attend_work_ratio (the paged-
        attention kernel's structural one-hot/kernel HBM ratio — a DROP
        means decode attend work crept back toward pool capacity);
        pre-kernel rounds on either side skip, never fail."""
        bg = load_bench_gate()

        def srv(ratio):
            return {"serving": {"attend_work_ratio": ratio,
                                "tokens_per_s": 50.0}}

        old = self._write(tmp_path, "old.json", srv(3.5))
        ok = self._write(tmp_path, "ok.json", srv(3.3))
        bad = self._write(tmp_path, "bad.json", srv(2.0))
        pre = self._write(tmp_path, "pre.json",
                          {"serving": {"tokens_per_s": 50.0}})
        assert bg.extract_metrics(srv(3.5))["attend_ratio"] == 3.5
        assert bg.extract_metrics(
            {"serving": {"tokens_per_s": 1.0}})["attend_ratio"] is None
        assert bg.main([old, ok]) == 0
        assert bg.main([old, bad]) == 1
        assert bg.main([old, bad, "--attend-drop", "0.60"]) == 0
        assert bg.main([pre, old]) == 0        # pre-kernel old side
        assert bg.main([old, pre]) == 0        # pre-kernel new side

    def test_gate_fails_on_goodput_regression(self, tmp_path):
        bg = load_bench_gate()
        old = self._write(tmp_path, "old.json",
                          {"goodput": {"goodput_fraction": 0.90}})
        new = self._write(tmp_path, "new.json",
                          {"goodput": {"goodput_fraction": 0.80}})
        assert bg.main([old, new, "--goodput-drop", "0.05"]) == 1
        assert bg.main([old, new, "--goodput-drop", "0.15"]) == 0

    def test_missing_metric_skips_never_fails(self, tmp_path):
        """Rounds recorded before the mfu field existed must pass."""
        bg = load_bench_gate()
        old = self._write(tmp_path, "old.json", {"parsed": {"value": 1.0}})
        new = self._write(tmp_path, "new.json", {"mfu": 0.5})
        assert bg.main([old, new]) == 0

    def test_latest_rounds_discovery(self, tmp_path):
        bg = load_bench_gate()
        for name in ("BENCH_r01.json", "BENCH_r02.json",
                     "BENCH_r10.json", "BENCH_r04_builder.json"):
            self._write(tmp_path, name, {})
        pair = bg.latest_rounds(str(tmp_path))
        assert [os.path.basename(p) for p in pair] == \
            ["BENCH_r02.json", "BENCH_r10.json"]   # numeric, no _builder
        assert bg.main(["--dir", str(tmp_path)]) == 0   # nothing comparable


# --------------------------------------------------------------------- #
# Optimizer-apply analytic pricing (one-pass vs two-pass HBM bytes)
# --------------------------------------------------------------------- #
class TestOptimizerApplyPricing:
    def test_fp16_two_pass_is_over_double(self):
        """The ISSUE-8 acceptance arithmetic, HONEST accounting: under
        fp16 the historical two-pass sequencing really paid the unscale
        read+write, the tree_has_inf_or_nan re-read, AND a traced
        overflow select over old+new p/m/v — >2x the one-pass bytes.
        (For non-fp16 the select was a folded constant; no saving is
        claimed there.)"""
        from deepspeed_tpu.ops.fused_update import apply_hbm_bytes
        params = {"w": jnp.zeros((1000, 1000), jnp.float32),
                  "b": jnp.zeros((1000,), jnp.float32)}
        pricing = apply_hbm_bytes(params, one_pass=True, fp16=True,
                                  cast_dtype=jnp.bfloat16, clip=True)
        assert pricing["active"] == pricing["one_pass"]
        assert pricing["ratio_two_over_one"] >= 2.0, pricing
        n = 1000 * 1000 + 1000
        # one-pass: apply kernel (g4 + p4 + mv8 read, p4 + mv8 write,
        # cast2 write) + the sqnorm re-read of g (the norm is NOT free
        # in one-pass mode — it is a wash with two-pass's norm read).
        assert pricing["one_pass"] == n * (4 + 12 + 12 + 2 + 4)

    def test_norm_wash_and_foldable_select_claim_nothing(self):
        """clip toggles the norm read on BOTH sides (a wash); non-fp16
        overflow select is priced at zero (XLA folds it); master-free
        bf16 without clip is byte-NEUTRAL between the modes."""
        from deepspeed_tpu.ops.fused_update import apply_hbm_bytes
        params = {"w": jnp.zeros((512, 512), jnp.bfloat16)}
        n = 512 * 512
        off = apply_hbm_bytes(params, clip=False)
        on = apply_hbm_bytes(params, clip=True)
        assert on["one_pass"] - off["one_pass"] == 4 * n
        assert on["two_pass"] - off["two_pass"] == 4 * n
        # the r05 bench shape: no clip, no fp16, no cast — modes equal
        assert off["ratio_two_over_one"] == 1.0, off

    def test_cast_pass_prices_only_the_reread(self):
        from deepspeed_tpu.ops.fused_update import apply_hbm_bytes
        params = {"w": jnp.zeros((512, 512), jnp.float32)}
        n = 512 * 512
        base = apply_hbm_bytes(params, clip=True)
        cast = apply_hbm_bytes(params, clip=True, cast_dtype=jnp.bfloat16)
        # cast write (2B) exists in BOTH modes; two-pass adds only the
        # updated-param re-read (4B) of the standalone cast pass.
        assert cast["one_pass"] - base["one_pass"] == 2 * n
        assert cast["two_pass"] - base["two_pass"] == (2 + 4) * n

    @pytest.mark.parametrize("grad_dtype,g_width", [
        (None, 2), (jnp.bfloat16, 2), (jnp.float32, 4)],
        ids=["as_the_parameter", "bf16", "f32"])
    def test_gradient_priced_at_the_width_it_arrives(self, grad_dtype,
                                                     g_width):
        """An in-place leaf's gradient is read at the width it reaches
        the kernel (default: the parameter's own, what a backward through
        it writes) — once by the kernel, once more by the norm; a packed
        leaf's at 4 B whatever arrives (the group buffer flattens in
        f32)."""
        from deepspeed_tpu.ops.fused_update import apply_hbm_bytes
        params = {"w": jnp.zeros((1024, 512), jnp.bfloat16),    # in place
                  "b": jnp.zeros((1000,), jnp.bfloat16)}        # packed
        n_w, n_b = 1024 * 512, 1000
        got = apply_hbm_bytes(params, clip=True, grad_dtype=grad_dtype)
        assert got["one_pass"] == \
            n_w * (2 * g_width + 2 + 2 + 16) + n_b * (2 * 4 + 2 + 2 + 16)
        off = apply_hbm_bytes(params, clip=False, grad_dtype=grad_dtype)
        assert got["one_pass"] - off["one_pass"] == n_w * g_width + n_b * 4

    def test_engine_payload_carries_one_pass_mode(self, tmp_path):
        """The dp=8 ZeRO-2 fused engine's cost model payload reports the
        apply path at one-pass pricing with the ~2x alternative ratio —
        the roofline acceptance record for the halved optimizer bytes."""
        from deepspeed_tpu.runtime.engine import DeepSpeedEngine
        from deepspeed_tpu.parallel.topology import build_mesh

        def loss_fn(params, batch, rng):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        params = {"w": jnp.zeros((32, 8), jnp.float32)}
        eng = DeepSpeedEngine(
            model=loss_fn, model_params=params,
            config={
                "train_batch_size": 16,
                "train_micro_batch_size_per_gpu": 2,
                "gradient_clipping": 1.0,
                "optimizer": {"type": "AdamW",
                              "params": {"lr": 1e-3, "fused": True}},
                "zero_optimization": {"stage": 2},
                "bf16": {"enabled": True},
                "steps_per_print": 10 ** 9,
                "telemetry": {"enabled": True,
                              "output_path": str(tmp_path),
                              "job_name": "oap",
                              "report_steps": 10 ** 9},
            }, mesh=build_mesh())
        r = np.random.default_rng(0)
        batch = (jnp.asarray(r.standard_normal((16, 32)), jnp.float32),
                 jnp.asarray(r.standard_normal((16, 8)), jnp.float32))
        eng.train_batch(batch)
        eng._maybe_build_cost_model()
        payload = eng.telemetry.cost_model_payload
        assert payload is not None
        oap = payload.get("optimizer_apply")
        assert oap is not None and oap["mode"] == "one_pass"
        # bf16 + fp32 masters + clip: the honest delta is the standalone
        # cast pass's param re-read — a modest >1.0 ratio (the ~2.5x
        # class is fp16-only; master-free bf16 is 1.0).
        assert oap["per_replica"]["ratio_two_over_one"] > 1.05
        assert oap["per_replica"]["active"] == \
            oap["per_replica"]["one_pass"]
        assert oap["zero_shard_divisor"] == 8
        assert oap["active_bytes_per_device"] * 8 <= \
            oap["per_replica"]["active"] + 8
        eng.telemetry.close()


class TestBenchGateKernels:
    def _write(self, tmp_path, name, doc):
        import json as _json
        p = tmp_path / name
        p.write_text(_json.dumps(doc))
        return str(p)

    def test_kernel_speedup_extracted_and_gated(self, tmp_path):
        bg = load_bench_gate()
        assert bg.extract_metrics(
            {"kernels": {"fused_speedup": 1.2}})["kernel_speedup"] == 1.2
        assert bg.extract_metrics(
            {"parsed": {"kernels": {"fused_speedup": 1.1}}}
        )["kernel_speedup"] == 1.1
        old = self._write(tmp_path, "old.json",
                          {"kernels": {"fused_speedup": 1.20}})
        bad = self._write(tmp_path, "bad.json",
                          {"kernels": {"fused_speedup": 1.00}})
        ok = self._write(tmp_path, "ok.json",
                         {"kernels": {"fused_speedup": 1.15}})
        assert bg.main([old, bad]) == 1          # -17% rel: regression
        assert bg.main([old, ok]) == 0           # -4% rel: within floor

    def test_pre_kernel_rounds_skip_never_fail(self, tmp_path):
        bg = load_bench_gate()
        old = self._write(tmp_path, "old.json", {"mfu": 0.5})
        new = self._write(tmp_path, "new.json",
                          {"mfu": 0.5,
                           "kernels": {"fused_speedup": 1.03}})
        assert bg.main([old, new]) == 0

    def test_recorded_r07_gates_against_r06(self):
        """The in-tree BENCH_r06 -> BENCH_r07 pair must pass the gate
        (both are honestly-labeled projected kernel rounds)."""
        import json as _json
        bg = load_bench_gate()
        r6 = os.path.join(REPO, "BENCH_r06.json")
        r7 = os.path.join(REPO, "BENCH_r07.json")
        assert bg.main([r6, r7]) == 0
        for path in (r6, r7):
            rec = _json.load(open(path))["parsed"]
            assert rec.get("projected") is True      # honesty label
            assert rec["kernels"]["fused_speedup"] > 1.0

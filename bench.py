"""Benchmark: GPT-2 training throughput on a TPU.

Exits non-zero without a TPU: a timing from the CPU backend or the
Pallas interpreter says nothing about the system.  Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The headline metric is model FLOPs utilisation-bearing throughput —
tokens/sec and TFLOPs/chip on a GPT-2 training step (ZeRO-2 + bf16), the
reference's own yardstick (SURVEY §6: DeepSpeed reports 64 TFLOPs/V100 ≈ 50%
of peak on its fused BERT kernels; `vs_baseline` is our achieved fraction of
peak vs their 0.50 fraction of peak).
"""
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def require_tpu():
    """The bench measures a chip or nothing: no CPU stand-in."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py needs a TPU; jax found platform "
                 f"{dev.platform!r} ({dev.device_kind})")


def pick_model():
    """The benchmark model: GPT-2 large, the largest ladder config whose
    full fp32 Adam state fits one chip's HBM (gpt2-xl at 1.5B needs
    18.7 GB of optimizer state alone — the reference pairs 1.5B with
    ZeRO-Offload for the same reason, BASELINE.json configs[3]).
    Unrolled layers + chunked CE head."""
    from deepspeed_tpu.models import GPT2_CONFIGS
    return dataclasses.replace(
        GPT2_CONFIGS["gpt2-large"], max_seq_length=1024,
        # NO remat + master-free bf16 (DS_BENCH_SR): stochastic rounding
        # drops the fp32 masters AND the cast cache — exactly the HBM
        # that lets remat=none fit at mbs=4. dots_flash remains the
        # fp32-master setting (DS_BENCH_SR=0 flips remat back too).
        remat_policy=os.environ.get(
            "DS_BENCH_REMAT",
            "none" if os.environ.get("DS_BENCH_SR", "1") == "1"
            else "dots_flash"),
        hidden_dropout=0.0, attn_dropout=0.0,
        scan_layers=False), int(os.environ.get("DS_BENCH_MBS", "4"))


# The chip peak table lives in monitor/peaks.py — ONE source of truth
# shared with the roofline cost model, env_report, and the bench gate.
# chip_peak_tflops() raises off-TPU and on a TPU kind with no row.
from deepspeed_tpu.monitor.peaks import (TPU_PEAK_TFLOPS,   # noqa: F401
                                         chip_peak_tflops)


def bench_offload_xl(gas: int = 1, n_steps: int = 2,
                     overlap: bool = None, host_threads: int = None,
                     bucket_mb: int = None):
    """North-star config (BASELINE.json): GPT-2 1.5B on ONE chip via
    ZeRO-Offload — full fp32 Adam state (17 GB) in host RAM, C++ SIMD Adam,
    bf16 grads D2H / params H2D each step. The reference's flagship
    ZeRO-Offload claim is exactly this shape of run (13B-on-one-V100,
    docs/_posts/2020-09-09-ZeRO-Offload.md:10).

    ``overlap`` (default env DS_BENCH_OFFLOAD_OVERLAP, on) selects the
    bucketed overlapped pipeline; False reproduces the serial numbers.
    ``host_threads``/``bucket_mb`` map to the zero_optimization knobs.

    NOT run inside the default bench (each offload step is host-Adam
    bound — tens of seconds at 1.5B): DS_BENCH_OFFLOAD=1 adds a live run
    to the headline line."""
    import dataclasses
    from deepspeed_tpu.models import GPT2_CONFIGS, gpt2_init, gpt2_loss_fn
    from deepspeed_tpu.models.gpt2 import gpt2_flops_per_token
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.parallel.topology import build_mesh

    if overlap is None:
        overlap = os.environ.get("DS_BENCH_OFFLOAD_OVERLAP", "1") == "1"
    if host_threads is None:
        host_threads = int(os.environ.get("DS_BENCH_OFFLOAD_THREADS", "0"))
    if bucket_mb is None:
        bucket_mb = int(os.environ.get("DS_BENCH_OFFLOAD_BUCKET_MB", "64"))
    cfg = dataclasses.replace(
        GPT2_CONFIGS["gpt2-xl"], max_seq_length=1024,
        remat_policy="dots", hidden_dropout=0.0, attn_dropout=0.0,
        # scan_layers: one compiled block (a 48-layer unroll at 1.5B
        # overwhelms the AOT compiler); offload throughput is transfer-
        # dominated regardless.
        scan_layers=True)
    micro_bs = 4
    # One-chip bench by definition (the flagship claim is big-model-on-ONE-
    # device); a full-host mesh would also break the batch triple at dp>1.
    mesh = build_mesh(devices=jax.devices()[:1])
    # Init the masters host-side: the offload engine keeps fp32 state in
    # host RAM anyway, so a device init would only be copied back.
    with jax.default_device(jax.devices("cpu")[0]):
        params = gpt2_init(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    ds_config = {
        "train_batch_size": micro_bs * gas,
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2, "cpu_offload": True,
                              "overlap_comm": overlap,
                              "offload_bucket_size": bucket_mb * 2 ** 20,
                              "offload_host_threads": host_threads},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "steps_per_print": 10 ** 9,
    }
    engine = DeepSpeedEngine(model=gpt2_loss_fn(cfg), model_params=params,
                             config=ds_config, mesh=mesh)
    del params
    S = cfg.max_seq_length
    batch = jnp.asarray(np.random.randint(
        0, cfg.vocab_size, size=(micro_bs * gas, S + 1), dtype=np.int32))
    engine.train_batch(batch)      # compile + first host step
    t0 = time.perf_counter()
    for _ in range(n_steps):
        engine.train_batch(batch)  # offload steps are host-synchronous
    dt = (time.perf_counter() - t0) / n_steps
    tokens_per_sec = micro_bs * gas * S / dt
    tflops = tokens_per_sec * gpt2_flops_per_token(cfg, S) / 1e12
    t = dict(engine.offload_timings or {})
    # Scalar phase components only (the per-bucket lists and pipeline
    # metadata ride alongside, not in the reconciliation sum).
    comp = {k: v for k, v in t.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
            and k.endswith("_ms") and k not in
            ("wall_ms", "pipeline_span_ms", "pipeline_work_ms",
             "d2h_reshard_ms")}   # reshard is already folded into d2h_ms
    comp_sum_ms = sum(comp.values())

    # Device-only step: params are resident and no H2D is pending after the
    # timed loop, so a bare grads pass fenced by the loss fetch is pure
    # compute — the number the round-4 record could not support.
    micro = engine._stack_micro_batches(batch)
    # Fence the last step's async param upload — without this the grad
    # pass blocks on the in-flight H2D and "device only" absorbs it.
    jax.block_until_ready(engine.state.params)
    t_dev = time.perf_counter()
    _, loss = engine._offload_grad_fn(
        engine.state.params, micro, engine._base_rng,
        jnp.asarray(engine.global_steps, jnp.int32),
        jnp.asarray(engine._offload.loss_scale, jnp.float32))
    _ = float(jax.device_get(loss))
    device_only_ms = (time.perf_counter() - t_dev) * 1e3

    # Transfer byte accounting (what moves host<->device each step):
    # bf16 grads down, bf16 params up.
    grad_bytes = sum(int(np.prod(l.shape)) * 2 for l in
                     jax.tree_util.tree_leaves(engine.state.params))
    # Projection at a stated host<->device bandwidth: same measured
    # device compute + host Adam, transfers at 10 GB/s.
    vm_gbs = 10.0
    xfer_ms = 2 * grad_bytes / (vm_gbs * 1e9) * 1e3      # D2H + H2D
    host_work_ms = t.get("host_step_ms", 0.0) + t.get("host_norm_ms", 0.0)
    serial_ms = device_only_ms + xfer_ms + host_work_ms
    # Threads beyond this host's physical cores can't scale the host Adam
    # (the projection models THIS host with a real link, so the local core
    # count is the honest cap even if the knob asks for more).
    threads = min(engine._offload.host_threads, os.cpu_count() or 1)
    if overlap:
        # Overlapped shape: transfers hide behind host Adam (or vice
        # versa), host Adam spreads over the worker pool — device +
        # max(host/threads, transfers), NOT the serial sum. The recorded
        # overlap_fraction is the measured evidence that the pipeline
        # actually hides work.
        proj_ms = device_only_ms + max(host_work_ms / max(1, threads),
                                       xfer_ms)
    else:
        proj_ms = serial_ms
    proj_tps = micro_bs * gas * S / (proj_ms / 1e3)
    return {
        "offload_model": f"gpt2-xl({n_params/1e9:.2f}B)",
        "offload_grad_accum_steps": gas,
        "offload_tokens_per_sec": round(tokens_per_sec, 1),
        "offload_tflops_per_chip": round(tflops, 2),
        "offload_step_wall_ms": round(dt * 1e3, 1),
        "offload_components_ms": {k: round(v, 1) for k, v in comp.items()},
        "offload_components_sum_ms": round(comp_sum_ms, 1),
        "offload_device_only_step_ms": round(device_only_ms, 1),
        "offload_transfer_bytes_each_way": grad_bytes,
        "offload_overlap": {
            "enabled": overlap,
            "host_threads": threads,
            "bucket_mb": bucket_mb,
            "num_buckets": t.get("num_buckets", 1),
            "overlap_fraction": round(t.get("overlap_fraction", 0.0), 4),
            "pipeline_span_ms": round(t.get("pipeline_span_ms", 0.0), 1),
            "pipeline_work_ms": round(t.get("pipeline_work_ms", 0.0), 1),
        },
        "projected_tpu_vm": {
            "assumed_host_link_gb_s": vm_gbs,
            "step_ms": round(proj_ms, 1),
            "tokens_per_sec": round(proj_tps, 1),
            "serial_step_ms": round(serial_ms, 1),
            "formula": "device + max(host/threads, transfers)" if overlap
                       else "device + transfers + host",
        },
    }


def bench_telemetry_overhead(n_steps: int = 40):
    """DS_BENCH_TELEMETRY=1: telemetry enabled-vs-disabled step-time
    overhead (design target < 1%) plus the instrumented device-fence
    counts, on gpt2-tiny. The tiny model makes the denominator a FAST
    step, so the measured fraction is a conservative upper bound for
    real models; equal fence counts are the hard part of the claim (the
    subsystem must add zero per-step host↔device syncs)."""
    import dataclasses
    import tempfile
    from deepspeed_tpu.models import GPT2_CONFIGS, gpt2_init, gpt2_loss_fn
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.parallel.topology import build_mesh
    import deepspeed_tpu.utils.timer as timer_mod

    cfg = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"],
                              hidden_dropout=0.0, attn_dropout=0.0)
    micro_bs = 4
    n_chips = jax.device_count()
    S = cfg.max_seq_length
    batch = jnp.asarray(np.random.randint(
        0, cfg.vocab_size, size=(micro_bs * n_chips, S + 1), dtype=np.int32))

    def run(enabled: bool):
        tmp = tempfile.mkdtemp(prefix="ds_bench_telemetry_")
        ds = {
            "train_batch_size": micro_bs * n_chips,
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": 1,
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "steps_per_print": 10 ** 9,
            # report_steps beyond the run: the timed window contains pure
            # hot-path cost, no drain (drains are boundary work by design).
            "telemetry": {"enabled": enabled, "output_path": tmp,
                          "report_steps": 10 ** 9},
        }
        engine = DeepSpeedEngine(model=gpt2_loss_fn(cfg),
                                 model_params=gpt2_init(
                                     jax.random.PRNGKey(0), cfg),
                                 config=ds, mesh=build_mesh())
        for _ in range(4):
            engine.train_batch(batch)
        float(jax.device_get(engine.state.loss_scale))
        sync0 = timer_mod.device_sync_count()
        t0 = time.perf_counter()
        for _ in range(n_steps):
            engine.train_batch(batch)
        float(jax.device_get(engine.state.loss_scale))
        dt_ms = (time.perf_counter() - t0) / n_steps * 1e3
        syncs = timer_mod.device_sync_count() - sync0
        engine.telemetry.close()
        return dt_ms, syncs

    off_ms, off_syncs = run(False)
    on_ms, on_syncs = run(True)
    return {
        "step_ms_disabled": round(off_ms, 4),
        "step_ms_enabled": round(on_ms, 4),
        "overhead_fraction": round((on_ms - off_ms) / max(off_ms, 1e-9), 4),
        "device_syncs_per_run": {"disabled": off_syncs, "enabled": on_syncs},
        "added_device_syncs": on_syncs - off_syncs,
        "n_steps": n_steps,
        "note": "gpt2-tiny denominator — overhead_fraction is a "
                "conservative upper bound for real model sizes, and on "
                "noisy dev hosts it is run-to-run jitter-dominated "
                "(per-step telemetry work is a deque append, ~µs); "
                "added_device_syncs == 0 is the hard claim",
    }


def bench_kernels_ablation(n_steps: int = None):
    """DS_BENCH_KERNELS=1: the ISSUE-8 ablation grid — fused vs unfused
    elementwise kernels (LayerNorm's: the FFN's bias + GELU is one
    expression on both sides) x one-pass vs two-pass optimizer update — on
    the bench model (gpt2-large, on the TPU).

    ``fused_speedup`` (unfused-elementwise two-pass step over fully-fused
    step) is the figure tools/bench_gate.py gates across rounds.
    """
    import dataclasses as _dc
    from deepspeed_tpu.models import gpt2_init, gpt2_loss_fn
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.parallel.topology import build_mesh

    cfg0, micro_bs = pick_model()
    if n_steps is None:
        n_steps = 10
    n_chips = jax.device_count()
    mesh = build_mesh()
    S = cfg0.max_seq_length
    batch = jnp.asarray(np.random.randint(
        0, cfg0.vocab_size, size=(micro_bs * n_chips, S + 1),
        dtype=np.int32))

    def run(fused_ln: bool, one_pass: bool):
        cfg = _dc.replace(cfg0, fused_kernels=fused_ln)
        ds = {
            "train_batch_size": micro_bs * n_chips,
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": 1,
            "gradient_clipping": 1.0,
            "bf16": {"enabled": True,
                     "stochastic_rounding":
                         os.environ.get("DS_BENCH_SR", "1") == "1"},
            "zero_optimization": {"stage": 2},
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-4, "fused": True}},
            "steps_per_print": 10 ** 9,
        }
        engine = DeepSpeedEngine(model=gpt2_loss_fn(cfg),
                                 model_params=gpt2_init(
                                     jax.random.PRNGKey(0), cfg),
                                 config=ds, mesh=mesh)
        if not one_pass:
            # Ablation-only switch: drop back to the historical two-pass
            # sequencing (separate norm read + post-apply select/cast)
            # while keeping the same fused apply kernel. The train step
            # builds lazily, so clearing this BEFORE the first batch is
            # authoritative — assert that invariant so a future eager
            # build turns this into a loud failure, not a silent no-op
            # arm measuring the wrong thing.
            assert engine._train_step_fn is None, \
                "train step already built; two-pass ablation arm invalid"
            engine._fused_step = None
        for _ in range(3):
            engine.train_batch(batch)
        jax.block_until_ready(engine.state)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            engine.train_batch(batch)
        jax.block_until_ready(engine.state)
        return (time.perf_counter() - t0) / n_steps * 1e3

    grid = {
        "fused_ln+one_pass": run(True, True),
        "fused_ln+two_pass": run(True, False),
        "unfused_ln+one_pass": run(False, True),
        "unfused_ln+two_pass": run(False, False),
    }
    base = grid["unfused_ln+two_pass"]
    best = grid["fused_ln+one_pass"]
    return {
        "model": f"{cfg0.hidden_size}x{cfg0.num_layers}",
        "step_ms": {k: round(v, 2) for k, v in grid.items()},
        "fused_speedup": round(base / max(best, 1e-9), 4),
        "one_pass_only_speedup": round(
            grid["fused_ln+two_pass"] / max(best, 1e-9), 4),
        "elementwise_only_speedup": round(
            grid["unfused_ln+one_pass"] / max(best, 1e-9), 4),
        "measured_on": jax.devices()[0].platform,
    }


def main():
    require_tpu()
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from deepspeed_tpu.models import gpt2_init, gpt2_loss_fn
    from deepspeed_tpu.models.gpt2 import gpt2_flops_per_token
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.parallel.topology import build_mesh

    cfg, micro_bs = pick_model()
    n_chips = jax.device_count()
    mesh = build_mesh()  # pure dp over all chips

    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    ds_config = {
        "train_batch_size": micro_bs * n_chips,
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": 1,
        # DS_BENCH_SR (default on): master-free bf16 with stochastic
        # rounding — drops the fp32 master copy AND the separate
        # cast-param cache, cutting optimizer-step HBM traffic (and
        # freeing the memory the remat=none default needs). Convergence
        # parity vs fp32 masters: tests/test_stochastic_rounding.py.
        "bf16": {"enabled": True,
                 "stochastic_rounding":
                     os.environ.get("DS_BENCH_SR", "1") == "1"},
        "zero_optimization": {"stage": 2},
        # DS_BENCH_FUSED (default on): single-pass Pallas multi-tensor
        # optimizer apply (ops/fused_update.py) — one HBM pass over
        # grad+param+m+v with clip + SR folded in, vs the optax chain's
        # per-leaf fusions. Parity: tests/test_fused_update.py.
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-4, "fused": os.environ.get(
                          "DS_BENCH_FUSED", "1") == "1"}},
        "steps_per_print": 10 ** 9,
    }
    engine = DeepSpeedEngine(model=gpt2_loss_fn(cfg), model_params=params,
                             config=ds_config, mesh=mesh)
    del params   # engine owns fresh buffers; don't pin 3 GB of fp32 masters

    # DS_BENCH_COMM=1: record the audited gradient-sync plan — which
    # lowering the engine actually runs (the hlo_audit probe, not the
    # docstring) and the analytic wire bytes it costs per step. This is
    # the ladder's provenance for the multi-chip scaling claim.
    dp_comm = None
    if os.environ.get("DS_BENCH_COMM") == "1":
        from deepspeed_tpu.parallel import hlo_audit
        wire = hlo_audit.grad_sync_wire_model(engine.state.params,
                                              engine.dp_size)
        mode = engine._grad_sync_mode
        declared = hlo_audit.zero2_grad_sync_lowering(engine.mesh, "data") \
            if engine.dp_size > 1 else "none"
        # Declarative mode on a regressed backend really pays the
        # all-reduce wire — record what the compiled program costs, not
        # what the declaration hoped for.
        reduce_scattered = (mode == "explicit" or
                            (mode == "declarative" and
                             declared == "reduce-scatter"))
        dp_comm = {
            "grad_sync_mode": mode,
            "declared_lowering": declared,
            "grad_wire_bytes_per_step":
                wire["reduce_scatter_wire_bytes"] if reduce_scattered
                else wire["all_reduce_wire_bytes"],
            "wire_model": wire,
        }

    S = cfg.max_seq_length
    # Device-resident batch = what an async input pipeline provides; a numpy
    # arg would be a synchronous H2D transfer inside every dispatch.
    batch = jnp.asarray(np.random.randint(
        0, cfg.vocab_size, size=(micro_bs * n_chips, S + 1), dtype=np.int32))

    # Warmup (compile) + timed steps.
    def sync():
        jax.block_until_ready(engine.state)

    # 4 warmup steps: compile + the throughput-timer's one-time window-start
    # fence (it lands at step 3; timing across it would serialize the
    # pipeline mid-measurement).
    for _ in range(4):
        engine.train_batch(batch)
    sync()
    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        engine.train_batch(batch)   # async dispatch pipelines the steps
    sync()
    dt = (time.perf_counter() - t0) / n_steps

    tokens_per_step = micro_bs * n_chips * S
    tokens_per_sec = tokens_per_step / dt
    flops_per_token = gpt2_flops_per_token(cfg, S)
    tflops_per_chip = tokens_per_sec * flops_per_token / n_chips / 1e12
    frac_peak = tflops_per_chip / chip_peak_tflops()

    # Reference fraction-of-peak: 64 TFLOPs on a 125 TFLOP V100 ≈ 0.512
    # (docs/_posts/2020-05-28-fastest-bert-training.md:15-16).
    ref_frac = 64.0 / 125.0
    record = {
        "metric": f"GPT2({cfg.hidden_size}x{cfg.num_layers}) train TFLOPs/chip",
        "value": round(tflops_per_chip, 2),
        "unit": f"TFLOPs/chip (bf16, {n_chips} chip(s), "
                f"{tokens_per_sec:,.0f} tok/s, {frac_peak:.1%} of peak)",
        "vs_baseline": round(frac_peak / ref_frac, 3),
        # Model-FLOPs utilisation against the shared monitor/peaks.py
        # table (true MFU: analytic model flops/token, remat recompute
        # excluded). tools/bench_gate.py diffs this field across rounds.
        "mfu": round(frac_peak, 4),
        # Ladder provenance: which optimizer apply produced this number.
        "fused_optimizer_apply": ds_config["optimizer"]["params"]["fused"],
    }
    if dp_comm is not None:
        record["dp_comm"] = dp_comm
    # DS_BENCH_KERNELS=1: the fused-elementwise x one/two-pass-optimizer
    # ablation grid (ISSUE 8); `kernels.fused_speedup` is gated by
    # tools/bench_gate.py across rounds. A phase that fails fails the
    # bench.
    if os.environ.get("DS_BENCH_KERNELS") == "1":
        record["kernels"] = bench_kernels_ablation()
    # DS_BENCH_TELEMETRY=1: enabled-vs-disabled telemetry overhead record
    # (<1% target + zero added device fences).
    if os.environ.get("DS_BENCH_TELEMETRY") == "1":
        record["telemetry"] = bench_telemetry_overhead()
    if os.environ.get("DS_BENCH_OFFLOAD") == "1":
        # Free the headline engine's HBM first (a live offload run needs it).
        del engine, batch
        record["extra"] = bench_offload_xl()
    record["device"] = {"platform": jax.devices()[0].platform,
                        "kind": jax.devices()[0].device_kind,
                        "count": n_chips}
    print(json.dumps(record))


if __name__ == "__main__":
    sys.exit(main())

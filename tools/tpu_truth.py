#!/usr/bin/env python
"""tpu_truth.py — the one-session TPU-truth harness (ROADMAP item 1).

One command sweeps the recorded bench ladder with a jax.profiler window
armed on each rung, reconciles every capture against the analytic
roofline, and writes ``TRUTH.json`` at the repo root enumerating every
bench artifact's measurement label:

  projected       analytic number only — no run backs it.
  cpu-structural  the identical capture->ingest->reconcile pipeline ran
                  end to end on the forced-CPU host mesh; the STRUCTURE
                  (collective schedule, bucket decomposition, sync
                  discipline) is real, the absolute walls are not TPU.
  measured        a real TPU trace backs the number. This label is only
                  ever written when ``jax.default_backend() == "tpu"``
                  AND the capture ingested successfully — never on this
                  CPU box, never on a failed capture.

``tools/bench_gate.py`` ratchets these labels: once an artifact is
``measured`` it may not silently regress to ``projected`` or lose its
reconciliation section in a later round.

Exit 0 = TRUTH.json written (labels are honest by construction, even
when individual rungs fail — failures keep the prior label and record
the error). Exit 1 = could not write TRUTH.json at all.
"""
import argparse
import glob
import json
import os
import sys

RUNBOOK = """\
THE ONE-SESSION HARDWARE RUNBOOK (run these on the TPU host, in order):

  1.  git clone <repo> && cd <repo>       # no code changes needed
  2.  python tools/tpu_truth.py           # do NOT set JAX_PLATFORMS
        - autodetects the TPU backend; the same rung runners that run
          here on CPU run there on the real mesh,
        - each rung arms a 2-step jax.profiler window, ingests the
          trace from the telemetry JSONL alone, and reconciles the
          bucket decomposition against the cost-model floors,
        - labels flip projected/cpu-structural -> measured ONLY when
          the TPU trace is actually captured and ingested.
  3.  python tools/telemetry_report.py <run>/truth_<rung>.jsonl
        # optional: inspect any rung's decomposition by hand
  4.  git add TRUTH.json && git commit    # bench_gate's label ratchet
        # now holds the line: measured stays measured.

Useful knobs:
  --only RUNG     run a single rung (kernels | zero3_prefetch | moe |
                  multislice | serving_attend); others keep their
                  prior labels.
  --steps N       train/decode steps per rung (default 10; the armed
                  window is steps 4..6 regardless).
  --out PATH      write somewhere other than <repo>/TRUTH.json.
  --keep-runs DIR keep the per-rung telemetry dirs for inspection
                  instead of a temp dir.

On this CPU box the sweep is the SAME pipeline end to end — the
hardware session is a re-run, not new code.
"""

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))


def _tpu_present() -> bool:
    if os.environ.get("JAX_PLATFORMS", "").startswith("tpu"):
        return True
    # Probe for the accelerator DEVICE, not the libtpu package — the
    # toolchain ships libtpu on CPU-only boxes too.
    return any(os.path.exists(p) for p in
               ("/dev/accel0", "/dev/vfio/0", "/sys/class/accel/accel0"))


if not _tpu_present():
    # CPU box: force the dp=8 host mesh BEFORE jax import, same as CI.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = \
            _flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

WINDOW = (4, 2)          # armed window: start_step, window_steps
DEFAULT_STEPS = 10


# ------------------------------------------------------------------ #
# Shared harness
# ------------------------------------------------------------------ #
def _summarizer():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(REPO, "tools",
                                         "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.summarize


def _tel_cfg(out_dir: str, rung: str) -> dict:
    return {"enabled": True, "output_path": out_dir,
            "job_name": f"truth_{rung}", "report_steps": 4,
            "profile": {"start_step": WINDOW[0],
                        "window_steps": WINDOW[1]}}


def _profile_of(out_dir: str, rung: str) -> dict:
    """Profile section + registered roofline paths, from the JSONL
    alone — the same no-side-channel contract profile_check enforces."""
    summary = _summarizer()(os.path.join(out_dir, f"truth_{rung}.jsonl"))
    prof = dict(summary.get("profile") or {})
    prof["registered_paths"] = sorted(
        (summary.get("roofline") or {}).get("paths") or {})
    return prof


# ------------------------------------------------------------------ #
# Rung runners — each returns the profile section for its capture
# ------------------------------------------------------------------ #
def run_kernels(out_dir: str, steps: int) -> dict:
    """Plain dp=8 data-parallel train: the kernel-round workload."""
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from simple_model import (base_config, random_batch, simple_loss_fn,
                              simple_model_params)
    cfg = base_config()
    cfg["telemetry"] = _tel_cfg(out_dir, "kernels")
    eng = DeepSpeedEngine(model=simple_loss_fn,
                          model_params=simple_model_params(
                              jax.random.PRNGKey(0)),
                          config=cfg)
    batch = random_batch(n=16)
    for _ in range(steps):
        eng.train_batch(batch=batch)
    eng.telemetry.close()
    return _profile_of(out_dir, "kernels")


def run_zero3(out_dir: str, steps: int) -> dict:
    """ZeRO-3 train: parameter partitioning + prefetch-overlapped
    gathers on the wire."""
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from simple_model import (base_config, random_batch, simple_loss_fn,
                              simple_model_params)
    cfg = base_config(zero_optimization={"stage": 3})
    cfg["telemetry"] = _tel_cfg(out_dir, "zero3_prefetch")
    eng = DeepSpeedEngine(model=simple_loss_fn,
                          model_params=simple_model_params(
                              jax.random.PRNGKey(0)),
                          config=cfg)
    batch = random_batch(n=16)
    for _ in range(steps):
        eng.train_batch(batch=batch)
    eng.telemetry.close()
    return _profile_of(out_dir, "zero3_prefetch")


def run_moe(out_dir: str, steps: int) -> dict:
    """GPT2-tiny MoE (8 experts, top-2, ep=4 x dp=2): routed
    all-to-all on the wire."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (GPT2_CONFIGS, gpt2_init,
                                           gpt2_loss_fn)
    from deepspeed_tpu.moe import MoEConfig, gpt2_moe_param_shardings
    from deepspeed_tpu.parallel.topology import build_mesh

    vocab, seq = 64, 33
    moe = MoEConfig(num_experts=8, top_k=2, capacity_factor=1.5,
                    expert_parallel_size=4)
    cfg = dataclasses.replace(
        GPT2_CONFIGS["gpt2-tiny"], vocab_size=vocab, max_seq_length=seq,
        hidden_size=128, num_heads=4, num_layers=2, hidden_dropout=0.0,
        attn_dropout=0.0, dtype=jnp.float32, fused_kernels=False,
        moe=moe)
    mesh = build_mesh(ep=4)
    ds_cfg = {
        "train_batch_size": 32, "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1,
        "zero_optimization": {"stage": 2}, "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
        "moe": {"num_experts": moe.num_experts, "top_k": moe.top_k,
                "capacity_factor": moe.capacity_factor,
                "aux_loss_weight": moe.aux_loss_weight,
                "z_loss_weight": moe.z_loss_weight,
                "expert_parallel_size": moe.expert_parallel_size,
                "grouped_gemm": moe.grouped_gemm},
        "steps_per_print": 10 ** 9,
        "telemetry": _tel_cfg(out_dir, "moe"),
    }
    eng, *_ = deepspeed_tpu.initialize(
        model=gpt2_loss_fn(cfg, mesh=mesh),
        model_params=gpt2_init(jax.random.PRNGKey(0), cfg),
        config=ds_cfg, mesh=mesh,
        param_shardings=gpt2_moe_param_shardings(cfg))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, vocab, size=(32, seq)).astype(np.int32)
    for _ in range(steps):
        eng.train_batch(batch=tokens)
    eng.telemetry.close()
    return _profile_of(out_dir, "moe")


def run_multislice(out_dir: str, steps: int) -> dict:
    """slices=2 x dp=4 two-tier mesh: in-slice reduce-scatter vs the
    once-per-step cross-slice (DCN-tier) all-reduce."""
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from simple_model import (base_config, random_batch, simple_loss_fn,
                              simple_model_params)
    cfg = base_config(zero_optimization={"stage": 2})
    cfg["mesh"] = {"slices": 2}
    cfg["telemetry"] = _tel_cfg(out_dir, "multislice")
    eng = DeepSpeedEngine(model=simple_loss_fn,
                          model_params=simple_model_params(
                              jax.random.PRNGKey(0)),
                          config=cfg)
    batch = random_batch(n=16)
    for _ in range(steps):
        eng.train_batch(batch=batch)
    eng.telemetry.close()
    return _profile_of(out_dir, "multislice")


def run_serving(out_dir: str, steps: int) -> dict:
    """Paged-KV serving decode: the attend path under a live window
    (profiler ticks on decode iterations)."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2_CONFIGS, gpt2_init

    cfg = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"],
                              dtype=jnp.float32)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(
        cfg, params,
        config={"inference": {"max_slots": 8, "max_seq_len": 64,
                              "prefill_chunk": 8, "block_size": 16,
                              "num_blocks": 0},
                "telemetry": _tel_cfg(out_dir, "serving_attend")})
    rng = np.random.default_rng(0)
    for slot in range(4):
        prompt = rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
        eng.prefill(prompt, slot=slot)
    for _ in range(max(steps, sum(WINDOW) + 2)):
        eng.decode_once()
    eng.telemetry.close()
    return _profile_of(out_dir, "serving_attend")


# ------------------------------------------------------------------ #
# The ladder
# ------------------------------------------------------------------ #
def _latest_kernel_round() -> str:
    rounds = sorted(glob.glob(os.path.join(REPO, "BENCH_r[0-9]*.json")))
    rounds = [r for r in rounds if "builder" not in r]
    return os.path.basename(rounds[-1]) if rounds else "BENCH_r07.json"


def ladder():
    return [
        ("kernels", _latest_kernel_round(), run_kernels),
        ("zero3_prefetch", "ZERO3_BENCH.json", run_zero3),
        ("moe", "MOE_BENCH.json", run_moe),
        ("multislice", "MULTISLICE_BENCH.json", run_multislice),
        ("serving_attend", "SERVE_BENCH.json", run_serving),
        # No profiled runner: this prices host-side wall clock
        # (resilience goodput) — a device trace does not back it.
        ("resilience", "RESILIENCE_BENCH.json", None),
    ]


# ------------------------------------------------------------------ #
# Labeling
# ------------------------------------------------------------------ #
def prior_label(doc) -> str:
    """Read an artifact's legacy honesty markers: any ``projected``
    flag / ``projection`` section / PROJECTION methodology ->
    projected; an explicitly CPU-meshed measurement -> cpu-structural;
    unknown provenance defaults to projected (the cautious label)."""
    projected = []
    cpu_backed = []

    def walk(o):
        if isinstance(o, dict):
            for k, v in o.items():
                if k == "projected" and v:
                    projected.append(k)
                elif k in ("projection", "projection_zero3",
                           "projected_tpu_vm", "production_projection"):
                    projected.append(k)
                elif k == "methodology" and isinstance(v, str) and \
                        ("PROJECTION" in v or "analytic" in v.lower()):
                    projected.append(k)
                elif k == "backend" and v == "cpu":
                    cpu_backed.append(k)
                elif k in ("measured", "measured_cpu", "goodput") and v:
                    cpu_backed.append(k)
                walk(v)
        elif isinstance(o, list):
            for v in o:
                walk(v)

    walk(doc)
    if projected:
        return "projected"
    if cpu_backed:
        return "cpu-structural"
    return "projected"


def capture_ok(prof: dict) -> bool:
    if not prof.get("available") or prof.get("error"):
        return False
    wins = prof.get("windows") or []
    if not any(w.get("phase") == "stop" and w.get("ok") for w in wins):
        return False
    return bool(prof.get("n_device_ops"))


def label_for(prof: dict, backend: str, prior: str) -> str:
    if not capture_ok(prof):
        return prior                       # failed rung: never upgrade
    return "measured" if backend == "tpu" else "cpu-structural"


def _artifact_entry(rung, fname, prof, backend, prior):
    entry = {
        "ladder": rung,
        "label": prior if prof is None else label_for(prof, backend,
                                                      prior),
        "prior_label": prior,
        "backend": backend,
    }
    if prof is None:
        entry["note"] = ("rung not profiled this sweep (no runner, or "
                         "skipped via --only); label carried from the "
                         "artifact's own provenance markers")
        return entry
    if prof.get("error"):
        entry["error"] = str(prof["error"])
    wins = [w for w in (prof.get("windows") or [])
            if w.get("phase") == "stop"]
    if wins:
        entry["window"] = wins[-1]
    for k in ("per_step_wall_ms", "per_step_ms", "sum_check",
              "pallas_families_ms", "n_device_ops"):
        if prof.get(k) is not None:
            entry[k] = prof[k]
    recon = prof.get("reconciliation")
    if isinstance(recon, dict):
        entry["reconciliation"] = {
            k: recon.get(k) for k in
            ("verdict", "dominant_bucket", "predicted_bound",
             "components", "paths", "divergences")
            if recon.get(k) is not None}
    if prof.get("registered_paths"):
        entry["registered_paths"] = prof["registered_paths"]
    return entry


# ------------------------------------------------------------------ #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tpu_truth.py",
        description=__doc__.split("\n\n")[0],
        epilog=RUNBOOK,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--only", choices=[r for r, _, fn in ladder()
                                       if fn is not None],
                    help="run a single ladder rung; the rest keep "
                         "their prior labels")
    ap.add_argument("--steps", type=int, default=DEFAULT_STEPS,
                    help="steps per rung (armed window is steps "
                         f"{WINDOW[0]}..{WINDOW[0] + WINDOW[1]})")
    ap.add_argument("--out", default=os.path.join(REPO, "TRUTH.json"),
                    help="output path (default <repo>/TRUTH.json)")
    ap.add_argument("--keep-runs", metavar="DIR", default=None,
                    help="keep per-rung telemetry dirs here instead "
                         "of a temp dir")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend == "tpu":
        from deepspeed_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
    print(f"tpu_truth: backend={backend}, devices={jax.device_count()}"
          f" -> new labels are "
          f"{'measured' if backend == 'tpu' else 'cpu-structural'}")

    import tempfile
    run_root = args.keep_runs or tempfile.mkdtemp(prefix="tpu_truth_")
    os.makedirs(run_root, exist_ok=True)

    artifacts = {}
    for rung, fname, runner in ladder():
        path = os.path.join(REPO, fname)
        try:
            with open(path) as f:
                doc = json.load(f)
            prior = prior_label(doc)
        except Exception:
            prior = "projected"
        prof = None
        if runner is not None and (args.only is None
                                   or args.only == rung):
            out_dir = os.path.join(run_root, rung)
            os.makedirs(out_dir, exist_ok=True)
            try:
                prof = runner(out_dir, args.steps)
            except Exception as e:  # noqa: BLE001 — rung isolation
                prof = {"available": False,
                        "error": f"{type(e).__name__}: {e}"}
        entry = _artifact_entry(rung, fname, prof, backend, prior)
        artifacts[fname] = entry
        recon = entry.get("reconciliation") or {}
        print(f"tpu_truth: {rung:<15} {fname:<22} "
              f"{entry['prior_label']} -> {entry['label']}"
              + (f" (verdict={recon.get('verdict')}, dominant="
                 f"{recon.get('dominant_bucket')}, predicted="
                 f"{recon.get('predicted_bound')})" if recon else "")
              + (f" ERROR: {entry['error']}" if entry.get("error")
                 else ""))

    truth = {
        "generated_by": "tools/tpu_truth.py",
        "backend": backend,
        "n_devices": int(jax.device_count()),
        "window": {"start_step": WINDOW[0], "window_steps": WINDOW[1]},
        "label_policy": {
            "projected": "analytic number only; no run backs it",
            "cpu-structural": "identical capture->ingest->reconcile "
                              "pipeline ran on the forced-CPU host "
                              "mesh; structure real, walls not TPU",
            "measured": "a real TPU trace backs the number "
                        "(jax.default_backend()=='tpu' and the "
                        "capture ingested)",
        },
        "artifacts": artifacts,
    }
    try:
        with open(args.out, "w") as f:
            json.dump(truth, f, indent=1, sort_keys=True)
            f.write("\n")
    except OSError as e:
        print(f"tpu_truth: FAILED to write {args.out}: {e}")
        return 1
    n_meas = sum(1 for a in artifacts.values()
                 if a["label"] == "measured")
    print(f"tpu_truth: wrote {args.out} — {len(artifacts)} artifacts, "
          f"{n_meas} measured"
          + ("" if backend == "tpu" else
             " (labels honest for this CPU box; re-run on a TPU host "
             "to flip them — see --help)"))
    return 0


if __name__ == "__main__":
    sys.exit(main())

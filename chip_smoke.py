"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py [--seed N]        # one chip (what the driver runs)
    python3 chip_smoke.py --chips 4         # the four-chip path only

One process drives the main path through the entry points a user calls,
at gpt2-large's full width and depth (1280 x 36, S=1024), random weights
from ``--seed``:

- *launcher*: ``bin/deepspeed examples/gpt2/train.py --model gpt2-tiny
  --steps 3`` as a child, BEFORE this process touches a JAX backend (a
  chip belongs to one process at a time); the child must reach the TPU.
- *kernels*: one eager autotune search per elementwise kernel at the
  train step's shapes — the only place a search may run (never under a
  trace) — and the evidence that its clock reads device work.
- *train*: ``deepspeed_tpu.initialize`` from a ``ds_config.json`` on
  disk (ZeRO-2, master-free bf16 with stochastic rounding, fused
  optimizer, ``fused_kernels`` auto), micro-batch 4 of byte-level
  ``examples/data/corpus.txt`` windows; finite falling loss, no
  recompile after the first step, ``save_checkpoint``.
- *serve*: ``InferenceEngine.from_train_checkpoint`` on that checkpoint,
  paged cache + speculative decoding, the Pallas paged-attention kernel
  (``paged_kernel: "auto"``) against the one-hot contraction.

``--chips 4`` runs only: the same widths under ZeRO-2 over
``build_mesh()`` = dp 4 (full depth), then dp 4 compared step by step
with the same global batch on a one-device mesh with 4 accumulation
steps (30 of the 36 layers: the one-device twin does not fit at 36).

Every phase raises on failure.  Without a TPU the script exits non-zero
and prints no result.  The LAST line of stdout is the result object.
"""
import argparse
import collections
import dataclasses
import importlib.metadata
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

MODEL = "gpt2-large"
MICRO_BATCH = 4
# bf16 logits of a 36-layer model, kernel vs one-hot attend: both paths
# accumulate in fp32 and round to bf16 at different points; logits are
# O(1-10), bf16 has 8 mantissa bits -> agreement to a few bf16 ulp of
# the largest logit.
LOGIT_ATOL = 0.25
# dp=4 vs 1 device x 4 accumulation steps: same global batch, different
# fp32 reduction order, and stochastic rounding noise is keyed by the
# element's position in the (differently sharded) flat buffer.
LOSS_RTOL_4CHIP = 0.02


def say(**kw):
    print(json.dumps(kw, default=str), flush=True)


# ------------------------------------------------------------------ #
# Phases (imported and run at gpt2-tiny on the CPU mesh by
# tests/test_chip_smoke.py; ``main`` refuses anything but a TPU)
# ------------------------------------------------------------------ #
def phase_launcher():
    """The user-facing launcher, in a child, before this process has
    initialised any backend."""
    from jax._src import xla_bridge
    assert not xla_bridge._backends, \
        "launcher phase must run before this process touches a backend"
    cmd = [sys.executable, os.path.join(ROOT, "bin", "deepspeed"),
           os.path.join(ROOT, "examples", "gpt2", "train.py"),
           "--model", "gpt2-tiny", "--steps", "3"]
    t0 = time.perf_counter()
    out = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True, timeout=600)
    line = next((l for l in out.stdout.splitlines()
                 if l.startswith("devices:")), "")
    say(phase="launcher", rc=out.returncode, child_line=line,
        seconds=round(time.perf_counter() - t0, 1))
    if out.returncode != 0:
        raise RuntimeError(f"bin/deepspeed exited {out.returncode}:\n"
                           + out.stdout[-4000:])
    return line


def model_config(name: str):
    """Published widths and depth, dropout off.  ``dots_flash`` remat:
    without remat the 36-layer step's saved activations (~7.5 GB at
    micro-batch 4) on top of the 7.7 GB of bf16 params + fp32 moments
    leave a 16 GB chip no headroom (tools/compile_rehearsal.py)."""
    from deepspeed_tpu.models import GPT2_CONFIGS
    return dataclasses.replace(GPT2_CONFIGS[name], hidden_dropout=0.0,
                               attn_dropout=0.0, remat_policy="dots_flash")


def twin_config(cfg):
    """The four-chip comparison's model: same widths, 5/6 of the depth
    (at 36 layers the one-device twin with its fp32 accumulator is 29 MB
    over a 16 GB chip; 30 fit)."""
    return dataclasses.replace(cfg,
                               num_layers=max(1, cfg.num_layers * 5 // 6))


def corpus_batches(seed: int, seq_len: int, rows_per_step: int, steps: int):
    """Byte-level next-byte windows of the vendored corpus, drawn from
    ``seed``: ``steps`` arrays of int32 [rows_per_step, seq_len + 1]."""
    import numpy as np
    raw = np.frombuffer(
        open(os.path.join(ROOT, "examples", "data", "corpus.txt"),
             "rb").read(), dtype=np.uint8)
    n_rows = len(raw) // (seq_len + 1)
    need = rows_per_step * steps
    assert n_rows >= need, f"corpus has {n_rows} windows, need {need}"
    rows = raw[:n_rows * (seq_len + 1)].reshape(n_rows, seq_len + 1)
    pick = np.random.default_rng(seed).permutation(n_rows)[:need]
    return [rows[pick[i * rows_per_step:(i + 1) * rows_per_step]]
            .astype(np.int32) for i in range(steps)]


def write_ds_config(workdir: str, *, n_replicas: int, gas: int,
                    telemetry_dir: str) -> str:
    """The training configuration, on disk as a user would keep it."""
    cfg = {
        "train_batch_size": MICRO_BATCH * n_replicas * gas,
        "train_micro_batch_size_per_gpu": MICRO_BATCH,
        "gradient_accumulation_steps": gas,
        "gradient_clipping": 1.0,
        "bf16": {"enabled": True, "stochastic_rounding": True},
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 2e-4, "weight_decay": 0.01}},
        "steps_per_print": 10 ** 9,
        "telemetry": {"enabled": True, "output_path": telemetry_dir,
                      "job_name": "chip_smoke", "report_steps": 10 ** 9,
                      "recompile_warmup_calls": 1,
                      "fail_on_recompile": True},
    }
    path = os.path.join(workdir, f"ds_config_dp{n_replicas}_gas{gas}.json")
    with open(path, "w") as f:
        json.dump(cfg, f, indent=1)
    return path


# What the default train step must hold on a TPU: fused LayerNorm
# (fused_kernels auto), flash attention forward + fused backward, the
# one-pass fused optimizer (norm + apply).
TRAIN_STEP_KERNELS = {
    "_ln_fwd_kernel", "_ln_bwd_kernel", "_fwd_kernel", "_bwd_fused_kernel",
    "_sqnorm_kernel", "_fused_adam_kernel"}
_KERNEL_NAME = re.compile(r'op_name="[^"]*?/([\w.\-]+)/pallas_call')
_INSTR_NAME = re.compile(r'^\s*(?:ROOT\s+)?%([A-Za-z_][\w\-]*?)(?:\.\d+)* = ')


def pallas_kernels(hlo_text: str):
    """{kernel name: count} over a compiled program's tpu_custom_calls:
    the ``name=`` each pallas_call was given (it is the last jax scope in
    the op_name; XLA also names the instruction after it)."""
    names = collections.Counter()
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line or "custom-call(" not in line:
            continue
        m = _KERNEL_NAME.search(line) or _INSTR_NAME.match(line)
        names[m.group(1) if m else "pallas_call"] += 1
    return dict(names)


def compiled_text(telemetry, name: str) -> str:
    """Optimized HLO of an engine's sentinel-registered step function
    (served from the compilation cache: the step already compiled)."""
    fn, args, kwargs = telemetry.sentinel.registered_paths()[name]
    return fn.lower(*args, **kwargs).compile().as_text()


def assert_not_interpreted():
    """Every kernel module's ``_interpret()`` switch is off here."""
    from deepspeed_tpu.ops import (flash_attention, fused_elementwise,
                                   fused_update, grouped_gemm)
    mods = (flash_attention, fused_elementwise, fused_update, grouped_gemm)
    assert not any(m._interpret() for m in mods), "Pallas interpret mode"
    return False


def peak_bytes():
    import jax
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in jax.local_devices()]


# The search's clock holds a dispatch beside the device's work (~0.8 ms
# on the v5e): at the LayerNorm's 21 MB a call, 4x the rows read 1.22x
# the time and 16x about twice (PR 51).
MORE_ROWS = 16


def phase_kernels(cfg):
    """Eager autotune searches at the train step's shapes.  Under the
    step's trace a search never runs (a runner's arrays are tracers
    there and the clock would read tracing); here the arrays are
    concrete, so the registry the step then HITS holds device timings.
    Evidence: the same kernel at ``MORE_ROWS`` x the rows must take
    longer."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops import autotune
    from deepspeed_tpu.ops.fused_elementwise import fused_layer_norm
    rows, H = MICRO_BATCH * cfg.max_seq_length, cfg.hidden_size
    autotune.reset()
    scale, bias = jnp.ones((H,), jnp.float32), jnp.zeros((H,), jnp.float32)
    for r in (rows, MORE_ROWS * rows):
        fused_layer_norm(jnp.zeros((r, H), cfg.dtype), scale, bias
                         ).block_until_ready()
    reg = autotune._load(autotune.registry_path())
    best = {}
    for key, ent in reg.items():
        if key.startswith("fused_ln_fwd|") and autotune.chip_kind() in key:
            n_rows = int(key.split("[")[1].split(",")[0])
            if n_rows in (rows, MORE_ROWS * rows):
                best[n_rows] = min(ent["timings_s"].values())
    slower = None if len(best) < 2 \
        else best[MORE_ROWS * rows] > 1.3 * best[rows]
    say(phase="kernels", autotune_counters=dict(autotune.counters),
        registry=autotune.registry_path(),
        ln_fwd_best_s={str(k): v for k, v in sorted(best.items())},
        search_times_device_work=slower)
    if autotune.search_allowed():
        # The eager clock the search replaced read 107 ms whatever the
        # rows (PR 21).
        assert slower, f"autotune search does not time device work: {best}"
    return best


def phase_train(cfg, seed: int, workdir: str, steps: int = 8):
    """A few optimizer steps through the user API; returns the
    checkpoint directory."""
    import jax
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2_init, gpt2_loss_fn
    from deepspeed_tpu.ops import autotune
    from deepspeed_tpu.parallel.topology import build_mesh

    ds_config = write_ds_config(workdir, n_replicas=1, gas=1,
                                telemetry_dir=os.path.join(workdir, "tel"))
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=ds_config, model=gpt2_loss_fn(cfg),
        model_params=gpt2_init(jax.random.PRNGKey(seed), cfg),
        mesh=build_mesh(devices=jax.devices()[:1]))
    batches = corpus_batches(seed, cfg.max_seq_length, MICRO_BATCH, steps)
    losses, step_s = [], []
    for b in batches:
        t0 = time.perf_counter()
        loss = jax.block_until_ready(engine.train_batch(b))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    compiles = engine.telemetry.sentinel.compile_counts()
    kernels = pallas_kernels(compiled_text(engine.telemetry, "train_step"))
    say(phase="train", model=cfg.name, seq=cfg.max_seq_length,
        micro_batch=MICRO_BATCH, losses=[round(l, 4) for l in losses],
        first_step_s=round(step_s[0], 2),
        step_ms=[round(s * 1e3, 1) for s in step_s[1:]],
        compiles=compiles, recompiles=engine.telemetry.recompile_count,
        pallas_kernels=kernels, interpret=assert_not_interpreted()
        if jax.default_backend() == "tpu" else "cpu-rehearsal",
        peak_bytes_in_use=peak_bytes(),
        autotune_counters=dict(autotune.counters))
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert compiles == {"train_step": 1}, compiles
    assert engine.telemetry.recompile_count == 0
    if jax.default_backend() == "tpu":
        missing = TRAIN_STEP_KERNELS - set(kernels)
        assert not missing, \
            f"kernels missing from the train step: {missing} / {kernels}"
    ckpt = os.path.join(workdir, "ckpt")
    t0 = time.perf_counter()
    engine.save_checkpoint(ckpt)
    say(phase="checkpoint", dir=ckpt,
        seconds=round(time.perf_counter() - t0, 1))
    engine.telemetry.close()
    return ckpt


def serve_config(cfg, paged_kernel, telemetry_dir: str):
    return {
        "inference": {"max_slots": 8,
                      "max_seq_len": min(256, cfg.max_seq_length),
                      "block_size": 16,
                      "spec_k": 4, "paged_kernel": paged_kernel},
        "telemetry": {"enabled": True, "output_path": telemetry_dir,
                      "job_name": f"smoke_serve_{paged_kernel}",
                      "report_steps": 10 ** 9, "fail_on_recompile": True},
    }


def phase_serve(cfg, seed: int, workdir: str, ckpt: str, n_requests: int = 8):
    """Serve the trained checkpoint with the paged kernel ("auto") and
    again with the one-hot contraction; compare."""
    import jax
    import numpy as np
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.inference.scheduler import shared_prefix_requests
    from deepspeed_tpu.parallel.topology import build_mesh

    on_tpu = jax.default_backend() == "tpu"
    mesh = build_mesh(devices=jax.devices()[:1])
    # Byte-level prompts (the checkpoint was trained on bytes).
    def requests():
        return shared_prefix_requests(
            n_requests, prefix_len=32, tail_len=(4, 12), max_new_tokens=16,
            vocab_size=256, seed=seed)

    prompt = np.asarray(requests()[0].prompt)
    result = {}
    # CPU rehearsal: "auto" resolves off there, so force the kernel on
    # (interpret mode) to walk the same path.
    for label, knob in (("kernel", "auto" if on_tpu else True),
                        ("onehot", False)):
        eng = InferenceEngine.from_train_checkpoint(
            ckpt, cfg, mesh=mesh,
            config=serve_config(cfg, knob,
                                os.path.join(workdir, "tel_" + label)))
        assert eng.paged_kernel is (label == "kernel"), \
            (label, eng.paged_kernel)
        t0 = time.perf_counter()
        report = eng.serve(requests())
        serve_s = time.perf_counter() - t0
        assert report["completed"] == n_requests, report["completed"]
        assert report["recompiles"] == 0, report["recompiles"]
        streams = {r["rid"]: r["tokens"] for r in report["requests"]}
        # First decode step's logits on a fresh slot.
        eng.reset_serving_stats()
        tok, _ = eng.prefill(prompt, slot=0, return_logits=True)
        eng.activate_slot(0, len(prompt), tok)
        _, logits = eng.decode_once(return_logits=True)
        kernels = pallas_kernels(compiled_text(eng.telemetry, "decode_step"))
        if label == "kernel" and on_tpu:
            assert "_pattn_kernel" in kernels, kernels
        result[label] = dict(streams=streams,
                             logits=np.asarray(logits)[0].astype(np.float32))
        say(phase="serve", attend=label, paged_kernel=eng.paged_kernel,
            completed=report["completed"], recompiles=report["recompiles"],
            serve_seconds=round(serve_s, 2),
            decode_step_pallas_kernels=kernels,
            prefix_hit_rate=report.get("prefix", {}).get("hit_rate"),
            spec_acceptance=report.get("spec", {}).get("acceptance_rate"),
            peak_bytes_in_use=peak_bytes())
        eng.close()
        del eng
    a, b = result["kernel"]["logits"], result["onehot"]["logits"]
    assert np.all(np.isfinite(a)) and a.shape == (cfg.vocab_size,), a.shape
    err = float(np.max(np.abs(a - b)))
    pairs = [(x, y) for rid, toks in result["kernel"]["streams"].items()
             for x, y in zip(toks, result["onehot"]["streams"][rid])]
    agree = sum(x == y for x, y in pairs) / max(1, len(pairs))
    say(phase="serve_compare", first_decode_logits_max_abs_err=err,
        atol=LOGIT_ATOL, argmax_equal=bool(a.argmax() == b.argmax()),
        greedy_token_agreement=agree)
    assert err <= LOGIT_ATOL, f"kernel vs one-hot logits differ by {err}"


def phase_four_chips(cfg, seed: int, workdir: str, steps: int = 4,
                     devices=None):
    """ZeRO-2 over dp=4 at full depth, then — at 5/6 of the depth, same
    widths — dp=4 against one device with 4 accumulation steps on the
    same global batches, all in one process.  (At 36 layers the
    one-device twin's fp32 accumulator puts it 29 MB over a 16 GB chip:
    15.78 of 15.75 GB by the chip compiler; 30 layers leave 2 GB.)
    ``devices`` defaults to all of them (``build_mesh()``'s default)."""
    import jax
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2_init, gpt2_loss_fn
    from deepspeed_tpu.parallel import hlo_audit
    from deepspeed_tpu.parallel.topology import build_mesh

    devices = list(devices or jax.devices())
    n = len(devices)
    assert n == 4, f"--chips 4 needs four devices, got {n}"
    batches = corpus_batches(seed, cfg.max_seq_length, MICRO_BATCH * n,
                             steps)

    def run(label, model_cfg, mesh, n_replicas, gas):
        ds_config = write_ds_config(
            workdir, n_replicas=n_replicas, gas=gas,
            telemetry_dir=os.path.join(workdir, "tel_" + label))
        engine, _, _, _ = deepspeed_tpu.initialize(
            config=ds_config, model=gpt2_loss_fn(model_cfg),
            model_params=gpt2_init(jax.random.PRNGKey(seed), model_cfg),
            mesh=mesh)
        losses, step_s = [], []
        for b in batches:
            t0 = time.perf_counter()
            losses.append(float(jax.block_until_ready(
                engine.train_batch(b))))
            step_s.append(time.perf_counter() - t0)
        assert all(np.isfinite(losses)), losses
        assert engine.telemetry.recompile_count == 0
        return engine, losses, step_s

    # --- dp = 4, full depth: is it really spread? ---
    engine, losses, step_s = run("dp4", cfg, build_mesh(devices=devices),
                                 n, 1)
    assert engine.dp_size == n, engine.dp_size
    moments = [l for l in jax.tree_util.tree_leaves(engine.state.opt_state)
               if getattr(l, "ndim", 0) >= 1]
    spread = sorted({len(l.sharding.device_set) for l in moments})
    # (The CPU backend, where this phase is only rehearsed, reports no
    # memory statistics.)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    text = compiled_text(engine.telemetry, "train_step")
    kinds = collections.Counter(
        o.kind for o in hlo_audit.parse_hlo_collectives(text))
    probe = hlo_audit.zero2_grad_sync_lowering(engine.mesh, "data")
    say(phase="four_chips", arm="dp4", layers=cfg.num_layers,
        losses=[round(l, 4) for l in losses],
        first_step_s=round(step_s[0], 2),
        step_ms=[round(s * 1e3, 1) for s in step_s[1:]],
        optimizer_moment_device_set_sizes=spread,
        bytes_in_use_per_device=in_use, peak_bytes_in_use=peak_bytes(),
        collectives=dict(kinds), grad_sync_mode=engine._grad_sync_mode,
        declared_sharding_lowers_to=probe,
        pallas_kernels=pallas_kernels(text))
    assert spread == [n], f"optimizer moments not spread over {n}: {spread}"
    if jax.default_backend() == "tpu":
        assert max(in_use) < 2 * min(in_use), \
            f"device memory not of the same order (device 0 kept the " \
            f"fp32 init?): {in_use}"
    assert kinds.get("reduce-scatter", 0) > 0 or \
        (probe == "all-reduce" and kinds.get("all-reduce", 0) > 0), kinds
    assert losses[-1] < losses[0], losses
    engine.telemetry.close()
    del engine, moments

    # --- same widths, 5/6 depth: dp=4 vs one device x 4 accumulation ---
    cut = twin_config(cfg)
    engine, losses4, _ = run("dp4_cut", cut, build_mesh(devices=devices),
                             n, 1)
    engine.telemetry.close()
    del engine
    engine, losses1, _ = run("dp1_gas4_cut", cut,
                             build_mesh(devices=devices[:1]), 1, n)
    engine.telemetry.close()
    rel = [abs(a - b) / abs(b) for a, b in zip(losses4, losses1)]
    say(phase="four_chips", arm="dp4 vs dp1_gas4", layers=cut.num_layers,
        losses_dp4=[round(l, 4) for l in losses4],
        losses_dp1_gas4=[round(l, 4) for l in losses1],
        rel_diff=[round(r, 5) for r in rel], rtol=LOSS_RTOL_4CHIP)
    assert max(rel) <= LOSS_RTOL_4CHIP, rel


# ------------------------------------------------------------------ #
def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run only the four-chip ZeRO-2 path and what "
                         "it is compared with")
    args = ap.parse_args()

    if args.chips == 1:
        child = phase_launcher()
        if "platform=tpu" not in child:
            sys.exit(f"launcher child did not reach a TPU: {child!r}")

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; jax found {dev.platform!r}")
    if len(jax.devices()) != args.chips:
        sys.exit(f"--chips {args.chips} but jax found "
                 f"{len(jax.devices())} device(s)")

    from deepspeed_tpu.monitor.peaks import chip_peaks
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    # Compile accounting from JAX's own monitoring events: persistent
    # cache hits/misses, and seconds spent in the backend compiler
    # (jit dispatch wraps compile-or-cache-load in this one event).
    cache_events = collections.Counter()
    compile_s = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **kw: cache_events.update([name])
        if "compilation_cache" in name else None)
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_s.update({name: secs})
        if name.endswith("backend_compile_duration") else None)
    cache_dir = enable_compile_cache()

    def compile_report(after: str):
        say(phase="compile_seconds", after=after,
            backend_compile_s=round(sum(compile_s.values()), 2),
            cache_hits=cache_events["/jax/compilation_cache/cache_hits"],
            cache_misses=cache_events["/jax/compilation_cache/cache_misses"])
        compile_s.clear()
        cache_events.clear()
    peaks = chip_peaks()          # raises for a TPU kind with no row
    assert not peaks.assumed
    say(phase="device", jax=jax.__version__,
        jaxlib=importlib.metadata.version("jaxlib"),
        libtpu=importlib.metadata.version("libtpu"),
        python=sys.version.split()[0], platform=dev.platform,
        device_kind=dev.device_kind, count=len(jax.devices()),
        chip_peaks=peaks.as_dict(), compile_cache_dir=cache_dir,
        compile_cache_entries_at_start=len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0)

    cfg = model_config(MODEL)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            phase_four_chips(cfg, args.seed, workdir)
            compile_report("four_chips")
        else:
            phase_kernels(cfg)
            compile_report("kernels")
            ckpt = phase_train(cfg, args.seed, workdir)
            compile_report("train")
            phase_serve(cfg, args.seed, workdir, ckpt)
            compile_report("serve")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    say(phase="compile_cache", dir=cache_dir,
        entries=len(os.listdir(cache_dir)))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()

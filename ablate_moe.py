"""MoE ablation: dense FFN vs 8-expert top-2 expert parallelism (dev tool).

Runs gpt2-tiny dense and its 8-expert top-2 MoE twin (ep=4 x dp=2)
through the full engine on the 8-device CPU mesh and records:

- **measured** CPU wall per step for both — honestly labeled: on the
  emulated mesh the all-to-all is memcpy, so the delta exercises the
  dispatch/bucketing/exchange STRUCTURE, not ICI latency (the
  ZERO3_BENCH convention). Measured drop fraction and
  expert load imbalance ride along (bench_gate parses the drop p95).
- the **params-per-step-FLOP headline** — the reason MoE exists: total
  trainable parameters grow ~E x on the FFN tree while per-token step
  FLOPs grow only ~top_k x on the same tree (+ the router's H*E
  logits), analytically derived from the actual param trees.
- the **analytic all-to-all wire bytes** (hlo_audit.moe_alltoall_wire_
  model — the same model COMM_AUDIT.json verifies against the compiled
  program to 5%) vs the FFN FLOP delta: what the expert-parallel wire
  costs against the compute it unlocks on the target chip.

- the **expert-compute ablation** — the einsum FFN pair vs the
  grouped-GEMM Pallas kernel (ops/grouped_gemm) at the exact dispatched
  shapes: both walls measured on TPU; on the CPU dev box the einsum
  wall is measured and the kernel's win is the structural HBM-byte
  projection (fused epilogue drops the [E,C,F] round-trip), honestly
  labeled ``projected``.

``--record`` writes MOE_BENCH.json; ``tools/bench_gate.py`` gates its
``moe.drop_fraction`` across rounds (pre-MoE rounds skip, never fail).

Usage: python ablate_moe.py [--steps N] [--record]
"""
import dataclasses
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = \
        _flags + " --xla_force_host_platform_device_count=8"

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402

import deepspeed_tpu           # noqa: E402
from deepspeed_tpu.models.gpt2 import (GPT2_CONFIGS, gpt2_init,  # noqa: E402
                                       gpt2_loss_fn)
from deepspeed_tpu.models.transformer import count_params  # noqa: E402
from deepspeed_tpu.moe import (MoEConfig,  # noqa: E402
                               gpt2_moe_param_shardings)
from deepspeed_tpu.parallel import hlo_audit  # noqa: E402
from deepspeed_tpu.parallel.topology import build_mesh  # noqa: E402

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "MOE_BENCH.json")
RECORD = "--record" in sys.argv
STEPS = 30
if "--steps" in sys.argv:
    STEPS = int(sys.argv[sys.argv.index("--steps") + 1])

E, K, CF, EP = 8, 2, 1.5, 4
B, SEQ = 32, 33


def _cfg(moe=None):
    return dataclasses.replace(
        GPT2_CONFIGS["gpt2-tiny"], vocab_size=64, max_seq_length=SEQ,
        hidden_dropout=0.0, attn_dropout=0.0, dtype=jnp.float32,
        fused_kernels=False, moe=moe)


def _engine(moe_cfg=None):
    ep = moe_cfg.expert_parallel_size if moe_cfg else 1
    mesh = build_mesh(ep=ep)
    cfg = _cfg(moe_cfg)
    ds = {"train_batch_size": B, "train_micro_batch_size_per_gpu": 4,
          "gradient_accumulation_steps": 1,
          "zero_optimization": {"stage": 1}, "gradient_clipping": 1.0,
          "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
          "steps_per_print": 10 ** 9}
    kw = {}
    if moe_cfg is not None:
        ds["moe"] = {"num_experts": moe_cfg.num_experts,
                     "top_k": moe_cfg.top_k,
                     "capacity_factor": moe_cfg.capacity_factor,
                     "expert_parallel_size": ep}
        kw["param_shardings"] = gpt2_moe_param_shardings(cfg)
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2_loss_fn(cfg, mesh=mesh),
        model_params=gpt2_init(jax.random.PRNGKey(0), cfg),
        config=ds, mesh=mesh, **kw)
    return engine, cfg


def _run(engine, steps):
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 64, size=(B, SEQ + 1)).astype(np.int32)
               for _ in range(steps + 3)]
    for b in batches[:3]:                       # warmup / compile
        engine.train_batch(b)
    jax.block_until_ready(engine.state.params)
    t0 = time.perf_counter()
    for b in batches[3:]:
        engine.train_batch(b)
    jax.block_until_ready(engine.state.params)
    wall = (time.perf_counter() - t0) / steps
    return wall


def main():
    moe_cfg = MoEConfig(num_experts=E, top_k=K, capacity_factor=CF,
                        expert_parallel_size=EP)
    dense_engine, dense_model = _engine(None)
    dense_wall = _run(dense_engine, STEPS)
    dense_params = count_params(jax.device_get(
        dense_engine.state.params))

    moe_engine, moe_model = _engine(moe_cfg)
    moe_wall = _run(moe_engine, STEPS)
    moe_params = count_params(jax.device_get(moe_engine.state.params))
    # Last step's stats via one extra recorded step.
    metrics = None
    rng = np.random.default_rng(1)
    moe_engine.train_batch(rng.integers(0, 64, size=(B, SEQ + 1))
                           .astype(np.int32))
    # metrics dict of the last step is not retained by train_batch;
    # recompute from a fresh step fn call
    mb = moe_engine._stack_micro_batches(
        rng.integers(0, 64, size=(B, SEQ + 1)).astype(np.int32))
    mb = jax.device_put(mb, moe_engine._batch_sharding(mb, leading_dims=2))
    moe_engine.state, metrics = moe_engine._train_step_fn(
        moe_engine.state, mb, moe_engine._base_rng)
    drop = float(jax.device_get(metrics["moe_drop_fraction"]))
    counts = np.asarray(jax.device_get(metrics["moe_expert_tokens"]))
    imbalance = float(counts.max() / max(1e-9, counts.mean()))

    # Analytic FFN tree: params grow ~E x, per-token FLOPs ~k x.
    H, F = dense_model.hidden_size, dense_model.ffn_size
    L = dense_model.num_layers
    ffn_dense = 2 * H * F
    router = H * E
    flops_ratio = (K * ffn_dense + router) / ffn_dense
    tokens_per_device = (B // moe_engine.replica_size) * SEQ
    wire = hlo_audit.moe_alltoall_wire_model(
        hidden=H, num_experts=E, top_k=K, capacity_factor=CF, ep=EP,
        n_moe_layers=L, bytes_per_el=4,
        tokens_per_device=tokens_per_device)
    # FFN matmul FLOPs the experts add per device per step (fwd+bwd, 6x
    # multiply-add accounting) vs the wire those tokens cost.
    ffn_flops_per_step = 6 * K * ffn_dense * L * tokens_per_device

    # --- Expert compute: einsum pair vs the grouped-GEMM kernel ------- #
    # The shard-local [E,C,H]x[E,H,F] FFN at the exact shapes the moe
    # engine above dispatches. On TPU both paths are timed; on the CPU
    # dev box only the einsum pair is timed (interpret-mode Pallas
    # measures the interpreter, not the kernel) and the grouped-GEMM win
    # is the structural HBM-byte projection (the BENCH_r06 convention).
    from deepspeed_tpu.ops.grouped_gemm import grouped_ffn
    Cap = int(wire["capacity"])
    rr = np.random.default_rng(2)
    xb = jnp.asarray(rr.standard_normal((E, Cap, H)), jnp.float32)
    ew1 = jnp.asarray(rr.standard_normal((E, H, F)) * H ** -0.5,
                      jnp.float32)
    eb1 = jnp.zeros((E, F), jnp.float32)
    ew2 = jnp.asarray(rr.standard_normal((E, F, H)) * F ** -0.5,
                      jnp.float32)
    eb2 = jnp.zeros((E, H), jnp.float32)

    def einsum_ffn(x, w1, b1, w2, b2):
        h = jnp.einsum("ech,ehf->ecf", x, w1) + b1[:, None, :]
        h = jax.nn.gelu(h, approximate=True)
        return jnp.einsum("ecf,efh->ech", h, w2) + b2[:, None, :]

    def _time_fn(fn, *a):
        f = jax.jit(fn)
        jax.block_until_ready(f(*a))
        reps = 100
        t0 = time.perf_counter()
        out = None
        for _ in range(reps):
            out = f(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps

    einsum_layer_wall = _time_fn(einsum_ffn, xb, ew1, eb1, ew2, eb2)
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        grouped_layer_wall = _time_fn(
            lambda *a: grouped_ffn(*a, False), xb, ew1, eb1, ew2, eb2)
        expert_speedup = einsum_layer_wall / grouped_layer_wall
        grouped_step = round(grouped_layer_wall * L, 6)
    else:
        # Fwd epilogue fusion drops the [E,C,F] pre-activation HBM
        # round-trip (1 write + 1 read per layer, f32); the backward's
        # recompute trades one extra grouped GEMM for not HOLDING that
        # residual across fwd->bwd (peak activation memory, not time).
        hbm_gb_s = 819.0
        saved_bytes = L * 2 * E * Cap * F * 4
        einsum_step = einsum_layer_wall * L
        # Projection target is the v5e HBM clock, not the CPU wall:
        # report the byte delta and its v5e-seconds, never a CPU ratio.
        expert_speedup = None
        grouped_step = None
    expert_compute = {
        "shapes": {"E": E, "C": Cap, "H": H, "F": F, "layers": L},
        "einsum_wall_s_per_step": round(einsum_layer_wall * L, 6),
        "grouped_gemm_wall_s_per_step": grouped_step,
        "measured_on": jax.default_backend(),
        "projected": not on_tpu,
    }
    if on_tpu:
        expert_compute["grouped_over_einsum_speedup"] = round(
            expert_speedup, 4)
    else:
        expert_compute.update({
            "projected_saved_hbm_bytes_per_step": int(saved_bytes),
            "projected_saved_s_per_step_v5e": round(
                saved_bytes / (hbm_gb_s * 1e9), 9),
            "assumptions": {
                "hbm_gb_s": hbm_gb_s,
                "model": ("fused bias+GELU epilogue removes the "
                          "[E,C,F] f32 pre-activation write+read per "
                          "layer fwd; bwd recompute is byte-neutral "
                          "(re-materializes what the einsum path saved)"
                          " but frees the held residual"),
            },
            "note": ("PROJECTED on the CPU dev box: the einsum wall is "
                     "the CPU structural figure; the grouped-GEMM win "
                     "is the analytic HBM-byte delta at v5e bandwidth. "
                     "A TPU session re-records both walls measured "
                     "(python ablate_moe.py --record)."),
        })

    record = {
        "generated_by": "ablate_moe.py",
        "methodology": (
            "8-device CPU host mesh (ep=4 x dp=2): walls exercise the "
            "dispatch/bucketing/all-to-all STRUCTURE, not ICI latency — "
            "the emulated interconnect is memcpy. Wire bytes are the "
            "analytic ring model COMM_AUDIT.json verifies against the "
            "compiled program; params/FLOP ratios are exact tree "
            "arithmetic. Same convention as ZERO3_BENCH."),
        "config": {"model": "gpt2-tiny", "num_experts": E, "top_k": K,
                   "capacity_factor": CF, "ep": EP, "batch": B,
                   "seq": SEQ, "steps": STEPS},
        "measured": {
            "dense_wall_s_per_step": round(dense_wall, 4),
            "moe_wall_s_per_step": round(moe_wall, 4),
            "moe_over_dense_wall": round(moe_wall / dense_wall, 3),
            "drop_fraction": round(drop, 5),
            "expert_imbalance_max_over_mean": round(imbalance, 3),
        },
        "headline": {
            "total_params_dense": int(dense_params),
            "total_params_moe": int(moe_params),
            "params_ratio": round(moe_params / dense_params, 3),
            "ffn_params_ratio": float(E),
            "ffn_flops_per_token_ratio": round(flops_ratio, 3),
            "note": (
                "the MoE scaling trade: the FFN parameter tree grows "
                f"{E}x while its per-token step FLOPs grow only "
                f"~{flops_ratio:.2f}x (top-{K} routing + the H*E "
                "router) — params per step-FLOP up "
                f"{E / flops_ratio:.1f}x on the FFN tree"),
        },
        "wire": {
            **{k: wire[k] for k in
               ("wire_bytes_per_token", "wire_bytes_per_step",
                "dispatch_buffer_bytes", "capacity")},
            "ffn_expert_flops_per_step_per_device":
                int(ffn_flops_per_step),
            "alltoall_bytes_per_expert_flop": round(
                wire["wire_bytes_per_step"] / ffn_flops_per_step, 6),
            "note": (
                "per optimizer step per device: 4 all-to-alls per MoE "
                "layer x (ep-1)/ep of the [E,C,H] buffer, vs the k x "
                "FFN matmul FLOPs those routed tokens execute"),
        },
        "expert_compute": expert_compute,
        # bench_gate parses this shape (drop-fraction ceiling gate).
        "moe": {"available": True,
                "drop_fraction": {"p95": round(drop, 5),
                                  "p50": round(drop, 5)}},
    }
    print(json.dumps(record, indent=1))
    if RECORD:
        with open(OUT, "w") as f:
            json.dump(record, f, indent=1)
        print(f"wrote {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Dev tool: how matmul-bound is the bench train step?

Times a pure-GEMM replay of the training step's entire matmul schedule —
per layer and per direction (fwd, dx, dw at their true shapes), the
chunked-CE head's three GEMMs, and the actual flash-attention fwd+bwd
kernels — and compares that floor against the measured end-to-end step.

floor/step >= 0.90 means the remaining MFU gap is in the matmuls
themselves (shape/tiling limits), not in elementwise work, the optimizer,
or dispatch — the "provably done" criterion for the utilization ladder.
Timing method: per-op cost is the SLOPE between a long-scan and a
length-1 call, so the fixed per-call dispatch cost cancels (see
timed()).

Usage: python profile_matmul_bound.py [model] [mbs] [measured step ms]
"""
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import GPT2_CONFIGS
from deepspeed_tpu.models.gpt2 import gpt2_flops_per_token

MODEL = sys.argv[1] if len(sys.argv) > 1 else "gpt2-large"
MBS = int(sys.argv[2]) if len(sys.argv) > 2 else 4
N = 256         # long-scan length: in-call work must dwarf per-call jitter

cfg = dataclasses.replace(GPT2_CONFIGS[MODEL], max_seq_length=1024)
S, H, V = cfg.max_seq_length, cfg.hidden_size, cfg.vocab_size
I = cfg.intermediate_size or 4 * H    # 0 = derived 4H (models.transformer)
nH, D = cfg.num_heads, cfg.hidden_size // cfg.num_heads
L, BS = cfg.num_layers, MBS * cfg.max_seq_length
key = jax.random.PRNGKey(0)


def timed(fn, *args):
    """ms per op via a two-point scan slope.

    Measurement rules:
    - the work inside ONE call must dwarf the per-call dispatch cost and
      its jitter -> scan length N (large), and the N=1 call time is
      SUBTRACTED (slope), not amortized;
    - the keep-alive feedback must need the full output: a one-element
      read lets XLA rewrite slice-of-dot into a vector dot and the GEMM
      evaporates; jnp.max(out) cannot be simplified away.
    """
    def make(length):
        @jax.jit
        def many(x, *rest):
            def body(c, _):
                out = fn(c, *rest)
                # max BEFORE any cast: astype would materialize a full
                # f32 copy of the output every iteration
                fb = jnp.max(out).astype(c.dtype)
                return c + fb * 1e-12, None
            c, _ = jax.lax.scan(body, x, None, length=length)
            return c
        return many

    def best(fn_, reps=3):
        _ = jax.block_until_ready(fn_(*args))
        _ = float(jnp.max(fn_(*args).astype(jnp.float32)))
        b = 1e9
        for _i in range(reps):
            t0 = time.perf_counter()
            _ = float(jnp.max(fn_(*args).astype(jnp.float32)))
            b = min(b, time.perf_counter() - t0)
        return b * 1e3

    t_long, t_one = best(make(N)), best(make(1))
    return max(t_long - t_one, 1e-6) / (N - 1)


def gemm_ms(m, k, n):
    """One [m,k]@[k,n] bf16 GEMM, timed in-scan."""
    a = jax.random.normal(key, (m, k), jnp.bfloat16)
    b = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.bfloat16)
    return timed(lambda aa, bb: jnp.dot(aa, bb,
                                        preferred_element_type=jnp.bfloat16),
                 a, b)


def linear_triple_ms(m, k, n):
    """fwd [m,k]@[k,n] + dx [m,n]@[n,k] + dw [k,m]@[m,n]."""
    return gemm_ms(m, k, n) + gemm_ms(m, n, k) + gemm_ms(k, m, n)


def flash_ms():
    from deepspeed_tpu.ops.flash_attention import flash_attention
    q = jax.random.normal(key, (MBS, S, nH, D), jnp.bfloat16)

    def fwd(qq):
        return flash_attention(qq, q, q, causal=True)

    def fb(qq):
        return jax.grad(lambda x: jnp.sum(
            fwd(x).astype(jnp.float32) ** 2))(qq)

    return timed(fwd, q), timed(fb, q)


def elementwise_ms():
    """Fused LN / bias+GELU kernels at the model's true shapes (fwd and
    fwd+bwd) — the measured cost of the elementwise work the ISSUE-8
    kernels leave on the table. TPU only (interpret-mode Pallas times
    the interpreter)."""
    from deepspeed_tpu.ops.fused_elementwise import (fused_bias_gelu,
                                                     fused_layer_norm)
    x = jax.random.normal(key, (BS, H), jnp.bfloat16)
    sc = jnp.ones((H,), jnp.float32)
    bi = jnp.zeros((H,), jnp.float32)
    y = jax.random.normal(key, (BS, I), jnp.bfloat16)
    bf = jnp.zeros((I,), jnp.float32)

    ln_fb = timed(lambda xx: jax.grad(lambda v: jnp.sum(
        fused_layer_norm(v, sc, bi).astype(jnp.float32) ** 2))(xx), x)
    ge_fb = timed(lambda yy: jax.grad(lambda v: jnp.sum(
        fused_bias_gelu(v, bf).astype(jnp.float32) ** 2))(yy), y)
    return ln_fb, ge_fb


def optimizer_apply_ms():
    """Analytic one-pass vs two-pass optimizer apply at the model's
    param count (ops/fused_update.apply_hbm_bytes priced at the chip
    HBM ceiling) — valid on any backend, it is arithmetic."""
    from deepspeed_tpu.models.gpt2 import gpt2_num_params
    from deepspeed_tpu.monitor.peaks import chip_peaks
    from deepspeed_tpu.ops.fused_update import apply_hbm_bytes
    n = gpt2_num_params(cfg)
    # Bench flags: master-free bf16 (params bf16, f32 moments), no
    # gradient clipping, no fp16 — at these flags one-pass == two-pass
    # in bytes (the honest model; fp16/cast configs are where the
    # two-pass sequencing paid extra passes).
    fake = {"p": jax.ShapeDtypeStruct((n,), jnp.bfloat16)}
    pricing = apply_hbm_bytes(fake, one_pass=True, clip=False, fp16=False)
    hbm = chip_peaks().hbm_bytes_per_sec
    return (pricing["one_pass"] / hbm * 1e3,
            pricing["two_pass"] / hbm * 1e3)


def main():
    print(f"{MODEL} mbs={MBS}: GEMM floor per train step", flush=True)
    per_layer = (linear_triple_ms(BS, H, 3 * H)     # qkv
                 + linear_triple_ms(BS, H, H)       # attn proj
                 + linear_triple_ms(BS, H, I)       # fc1
                 + linear_triple_ms(BS, I, H))      # fc2
    t_head = linear_triple_ms(BS, H, V)             # chunked-CE GEMMs
    t_attn_f, t_attn_fb = flash_ms()
    # remat "dots_flash" saves flash residuals: attention cost = fwd + the
    # fused bwd pass (which internally replays fwd once) = t_attn_fb.
    floor = per_layer * L + t_head + t_attn_fb * L
    print(f"  linear GEMMs x{L}: {per_layer * L:7.1f} ms "
          f"({per_layer:.3f}/layer)", flush=True)
    print(f"  CE-head GEMMs   : {t_head:7.1f} ms", flush=True)
    print(f"  flash attn x{L}  : {t_attn_fb * L:7.1f} ms "
          f"(fwd alone {t_attn_f * L:.1f})", flush=True)
    print(f"  GEMM floor      : {floor:7.1f} ms", flush=True)

    if len(sys.argv) <= 3:
        print("  (pass a step time measured on this chip as the third "
              "argument for the matmul-bound ratio)", flush=True)
        return
    achieved_ms = float(sys.argv[3])
    provenance = "cli"
    ratio = floor / achieved_ms
    flops = gpt2_flops_per_token(cfg, S) * MBS * S
    print(f"  achieved step   : {achieved_ms:7.1f} ms "
          f"({flops / achieved_ms / 1e9:.1f} TFLOPs) [{provenance}]",
          flush=True)
    print(f"  floor MFU       : {flops / floor / 1e9:7.1f} TFLOPs if "
          f"matmuls alone", flush=True)
    print(f"  matmul-bound ratio: {ratio:.2f} "
          f"({'>=0.90: matmul-bound' if ratio >= 0.9 else 'gap is non-GEMM work'})",
          flush=True)

    # --- ISSUE-8 non-GEMM decomposition: where the residual gap sits
    # with the fused kernels + one-pass optimizer in place. ---
    one_ms, two_ms = optimizer_apply_ms()
    print(f"  optimizer apply : {one_ms:7.1f} ms analytic one-pass "
          f"(two-pass {two_ms:.1f} at the bench flags — byte-equal "
          "here; fp16/cast configs are where two-pass paid more)",
          flush=True)
    if jax.devices()[0].platform == "tpu":
        ln_fb, ge_fb = elementwise_ms()
        elem = (ln_fb * 3 + ge_fb) * L   # 2 block LNs + ln_f share + GELU
        print(f"  fused LN/GELU   : {elem:7.1f} ms measured "
              f"(LN fwd+bwd {ln_fb:.3f}, GELU fwd+bwd {ge_fb:.3f} "
              f"per layer-instance)", flush=True)
    else:
        elem = None
        print("  fused LN/GELU   : skipped (CPU dev box — interpret-"
              "mode Pallas times the interpreter; see BENCH_r06's "
              "analytic model)", flush=True)
    residual = achieved_ms - floor - one_ms - (elem or 0.0)
    print(f"  residual non-GEMM gap: {residual:7.1f} ms "
          "(dispatch, remaining elementwise, grad-accum plumbing)",
          flush=True)


if __name__ == "__main__":
    main()

"""Perf ablation: fused Pallas optimizer apply vs the optax chain (dev
tool, not shipped API).

Times ONLY the optimizer apply (grads fixed, full train step excluded) for
a GPT-2-shaped param tree, across:

    optax           — optax.adamw update + apply_updates (XLA's own fusion)
    fused           — the Pallas apply (ops/fused_update): large leaves
                      updated in place, the rest through one packed buffer

and, under --sr, the master-free bf16 variants (stochastic-rounding write).

Timing is the two-point scan-slope method from profile_matmul_bound.py:
per-op cost = (t(scan N) - t(scan 1)) / (N - 1), so the fixed per-call
dispatch cost cancels.

Also prints the roofline: minimum HBM bytes an apply must move per param
element (read g+p+m+v, write p+m+v) and the implied HBM bandwidth — if
the fused apply's achieved GB/s sits at the chip's HBM ceiling, the
optimizer step is provably bandwidth-bound and no further kernel work
can buy more.

Usage: python ablate_fused_update.py [model] [--sr]
"""
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deepspeed_tpu.models import GPT2_CONFIGS, gpt2_init
from deepspeed_tpu.ops.fused_update import fused_adam

ARGS = [a for a in sys.argv[1:] if not a.startswith("--")]
SR = "--sr" in sys.argv
MODEL = ARGS[0] if ARGS else (
    "gpt2-large" if jax.devices()[0].platform == "tpu" else "gpt2-tiny")
N = 32 if jax.devices()[0].platform == "tpu" else 4

# v5e HBM ~819 GB/s (public figure); used only for the roofline fraction.
HBM_GBS = {"v5e": 819.0, "v4": 1228.0, "v5p": 2765.0, "v6e": 1640.0}


def chip_hbm_gbs() -> float:
    kind = getattr(jax.devices()[0], "device_kind", "").lower()
    for k, v in HBM_GBS.items():
        if k in kind:
            return v
    return 819.0


def timed_apply(apply_fn, grads, params, opt_state) -> float:
    """ms per apply via the two-point scan slope (see module docstring)."""
    def make(length):
        @jax.jit
        def many(g, p, s):
            def body(carry, _):
                p, s = carry
                return apply_fn(g, p, s), None
            (p, s), _ = jax.lax.scan(body, (p, s), None, length=length)
            return p, s
        return many

    def run(length):
        fn = make(length)
        out = fn(grads, params, opt_state)       # compile + warm
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = fn(grads, params, opt_state)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) * 1e3

    t_n, t_1 = run(N), run(1)
    return max(0.0, (t_n - t_1) / (N - 1))


def main():
    cfg = dataclasses.replace(GPT2_CONFIGS[MODEL], max_seq_length=256)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    pdtype = jnp.bfloat16 if SR else jnp.float32
    params = jax.tree_util.tree_map(
        lambda x: x.astype(pdtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, params)
    grads = jax.tree_util.tree_map(
        lambda x: (jax.random.normal(jax.random.PRNGKey(1), x.shape,
                                     jnp.float32) * 1e-3).astype(x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    n_leaves = len([l for l in jax.tree_util.tree_leaves(params)
                    if jnp.issubdtype(l.dtype, jnp.floating)])
    n_elems = sum(int(np.prod(l.shape))
                  for l in jax.tree_util.tree_leaves(params)
                  if jnp.issubdtype(l.dtype, jnp.floating))
    psize = 2 if SR else 4
    # Grads are f32 end-to-end: the engine promotes them at birth (f32
    # accumulation / second-moment precision) and the fused front end
    # flattens them in f32.
    gsize = 4
    # One apply must at minimum read g+p+m+v and write p+m+v (m/v f32).
    min_bytes = n_elems * (gsize + psize + 4 + 4 + psize + 4 + 4)

    sched = lambda c: jnp.asarray(1e-4, jnp.float32)
    key = jax.random.PRNGKey(7)
    variants = {}

    tx = optax.adamw(sched, weight_decay=0.01)

    def optax_apply(g, p, s):
        u, s = tx.update(g, s, p)
        if SR:
            from deepspeed_tpu.ops.stochastic_rounding import \
                tree_stochastic_round_bf16
            summed = jax.tree_util.tree_map(
                lambda p_, u_: p_.astype(jnp.float32) + u_, p, u)
            return tree_stochastic_round_bf16(summed, key), s
        return optax.apply_updates(p, u), s

    # Master-free: moments must init f32 even from bf16 params.
    opt_init = (lambda p: tx.init(jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), p))) if SR else tx.init
    variants["optax"] = (optax_apply, opt_init(params))

    ftx = fused_adam(sched, weight_decay=0.01)

    def fused_apply(g, p, s):
        return ftx.fused_apply(g, s, p, sr_key=key if SR else None)
    variants["fused"] = (fused_apply, ftx.init(params))

    results = {}
    for name, (fn, st) in variants.items():
        ms = timed_apply(fn, grads, params, st)
        results[name] = round(ms, 3)

    fused_ms = results["fused"]
    rec = {
        "model": f"{MODEL} ({n_elems/1e6:.1f}M params, {n_leaves} leaves)",
        "mode": "master-free bf16 + SR" if SR else "fp32 params",
        "ms_per_apply": results,
        "optax_vs_fused": round(results["optax"] / max(fused_ms, 1e-9), 2),
        "roofline": {
            "min_bytes_per_apply": min_bytes,
            "fused_achieved_gb_s": round(
                min_bytes / max(fused_ms, 1e-9) / 1e6, 1),
            "hbm_peak_gb_s": chip_hbm_gbs(),
            "hbm_bound_fraction": round(
                min_bytes / max(fused_ms, 1e-9) / 1e6 / chip_hbm_gbs(),
                3),
        },
    }
    print(json.dumps(rec))


if __name__ == "__main__":
    sys.exit(main())

"""Dev tool: block-sparse attention speedup vs dense-causal flash.

Reproduces the VERDICT metric: BigBird layout, S=32768, D=64, fwd+bwd,
vs the dense causal kernel at the same shapes. Sweeps super-tile factors:
bare ints are k-widening, "QxK" pairs (e.g. 2x4) widen both dims.
Usage: python bench_sparse.py [S] [tiles...]
"""
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu.ops.sparse_flash as sf
from deepspeed_tpu.utils.compile_cache import enable_compile_cache
from deepspeed_tpu.ops.flash_attention import _flash
from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
    BigBirdSparsityConfig)

if jax.devices()[0].platform != "tpu":
    sys.exit("bench_sparse.py needs a TPU (interpret-mode timings measure "
             "the interpreter)")
enable_compile_cache()
S = int(sys.argv[1]) if len(sys.argv) > 1 else 32768


def _tile(a):
    return tuple(int(x) for x in a.split("x")) if "x" in a else (1, int(a))


TILES = [_tile(a) for a in sys.argv[2:]] or \
    [(1, 1), (1, 4), (2, 2), (2, 4), (4, 2), (2, 8), (4, 4)]
B, NH, D = 1, 4, 64
N = 10

import os
_bb = dict(num_heads=NH,
           block=int(os.environ.get("DS_BENCH_BLOCK", "128")),
           different_layout_per_head=False)
if os.environ.get("DS_BENCH_DENSE_BB") == "1":
    # ~0.105 density at S=32768 (the VERDICT r3 metric point), scaled so
    # density holds across block sizes
    _sc = 128 / _bb["block"]
    _bb.update(num_random_blocks=max(1, int(12 * _sc)),
               num_sliding_window_blocks=max(1, int(9 * _sc)) | 1,
               num_global_blocks=max(1, int(3 * _sc)))
cfg = BigBirdSparsityConfig(**_bb)
layout = np.asarray(cfg.make_layout(S))
density = layout.mean()
key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (B * NH, S, D), jnp.bfloat16)
k = jax.random.normal(jax.random.fold_in(key, 1), (B * NH, S, D), jnp.bfloat16)
v = jax.random.normal(jax.random.fold_in(key, 2), (B * NH, S, D), jnp.bfloat16)
seed = jnp.zeros((), jnp.int32)
scale = 1.0 / math.sqrt(D)
print(f"S={S} heads={NH} density={density:.3f} "
      f"(ceiling ~{0.5/density:.1f}x vs dense-causal)", flush=True)


def timeit(make_fb):
    @jax.jit
    def many(q):
        def body(c, _):
            return make_fb(c), None
        out, _ = jax.lax.scan(body, q, None, length=N)
        return out
    out = many(q)
    _ = float(jnp.sum(out[0, 0].astype(jnp.float32)))
    t0 = time.perf_counter()
    out = many(q)
    _ = float(jnp.sum(out[0, 0].astype(jnp.float32)))
    return (time.perf_counter() - t0) / N * 1000


def dense_fb(c):
    def f(qq, kk, vv):
        o = _flash(qq, kk, vv, seed, scale, True, 0.0)
        return jnp.sum(o.astype(jnp.float32) ** 2)
    dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(c, k, v)
    return (dq + dk + dv).astype(c.dtype)


def sparse_fb(widen, qwiden):
    def fb(c):
        def f(qq, kk, vv):
            o = sf.sparse_flash_attention(qq, kk, vv, layout, causal=True,
                                          scale=scale, seed=seed,
                                          widen=widen, qwiden=qwiden)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(c, k, v)
        return (dq + dk + dv).astype(c.dtype)
    return fb


t_dense = timeit(dense_fb)
print(f"dense causal : {t_dense:8.1f} ms fwd+bwd", flush=True)
auto = sf.pick_tile(np.asarray(layout), block=S // layout.shape[1])
print(f"pick_tile auto: qw={auto[0]} kw={auto[1]}", flush=True)
for qw, w in TILES:
    lay2 = np.asarray(layout) != 0
    H_, nQ_, nK_ = lay2.shape
    if nK_ % w != 0 or nQ_ % qw != 0 or qw * w > 31:
        print(f"sparse {qw}x{w}: skipped (indivisible or >31 bits)",
              flush=True)
        continue
    nnz_w = sf.supertile_nnz(lay2, qw, w)
    t = timeit(sparse_fb(w, qw))
    print(f"sparse q{qw}xk{w}: {t:8.1f} ms fwd+bwd  ({t_dense/t:4.2f}x vs "
          f"dense; steps/head ~{nnz_w//H_})", flush=True)

"""Dev tool: micro-benchmark attention kernels standalone.

Single-dispatch timing: N iterations are chained inside one jitted
lax.scan (output feeds the next call's q), so per-dispatch overhead
doesn't swamp the kernel time.
Usage: python ablate_attn.py
"""
import math
import time

import jax
import jax.numpy as jnp

import deepspeed_tpu.ops.flash_attention as fa

B, S, NH, D = 4, 1024, 20, 64
L = 36
N = 20

key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (B * NH, S, D), jnp.bfloat16)
k = jax.random.normal(jax.random.fold_in(key, 1), (B * NH, S, D), jnp.bfloat16)
v = jax.random.normal(jax.random.fold_in(key, 2), (B * NH, S, D), jnp.bfloat16)
seed = jnp.zeros((), jnp.int32)
scale = 1.0 / math.sqrt(D)

fl_fwd_full = 4 * B * NH * S * S * D / 1e12


def timeit_chained(one, qinit, *rest):
    """one(q, *rest) -> same-shape-as-q; runs N chained iterations."""
    @jax.jit
    def many(q):
        def body(c, _):
            return one(c, *rest), None
        out, _ = jax.lax.scan(body, q, None, length=N)
        return out

    out = many(qinit)
    _ = float(jnp.sum(out[0, 0].astype(jnp.float32)))
    t0 = time.perf_counter()
    out = many(q)
    _ = float(jnp.sum(out[0, 0].astype(jnp.float32)))
    return (time.perf_counter() - t0) / N * 1000


def report(name, t_fwd, t_fb):
    print(f"{name:28s}: fwd {t_fwd:6.2f} ms ({fl_fwd_full/t_fwd*1000:6.1f} TF-equiv)"
          f"   fwd+bwd {t_fb:7.2f} ms   per-model {t_fb*L:6.1f} ms", flush=True)


def bench_ours(block):
    fa._BLOCK_TARGET = block

    def fwd_one(q, k, v):
        return fa._flash(q, k, v, seed, scale, True, 0.0).astype(q.dtype)

    def fb_one(q, k, v):
        def f(qq, kk, vv):
            o = fa._flash(qq, kk, vv, seed, scale, True, 0.0)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        return (dq + dk + dv).astype(q.dtype)

    report(f"ours block={block}", timeit_chained(fwd_one, q, k, v),
           timeit_chained(fb_one, q, k, v))


def bench_xla_dense():
    from deepspeed_tpu.models.transformer import dense_attention
    q4 = q.reshape(B, NH, S, D).transpose(0, 2, 1, 3)

    def fwd_one(q, k, v):
        return dense_attention(q, k, v, mask=None, causal=True).astype(q.dtype)

    def fb_one(q, k, v):
        def f(qq, kk, vv):
            o = dense_attention(qq, kk, vv, mask=None, causal=True)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        return (dq + dk + dv).astype(q.dtype)

    report("xla dense", timeit_chained(fwd_one, q4, q4, q4),
           timeit_chained(fb_one, q4, q4, q4))


def bench_jax_flash(bq, bkmaj, bk):
    from jax.experimental.pallas.ops.tpu import flash_attention as jfa
    bs = jfa.BlockSizes(block_q=bq, block_k_major=bkmaj, block_k=bk, block_b=1,
                        block_q_major_dkv=bq, block_k_major_dkv=bkmaj,
                        block_k_dkv=bk, block_q_dkv=bq,
                        block_k_major_dq=bkmaj, block_k_dq=bk, block_q_dq=bq)
    q4 = q.reshape(B, NH, S, D)

    def fwd_one(q, k, v):
        return jfa.flash_attention(q, k, v, causal=True, sm_scale=scale,
                                   block_sizes=bs).astype(q.dtype)

    def fb_one(q, k, v):
        def f(qq, kk, vv):
            o = jfa.flash_attention(qq, kk, vv, causal=True, sm_scale=scale,
                                    block_sizes=bs)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        return (dq + dk + dv).astype(q.dtype)

    report(f"jax flash q{bq}/k{bkmaj}/{bk}", timeit_chained(fwd_one, q4, q4, q4),
           timeit_chained(fb_one, q4, q4, q4))


for blk in (1024, 512, 256):
    try:
        bench_ours(blk)
    except Exception as e:
        print("ours", blk, "failed:", str(e)[:150], flush=True)
try:
    bench_xla_dense()
except Exception as e:
    print("xla dense failed:", str(e)[:300], flush=True)
for cfgs in ((512, 1024, 512), (512, 512, 512), (256, 512, 256)):
    try:
        bench_jax_flash(*cfgs)
    except Exception as e:
        print("jax flash", cfgs, "failed:", str(e)[:120])

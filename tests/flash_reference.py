"""Dense references for the flash kernels' tests: the keep-mask hash
replayed elementwise and softmax attention under that identical mask
(shared by test_flash_bands.py and the slow test_flash_dropout.py)."""
import jax
import jax.numpy as jnp
import numpy as np


def make_qkv(key, B, S, nH, D, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    shape = (B, S, nH, D)
    return tuple(jax.random.normal(k, shape, dtype) * 0.3 for k in ks)


def keep_mask(seed, BH, S, rate):
    """Elementwise replica of the kernel's _dropout_keep hash over the full
    [BH, S, S] score grid (block decomposition is irrelevant: the hash is a
    pure function of (seed, bh, q_pos, k_pos))."""
    bh = jnp.arange(BH, dtype=jnp.uint32)[:, None, None]
    qpos = jnp.arange(S, dtype=jnp.uint32)[None, :, None]
    kpos = jnp.arange(S, dtype=jnp.uint32)[None, None, :]
    stream = jnp.uint32(np.uint32(seed)) ^ (bh * jnp.uint32(0x85EBCA6B))
    x = qpos * jnp.uint32(0x9E3779B9) + kpos + stream
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    u = (x >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / 16777216.0)
    return u >= rate


def dense_dropped(q, k, v, keep, rate, causal):
    """softmax(qk/sqrt d) -> apply exact keep mask -> @v. q,k,v [B,S,nH,D];
    keep [B*nH, S, S]."""
    B, S, nH, D = q.shape
    qt = jnp.einsum("bsnd,btnd->bnst", q, k).astype(jnp.float32)
    qt = qt / np.sqrt(D)
    if causal:
        cm = jnp.tril(jnp.ones((S, S), jnp.bool_))
        qt = jnp.where(cm[None, None], qt, -1e30)
    w = jax.nn.softmax(qt, axis=-1)
    w = jnp.where(keep.reshape(B, nH, S, S), w / (1.0 - rate), 0.0)
    return jnp.einsum("bnst,btnd->bsnd", w.astype(v.dtype), v)

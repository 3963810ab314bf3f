"""Ring attention (sequence parallelism): exactness vs dense attention on
the virtual multi-chip mesh, causal + bidirectional, gradients included."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.ring_attention import ring_attention, ring_attention_fn
from deepspeed_tpu.models.transformer import dense_attention
from deepspeed_tpu.parallel.topology import build_mesh


# The sp>1 meshes below all carry a dp axis > 1 alongside the manual seq
# axis — a partially-manual shard_map old jax cannot compile.


def _qkv(seed, B=2, S=32, nH=2, D=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, S, nH, D), jnp.float32) * 0.4
                 for k in ks)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sp", [2, 4])
def test_ring_matches_dense(causal, sp):
    mesh = build_mesh(sp=sp, devices=jax.devices()[:sp * 2])  # dp=2 x sp
    q, k, v = _qkv(0)
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=causal))(q, k, v)
    ref = dense_attention(q, k, v, mask=None, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_grads_match_dense(causal):
    mesh = build_mesh(sp=4, devices=jax.devices()[:8])
    q, k, v = _qkv(1)
    probe = jax.random.normal(jax.random.PRNGKey(9), q.shape) * 0.1

    def loss_ring(q, k, v):
        o = ring_attention(q, k, v, mesh, causal=causal)
        return jnp.sum(o * probe)

    def loss_dense(q, k, v):
        o = dense_attention(q, k, v, mask=None, causal=causal)
        return jnp.sum(o * probe)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gr, gd, n in zip(g_ring, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   rtol=5e-5, atol=5e-5,
                                   err_msg=f"d{n}")


def test_ring_in_transformer_block():
    """ring_attention_fn plugs into apply_blocks as the attention_fn."""
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  apply_blocks,
                                                  init_block_params)
    mesh = build_mesh(sp=4, devices=jax.devices()[:8])
    cfg = TransformerConfig(hidden_size=32, num_heads=2, num_layers=2,
                            max_seq_length=32, hidden_dropout=0.0,
                            attn_dropout=0.0, causal=True)
    p = init_block_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32), jnp.float32)
    ring = jax.jit(lambda p, x: apply_blocks(
        p, x, cfg, deterministic=True,
        attention_fn=ring_attention_fn(mesh)))(p, x)
    ref = apply_blocks(p, x, cfg, deterministic=True,
                       attention_fn=dense_attention)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_sp1_fallback():
    mesh = build_mesh(devices=jax.devices()[:2])   # no seq axis
    q, k, v = _qkv(2, S=16)
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = dense_attention(q, k, v, mask=None, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)

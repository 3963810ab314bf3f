"""In-kernel attention dropout: the flash path must run (no dense fallback)
under real training configs (dropout 0.1), and its forward/backward must
match a dense reference that applies the *identical* regenerated mask.

Reference behavior being matched: the fused kernel keeps dropout inside the
attention computation and replays the same mask in backward
(ops/transformer/transformer.py:330-466, csrc/transformer/
dropout_kernels.cu) — here the mask is regenerated from the seed instead of
saved.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import flash_attention as fa
from flash_reference import (dense_dropped as _dense_dropped,
                             keep_mask as _keep_mask, make_qkv as _make_qkv)

pytestmark = pytest.mark.slow  # whole-module slow tier (see conftest)


@pytest.mark.parametrize("causal", [False, True])
def test_dropout_fwd_matches_masked_dense(causal):
    B, S, nH, D = 2, 256, 2, 64
    rate = 0.1
    q, k, v = _make_qkv(jax.random.PRNGKey(0), B, S, nH, D)
    rng = jax.random.PRNGKey(7)

    out = fa.flash_attention(q, k, v, causal=causal, attn_dropout=rate,
                             rng=rng, deterministic=False)

    seed = int(jax.random.bits(rng, (), jnp.uint32))
    keep = _keep_mask(seed, B * nH, S, rate)
    ref = _dense_dropped(q, k, v, keep, rate, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_dropout_grads_match_masked_dense(causal):
    B, S, nH, D = 1, 256, 2, 64
    rate = 0.15
    q, k, v = _make_qkv(jax.random.PRNGKey(1), B, S, nH, D)
    rng = jax.random.PRNGKey(11)
    seed = int(jax.random.bits(rng, (), jnp.uint32))
    keep = _keep_mask(seed, B * nH, S, rate)

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, causal=causal, attn_dropout=rate,
                               rng=rng, deterministic=False)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape) * 0.01))

    def loss_ref(q, k, v):
        o = _dense_dropped(q, k, v, keep, rate, causal)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape) * 0.01))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} mismatch")


def test_flash_path_taken_with_dropout(monkeypatch):
    """The default training config (dropout 0.1, 128-aligned seq) must run
    the kernel — the silent dense fallback for dropout is gone."""
    import deepspeed_tpu.models.transformer as mt

    def boom(*a, **kw):
        raise AssertionError("dense fallback used despite dropout>0")

    monkeypatch.setattr(mt, "dense_attention", boom)
    q, k, v = _make_qkv(jax.random.PRNGKey(2), 1, 128, 2, 64)
    out = fa.flash_attention(q, k, v, causal=True, attn_dropout=0.1,
                             rng=jax.random.PRNGKey(3), deterministic=False)
    assert out.shape == q.shape


def test_dropout_deterministic_given_rng():
    q, k, v = _make_qkv(jax.random.PRNGKey(4), 1, 128, 2, 64)
    rng = jax.random.PRNGKey(5)
    o1 = fa.flash_attention(q, k, v, attn_dropout=0.2, rng=rng,
                            deterministic=False)
    o2 = fa.flash_attention(q, k, v, attn_dropout=0.2, rng=rng,
                            deterministic=False)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o3 = fa.flash_attention(q, k, v, attn_dropout=0.2,
                            rng=jax.random.PRNGKey(6), deterministic=False)
    assert np.abs(np.asarray(o1) - np.asarray(o3)).max() > 1e-6


def test_dropout_fraction_and_scaling():
    """Dropped fraction ~= rate; kept weights scaled by 1/(1-rate):
    E[out] ~= dropout-free out."""
    B, S, nH, D = 2, 256, 4, 64
    rate = 0.3
    q, k, v = _make_qkv(jax.random.PRNGKey(8), B, S, nH, D)
    seeds = [int(jax.random.bits(jax.random.PRNGKey(i), (), jnp.uint32))
             for i in range(4)]
    fracs = [float(jnp.mean(~_keep_mask(s, B * nH, S, rate)))
             for s in seeds]
    assert abs(np.mean(fracs) - rate) < 0.01

    outs = [fa.flash_attention(q, k, v, attn_dropout=rate,
                               rng=jax.random.PRNGKey(i),
                               deterministic=False) for i in range(8)]
    mean_out = np.mean([np.asarray(o) for o in outs], axis=0)
    base = fa.flash_attention(q, k, v, attn_dropout=0.0, deterministic=True)
    # Monte-Carlo over 8 masks: loose tolerance, catches missing 1/(1-p).
    err = np.abs(mean_out - np.asarray(base)).mean()
    scale_err = np.abs(np.asarray(base)).mean()
    assert err < 0.25 * scale_err, (err, scale_err)

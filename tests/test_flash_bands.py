"""The dense flash kernels one block covers (`_fwd_kernel`'s whole-row body
and `_bwd_fused_kernel`), numerically, in interpret mode: what every train
cell runs in every layer.  A causal square is computed in static row bands
that stop at their own diagonal tile; everything else is one band = the
full rectangle.  Held against the dense reference under the IDENTICAL
regenerated keep-mask, and against the full-square body the kernels had
before the bands (kept here as the oracle: it reads every tile).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from deepspeed_tpu.ops import flash_attention as fa
from flash_reference import dense_dropped, keep_mask, make_qkv

B, NH, D = 1, 2, 64
BANDS = 4
S = BANDS * fa._BAND
assert fa._pick_block(S) == S, "one block must cover S: the kernels under test"
RNG = jax.random.PRNGKey(7)
SEED = int(jax.random.bits(RNG, (), jnp.uint32))
SCALE = 1.0 / math.sqrt(D)


def _full_square_fwd(q, k, v, seed, causal, dropout):
    """The whole-row forward as it was before the bands: ONE [S, S] score
    square a head, masked after the fact.  q, k, v [BH, S, D]."""
    def body(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref):
        bh = pl.program_id(0)
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        n = q.shape[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * SCALE
        if causal:
            s = fa._causal_mask(s, 0, 0, n, n)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        if dropout > 0.0:
            keep = fa._dropout_keep(seed_ref[0, 0], bh, 0, 0, n, n, dropout)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout)), 0.0)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (pv / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m[:, 0] + jnp.log(l_safe[:, 0])

    BH, n, d = q.shape
    full = pl.BlockSpec((1, n, d), lambda b: (b, 0, 0))
    return pl.pallas_call(
        body, grid=(BH,),
        in_specs=[pl.BlockSpec((1, 1), lambda b: (0, 0)), full, full, full],
        out_specs=[full, pl.BlockSpec((1, 1, n), lambda b: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, n, d), q.dtype),
                   jax.ShapeDtypeStruct((BH, 1, n), jnp.float32)],
        interpret=True)(fa._seed_arr(seed), q, k, v)


def _weights(x):
    return jnp.cos(jnp.arange(x.size).reshape(x.shape) * 0.01)


@functools.lru_cache(maxsize=None)
def _flash_and_dense(causal, rate):
    """(output, dq, dk, dv) of the kernels and of the dense reference under
    the same mask, once per (causal, rate)."""
    q, k, v = make_qkv(jax.random.PRNGKey(0), B, S, NH, D)
    keep = keep_mask(SEED, B * NH, S, rate)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, attn_dropout=rate,
                                  rng=RNG, deterministic=rate == 0.0)

    def dense(q, k, v):
        return dense_dropped(q, k, v, keep, rate, causal)

    out = []
    for fn in (flash, dense):
        o = fn(q, k, v)
        grads = jax.grad(lambda *a: jnp.sum(fn(*a) * _weights(o)),
                         argnums=(0, 1, 2))(q, k, v)
        out.append(dict(zip(("forward", "dq", "dk", "dv"),
                            map(np.asarray, (o,) + grads))))
    return out


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
@pytest.mark.parametrize("rate", [0.0, 0.1])
@pytest.mark.parametrize("causal", [False, True])
def test_kernels_match_the_dense_reference_under_the_same_mask(
        causal, rate, what):
    assert fa._row_band(S, S, causal) == (fa._BAND if causal else S)
    flash, dense = _flash_and_dense(causal, rate)
    tol = 2e-4 if what == "forward" else 2e-3     # test_flash_dropout's
    np.testing.assert_allclose(flash[what], dense[what], rtol=tol, atol=tol)


_bh = fa._to_bh


@pytest.mark.parametrize("rate", [0.0, 0.1])
def test_non_causal_is_the_full_square_bit_for_bit(rate):
    """Every causal=False caller is ONE band: the body the kernel had."""
    q, k, v = map(_bh, make_qkv(jax.random.PRNGKey(1), B, S, NH, D))
    seed = jnp.asarray(SEED, jnp.uint32).astype(jnp.int32)
    o, lse = fa._flash_fwd(q, k, v, None, SCALE, False, rate, seed)
    o0, lse0 = _full_square_fwd(q, k, v, seed, False, rate)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o0))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse0))


def test_the_kernels_keep_mask_is_the_position_hash():
    """Read the mask the banded kernel APPLIED back out of it: with q = 0
    every visible weight is 1 / (row + 1), and one-hot values carry 64
    columns of the kept weights a call."""
    rate = 0.1
    seed = jnp.asarray(SEED, jnp.uint32).astype(jnp.int32)
    z = jnp.zeros((B * NH, S, D), jnp.float32)
    kept = []
    for c in range(S // D):
        v = jnp.zeros((S, D)).at[c * D + jnp.arange(D), jnp.arange(D)].set(1.)
        o, _ = fa._flash_fwd(z, z, jnp.broadcast_to(v, z.shape), None, SCALE,
                             True, rate, seed)
        kept.append(np.asarray(o) != 0.0)
    kept = np.concatenate(kept, axis=-1)                   # [BH, S, S]
    want = np.asarray(keep_mask(SEED, B * NH, S, rate)) & \
        np.tril(np.ones((S, S), bool))
    np.testing.assert_array_equal(kept, want)
    # ... and the kept weights are the full-square body's.
    q, k, v = map(_bh, make_qkv(jax.random.PRNGKey(2), B, S, NH, D))
    o, lse = fa._flash_fwd(q, k, v, None, SCALE, True, rate, seed)
    o0, lse0 = _full_square_fwd(q, k, v, seed, True, rate)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o0), rtol=0,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse0), rtol=0,
                               atol=1e-6)


@pytest.mark.parametrize("what", ["forward", "dq"])
def test_tiles_above_the_diagonal_are_never_read(what):
    """Values of inf from key band j on: the query bands below j never read
    them.  The full-square body does (0 x inf = NaN)."""
    j = BANDS - 1
    q, k, v = map(_bh, make_qkv(jax.random.PRNGKey(3), B, S, NH, D))
    v_inf = v.at[:, j * fa._BAND:].set(jnp.inf)
    below = slice(0, j * fa._BAND)
    seed = jnp.zeros((), jnp.int32)
    if what == "forward":
        def run(v):
            return fa._flash_fwd(q, k, v, None, SCALE, True, 0.0, seed)[0]
        square = _full_square_fwd(q, k, v_inf, seed, True, 0.0)[0]
        assert np.isnan(np.asarray(square)[:, below]).all()
    else:
        def run(v):
            return jax.grad(lambda q: jnp.sum(
                fa._flash(q, k, v, seed, SCALE, True, 0.0)[:, below]
                * _weights(q)[:, below]))(q)
    clean, dirty = np.asarray(run(v)), np.asarray(run(v_inf))
    assert np.isfinite(dirty[:, below]).all()
    np.testing.assert_array_equal(dirty[:, below], clean[:, below])
    if what == "forward":
        assert not np.isfinite(dirty[:, j * fa._BAND:]).any()


@pytest.mark.parametrize("s, sk, causal, want", [
    (1024, 1024, True,
     fa._BAND ** 2 * (1024 // fa._BAND) * (1024 // fa._BAND + 1) // 2),
    (1024, 1024, False, 1024 * 1024),
    (1024, 512, False, 1024 * 512),
    (fa._BAND, fa._BAND, True, fa._BAND ** 2),      # below two bands
    (2048, 2048, True, 3 * 1024 * 1024),            # the grid path's blocks
])
def test_computed_scores(s, sk, causal, want):
    assert fa.computed_scores(s, sk, causal) == want
    if (s, sk, causal) == (1024, 1024, True):     # by the constant chosen
        assert want == {128: 589_824, 256: 655_360, 512: 786_432}[fa._BAND]


@pytest.mark.parametrize("causal", [False, True])
def test_the_calls_cost_estimates_carry_the_count(causal):
    x = jnp.zeros((NH, 1024, D), jnp.bfloat16)
    seed = jnp.zeros((), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        fa._flash(q, k, v, seed, SCALE, causal, 0.1).astype(jnp.float32)),
        argnums=(0, 1, 2)))(x, x, x)
    costs = {}

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                costs[eqn.params["name"]] = eqn.params["cost_estimate"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    scores = fa.computed_scores(1024, 1024, causal)
    for name, matmuls in (("_fwd_kernel", 2), ("_bwd_fused_kernel", 5)):
        assert costs[name].flops == 2 * matmuls * NH * scores * D, name
        assert costs[name].transcendentals == NH * scores, name

"""Kimi Delta Attention's three forms (``ops/kda.py``, PR 52) held to each
other: the chunked delta rule (the WY / UT form with a decay a CHANNEL)
against the token-by-token recurrence under decays from 0.999 down to 1e-3 a
token, across chunk and sub-block edges, from a carried state, past dead
rows and at every state it can keep; the triangular inverse against numpy's;
the decode kernel (interpret mode) against the plain update, dead slots
included; and all three where the write strength ``beta`` is drawn on (1, 2)
(``kda_allow_neg_eigval``: Solar-Open2's layers, PR 64) and at its 64 heads
of 128."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deepspeed_tpu.ops import kda                              # noqa: E402

# decay a token and channel, drawn log-uniform on (lo, hi)
DECAYS = {"slow": (0.999, 0.9999), "mixed": (1e-3, 0.999),
          "fast": (1e-3, 2e-3)}


def case(T, nh=2, dk=32, dv=16, decay="mixed", seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1,      # noqa: E731
                                         keepdims=True)
    lo, hi = DECAYS[decay]
    return dict(
        S0=jax.random.normal(ks[5], (nh, dk, dv)),
        q=unit(jax.random.normal(ks[0], (T, nh, dk))) * dk ** -0.5,
        k=unit(jax.random.normal(ks[1], (T, nh, dk))),
        v=jax.random.normal(ks[2], (T, nh, dv)),
        g=jax.random.uniform(ks[3], (T, nh, dk), minval=np.log(lo),
                             maxval=np.log(hi)),
        beta=jax.random.uniform(ks[4], (T, nh), minval=0.1, maxval=0.9))


def token_by_token(c, rows=None):
    """(o [rows, nh, dv], the state after every row)."""
    def step(S, r):
        o, S = kda.recurrent_update(S[None], *(x[None] for x in r))
        return S[0], (o[0], S[0])
    steps = tuple(c[n][:rows] for n in ("q", "k", "v", "g", "beta"))
    _, (o, states) = jax.lax.scan(step, c["S0"], steps)
    return o, states


def chunked(c, chunk, keep=None, **kw):
    return kda.chunked_delta_rule(
        c["S0"], c["q"], c["k"], c["v"], c["g"], c["beta"], chunk=chunk,
        keep=keep, **kw)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("T,chunk", [(128, 64), (64, 16), (96, 32),
                                     (48, 8)])
def test_the_chunked_form_is_the_recurrence(decay, T, chunk):
    """Every row's output (so every sub-block's edge inside a chunk) and the
    state after every chunk, in float32 to 1e-5 — with ``e^-G`` past
    float32's range in the fast cases (64 rows at 1e-3 a token: e^442)."""
    c = case(T, decay=decay)
    o_want, states = token_by_token(c)
    for i in range(T // chunk):
        o, S, kept = chunked(c, chunk, keep=jnp.int32(i))
        np.testing.assert_allclose(kept, states[(i + 1) * chunk - 1],
                                   atol=1e-5)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, o_want, atol=1e-5)
    np.testing.assert_allclose(S, states[-1], atol=1e-5)


# beta on (1, 2): the transition I - beta k k^T has an eigenvalue in (-1, 0)
# along the key.  The recurrence stays a contraction, but the chunked form's
# A = beta . tril(K K^T . decay) has entries up to 2 and the unit-lower
# inverse grows with them; under a slow decay the STATE grows too (to 6-14
# where beta < 1 leaves 2-3).  Measured here in float32 (PR 64): outputs
# within 1e-6 (3.4e-6 at beta on (1.9, 2) under the slow decay), states within
# 4.3e-6 (2.1e-5 there, of a largest entry of 14): 3-4x what beta < 1 reads
# and the same RELATIVE to the state, so the limits are the file's 1e-5 on
# outputs and 1e-5 of the state's largest entry on states.
STRONG = {"over_one": (1.0, 2.0), "near_two": (1.9, 2.0)}


def strong(c, name, seed=9):
    lo, hi = STRONG[name]
    return dict(c, beta=jax.random.uniform(
        jax.random.PRNGKey(seed), c["beta"].shape, minval=lo, maxval=hi))


@pytest.mark.parametrize("beta", sorted(STRONG))
@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("T,chunk", [(128, 64), (64, 16)])
def test_the_chunked_form_is_the_recurrence_where_the_write_passes_one(
        beta, decay, T, chunk):
    c = strong(case(T, decay=decay), beta)
    assert float(c["beta"].min()) >= 1.0
    o_want, states = token_by_token(c)
    o, S, kept = chunked(c, chunk, keep=jnp.int32(0))
    scale = max(1.0, float(jnp.abs(states[-1]).max()))
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, o_want, atol=1e-5)
    np.testing.assert_allclose(S, states[-1], atol=1e-5 * scale)
    np.testing.assert_allclose(kept, states[chunk - 1], atol=1e-5 * scale)


def test_a_write_of_strength_two_reflects_the_state_along_the_key():
    """At beta = 2 and no decay, ``I - 2 k k^T`` is a reflection: the state's
    component along k changes sign (plus the write), its norm is kept — the
    eigenvalue -1 the published key allows; a second such step undoes it."""
    k = jnp.zeros((1, 1, 8)).at[0, 0, 2].set(1.0)
    S0 = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 8, 4))
    zero_v = jnp.zeros((1, 1, 4))
    g = jnp.zeros((1, 1, 8))
    two = jnp.full((1, 1), 2.0)
    _, S1 = kda.recurrent_update(S0, k * 8 ** -0.5, k, zero_v, g, two)
    np.testing.assert_allclose(S1[0, 0, 2], -S0[0, 0, 2], atol=1e-6)
    np.testing.assert_allclose(np.delete(S1[0, 0], 2, 0),
                               np.delete(S0[0, 0], 2, 0), atol=1e-6)
    _, S2 = kda.recurrent_update(S1, k * 8 ** -0.5, k, zero_v, g, two)
    np.testing.assert_allclose(S2, S0, atol=1e-6)


def test_a_chunk_that_is_no_multiple_of_the_sub_block_is_one_sub_block():
    c = case(40, decay="fast")
    o_want, states = token_by_token(c)
    o, S, _ = chunked(c, 20)                       # 20 % 16 != 0
    np.testing.assert_allclose(o, o_want, atol=1e-5)
    np.testing.assert_allclose(S, states[-1], atol=1e-5)


def test_dead_rows_neither_decay_the_state_nor_write_to_it():
    c = case(64, decay="mixed", seed=1)
    o_want, states = token_by_token(c, 37)
    live = jnp.arange(64) < 37
    c = dict(c, g=jnp.where(live[:, None, None], c["g"], 0.0),
             beta=jnp.where(live[:, None], c["beta"], 0.0))
    o, S, _ = chunked(c, 16)
    np.testing.assert_allclose(o[:37], o_want, atol=1e-5)
    np.testing.assert_allclose(S, states[36], atol=1e-5)


def test_the_default_products_are_three_bf16_passes_not_one():
    """``Precision.HIGH`` on the products against the state (what the
    serving path runs); on the CPU every precision is float32, so only the
    argument's plumbing is seen here — the chip decides the rest
    (perfbench/runners/longgen.py, rule 3 b)."""
    c = case(32, seed=2)
    o_want, states = token_by_token(c)
    o, S, _ = kda.chunked_delta_rule(
        c["S0"], c["q"], c["k"], c["v"], c["g"], c["beta"], chunk=16)
    np.testing.assert_allclose(o, o_want, atol=1e-5)
    np.testing.assert_allclose(S, states[-1], atol=1e-5)


def test_the_chunked_form_refuses_rows_that_are_not_whole_chunks():
    c = case(24)
    with pytest.raises(ValueError, match="in chunks of"):
        chunked(c, 7)


@pytest.mark.parametrize("C", [16, 32, 64, 24])
def test_the_unit_lower_inverse_is_the_inverse(C):
    A = np.tril(np.random.default_rng(C).normal(size=(3, C, C)), -1) \
        .astype(np.float32) * 0.3
    T = kda.unit_lower_inverse(jnp.asarray(A))
    want = np.linalg.inv(np.eye(C, dtype=np.float64) + A.astype(np.float64))
    np.testing.assert_allclose(T, want, atol=2e-4, rtol=2e-4)
    assert not np.triu(np.asarray(T), 1).any()


@pytest.mark.parametrize("pages", [[3, -1, 0, 5, -1], [-1, -1, -1, -1, -1],
                                   [1, 2, 3, 4, 5]])
@pytest.mark.parametrize("nh,d", [(4, 128), (2, 16)])
def test_the_decode_kernel_is_the_plain_update_in_place(pages, nh, d):
    c = case(5, nh=nh, dk=d, dv=d, decay="mixed", seed=3)
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 1, 6, nh, d, d))
    pages = jnp.asarray([pages], jnp.int32)
    args = tuple(c[n][None] for n in ("q", "k", "v", "g", "beta"))
    o, new = jax.jit(kda.state_update)(pool, 1, pages, *args)
    live = np.asarray(pages[0] >= 0)
    at = jnp.maximum(pages[0], 0)
    o_want, S_want = kda.recurrent_update(pool[1, 0, at],
                                          *(a[0] for a in args))
    np.testing.assert_allclose(np.asarray(o[0])[live],
                               np.asarray(o_want)[live], atol=1e-5)
    assert not np.asarray(o[0])[~live].any()
    want = np.array(pool)
    want[1, 0, np.asarray(at)[live]] = np.asarray(S_want)[live]
    np.testing.assert_allclose(new, want, atol=1e-6)   # other pages as were


@pytest.mark.parametrize("beta", sorted(STRONG))
def test_the_decode_kernel_at_64_heads_where_the_write_passes_one(beta):
    """Solar-Open2's tile: 64 heads of 128 x 128, 16 a grid step, four steps
    a stream; a dead slot between two live ones."""
    nh, d = 64, 128
    c = strong(case(3, nh=nh, dk=d, dv=d, decay="mixed", seed=3), beta)
    pool = jax.random.normal(jax.random.PRNGKey(9), (1, 1, 3, nh, d, d))
    pages = jnp.asarray([[2, -1, 0]], jnp.int32)
    args = tuple(c[n][None] for n in ("q", "k", "v", "g", "beta"))
    o, new = jax.jit(kda.state_update)(pool, 0, pages, *args)
    live = np.asarray(pages[0] >= 0)
    at = jnp.maximum(pages[0], 0)
    o_want, S_want = kda.recurrent_update(pool[0, 0, at],
                                          *(a[0] for a in args))
    np.testing.assert_allclose(np.asarray(o[0])[live],
                               np.asarray(o_want)[live], atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(new)[0, 0, np.asarray(at)[live]],
        np.asarray(S_want)[live], atol=2e-6)
    np.testing.assert_array_equal(np.asarray(new)[0, 0, 1], pool[0, 0, 1])


def test_the_kernel_tiles_heads_under_its_budget():
    assert kda.state_tile(32, 128, 128) == (32, 128, 128)
    assert kda.tile_heads(32, 128, 128) == 16          # 1 MiB a grid step
    assert kda.tile_heads(64, 128, 128) == 16          # four steps a stream
    assert kda.tile_heads(2, 16, 16) == 2
    assert kda.tile_heads(7, 16, 16) == 1              # 3 x 7 > 16 columns


def test_every_product_of_the_chunked_form_states_its_precision():
    """An einsum without ``precision=`` is ONE bf16 pass on the chip and
    float32 here, so no comparison on the CPU can see it: every
    ``dot_general`` the chunked form traces — the pairs inside and between
    sub-blocks, the inverse, the scan's products against the state — has to
    ask for three passes or six."""
    c = case(64, decay="mixed")
    jaxpr = jax.make_jaxpr(lambda *a: kda.chunked_delta_rule(*a, chunk=32))(
        c["S0"], c["q"], c["k"], c["v"], c["g"], c["beta"])

    def dots(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)
    found = list(dots(jaxpr.jaxpr))
    assert len(found) >= 9
    enough = (jax.lax.Precision.HIGH, jax.lax.Precision.HIGHEST)
    for eqn in found:
        asked = eqn.params["precision"]
        asked = asked if isinstance(asked, tuple) else (asked, asked)
        assert all(p in enough for p in asked), (asked, eqn)

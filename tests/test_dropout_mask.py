"""The residual-dropout keep-mask (``models/transformer.dropout``): a
counter hash of (the key, the element's global index).

What the tests hold it to is what ``jax.random.bernoulli`` gave for free:
the rate; a pure function of the key (eager = jitted = rematerialized);
masks of different keys independent — in particular NOT one sequence read
at two offsets; no pattern along rows or lanes; one mask whatever the
layout. And the counter that says the mechanism engages: no per-element
threefry left in the model's jaxpr.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.models import transformer
from deepspeed_tpu.models.gpt2 import (GPT2_CONFIGS, gpt2_init,
                                       gpt2_loss_fn)
from deepspeed_tpu.models.transformer import dropout
from deepspeed_tpu.ops.counter_hash import hash_u32


def keep_mask(shape, rate, key):
    return np.asarray(dropout(jnp.ones(shape, jnp.float32), rate, key,
                              False) != 0)


def sigma(p, n):
    return math.sqrt(p * (1 - p) / n)


# ------------------------------------------------------------------ #
# The rate
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("rate,shape", [(0.1, (4096, 1280)),
                                        (0.5, (1024, 1024)),
                                        (0.01, (1024, 1024))])
def test_keep_rate(rate, shape):
    keep = keep_mask(shape, rate, jax.random.PRNGKey(0))
    assert abs(keep.mean() - (1 - rate)) < 4 * sigma(rate, keep.size)


def test_rows_and_lanes_each_keep_the_rate():
    """No stripe: every row and every column keeps 0.9, and their means
    scatter as independent draws would (a striped mask keeps the overall
    rate while whole lanes sit far off it)."""
    keep = keep_mask((4096, 1280), 0.1, jax.random.PRNGKey(1))
    for axis in (0, 1):
        means = keep.mean(axis=axis)
        s = sigma(0.1, keep.shape[axis])
        # 5 sigma: the largest of a few thousand means, not one draw.
        assert np.abs(means - 0.9).max() < 5 * s, axis
        assert 0.9 < means.std() / s < 1.1, axis


# ------------------------------------------------------------------ #
# A pure function of the key
# ------------------------------------------------------------------ #
def test_same_key_same_mask_eager_jitted_and_rematerialized():
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (64, 256))) + 1.0
    key = jax.random.PRNGKey(3)

    def f(x):
        return dropout(x, 0.1, key, False)
    eager = np.asarray(f(x))
    for other in (jax.jit(f)(x), jax.checkpoint(f)(x)):
        np.testing.assert_array_equal(eager != 0, np.asarray(other) != 0)
        # XLA may turn the division by a constant into a multiplication
        np.testing.assert_allclose(eager, other, rtol=1e-6)
    # The backward of a rematerialized dropout regenerates the mask: the
    # gradient is keep / (1 - p) with the FORWARD's mask.
    grad = jax.jit(jax.grad(lambda x: jax.checkpoint(f)(x).sum()))(x)
    np.testing.assert_allclose(grad, np.where(eager != 0, 1 / 0.9, 0.0),
                               rtol=1e-6)


@pytest.mark.parametrize("case", ["deterministic", "rate_zero", "no_rng"])
def test_no_draw_returns_the_input_itself(case):
    x = jnp.ones((8, 128))
    key = jax.random.PRNGKey(0)
    out = {"deterministic": lambda: dropout(x, 0.1, key, True),
           "rate_zero": lambda: dropout(x, 0.0, key, False),
           "no_rng": lambda: dropout(x, 0.1, None, False)}[case]()
    assert out is x


def test_bf16_kept_values_are_the_scaled_input():
    x = jax.random.normal(jax.random.PRNGKey(4), (256, 256), jnp.bfloat16)
    y = dropout(x, 0.1, jax.random.PRNGKey(5), False)
    assert y.dtype == jnp.bfloat16
    keep = keep_mask(x.shape, 0.1, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(
        np.asarray(y, np.float32),
        np.where(keep, np.asarray(x / (1.0 - 0.1), np.float32), 0.0))


# ------------------------------------------------------------------ #
# Two keys: independent masks, not one sequence at two offsets
# ------------------------------------------------------------------ #
WINDOW = 4096


def agreement_by_shift(a, b, window=WINDOW):
    """Share of positions where ``a[i] == b[i + s]`` for every shift
    ``s`` in ``[-window, window]`` (exact counts through an FFT
    correlation of the +-1 sequences)."""
    n = a.size
    size = 1 << (2 * n - 1).bit_length()
    fa = np.fft.rfft(2.0 * a - 1, size)
    fb = np.fft.rfft(2.0 * b - 1, size)
    corr = np.rint(np.fft.irfft(np.conj(fa) * fb, size))
    shifts = np.arange(-window, window + 1)
    overlap = n - np.abs(shifts)
    return shifts, (corr[shifts] / overlap + 1) / 2, overlap


def test_two_keys_agree_where_independent_masks_would_at_every_shift():
    n = 1 << 18
    k1, k2 = jax.random.split(jax.random.PRNGKey(6))
    a, b = keep_mask((n,), 0.1, k1), keep_mask((n,), 0.1, k2)
    agree = 0.9 ** 2 + 0.1 ** 2
    assert abs((a == b).mean() - agree) < 4 * sigma(agree, n)
    _, share, overlap = agreement_by_shift(a, b)
    # 5 sigma: the farthest of 8,193 comparisons, not one.
    assert (np.abs(share - agree)
            < 5 * np.sqrt(agree * (1 - agree) / overlap)).all()


def test_the_shift_detector_catches_a_one_round_hash():
    """What the keyed second round is for: ``f(i + s)`` alone makes one
    site's mask another's, shifted by the difference of their seeds."""
    n = 1 << 16
    idx = jnp.arange(n, dtype=jnp.uint32)
    a = np.asarray(hash_u32(idx + jnp.uint32(777_000)) >= 2 ** 32 // 10)
    b = np.asarray(hash_u32(idx + jnp.uint32(776_000)) >= 2 ** 32 // 10)
    shifts, share, _ = agreement_by_shift(a, b)
    assert share[shifts == 1000] == 1.0


def test_the_72_sites_of_one_forward_draw_distinct_masks(monkeypatch):
    """gpt2-large's count: 36 layers x (after the attention projection,
    after the FFN) through ``apply_blocks``' own key splitting."""
    cfg = transformer.TransformerConfig(
        hidden_size=64, num_heads=2, num_layers=36, max_seq_length=32,
        causal=True, attn_dropout=0.0, scan_layers=False,
        fused_kernels=False, dtype=jnp.float32)
    masks = []

    def recording(x, rate, rng, deterministic):
        if x.ndim == 3 and rng is not None and rate > 0:
            masks.append(keep_mask(x.shape, rate, rng).ravel())
        return dropout(x, rate, rng, deterministic)
    monkeypatch.setattr(transformer, "dropout", recording)
    stacked = transformer.init_block_params(jax.random.PRNGKey(7), cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 64))
    transformer.apply_blocks(stacked, x, cfg, rng=jax.random.PRNGKey(9),
                             deterministic=False,
                             attention_fn=transformer.dense_attention)
    assert len(masks) == 72
    m = np.stack(masks).astype(np.float64)
    n = m.shape[1]
    same = (m @ m.T + (1 - m) @ (1 - m).T) / n
    off = same[~np.eye(72, dtype=bool)]
    assert off.max() < 1.0
    agree = 0.9 ** 2 + 0.1 ** 2
    # 5 sigma over 2,556 pairs.
    assert np.abs(off - agree).max() < 5 * sigma(agree, n)


# ------------------------------------------------------------------ #
# The index is the GLOBAL position
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("spec", [P("data"), P(None, "data"),
                                  P(None, None, "data")])
def test_a_sharded_input_gets_the_unsharded_mask(spec):
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    x = jnp.ones((8, 128, 256), jnp.float32)
    key = jax.random.PRNGKey(10)
    want = dropout(x, 0.1, key, False)
    sharding = NamedSharding(mesh, spec)
    got = jax.jit(lambda x, k: dropout(x, 0.1, k, False),
                  in_shardings=(sharding, None),
                  out_shardings=sharding)(jax.device_put(x, sharding), key)
    assert got.sharding.is_equivalent_to(sharding, 3)
    np.testing.assert_array_equal(want, got)


def test_leading_dims_past_32_bits_fold_into_the_words(monkeypatch):
    """A tensor of 2**32 elements or more: the trailing dims that fit are
    numbered, the leading ones key the hash. Shown at a small size by
    shrinking the index space; the real limit is traced, not run."""
    low, high = jax.eval_shape(
        lambda: transformer._element_index((3, 1 << 31, 2)))
    assert low.shape == high.shape == (3, 1 << 31, 2)
    assert transformer._element_index((4096, 1280))[1] is None

    monkeypatch.setattr(transformer, "_INDEX_SPACE", 1 << 14)
    low, high = transformer._element_index((6, 64, 128))
    assert int(low.max()) == 64 * 128 - 1 and int(high.max()) == 5
    slabs = keep_mask((6, 64, 128), 0.1, jax.random.PRNGKey(11))
    agree = 0.9 ** 2 + 0.1 ** 2
    for i in range(6):
        for j in range(i):
            assert abs((slabs[i] == slabs[j]).mean() - agree) \
                < 5 * sigma(agree, slabs[i].size), (i, j)
    _, share, overlap = agreement_by_shift(slabs[0].ravel(),
                                           slabs[1].ravel(), 512)
    assert (np.abs(share - agree)
            < 5 * np.sqrt(agree * (1 - agree) / overlap)).all()


# ------------------------------------------------------------------ #
# The counter: the model's program draws nothing per element
# ------------------------------------------------------------------ #
RANDOM = {"threefry2x32", "random_bits", "random_split", "random_fold_in"}


def equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def words(aval):
    per = 2 if jax.dtypes.issubdtype(aval.dtype, jax.dtypes.prng_key) else 1
    return per * math.prod(aval.shape)


def loss_equations(hidden_dropout, attn_dropout):
    cfg = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"],
                              hidden_dropout=hidden_dropout,
                              attn_dropout=attn_dropout)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    batch = jnp.zeros((2, 33), jnp.int32)
    jaxpr = jax.make_jaxpr(gpt2_loss_fn(cfg))(params, batch,
                                              jax.random.PRNGKey(1))
    return list(equations(jaxpr.jaxpr))


def test_the_loss_draws_no_more_than_a_few_words_per_site():
    eqns = loss_equations(0.1, 0.1)
    drawn = [(e.primitive.name, max(words(v.aval) for v in e.outvars))
             for e in eqns if e.primitive.name in RANDOM]
    assert drawn and max(n for _, n in drawn) <= 8, drawn
    hashed = [e for e in eqns if e.primitive.name == "shift_right_logical"
              and e.outvars[0].aval.shape == (2, 32, 128)]
    # two sites in the scanned block, two finalizers of three shifts each
    assert len(hashed) == 12


def test_without_dropout_the_loss_holds_none_of_the_hash():
    names = {e.primitive.name for e in loss_equations(0.0, 0.0)}
    assert "shift_right_logical" not in names
    assert not names & {"threefry2x32", "random_bits"}


def test_the_scope_is_one_the_trace_readers_know():
    from deepspeed_tpu.monitor.xplane_reader import scope_of
    assert scope_of("jit(train_step)/fwd_bwd/while/body/"
                    "rematted_computation/attn/dropout/xor")[0] == \
        ("fwd_bwd", "attn", "dropout")

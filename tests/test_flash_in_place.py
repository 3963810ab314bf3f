"""The whole-sequence flash kernels IN PLACE over [B, S, nH*dH]: a grid step
is a lane tile (two 64-wide heads side by side, or one head of 128), q / k
/ v are read and o / dq / dk / dv written where the projections leave them,
and nothing the size of an operand is transposed on the way.  In interpret
mode: against the dense reference under the identical regenerated
keep-mask, against the relayout path (`_to_bh` + the same kernels one head a
step) for the mask itself, and the shapes that keep the relayout path
against what the parent of PR 47 gave for them.
"""
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import flash_attention as fa
from flash_reference import dense_dropped, keep_mask, make_qkv

RNG = jax.random.PRNGKey(7)
SEED = int(jax.random.bits(RNG, (), jnp.uint32))
WHAT = ("forward", "dq", "dk", "dv")
# (nH, dH): two tiles of two heads; cell 1's ten tiles; a head a tile.
HEADS = [(4, 64), (20, 64), (2, 128)]
# causal -> S: four bands at S = 1024 where the heads are few, else the
# smallest S that has two; non-causal is one band over the rectangle.
BANDED = {(4, 64): 4 * fa._BAND, (20, 64): 2 * fa._BAND, (2, 128): 2 * fa._BAND}


def _weights(x):
    return jnp.cos(jnp.arange(x.size).reshape(x.shape) * 0.01)


def _with_grads(fn, q, k, v):
    o = fn(q, k, v)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a) * _weights(o)),
                     argnums=(0, 1, 2))(q, k, v)
    return dict(zip(WHAT, map(np.asarray, (o, *grads))))


def _relayout(q, k, v, causal, rate):
    """The path every dense call took before PR 47, spelled out."""
    B, S, nH, D = q.shape
    seed = jnp.asarray(SEED, jnp.uint32).astype(jnp.int32)
    o = fa._flash(fa._to_bh(q), fa._to_bh(k), fa._to_bh(v), seed,
                  1.0 / math.sqrt(D), causal, rate)
    return o.reshape(B, nH, S, D).transpose(0, 2, 1, 3)


@functools.lru_cache(maxsize=None)
def _three_ways(heads, causal, rate):
    nH, D = heads
    S = BANDED[heads] if causal else fa._BAND
    assert fa._row_band(S, S, causal) == (fa._BAND if causal else S)
    q, k, v = make_qkv(jax.random.PRNGKey(nH), 1, S, nH, D)
    keep = keep_mask(SEED, nH, S, rate)
    before = dict(fa.lowered)
    in_place = _with_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, attn_dropout=rate, rng=RNG,
            deterministic=rate == 0.0), q, k, v)
    assert fa.lowered["relayout"] == before["relayout"]
    assert fa.lowered["in_place"] > before["in_place"]
    return (in_place,
            _with_grads(lambda q, k, v: dense_dropped(q, k, v, keep, rate,
                                                      causal), q, k, v),
            _with_grads(lambda q, k, v: _relayout(q, k, v, causal, rate),
                        q, k, v))


@pytest.mark.parametrize("what", WHAT)
@pytest.mark.parametrize("rate", [0.0, 0.1])
@pytest.mark.parametrize("causal", [True, False], ids=["bands", "one_band"])
@pytest.mark.parametrize("heads", HEADS, ids=lambda h: f"{h[0]}x{h[1]}")
def test_in_place_matches_the_dense_reference_and_the_relayout_paths_mask(
        heads, causal, rate, what):
    in_place, dense, relayout = _three_ways(heads, causal, rate)
    tol = 2e-4 if what == "forward" else 2e-3     # test_flash_bands'
    np.testing.assert_allclose(in_place[what], dense[what], rtol=tol,
                               atol=tol)
    if what == "forward":
        # Zeros on the neighbour's lanes add nothing to a contraction: the
        # output is the relayout path's to the last bit, so the keep-mask
        # is its keep-mask.
        np.testing.assert_array_equal(in_place[what], relayout[what])
    else:       # delta = rowsum(do o) is summed in the kernel: another order
        np.testing.assert_allclose(in_place[what], relayout[what], rtol=0,
                                   atol=1e-5)


def test_the_fused_projection_is_read_where_it_lies():
    """`flash_attention_qkv` over [B, S, 3H] = `flash_attention` over its
    thirds, and its gradient is the thirds' gradients side by side."""
    nH, D, S = 4, 64, 2 * fa._BAND
    qkv = jnp.concatenate([x.reshape(1, S, nH * D) for x in make_qkv(
        jax.random.PRNGKey(4), 1, S, nH, D)], axis=-1)

    def fused(qkv):
        return fa.flash_attention_qkv(qkv, nH, causal=True, attn_dropout=0.1,
                                      rng=RNG, deterministic=False)

    def split(qkv):
        q, k, v = (x.reshape(1, S, nH, D) for x in jnp.split(qkv, 3, -1))
        return fa.flash_attention(q, k, v, causal=True, attn_dropout=0.1,
                                  rng=RNG, deterministic=False
                                  ).reshape(1, S, nH * D)
    o = fused(qkv)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(split(qkv)))
    g = [jax.grad(lambda x: jnp.sum(f(x) * _weights(o)))(qkv)
         for f in (fused, split)]
    np.testing.assert_array_equal(np.asarray(g[0]), np.asarray(g[1]))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("entry", ["q_k_v", "fused_qkv"])
def test_nothing_the_size_of_an_operand_is_transposed_at_cell_1s_shape(entry):
    """`jax.grad` through the call at `train.gpt2-large.zero2`'s shape
    (micro-batch 4, S 1024, 20 heads of 64, bf16, attn_pdrop 0.1): no
    `transpose` of a B*S*nH*dH-element array in forward or backward, from
    the fused projection no slice of one either, and the counter reads
    `in_place`."""
    B, S, nH, D = 4, 1024, 20, 64
    before = dict(fa.lowered)
    if entry == "q_k_v":
        args = [jax.ShapeDtypeStruct((B, S, nH, D), jnp.bfloat16)] * 3

        def loss(q, k, v):
            return jnp.sum(fa.flash_attention(
                q, k, v, causal=True, attn_dropout=0.1, rng=RNG,
                deterministic=False).astype(jnp.float32))
    else:
        args = [jax.ShapeDtypeStruct((B, S, 3 * nH * D), jnp.bfloat16)]

        def loss(qkv):
            return jnp.sum(fa.flash_attention_qkv(
                qkv, nH, causal=True, attn_dropout=0.1, rng=RNG,
                deterministic=False).astype(jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(len(args)))))(
        *args)
    assert fa.lowered["in_place"] == before["in_place"] + 1
    assert fa.lowered["relayout"] == before["relayout"]
    moved = {"transpose"} | ({"slice", "split", "dynamic_slice", "gather"}
                             if entry == "fused_qkv" else set())
    calls = []
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn.params["name"])
        elif eqn.primitive.name in moved:
            assert all(math.prod(x.aval.shape) < B * S * nH * D
                       for x in eqn.invars), eqn
    assert calls == ["_fwd_kernel", "_bwd_fused_kernel"]


@pytest.mark.parametrize("heads, tile_heads", [((20, 64), 2), ((2, 128), 1)])
def test_the_calls_cost_estimates_are_the_heads_not_the_tiles(heads,
                                                              tile_heads):
    """A grid step is `tile_heads` heads' scores: the estimate counts a
    head's `computed_scores` over its own dH, as the relayout call's."""
    nH, D = heads
    x = jax.ShapeDtypeStruct((1, 1024, nH, D), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, causal=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(x, x, x)
    calls = {e.params["name"]: e.params for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"}
    scores = fa.computed_scores(1024, 1024, True)
    for name, matmuls in (("_fwd_kernel", 2), ("_bwd_fused_kernel", 5)):
        cost = calls[name]["cost_estimate"]
        assert cost.flops == 2 * matmuls * nH * scores * D, name
        assert cost.transcendentals == nH * scores, name
        assert calls[name]["grid_mapping"].grid[0] == nH // tile_heads, name


@pytest.mark.parametrize("S, Sk, nH, dH, layout, want", [
    (1024, 1024, 20, 64, None, 128),        # cell 1
    (1024, 1024, 16, 64, None, 128),        # cell 3
    (128, 128, 12, 64, None, 128),          # BERT, one band
    (512, 512, 2, 128, None, 128),
    (512, 512, 2, 256, None, 256),
    (1024, 1024, 25, 64, None, 0),          # an odd head count
    (1024, 1024, 8, 32, None, 0),
    (1024, 1024, 8, 96, None, 0),
    (2048, 2048, 20, 64, None, 0),          # two blocks: the grid path
    (1024, 512, 20, 64, None, 0),
    (1024, 1024, 20, 64, "a layout", 0),
])
def test_the_path_follows_the_shapes_alone(S, Sk, nH, dH, layout, want):
    assert fa.tile_lanes(S, Sk, nH, dH, layout) == want


GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "flash_relayout_pr46.npz")
# name -> (B, S, nH, dH, causal, rate, a layout?, rows kept in the file):
# what `_scratch/p47_golden.py` gave the PARENT's module (commit f4d3394).
KEPT = {
    "odd_heads": (1, 256, 3, 64, True, 0.1, False, 8),
    "layout": (1, 256, 2, 64, True, 0.1, True, 8),
    "two_blocks": (1, 2048, 2, 64, True, 0.1, False, 64),
    "narrow_heads": (1, 256, 4, 32, False, 0.0, False, 8),
}


@pytest.mark.parametrize("name", sorted(KEPT))
def test_the_other_shapes_keep_the_relayout_path_and_its_outputs(name):
    B, S, nH, D, causal, rate, sparse, every = KEPT[name]
    layout = jnp.asarray(np.tril(np.ones((nH, 2, 2), np.int32))) \
        if sparse else None
    q, k, v = make_qkv(jax.random.PRNGKey(len(name)), B, S, nH, D)
    before = dict(fa.lowered)
    got = _with_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, attn_dropout=rate,
            rng=jax.random.PRNGKey(5), deterministic=rate == 0.0,
            layout=layout), q, k, v)
    assert fa.lowered["in_place"] == before["in_place"]
    assert fa.lowered["relayout"] > before["relayout"]
    with np.load(GOLDEN) as want:
        for what, key in zip(WHAT, ("o", "dq", "dk", "dv")):
            np.testing.assert_array_equal(got[what][:, ::every],
                                          want[f"{name}.{key}"], err_msg=what)


def test_a_block_hands_its_fused_projection_to_the_default_attention(
        monkeypatch):
    """`transformer_block` without an ``attention_fn`` gives the default the
    projection as the GEMM leaves it (`auto_attention_qkv`; on the chip
    that is `flash_attention_qkv`, stood in for here in interpret mode) and
    computes what it computes through a plug that is handed q, k, v."""
    from deepspeed_tpu.models import transformer as tr
    cfg = tr.TransformerConfig(
        hidden_size=128, num_heads=2, num_layers=1, causal=True,
        hidden_dropout=0.0, attn_dropout=0.0, dtype=jnp.float32,
        fused_kernels=False)
    params = jax.tree_util.tree_map(
        lambda x: x[0], tr.init_block_params(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2 * fa._BAND, 128))
    handed = []

    def kernels(qkv, num_heads, **kw):
        handed.append(qkv.shape)
        return fa.flash_attention_qkv(qkv, num_heads, **kw)
    monkeypatch.setattr(fa, "auto_attention_qkv", kernels)
    before = dict(fa.lowered)

    def loss(attention_fn):
        return lambda p, x: jnp.sum(tr.transformer_block(
            p, x, cfg, attention_fn=attention_fn) * _weights(x))
    got = jax.value_and_grad(loss(None), argnums=(0, 1))(params, x)
    want = jax.value_and_grad(loss(tr.dense_attention), argnums=(0, 1))(
        params, x)
    assert handed == [(2, 2 * fa._BAND, 3 * 128)]
    assert fa.lowered == {**before, "in_place": before["in_place"] + 1}
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-3)

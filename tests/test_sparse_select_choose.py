"""``ops.sparse_select.choose`` finds its ``topk`` blocks by a threshold and
writes them out by counting (PR 60), as the pool block ids the row's table
holds there: held here, to the element, to the two sorts it replaced —
``lax.top_k`` (ties to the lower block) and ``jnp.sort`` of the indices, the
parent's lines, kept below as the reference — read through the table, and
the programs are held to carry neither a ``sort`` nor a ``top_k``.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deepspeed_tpu.ops import sparse_select                     # noqa: E402
from deepspeed_tpu.ops.sparse_select import Sizes               # noqa: E402

# the tiny model's numbers (``tests/test_minicpm_sala_serving.py``) and the
# benchmark cell's (``perfbench/configs/minicpm-sala.json``)
TINY = Sizes(stride=4, block=16, topk=4, window_blocks=2, init_blocks=1,
             dense_len=64)
CELL = Sizes(stride=16, block=64, topk=64, window_blocks=32, init_blocks=1,
             dense_len=8192)


def by_two_sorts(score, pos, sz):
    """``choose`` as PR 59 left it."""
    width = sz.width
    newest = pos // sz.block
    dense = (pos + 1 <= sz.dense_len)[:, None, None]
    k = min(sz.topk, score.shape[-1])
    _, top = lax.top_k(score, k)                 # ties: the lower index
    top = jnp.sort(top.astype(jnp.int32), axis=-1)
    top = jnp.pad(top, ((0, 0), (0, 0), (0, width - k)), constant_values=-1)
    slot = jnp.arange(width, dtype=jnp.int32)[None, None]
    every = jnp.where(slot <= newest[:, None, None], slot, -1)
    logical = jnp.where(dense, every, top)
    n = jnp.where(dense[..., 0], jnp.minimum(newest + 1, width)[:, None],
                  jnp.minimum(k, newest + 1)[:, None])
    n = jnp.broadcast_to(n, logical.shape[:2]).astype(jnp.int32)
    return jnp.where(slot < n[..., None], logical, -1), n


def dressed(raw, pos, sz):
    """``block_scores``'s last two lines over raw scores ``[rows, nKV, W]``:
    ``+inf`` for the first ``init_blocks`` and the newest ``window_blocks``
    blocks, ``-1.0`` for a block past the newest."""
    b = np.arange(raw.shape[-1])
    newest = (pos // sz.block)[:, None]
    forced = (b[None] < sz.init_blocks) | (b[None] > newest
                                           - sz.window_blocks)
    raw = np.where(forced[:, None], np.inf, raw)
    return np.where((b[None] <= newest)[:, None], raw, -1.0).astype(
        np.float32)


def _case(name):
    """(score [rows, nKV, W] fp32, pos [rows], Sizes) of a named case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "random":                         # softmax-like sums, W = 300
        sz, W, rows = CELL._replace(dense_len=64), 300, 24
        pos = rng.integers(64 * 70, 64 * W, rows)
        return dressed(rng.random((rows, 2, W)), pos, sz), pos, sz
    if name == "signed":                         # keys on both sides of zero
        sz, W, rows = CELL._replace(dense_len=64), 300, 16
        pos = np.full(rows, 64 * W - 1)
        return rng.normal(size=(rows, 2, W)).astype(np.float32), pos, sz
    if name == "eighths":                        # ties inside and at the edge
        sz, W, rows = CELL._replace(dense_len=64), 300, 24
        pos = rng.integers(64 * 70, 64 * W, rows)
        raw = rng.integers(0, 8, (rows, 2, W)) / 8.0
        return dressed(raw, pos, sz), pos, sz
    if name == "all_equal":                      # nothing but ties
        sz, W, rows = CELL._replace(dense_len=64), 300, 8
        return (np.full((rows, 2, W), 0.25, np.float32),
                np.full(rows, 64 * W - 1), sz)
    if name == "forced_ends_dead_behind":        # +inf at both ends, -1 dead
        sz = CELL._replace(dense_len=64, init_blocks=3, window_blocks=40)
        W, rows = 300, 24
        pos = rng.integers(64 * 80, 64 * 200, rows)
        return dressed(rng.random((rows, 2, W)), pos, sz), pos, sz
    if name == "more_forced_than_topk":          # ties among the +inf
        sz = CELL._replace(dense_len=64, init_blocks=30, window_blocks=50)
        W, rows = 300, 12
        pos = rng.integers(64 * 120, 64 * W, rows)
        return dressed(rng.random((rows, 2, W)), pos, sz), pos, sz
    if name == "fewer_live_than_topk":           # the dead fill the top-k
        sz, W, rows = CELL._replace(dense_len=64), 300, 40
        pos = rng.integers(65, 64 * 63, rows)
        return dressed(rng.random((rows, 2, W)), pos, sz), pos, sz
    if name == "narrower_than_topk":             # W < topk: every block
        sz, W, rows = TINY._replace(topk=12, dense_len=16), 7, 40
        pos = rng.integers(0, 16 * W, rows)
        return dressed(rng.random((rows, 2, W)), pos, sz), pos, sz
    if name == "across_dense_len":               # a chunk's rows, both rules
        sz, W = TINY, 10
        pos = np.arange(32, 160)
        return dressed(rng.integers(0, 4, (128, 2, W)) / 4.0, pos, sz), \
            pos, sz
    if name == "cell":                           # W = 2,072, topk = 64
        sz, W, rows = CELL, 2072, 12
        pos = rng.integers(32768, 131072, rows)
        return dressed(rng.random((rows, 2, W)), pos, sz), pos, sz
    if name == "cell_quantised":                 # the cell's, ties throughout
        sz, W, rows = CELL, 2072, 12
        pos = rng.integers(8000, 131072, rows)
        pos[:2] = (8191, 8192)                   # either side of dense_len
        raw = rng.integers(0, 16, (rows, 2, W)) / 16.0
        return dressed(raw, pos, sz), pos, sz
    raise KeyError(name)


CASES = ["random", "signed", "eighths", "all_equal",
         "forced_ends_dead_behind", "more_forced_than_topk",
         "fewer_live_than_topk", "narrower_than_topk", "across_dense_len",
         "cell", "cell_quantised"]


def tables_of(pos, W, sz, rng):
    """A table a row as the engine holds it: distinct pool block ids up to
    the newest block, ``-1`` past it."""
    table = np.stack([rng.permutation(4 * W)[:W] for _ in pos])
    return np.where(np.arange(W)[None] <= (pos // sz.block)[:, None],
                    table, -1).astype(np.int32)


@pytest.mark.parametrize("name", CASES)
def test_choose_is_the_two_sorts_to_the_element(name):
    score, pos, sz = _case(name)
    W = score.shape[-1]
    table = tables_of(pos, W, sz, np.random.default_rng(len(name)))
    score, pos = jnp.asarray(score), jnp.asarray(pos, jnp.int32)
    logical, want_n = by_two_sorts(score, pos, sz)
    # ... read through the table as PR 59's ``select_blocks`` read them
    want = np.where(np.asarray(logical) >= 0, np.take_along_axis(
        table[:, None], np.maximum(np.asarray(logical), 0), axis=-1), -1)
    run = jax.jit(sparse_select.choose, static_argnums=3)
    got, got_n = run(score, pos, jnp.asarray(table), sz)
    assert got.shape == want.shape == (len(pos), 2, sz.width)
    assert got.dtype == logical.dtype and got_n.dtype == want_n.dtype
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_n, want_n)
    # the logical blocks themselves: the identity as every row's table
    same, _ = run(score, pos, jnp.broadcast_to(
        jnp.arange(W, dtype=jnp.int32), (len(pos), W)), sz)
    np.testing.assert_array_equal(same, logical)
    # the cases are what their names say
    k = min(sz.topk, score.shape[-1])
    sparse = np.asarray(pos) + 1 > sz.dense_len
    if name in ("eighths", "all_equal", "cell_quantised"):
        kth = np.sort(np.asarray(score), axis=-1)[..., -k]
        assert ((np.asarray(score) == kth[..., None]).sum(-1)[sparse]
                > 1).all()                       # a tie across the edge
    if name == "fewer_live_than_topk":
        assert (np.asarray(want_n) < k).all()
    if name in ("across_dense_len", "cell_quantised"):
        assert sparse.any() and not sparse.all()


def _primitives(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, found)
    return found


@pytest.mark.parametrize("S,K", [(4, 1), (1, 32)])
def test_the_selection_holds_no_sort(S, K):
    """``select_blocks`` as ``decode_step`` (one row a stream) and as
    ``prefill_step`` (a chunk's rows) trace it: neither a ``sort`` nor a
    ``top_k`` primitive anywhere in the jaxpr, the maps' bodies included."""
    sz, W, nKV, nH, D = TINY, 10, 2, 8, 16
    found = _primitives(jax.make_jaxpr(
        lambda q, ck, table, pos, live: sparse_select.select_blocks(
            q, ck, 0, table, pos, live, sz, 0.25))(
        jnp.zeros((S, K, nH, D)), jnp.zeros((1, 1, 16, nKV, 4, D)),
        jnp.zeros((S, W), jnp.int32), jnp.zeros((S, K), jnp.int32),
        jnp.ones((S, K), bool)).jaxpr, set())
    assert {"exp", "shift_left"} <= found     # it looked inside the loops
    assert not {"sort", "top_k"} & found, sorted(found)
    # ... and the reference above is made of exactly those two
    two = _primitives(jax.make_jaxpr(lambda s, p: by_two_sorts(s, p, sz))(
        jnp.zeros((4, nKV, W)), jnp.zeros((4,), jnp.int32)).jaxpr, set())
    assert {"sort", "top_k"} <= two

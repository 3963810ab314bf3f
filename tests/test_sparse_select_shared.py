"""A decode step's selection reads a shared block's pooled keys once a
program (PR 63): ``ops.sparse_select.select_blocks`` with one row a stream
groups the streams whose tables begin with the same blocks, gathers a
group's shared prefix once a tile of streams and scores the tile's query
rows against it in one product, and reads per stream only the slots past
what the group shares; where the tables share nothing (or a stream's own
tail is longer than the static bound) it takes the per-stream arm.  Held
here to that per-stream arm as PR 62 left it — its lines kept below as the
reference — over the ways tables share and do not: block scores equal up to
the order of an fp32 sum, chosen ids and counts EQUAL on inputs with a
margin at the cut, and the counter ``ck_blocks_read`` says which arm ran.
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

TINY = Sizes(stride=4, block=16, topk=4, window_blocks=2, init_blocks=1,
             dense_len=64)
nKV, nH, D, R = 2, 8, 16, TINY.per_block
RTOL = 2e-6        # a few ulps of fp32: the order of a sum, nothing else
MARGIN = 1e-4      # the least relative gap at the cut a case may have


# --------------------------------------------------------------------- #
# The per-stream arm as PR 62 left it (``select_blocks``, ``K == 1``)
# --------------------------------------------------------------------- #
def block_scores_pr62(q, pooled, pos, sz, scale):
    W = pooled.shape[0]
    f32 = jnp.float32
    if q.dtype == jnp.bfloat16 and pooled.dtype == jnp.bfloat16:
        how = dict(preferred_element_type=f32)
    else:
        q, pooled = q.astype(f32), pooled.astype(f32)
        how = dict(precision=lax.Precision.HIGHEST)
    s = jnp.einsum("tnmd,wnrd->tnmwr", q, pooled, **how) * scale
    s = s.reshape(s.shape[:3] + (W * R,))
    g = jnp.arange(W * R, dtype=jnp.int32)
    seen = (g[None] >= 1) & (g[None] <= (pos[:, None] + 1) // sz.stride - 1)
    s = jnp.where(seen[:, None, None], s, -jnp.inf)
    a = jnp.exp(s - jnp.max(jnp.where(seen[:, None, None], s, -1e30),
                            axis=-1, keepdims=True))
    a = a / jnp.maximum(a.sum(-1, keepdims=True), 1e-30)
    r = a.sum(axis=2)
    own = r.reshape(r.shape[:2] + (W, R)).max(-1)
    nxt = jnp.pad(r[..., R::R], ((0, 0), (0, 0), (0, 1)))
    score = jnp.maximum(own, nxt)
    b = jnp.arange(W, dtype=jnp.int32)
    newest = (pos // sz.block)[:, None]
    forced = (b[None] < sz.init_blocks) | (b[None] > newest
                                           - sz.window_blocks)
    score = jnp.where(forced[:, None], jnp.inf, score)
    return jnp.where((b[None] <= newest)[:, None], score, -1.0)


def select_pr62(q, ck, layer, table, pos, live, sz, scale):
    """(scores [S, nKV, W], ids, counts): every stream gathers its own
    pooled rows through its table."""
    S, K, _, _ = q.shape
    G = ck.shape[1]
    group = jnp.arange(S, dtype=jnp.int32) // (S // G)
    qg = q.reshape(S, K, nKV, nH // nKV, D)

    def of_stream(q_s, row, g, pos_s):
        return block_scores_pr62(q_s, ck[layer, g, jnp.maximum(row, 0)],
                                 pos_s, sz, scale)

    score = jax.vmap(of_stream)(qg, table, group, pos)[:, 0]
    ids, n = sparse_select.choose(score, pos[:, 0], table, sz)
    return (score, jnp.where(live[..., None, None], ids[:, None],
                             sparse_select.DEAD_BLOCK),
            jnp.where(live[..., None], n[:, None], 0))


# --------------------------------------------------------------------- #
# Tables as the engine holds them
# --------------------------------------------------------------------- #
class Case:
    """S streams over ``G`` pool groups; ``docs``: a document's length in
    blocks; stream i holds the first ``share[i]`` blocks of document
    ``doc[i]`` (-1: none) of ITS pool group and ``own[i]`` blocks of its own
    after them, its newest position inside the last one.  The same document
    has the SAME block ids in every pool group (and other pooled keys)."""

    def __init__(self, name, S, W, docs, doc, share, own, G=1, dead=(),
                 bounds=(4, 6), bound="fits", dtype=jnp.float32):
        self.name, self.S, self.W, self.G = name, S, W, G
        self.docs, self.doc, self.share, self.own = docs, doc, share, own
        # (streams a tile, a stream's own slots) the case runs under; None:
        # the program's own
        self.dead, self.bounds = set(dead), bounds
        self.bound, self.dtype = bound, dtype

    @property
    def tile(self):
        return (self.bounds or (sparse_select._TILE_STREAMS,))[0]

    @property
    def tail(self):
        return (self.bounds or (None, sparse_select._TAIL_SLOTS))[1]

    def arrays(self, seed):
        rng = np.random.default_rng(seed)
        B = 4 * self.W + sum(self.docs)
        ids = rng.permutation(B)
        doc_blocks, at = [], 0
        for n in self.docs:
            doc_blocks.append(ids[at:at + n])
            at += n
        table = np.full((self.S, self.W), -1, np.int32)
        pos = np.zeros(self.S, np.int32)
        free = list(ids[at:])
        for i in range(self.S):
            row = list(doc_blocks[self.doc[i]][:self.share[i]]) \
                if self.doc[i] >= 0 else []
            row += [free.pop() for _ in range(self.own[i])]
            table[i, :len(row)] = row
            pos[i] = (len(row) - 1) * TINY.block + rng.integers(TINY.block)
        live = np.array([i not in self.dead for i in range(self.S)])
        table[~live] = -1
        pos[~live] = 0
        ck = rng.normal(size=(2, self.G, B, nKV, R, D)).astype(np.float32)
        q = 2 * rng.normal(size=(self.S, 1, nH, D)).astype(np.float32)
        return (jnp.asarray(q, self.dtype), jnp.asarray(ck, self.dtype),
                jnp.asarray(table), jnp.asarray(pos)[:, None],
                jnp.asarray(live)[:, None])

    def groups(self):
        """[(streams, shared length)] of the sharing groups: a (pool group,
        document) — or a stream of no document — sharing what every member
        with blocks of its own holds of the document, no further than the
        longest member reaches."""
        per = self.S // self.G
        groups = {}
        for i in range(self.S):
            if i in self.dead:
                continue
            key = (i // per, self.doc[i]) if self.doc[i] >= 0 \
                and self.share[i] > 0 else ("own", i)
            groups.setdefault(key, []).append(i)
        return [(len(m), min(
            [self.share[i] for i in m if self.own[i]]
            + [max(self.share[i] + self.own[i] for i in m)]))
            for m in groups.values()]

    def tiles(self):
        """Tiles the grouping needs: each group padded to whole tiles."""
        return sum(-(-n // self.tile) for n, _ in self.groups())

    def blocks_read(self):
        """Blocks of pooled keys the shared arm gathers, a K/V head: a tile
        its group's table row once and its streams' own tails."""
        return self.tiles() * (self.W + self.tile * self.tail)


def _zipf(S, P, seed):
    w = 1.0 / np.arange(1, P + 1)
    return list(np.random.default_rng(seed).choice(P, S, p=w / w.sum()))


CASES = [
    # no two streams share a block: 8 groups of one where 3 tiles may be
    Case("nothing_shared", 8, 24, [], [-1] * 8, [0] * 8,
         [5, 9, 12, 7, 20, 6, 11, 15], bound="tiles"),
    Case("one_document", 12, 24, [14], [0] * 12, [14] * 12,
         [1, 2, 3, 1, 4, 2, 6, 1, 2, 3, 5, 1]),
    Case("eight_documents_zipf", 32, 40,
         [30, 9, 17, 3, 24, 12, 6, 21], _zipf(32, 8, 3),
         [[30, 9, 17, 3, 24, 12, 6, 21][d] for d in _zipf(32, 8, 3)],
         [1 + i % 6 for i in range(32)]),
    # a prefix shorter than ``dense_len`` (4 blocks) under streams past it,
    # and one longer
    Case("prefix_inside_dense_len", 8, 24, [2, 3], [0, 0, 0, 1, 1, 1, 0, 1],
         [2, 2, 2, 3, 3, 3, 2, 3], [6, 4, 1, 5, 6, 2, 3, 1]),
    Case("prefix_past_dense_len", 8, 24, [9, 15], [0, 0, 0, 1, 1, 1, 0, 1],
         [9, 9, 9, 15, 15, 15, 9, 15], [1, 2, 3, 4, 5, 6, 1, 2]),
    # stream 2 wrote into the document's last block: a copy of its own
    Case("copy_on_write_a_block_early", 8, 24, [10], [0] * 8,
         [10, 10, 9, 10, 10, 10, 10, 10], [2, 1, 3, 4, 1, 2, 5, 3]),
    # a stream still inside the blocks its group shares (and one at
    # position 0 of a table of one block)
    Case("inside_the_prefix", 8, 24, [12], [0] * 7 + [-1],
         [12, 3, 12, 7, 12, 1, 12, 0], [2, 0, 1, 0, 3, 0, 0, 1]),
    Case("dead_streams", 12, 24, [11, 8], [0, 1] * 6, [11, 8] * 6,
         [1 + i % 5 for i in range(12)], dead=(0, 5, 6, 11)),
    # the same block ids in both pool groups: other pooled keys, two groups
    Case("two_pool_groups", 16, 24, [10, 13], [0, 1] * 8, [10, 13] * 8,
         [1 + i % 4 for i in range(16)], G=2),
    # a tail longer than the static bound (6 slots): stream 3's 9 own blocks
    Case("a_tail_too_long", 8, 24, [8], [0] * 8, [8] * 8,
         [1, 2, 3, 9, 1, 2, 3, 4], bound="tail"),
    # more groups than tiles may hold: 6 documents + 3 streams of their own
    # over 12 streams are 9 tiles of 4 where 4 may be
    Case("more_groups_than_tiles", 12, 24, [5, 6, 7, 8, 9, 10],
         [0, 1, 2, 3, 4, 5, 0, 1, 2, -1, -1, -1],
         [5, 6, 7, 8, 9, 10, 5, 6, 7, 0, 0, 0],
         [1, 2, 3, 1, 2, 3, 1, 2, 3, 7, 9, 11], bound="tiles"),
    # as many groups as tiles may be: 3 streams of their own, the rest dead
    Case("as_many_groups_as_tiles", 8, 24, [], [-1] * 8, [0] * 8,
         [5, 9, 12, 1, 1, 1, 1, 1], dead=(3, 4, 5, 6, 7)),
    # the constants as the program has them (tiles of 32, tails of 32)
    Case("the_programs_own_bounds", 16, 64, [22, 31], [0, 1] * 8,
         [22, 31] * 8, [1 + 2 * i for i in range(16)], bounds=None),
    Case("bfloat16", 12, 24, [14, 6], [0, 1] * 6, [14, 6] * 6,
         [1 + i % 5 for i in range(12)], dtype=jnp.bfloat16),
]


def _run(case, monkeypatch, seed):
    if case.bounds:
        monkeypatch.setattr(sparse_select, "_TILE_STREAMS", case.bounds[0])
        monkeypatch.setattr(sparse_select, "_TAIL_SLOTS", case.bounds[1])
    q, ck, table, pos, live = case.arrays(seed)
    want = jax.jit(lambda *a: select_pr62(*a, TINY, 0.25))(
        q, ck, 1, table, pos, live)
    got = jax.jit(lambda *a: (sparse_select.select_blocks_counted(
        *a, TINY, 0.25)))(q, ck, 1, table, pos, live)
    return (q, ck, table, pos, live), want, got


def _margin(score, pos):
    """The least relative gap, over the rows that select (past
    ``dense_len``), between the k-th largest block score and the nearest
    OTHER value on either side.  (Equal scores are no risk: neighbouring
    blocks tie exactly where the first pooled row of the later one is the
    largest of both — the same number in either arm — and ties go to the
    lower block in both.)"""
    least = np.inf
    score, pos = np.asarray(score, np.float64), np.asarray(pos)
    for row in score[pos + 1 > TINY.dense_len].reshape(-1, score.shape[-1]):
        kth = -np.sort(-row)[min(TINY.topk, len(row)) - 1]
        other = row[np.isfinite(row) & (row != kth) & (row >= 0)]
        if np.isfinite(kth) and len(other):
            least = min(least, np.abs(other - kth).min() / kth)
    return least


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_the_shared_read_is_the_per_stream_read(case, monkeypatch):
    for seed in range(63, 73):        # the first seed with room at the cut
        (q, ck, table, pos, live), want, got = _run(case, monkeypatch, seed)
        if _margin(want[0], pos[:, 0]) > MARGIN:
            break
    else:
        raise AssertionError("no seed of ten leaves a margin at the cut")
    score_w, ids_w, n_w = want
    ids, n, read = got
    S, W = case.S, case.W
    # which arm ran is what the tables say, and the counter says it
    fits = case.bound == "fits"
    if fits:
        assert case.tiles() <= sparse_select._max_tiles(S)
        assert int(read) == case.blocks_read() * nKV
    else:
        if case.bound == "tiles":
            assert case.tiles() > sparse_select._max_tiles(S)
        assert int(read) == S * W * nKV
    # the sets: equal, dead streams empty
    np.testing.assert_array_equal(ids, ids_w)
    np.testing.assert_array_equal(n, n_w)
    dead = ~np.asarray(live)[:, 0]
    assert (np.asarray(ids)[dead] == -1).all() and not np.asarray(n)[dead].any()
    assert (np.asarray(n)[~dead] > 0).all()
    # the scores the arm taken computed, a live stream's
    if fits:
        plan = sparse_select._shared_plan(
            table, jnp.arange(S, dtype=jnp.int32) // (S // case.G), pos[:, 0],
            live[:, 0], ck.shape[2], TINY)
        assert bool(plan.fits) and int(plan.tiles) == case.tiles()
        score = _shared_scores(case, q, ck, table, pos, plan)
        ok = ~dead
        got_s, want_s = np.asarray(score)[ok], np.asarray(score_w)[ok]
        assert (np.isinf(got_s) == np.isinf(want_s)).all()
        assert ((got_s == -1.0) == (want_s == -1.0)).all()
        fin = np.isfinite(want_s)
        np.testing.assert_allclose(got_s[fin], want_s[fin], rtol=RTOL,
                                   atol=1e-9)


def _shared_scores(case, q, ck, table, pos, plan):
    """The dressed scores ``[S, nKV, W]`` as the shared arm computes them,
    from its own pieces (``_tile_scores`` a tile in use)."""
    S, W, b, T = case.S, case.W, case.tile, case.tail
    group = jnp.arange(S, dtype=jnp.int32) // (S // case.G)
    qg = q.reshape(S, nKV, nH // nKV, D)
    own = jnp.pad(table, ((0, 0), (0, T)), constant_values=-1)
    raw = []
    for t in range(int(plan.tiles)):
        st = plan.streams[t]
        g = group[st[0]]
        their = ck[1, g, jnp.maximum(table[plan.first[t]], 0)]  # once
        raw.append(sparse_select._tile_scores(
            qg[st], their, ck, 1, g, plan.shared[t],
            own[st], pos[st, 0], TINY, 0.25))
    raw = jnp.concatenate(raw)
    return sparse_select._dress(raw[plan.at], pos[:, 0], TINY)


def test_streams_of_two_pool_groups_are_never_one_group(monkeypatch):
    """Equal block ids in both pool groups: the plan keeps a group's streams
    inside their pool group, a tile reads ONE group's pool."""
    case = [c for c in CASES if c.name == "two_pool_groups"][0]
    (q, ck, table, pos, live), _, _ = _run(case, monkeypatch, 63)
    S = case.S
    group = jnp.arange(S, dtype=jnp.int32) // (S // 2)
    plan = sparse_select._shared_plan(table, group, pos[:, 0], live[:, 0],
                                      ck.shape[2], TINY)
    streams = np.asarray(plan.streams)[:int(plan.tiles)]
    at = np.asarray(plan.at)
    for s in range(S):
        tile = streams[at[s] // case.tile]
        assert tile[at[s] % case.tile] == s
        mates = {int(m) for m in tile if at[m] // case.tile
                 == at[s] // case.tile}
        assert {m // (S // 2) for m in mates} == {s // (S // 2)}
        assert {case.doc[m] for m in mates} == {case.doc[s]}
    assert sorted(np.asarray(plan.shared)[:int(plan.tiles)]) \
        == sorted([10, 10, 13, 13])


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _pooled_gathers(jaxpr):
    """Blocks a gather of pooled keys (``[..., nKV, R, D]``) yields, each."""
    return [int(np.prod(e.outvars[0].aval.shape[:-3])) for e in _eqns(jaxpr)
            if e.primitive.name == "gather"
            and e.outvars[0].aval.shape[-3:] == (nKV, R, D)]


def test_the_shared_arm_holds_no_gather_of_every_streams_table():
    """``decode_step``'s selection at S = 64 streams of W = 40 slots: the arm
    that reads a shared block once gathers pooled keys a TILE at a time —
    ``W`` blocks (the tile's group's table row) and ``tile x tail`` (its
    streams' own) — in ONE loop of as many steps as the tables need tiles,
    and no ``S x W`` — nor a batch of streams' ``16 x W`` — blocks anywhere;
    the per-stream arm beside it still does."""
    S, W = 64, 40
    b, T = sparse_select._TILE_STREAMS, sparse_select._TAIL_SLOTS
    jaxpr = jax.make_jaxpr(
        lambda q, ck, table, pos, live: sparse_select.select_blocks_counted(
            q, ck, 0, table, pos, live, TINY, 0.25))(
        jnp.zeros((S, 1, 4 * nH, D)), jnp.zeros((1, 1, 16, nKV, R, D)),
        jnp.zeros((S, W), jnp.int32), jnp.zeros((S, 1), jnp.int32),
        jnp.ones((S, 1), bool)).jaxpr
    arms = [e for e in jaxpr.eqns if e.primitive.name == "cond"
            and any(_pooled_gathers(br.jaxpr) for br in e.params["branches"])]
    assert len(arms) == 1
    each, once = (br.jaxpr for br in arms[0].params["branches"])
    assert _pooled_gathers(each) == [sparse_select._BATCH_STREAMS * W]
    assert sorted(_pooled_gathers(once)) == sorted([W, b * T])
    loops = [e.primitive.name for e in _eqns(once)
             if e.primitive.name in ("while", "scan")]
    assert loops == ["while"]
    assert not {"sort", "top_k", "scatter", "cumsum"} & {
        e.primitive.name for e in _eqns(jaxpr)}

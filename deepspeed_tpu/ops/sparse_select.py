"""Block selection without weights of its own (InfLLM-V2, the ``minicpm4``
mixer of ``models/minicpm_sala.py``): a query token reads, of its stream's
K/V blocks, only the ``topk`` whose POOLED keys its own queries score
highest, a set a K/V head.

**Pooled keys.**  ``c_j`` is the mean of the ``2 * stride`` keys ``k[stride *
j .. stride * (j + 2) - 1]`` of one K/V head.  It is kept in the pool ``ck``
BESIDE the K/V pages, ``R = block / stride`` rows a block, in the block where
its window ENDS: global pooled row ``j + 1`` (row ``(j + 1) % R`` of logical
block ``(j + 1) // R``; global row 0 is never written and never visible).  A
block's ``ck`` rows are then a function of the tokens up to the block's end
and of nothing after it, which is exactly what a shared prefix block
guarantees: sharing, copy on write and ``copy_pages`` carry them with no rule
of their own.  ``write_pooled_chunk`` (a prefill chunk's rows: every window
that ends inside the chunk, from the chunk's own keys and the ``stride`` keys
before it) and ``write_pooled_rows`` (one row a stream: the window that ends
at that row, if one does, read back from the K pool) write them, after the
K rows themselves.

**Selection** (``select_blocks``), for the query at position ``t`` (``n = t +
1`` tokens, newest block ``b_t``): every block ``0 .. b_t`` where ``n <=
dense_len``; else, over the visible pooled rows ``1 <= g <= (t + 1) // stride
- 1``: ``a[i, g] = softmax_g(q_i . c_g / sqrt(D))`` a query head, ``r[h, g]``
its sum over the ``group`` query heads of K/V head ``h``, ``B[h, b] = max
r[h, R b .. R b + R]`` (the pooled keys whose windows touch block b),
``+inf`` for the first ``init_blocks`` and the newest ``window_blocks``
blocks, and the ``topk`` blocks of largest ``B`` (ties to the lower block) in
ascending order.  Returned through the stream's table as POOL block ids, a
row and K/V head: ``[rows, nKV, width]`` with ``width = max(topk, dense_len /
block)``, dead slots ``-1``, and the live count.

All of it is plain ``jax.numpy``, and the fp32 scores of 256 streams x 32
heads x 8k pooled keys never stand whole (a tile or a batch at a time under
``lax.map``); their block maxima do (4 MB), and the ``topk`` largest of each
row are found once a program, by a threshold and a count (``choose``: no
sort).  fp32 scores, softmax, sums and order; what it reads is the pool's
dtype.

**How the pooled keys are read.**  A chunk's rows (``K > 1``) are ONE
stream's: its pooled rows are gathered through its table once and scored a
batch of rows at a time.  A decode step's rows (``K == 1``) are a stream
each, and streams of one document hold the SAME blocks at the same leading
slots of their tables (the prefix cache shares whole leading blocks), so the
step reads its tables before its keys (``_shared_plan``, on the device: the
live streams of one pool group whose slot 0 agrees are a sharing group, its
shared length the leading slots on which every member agrees with the
longest), deals each group's streams into tiles of ``_TILE_STREAMS`` and,
a tile (``_tile_scores``, a step of a loop of as many steps as the tables
need tiles): gathers the group's pooled keys ``[W, nKV, R, D]`` through
that table row ONCE for the tile's streams, contracts them with the tile's
``32 x group`` query rows a K/V head in one product, and reads per stream
only the ``_TAIL_SLOTS``
slots from the shared length on (a question, a reply, a block copied on
write), at the stream's own logical blocks — one softmax a query head over
both parts, the last shared block's score taking the first pooled row of
the stream's own next block.  Where the tables say there is too little to
share — more tiles than ``_max_tiles``, or a tail longer than the
bound — a ``lax.cond`` takes the per-stream arm: every stream's pooled rows
through its whole table, ``_BATCH_STREAMS`` at a time.  One algorithm chosen
by its input; the scores agree up to the order of an fp32 sum and the sets
to the element (``tests/test_sparse_select_shared.py``).
``select_blocks_counted`` also returns the blocks of pooled keys it gathered
(the counter ``ck_blocks_read``).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

DEAD_BLOCK = -1
_BATCH_STREAMS = 16         # decode: streams scored at once, a stream's
                            # pooled keys each (10.4 ms a layer at 256
                            # streams x 2,072 slots; 11.8 at 8, 12.2 at 32,
                            # 11.9 at 64: PERF.md section 6, PR 63)
_TILE_STREAMS = 32          # decode: streams of one sharing group scored in
                            # one product, a step of a loop (a step costs
                            # ~5 us of its own whatever it holds: 1.70 ms a
                            # layer at 32, 1.61 at 16, 1.65 at 8; PERF.md
                            # section 6, PR 63)
_TAIL_SLOTS = 32            # decode: a stream's own slots past what its
                            # group shares (a question and a reply: 2k tokens)
_BATCH_ROWS = 128           # prefill: a chunk's rows scored at once


class Sizes(NamedTuple):
    """The selection's numbers (``models.minicpm_sala.MinicpmSalaConfig``'s
    ``sparse_*``)."""
    stride: int
    block: int
    topk: int
    window_blocks: int
    init_blocks: int
    dense_len: int

    @property
    def per_block(self) -> int:
        return self.block // self.stride

    @property
    def width(self) -> int:
        return max(self.topk, self.dense_len // self.block)

    @classmethod
    def of(cls, cfg) -> "Sizes":
        return cls(cfg.sparse_kernel_stride, cfg.sparse_block_size,
                   cfg.sparse_topk,
                   cfg.sparse_window_size // cfg.sparse_block_size,
                   cfg.sparse_init_blocks, cfg.sparse_dense_len)


# --------------------------------------------------------------------- #
# Writing the pooled keys
# --------------------------------------------------------------------- #
def _k_rows(pool_k, layer, group, blk, off, D: int):
    """Key rows out of the K pool as held ``[L, G, B, nKV, bs/f, f*D]``
    (``kv_cache.kv_fold``: f positions side by side in the lanes where D <
    128): ``[..., nKV, D]`` for (group, block, offset) of equal shapes.  A
    gather of ROWS — every index of the pool but its lanes is given, the
    head's too — so that the pool is read in the layout it is held in (a
    slice over the heads between two gathered dimensions made the compiler
    re-lay the whole pool, heads next to lanes, ahead of every call: 2.7 ms
    a layer at 1.07 GB; PERF.md section 6, PR 59); no layer is sliced out."""
    f = pool_k.shape[-1] // D
    head = jnp.arange(pool_k.shape[3], dtype=jnp.int32)
    rows = pool_k[layer, group[..., None], blk[..., None], head,
                  (off // f)[..., None]]                    # [..., nKV, f*D]
    if f == 1:
        return rows
    rows = rows.reshape(rows.shape[:-1] + (f, D))
    pick = jnp.broadcast_to((off % f)[..., None, None, None],
                            rows.shape[:-2] + (1, D))
    return jnp.take_along_axis(rows, pick, axis=-2)[..., 0, :]


def _put_rows(ck, layer, group, blk, row, pooled):
    """``pooled [..., nKV, D]`` into ``ck [L, G, B, nKV, R, D]`` at (group,
    block, row) of equal shapes, a block index of ``B`` dropped: a scatter
    of rows, the head's index given (see ``_k_rows``)."""
    head = jnp.arange(ck.shape[3], dtype=jnp.int32)
    return ck.at[layer, group[..., None], blk[..., None], head,
                 row[..., None]].set(pooled.astype(ck.dtype), mode="drop")


def write_pooled_chunk(ck, pool_k, layer, k, table, start, last_idx, active,
                       sz: Sizes):
    """A prefill chunk of ONE stream a group.  ck ``[L, G, B, nKV, R, D]``;
    k ``[G, C, nKV, D]`` the chunk's keys (as the K pool now holds them);
    table ``[G, W]``; ``start`` [G] (a multiple of ``block``), ``last_idx``
    [G] the last live row, ``active`` [G].  Window i ends at chunk row
    ``stride * i + stride - 1`` and takes the ``stride`` rows before the
    chunk from the pool (the previous block's last)."""
    G, C = k.shape[:2]
    s, R = sz.stride, sz.per_block
    n = C // s
    f32 = jnp.float32
    g_idx = jnp.arange(G, dtype=jnp.int32)[:, None]
    # the ``stride`` keys before the chunk (zeros at position 0: unused)
    prev_pos = start[:, None] - s + jnp.arange(s, dtype=jnp.int32)[None]
    prev_blk = jnp.take_along_axis(
        table, jnp.maximum(prev_pos, 0) // sz.block, axis=1)
    prev = _k_rows(pool_k, layer, jnp.broadcast_to(g_idx, prev_blk.shape),
                   jnp.maximum(prev_blk, 0),
                   jnp.maximum(prev_pos, 0) % sz.block,
                   k.shape[-1])                             # [G, s, nKV, D]
    rows = jnp.concatenate([prev.astype(f32),
                            k.astype(pool_k.dtype).astype(f32)], axis=1)
    halves = rows.reshape((G, n + 1, s) + rows.shape[2:]).sum(axis=2)
    pooled = (halves[:, :-1] + halves[:, 1:]) / (2 * s)     # [G, n, nKV, D]
    end = start[:, None] + s * jnp.arange(n, dtype=jnp.int32)[None] + s - 1
    ok = (active[:, None] > 0) & (end >= 2 * s - 1) \
        & (s * jnp.arange(n)[None] + s - 1 <= last_idx[:, None])
    blk = jnp.take_along_axis(table, end // sz.block, axis=1)
    blk = jnp.where(ok & (blk >= 0), blk, ck.shape[2])      # dropped
    return _put_rows(ck, layer, jnp.broadcast_to(g_idx, blk.shape), blk,
                     (end % sz.block) // s, pooled)


def write_pooled_rows(ck, pool_k, layer, table, pos, live, sz: Sizes):
    """One row a stream (decode).  table ``[G, Sg, W]``, pos / live ``[G,
    Sg]``: where a window ends at ``pos`` its ``2 * stride`` keys are read
    back from the K pool (the newest was written just before)."""
    G, Sg = pos.shape
    s = sz.stride
    ends = live & ((pos + 1) % s == 0) & (pos >= 2 * s - 1)
    at = jnp.maximum(pos[..., None] - (2 * s - 1)
                     + jnp.arange(2 * s, dtype=jnp.int32), 0)  # [G, Sg, 2s]
    blk = jnp.take_along_axis(table, at // sz.block, axis=2)
    g_idx = jnp.arange(G, dtype=jnp.int32)[:, None, None]
    rows = _k_rows(pool_k, layer, jnp.broadcast_to(g_idx, blk.shape),
                   jnp.maximum(blk, 0), at % sz.block, ck.shape[-1])
    pooled = rows.astype(jnp.float32).mean(axis=2)          # [G, Sg, nKV, D]
    to = jnp.take_along_axis(table, (jnp.maximum(pos, 0) // sz.block
                                     )[..., None], axis=2)[..., 0]
    to = jnp.where(ends & (to >= 0), to, ck.shape[2])
    return _put_rows(ck, layer, jnp.broadcast_to(g_idx[..., 0], to.shape),
                     to, (jnp.maximum(pos, 0) % sz.block) // s, pooled)


# --------------------------------------------------------------------- #
# Selection
# --------------------------------------------------------------------- #
def _contract(spec: str, q, pooled):
    """Queries against pooled keys, fp32 out: bf16 operands are contracted
    as they are (their products are exact in fp32), fp32 ones at the
    highest precision."""
    f32 = jnp.float32
    if q.dtype == jnp.bfloat16 and pooled.dtype == jnp.bfloat16:
        how = dict(preferred_element_type=f32)
    else:
        q, pooled = q.astype(f32), pooled.astype(f32)
        how = dict(precision=lax.Precision.HIGHEST)
    return jnp.einsum(spec, q, pooled, **how)


def _dress(score, pos, sz: Sizes):
    """Raw block scores ``[rows, nKV, W]`` with the rule's ends: ``+inf`` for
    a forced block (the first ``init_blocks``, the newest
    ``window_blocks``), -1 for a block past the newest."""
    b = jnp.arange(score.shape[-1], dtype=jnp.int32)
    newest = (pos // sz.block)[:, None]
    forced = (b[None] < sz.init_blocks) | (b[None] > newest
                                           - sz.window_blocks)
    score = jnp.where(forced[:, None], jnp.inf, score)
    return jnp.where((b[None] <= newest)[:, None], score, -1.0)


def block_scores(q, pooled, pos, sz: Sizes, scale: float):
    """``B [rows, nKV, W]`` (fp32; -1 for a block past the newest, +inf
    for a forced one) of query rows q ``[rows, nKV, group, D]`` at
    positions ``pos`` [rows] against one stream's pooled rows ``[W, nKV, R,
    D]`` (global row ``W-index * R + R-index``)."""
    W, _, R, _ = pooled.shape
    s = _contract("tnmd,wnrd->tnmwr", q, pooled) * scale
    s = s.reshape(s.shape[:3] + (W * R,))
    g = jnp.arange(W * R, dtype=jnp.int32)
    seen = (g[None] >= 1) & (g[None] <= (pos[:, None] + 1) // sz.stride - 1)
    s = jnp.where(seen[:, None, None], s, -jnp.inf)
    a = jnp.exp(s - jnp.max(jnp.where(seen[:, None, None], s, -1e30),
                            axis=-1, keepdims=True))
    a = a / jnp.maximum(a.sum(-1, keepdims=True), 1e-30)
    r = a.sum(axis=2)                                       # [rows, nKV, WR]
    own = r.reshape(r.shape[:2] + (W, R)).max(-1)
    nxt = jnp.pad(r[..., R::R], ((0, 0), (0, 0), (0, 1)))  # next block's first
    return _dress(jnp.maximum(own, nxt), pos, sz)


def _order_keys(score):
    """fp32 -> int32 whose SIGNED order is the floats' (the sign trick: a
    negative float's lower 31 bits flipped).  -0 lies below +0 here; no
    score is -0 (softmax sums, ``+inf``, ``-1.0``)."""
    bits = lax.bitcast_convert_type(score, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _largest(score, k: int, values):
    """``values [..., W]`` (int32) at the ``k <= W`` entries of largest
    ``score [..., W]``, ties to the LOWER index, in ASCENDING order of their
    indices ``[..., k]`` — ``values[sort(top_k(score, k)[1])]``, found by
    a threshold and written by counting: on the chip ``lax.top_k`` over
    2,072 entries is a full sort, a batch of rows at a time (3.5 ms a layer
    at 256 rows x 2 heads), and a gather of 65,536 ids through the tables
    costs 0.67 ms more (PERF.md section 6, PR 60).

    The k-th largest key ``kth`` by 32 steps of bisection over the bits
    (the largest t with ``#{key >= t} >= k``), over the keys held ``[W,
    rows]`` — the rows along the lanes, so that a step's count is a sum of
    whole registers: the loop carries its arrays in the layout their shape
    says, and ``[rows, nKV, W]`` is 2 sublanes of 8 and a reduction across
    the lanes (0.31 ms for the 32 steps against 0.04; PERF.md section 6, PR
    60); then ``c[w]``, how many of the entries up to w are chosen — every
    key above ``kth`` and the first ``k - #above`` of those equal to it — by
    a product with a triangular 0/1 matrix (exact: counts <= W in an fp32
    accumulator; a running sum along the lanes is a ``reduce-window`` on
    the chip); slot j takes the one chosen entry whose count is j + 1."""
    W = score.shape[-1]
    key = _order_keys(score)
    by_row = key.reshape(-1, W).T                            # [W, rows]

    def narrow(i, t):
        cand = t ^ lax.shift_left(jnp.int32(1), 31 - i)     # bit 31: to 0
        enough = (by_row >= cand[None]).sum(0, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)

    kth = lax.fori_loop(
        0, 32, narrow,
        jnp.full(by_row.shape[1:], jnp.iinfo(jnp.int32).min, jnp.int32)
    ).reshape(key.shape[:-1])
    above, equal = key > kth[..., None], key == kth[..., None]
    w = jnp.arange(W, dtype=jnp.int32)
    upto = (w[:, None] <= w[None]).astype(jnp.bfloat16)      # [w, v]: w <= v
    run = jnp.einsum("a...w,wv->a...v",
                     jnp.stack([above, equal]).astype(jnp.bfloat16), upto,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    need = (k - above.sum(-1, dtype=jnp.int32))[..., None]
    nth = jnp.where(above | (equal & (run[1] <= need)),
                    run[0] + jnp.minimum(run[1], need), 0)   # 1 .. k, else 0
    slot = jnp.arange(1, k + 1, dtype=jnp.int32)
    return jnp.where(nth[..., None, :] == slot[:, None],
                     values[..., None, :], 0).sum(-1, dtype=jnp.int32)


def choose(score, pos, table, sz: Sizes):
    """(``table``'s entries ``[rows, nKV, width]`` at the chosen logical
    blocks in ascending order, -1 past the live count; live count ``[rows,
    nKV]``) from ``block_scores``'s; ``table [rows, W]`` a row's pool block
    ids (``arange(W)`` gives the logical blocks themselves)."""
    width, W = sz.width, score.shape[-1]
    newest = pos // sz.block
    dense = (pos + 1 <= sz.dense_len)[:, None, None]
    k = min(sz.topk, W)
    top = jnp.pad(_largest(score, k, table[:, None]),
                  ((0, 0), (0, 0), (0, width - k)), constant_values=-1)
    slot = jnp.arange(width, dtype=jnp.int32)[None, None]
    first = jnp.pad(table, ((0, 0), (0, max(width - W, 0))),
                    constant_values=-1)[:, None, :width]
    ids = jnp.where(dense, first, top)
    n = jnp.where(dense[..., 0], jnp.minimum(newest + 1, width)[:, None],
                  jnp.minimum(k, newest + 1)[:, None])
    n = jnp.broadcast_to(n, ids.shape[:2]).astype(jnp.int32)
    return jnp.where(slot < n[..., None], ids, -1), n


def _batches(n: int, size: int) -> int:
    while n % size:
        size -= 1
    return size


class _Shared(NamedTuple):
    """What a decode step's tables say of the blocks its streams share
    (``_shared_plan``), the streams dealt into tiles of ``_TILE_STREAMS``
    slots of ONE sharing group each."""
    fits: jax.Array         # []: the tiles and the tails are inside bounds
    tiles: jax.Array        # []: tiles in use
    streams: jax.Array      # [NT, b]: the stream in a slot (any, if none)
    first: jax.Array        # [NT]: the stream whose table row a tile's
    #                         group shares ..
    shared: jax.Array       # [NT]: .. over this many leading slots
    at: jax.Array           # [S]: a stream's slot, ``tile * b + slot``


def _max_tiles(S: int) -> int:
    """Tiles a decode step of ``S`` streams may need and still share: those
    its streams fill and a partial one for each of ``S / 8`` groups."""
    return -(-S // _TILE_STREAMS) + S // 8


def _shared_plan(table, group, pos, live, num_blocks: int, sz: Sizes
                 ) -> _Shared:
    """A sharing group: the live streams of one pool group whose slot 0 holds
    the same block (the prefix cache shares whole LEADING blocks); its
    shared length: the leading slots on which every member agrees with the
    longest, no further than that one reaches.  Groups in the order of
    their longest streams, each padded to whole tiles.  All of it compares
    over ``[S, S]`` and ``[S, W]`` int32: no sort, no scatter.  A live
    stream whose slot 0 is dead is a group of its own that shares
    nothing."""
    S, W = table.shape
    b, T, NT = _TILE_STREAMS, _TAIL_SLOTS, _max_tiles(S)
    i32 = jnp.int32
    s = jnp.arange(S, dtype=i32)
    key = jnp.where(table[:, 0] >= 0, group * num_blocks + table[:, 0],
                    -1 - s)
    same = (key[:, None] == key[None]) & live[:, None] & live[None]
    # the group's first: its longest stream (the lowest of them), whose
    # table row the others are held to wherever they hold a block
    first = jnp.argmax(jnp.where(same, pos[None], -1), axis=1).astype(i32)
    w = jnp.arange(W, dtype=i32)
    agree = jnp.min(jnp.where((table != table[first]) & (table >= 0),
                              w[None], W), axis=1)
    reach = pos // sz.block + 1                             # blocks held
    shared = jnp.minimum(
        jnp.min(jnp.where(same, agree[None], W), axis=1),
        jnp.max(jnp.where(same, reach[None], 0), axis=1))
    rank = (same & (s[None] < s[:, None])).sum(1, dtype=i32)
    leads = live & (first == s)
    tiles_of = jnp.where(leads, -(-same.sum(1, dtype=i32) // b), 0)
    base = jnp.where(s[None] < first[:, None], tiles_of[None], 0).sum(1)
    at = (base + rank // b) * b + rank % b
    tiles = tiles_of.sum()
    tail = jnp.max(jnp.where(live, reach - shared, 0))
    slots = jnp.arange(NT * b, dtype=i32)
    streams = jnp.argmax((at[None] == slots[:, None]) & live[None],
                         axis=1).astype(i32).reshape(NT, b)
    return _Shared((tiles <= NT) & (tail <= T), tiles, streams,
                   first[streams[:, 0]], shared[streams[:, 0]], at)


def _tile_scores(q, their, ck, layer, g, shared, own_table, pos, sz: Sizes,
                 scale: float):
    """Raw block scores ``[b, nKV, W]`` of a tile: ``b`` streams of ONE
    sharing group — q ``[b, nKV, group, D]``, pos [b] — against ``their``
    ``[W, nKV, R, D]``, the pooled keys of the group's table row (its
    ``shared`` leading blocks count), contracted with all ``b x group``
    query rows of a K/V head in one product, and each stream against its
    OWN ``_TAIL_SLOTS`` slots from ``shared`` on (``own_table [b, W +
    _TAIL_SLOTS]``, its table padded with dead slots), gathered here.  A
    query head's maximum and normaliser span both parts; the last shared
    block's score takes the first pooled row of the stream's own next
    block."""
    b, nKV = q.shape[:2]
    W, T, R = their.shape[0], _TAIL_SLOTS, ck.shape[4]
    tail = lax.dynamic_slice_in_dim(own_table, shared, T, axis=1)
    own = ck[layer, g, jnp.maximum(tail, 0)]             # [b, T, nKV, R, D]
    sp = (_contract("bnmd,wnrd->nbmwr", q, their) * scale).reshape(
        nKV, b, -1, W * R)
    st = (_contract("bnmd,bjnrd->nbmjr", q, own) * scale).reshape(
        nKV, b, -1, T * R)
    last = ((pos + 1) // sz.stride - 1)[:, None]             # newest seen
    gp = jnp.arange(W * R, dtype=jnp.int32)[None]
    gt = shared * R + jnp.arange(T * R, dtype=jnp.int32)[None]
    seen_p = ((gp >= 1) & (gp <= last) & (gp < shared * R))[None, :, None]
    seen_t = ((gt >= 1) & (gt <= last))[None, :, None]
    top = jnp.maximum(
        jnp.max(jnp.where(seen_p, sp, -1e30), axis=-1, keepdims=True),
        jnp.max(jnp.where(seen_t, st, -1e30), axis=-1, keepdims=True))
    ap = jnp.exp(jnp.where(seen_p, sp, -jnp.inf) - top)
    at = jnp.exp(jnp.where(seen_t, st, -jnp.inf) - top)
    z = jnp.maximum(ap.sum(-1, keepdims=True) + at.sum(-1, keepdims=True),
                    1e-30)
    rp, rt = (ap / z).sum(axis=2), (at / z).sum(axis=2)     # [nKV, b, rows]

    def per_block(r, n):
        """max over a block's rows and the next block's first."""
        r = r.reshape(r.shape[:2] + (n, R))
        nxt = jnp.pad(r[..., 1:, 0], ((0, 0), (0, 0), (0, 1)))
        return jnp.maximum(r.max(-1), nxt)

    # the tail's blocks shared - 1 (its ``nxt``) .. shared + T - 1, put at
    # their logical places; scores are >= 0 and 0 where a part sees nothing
    tail_b = jnp.concatenate([rt[..., :1], per_block(rt, T)], axis=-1)
    placed = lax.dynamic_update_slice_in_dim(
        jnp.zeros((nKV, b, 1 + W + T), jnp.float32), tail_b, shared,
        axis=2)[..., 1:W + 1]
    return jnp.maximum(per_block(rp, W), placed).swapaxes(0, 1)


def select_blocks_counted(q, ck, layer, table, pos, live, sz: Sizes,
                          scale: float
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q ``[S, K, nH, D]`` (query head ``h * group + m`` reads K/V head h);
    ck ``[L, G, B, nKV, R, D]``; table ``[S, W]`` (each stream's, group
    ``s // (S / G)``); pos / live ``[S, K]``.  Returns (pool block ids ``[S,
    K, nKV, width]`` in ascending logical order, ``DEAD_BLOCK`` past the
    count; count ``[S, K, nKV]``, 0 for a dead row; the ``ck`` blocks the
    scores were read from, a K/V head each: int32 [])."""
    S, K, nH, D = q.shape
    G, nKV, R = ck.shape[1], ck.shape[3], ck.shape[4]
    W = table.shape[1]
    group = jnp.arange(S, dtype=jnp.int32) // (S // G)
    qg = q.reshape(S, K, nKV, nH // nKV, D)

    def of_stream(q_s, row, g, pos_s):
        """One stream's K rows: its pooled rows gathered once."""
        pooled = ck[layer, g, jnp.maximum(row, 0)]          # [W, nKV, R, D]
        return block_scores(q_s, pooled, pos_s, sz, scale)

    def a_stream_each():
        b = _batches(S, _BATCH_STREAMS)
        split = lambda v: v.reshape((S // b, b) + v.shape[1:])  # noqa: E731
        score = lax.map(
            lambda a: jax.vmap(of_stream)(*a),
            (split(qg), split(table), split(group), split(pos)))
        return score.reshape(S, nKV, W), jnp.int32(S * W * nKV)

    def a_shared_block_once(plan: _Shared):
        b, T = _TILE_STREAMS, _TAIL_SLOTS
        own = jnp.pad(table, ((0, 0), (0, T)), constant_values=DEAD_BLOCK)
        t = plan.streams
        q_t, own_t, pos_t = qg[t][:, :, 0], own[t], pos[t][..., 0]

        def of_tile(i, raw):
            """The tile's group's pooled keys, gathered ONCE for its
            streams through the group's longest stream's table row."""
            g = group[t[i, 0]]
            their = ck[layer, g, jnp.maximum(table[plan.first[i]], 0)]
            return lax.dynamic_update_index_in_dim(raw, _tile_scores(
                q_t[i], their, ck, layer, g, plan.shared[i], own_t[i],
                pos_t[i], sz, scale), i, axis=0)

        # (a loop of as many steps as there are tiles in use: the others
        # cost nothing, and their rows are read by no stream)
        raw = lax.fori_loop(0, plan.tiles, of_tile, jnp.zeros(
            (t.shape[0], b, nKV, W), jnp.float32))
        score = _dress(raw.reshape(-1, nKV, W)[plan.at], pos[:, 0], sz)
        return score, (plan.tiles * (W + b * T) * nKV).astype(jnp.int32)

    if K == 1:
        # what the tables say decides: a shared block's pooled keys once a
        # program, or (nothing shared, or long tails) a stream's each
        plan = _shared_plan(table, group, pos[:, 0], live[:, 0],
                            ck.shape[2], sz)
        score, read = lax.cond(plan.fits,
                               lambda: a_shared_block_once(plan),
                               a_stream_each)
    else:
        b = _batches(K, _BATCH_ROWS)
        score = lax.map(
            lambda s: lax.map(
                lambda a: of_stream(a[0], s[1], s[2], a[1]),
                (s[0].reshape((K // b, b) + s[0].shape[1:]),
                 s[3].reshape(K // b, b))),
            (qg, table, group, pos))
        read = jnp.int32(S * (K // b) * W * nKV)
    # the block scores of a whole program are small (256 x 2 x 2,072 fp32 =
    # 4.2 MB where the scores they are the maxima of are batched above): the
    # order is found once, for every row
    ids, n = choose(score.reshape(S * K, nKV, W), pos.reshape(S * K),
                    jnp.repeat(table, K, axis=0), sz)
    ids, n = ids.reshape(S, K, nKV, sz.width), n.reshape(S, K, nKV)
    n = jnp.where(live[..., None], n, 0)
    return jnp.where(live[..., None, None], ids, DEAD_BLOCK), n, read


def select_blocks(q, ck, layer, table, pos, live, sz: Sizes, scale: float
                  ) -> Tuple[jax.Array, jax.Array]:
    """``select_blocks_counted``'s ids and counts."""
    return select_blocks_counted(q, ck, layer, table, pos, live, sz,
                                 scale)[:2]


__all__ = ["Sizes", "write_pooled_chunk", "write_pooled_rows",
           "block_scores", "choose", "select_blocks",
           "select_blocks_counted", "DEAD_BLOCK"]

"""Pallas paged-attention: decode attend does O(context) work, not O(pool).

The serving tier's paged KV cache (inference/kv_cache.py) stores K/V in a
stacked block pool (logically ``[L, G, B, nH, bs, D]``, held lane-dense —
see ``kv_cache.kv_fold``) and the baseline ``paged_attend`` scores each
query against ALL B pool blocks of a layer, then routes through the one-hot
block-table selector — per-token attend FLOPs and HBM bytes scale with
pool CAPACITY, not the request's live context. This module is the real
kernel the one-hot contraction stood in for: the host-built block tables
ride in as scalar-prefetch indices (the sparse_flash.py flattened-LUT
pattern) and a grid step takes ONE stream (and head block) through ALL its
ceil(context/bs) live blocks, P table slots at a time: the WHOLE stacked
pool stays in HBM (``pl.ANY``) and the step copies each live block's tile
— every head of the block, one contiguous DMA, named by one precomputed
index with the layer's offset added (no layer is ever sliced out of the
pool, relaid or copied around the call) — into one half of a VMEM buffer
(during a stream's last group: the NEXT stream's first tiles) while it
attends the previous P tiles in the other: scores and the weighted sum over
P*bs positions at once, online-softmax accumulation in fp32 scratch updated
once a group, and an inclusive position mask (one vector compare) so the
final partial block contributes exactly its written rows.

Shapes follow the one-hot path exactly: q is ``[G, Q, K, nH, D]`` where K
is the query rows PER STREAM — 1 for plain decode, k+1 for speculative
verify, the chunk width for chunked prefill. All K rows of a stream share
its block table; ``positions[g, q, k]`` is each row's inclusive last
attendable position (per-row causal offsets), so all three serving paths
enter through ONE call with one plan, one walk of the live blocks and one
mask (``_walk_blocks``).  The arithmetic inside a group has two bodies,
picked by the shapes alone (``_dense``): a step of few query rows a K/V
head — decode, verify — is bound by its copies and keeps
``_pattn_kernel``; a step of ``_DENSE_ROWS`` or more — a run of a prefill
chunk — is bound by its products and its softmax arithmetic and takes
``_pattn_chunk_kernel`` (operands as stored, lane-wide row state, groups
of ``_CHUNK_KEYS`` keys, no mask where no row's edge lies).  Heads a step
and slots a group come from the shapes too (``_tile_rule``), under a VMEM
budget.

Static-shape discipline: the grid is ``(G*Q, nH/bh)`` — compile-time
constants — and the loop over a stream's groups runs to its LIVE count, a
scalar the table state decides: a dead stream's step copies and attends
nothing and emits zeros. Compute and HBM traffic scale with
ceil(context/bs); the compiled shape never changes, so the serving
engine's zero-recompile sentinel holds. bf16 pools (``kv_cache_dtype:
bf16``) dequantize in-VMEM: ``_pattn_kernel`` upcasts tiles to fp32 at
the register level (the chunk body hands them to the MXU as they are:
the same products on the chip), accumulation is fp32, and only the final
output drops back to q's dtype. What no layer changes — live counts, tile
rows, the mask's row limits — is an ``AttendPlan`` the caller builds once
an execution.

On CPU the kernel runs in interpret mode — which is how the dp=8 CPU-mesh
tier-1 proves logit parity against the one-hot baseline.

``paged_write`` is the other half of the same mechanism: new K/V rows are
written INTO the donated pool where it lies (an aliased ``pallas_call``
whose scalar-prefetched tile index and offsets pick the block tile of a
RUN of rows — the consecutive rows of one stream that lie in one block: a
prefill chunk's pages, a model of blocks' block; one row where a stream
brings one — read-modify-write of that one tile in VMEM), so a step's cost
is O(rows written), never O(pool), and its grid steps O(runs).
"""
from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from .flash_attention import NEG_INF, _interpret
from ..parallel import comm
from ..parallel.topology import DP_AXIS, MP_AXIS

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

_ENV_KNOB = "DS_PAGED_KERNEL"
# How many heads of a step the kernel's head loop lays side by side (the
# scheduler interleaves their MXU / VPU chains). Timed on the v5e at
# gpt2-large's decode shape (20 heads of 64, a group of 16 slots x 16
# positions): 1 / 2 / 4 / 10 / 20 heads 30.6 / 18.1 / 14.4 / 12.7 / 12.3 ms
# an execution; past four every start pays for it in lowering time (0.18 /
# 0.38 / 0.72 s at 4 / 10 / 20). A group is what ``_tile_rule`` makes it by
# shape (4 K/V heads of 128 in blocks of 64: 16 slots x 64 positions, all
# four heads side by side); a head's body costs the same ops whatever the
# group's width (``stack`` is one load).
_HEAD_UNROLL = 4


def paged_kernel_enabled(flag="auto") -> bool:
    """Resolve the ``inference.paged_kernel`` knob (the established
    gating contract — see fused_elementwise_enabled): True/False force;
    ``DS_PAGED_KERNEL=0/1`` overrides "auto"; otherwise on for TPU, off
    for CPU/GPU. Forced-on off-TPU runs the kernel in interpret mode —
    bit-for-bit the same program, pure XLA execution — which is how the
    CPU-mesh tier-1 tests the kernel paths."""
    if flag is True or flag is False:
        return bool(flag)
    env = os.environ.get(_ENV_KNOB)
    if env in ("0", "1"):
        return env == "1"
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------- #
# Analytic attend cost model (the structural ratio SERVE_BENCH reports)
# --------------------------------------------------------------------- #

def _attend_keys(block_size: int, context: Optional[int] = None,
                 pool_blocks: Optional[int] = None) -> int:
    """Key rows one attend touches. Pass ``pool_blocks`` for the one-hot
    contraction's pool-capacity term (B*bs — every pool row, every
    token) or ``context`` for the kernel's live-context term
    (ceil(ctx/bs)*bs — the stream's own blocks, final one padded)."""
    if (context is None) == (pool_blocks is None):
        raise ValueError("pass exactly one of context= / pool_blocks=")
    if pool_blocks is not None:
        return int(pool_blocks) * int(block_size)
    ctx = max(1, int(context))
    return -(-ctx // int(block_size)) * int(block_size)


def attend_flops_per_token(num_heads: int, head_dim: int, block_size: int,
                           *, context: Optional[int] = None,
                           pool_blocks: Optional[int] = None,
                           num_layers: int = 1) -> int:
    """Analytic attend FLOPs to decode ONE token: 2*nH*D per key row for
    the QK^T scores plus the same for the PV combine. Dominant terms
    only (softmax and the one-hot selector contractions are excluded on
    both sides, so the kernel/one-hot ratio is conservative). The kernel
    contracts a group of P blocks at once and masks what lies past the
    context: block-rounded keys are what it is charged, as before."""
    keys = _attend_keys(block_size, context, pool_blocks)
    return 4 * int(num_heads) * int(head_dim) * keys * int(num_layers)


def attend_hbm_bytes_per_token(num_heads: int, head_dim: int,
                               block_size: int, *,
                               context: Optional[int] = None,
                               pool_blocks: Optional[int] = None,
                               kv_itemsize: int = 4,
                               num_layers: int = 1) -> int:
    """Analytic K+V HBM bytes one decode attend streams: 2 (K and V)
    planes of ``keys * nH * D`` elements per layer. The one-hot side
    reads the whole pool; the kernel copies ceil(ctx/bs) block tiles of
    ``nH * bs * D`` elements (one DMA each: a LIVE slot of a group is
    copied, a dead one is not), so the count is exact for it."""
    keys = _attend_keys(block_size, context, pool_blocks)
    return (2 * keys * int(num_heads) * int(head_dim)
            * int(kv_itemsize) * int(num_layers))


def attend_step_counts(live_blocks, *, K: int, num_heads: int,
                       head_dim: int, block_size: int, table_width: int,
                       kv_itemsize: int, q_itemsize: int = 2):
    """(steps, live steps) the kernel sequences for ONE layer's attend,
    from each stream's live block count — host integers, no device work.
    A step is one pass of the kernel's sequencing for one head block: a
    group of P table slots of a live stream (live: it copies and attends
    at least one block), or the empty grid step of a dead stream.
    ``num_heads`` is what one shard holds."""
    bh, P_ = _tile_rule(K, num_heads, head_dim, block_size, table_width,
                        kv_itemsize, q_itemsize)
    groups = -(-np.asarray(live_blocks, np.int64) // P_)
    per = num_heads // bh
    return int(np.maximum(groups, 1).sum()) * per, int(groups.sum()) * per


def attend_cold_steps(live_blocks, *, calls: int = 1) -> int:
    """Live steps of ONE layer's attend whose first group nothing started
    (host integers): both attend kernels start a step's first copies
    during the step before it, so a live stream starts cold only as the
    first of its call (``calls``: the shards of a dp mesh, each a call
    over its own run of streams) or after a dead stream.  Further head
    blocks or row tiles of a live stream follow a live step."""
    live = np.asarray(live_blocks).reshape(calls, -1) > 0
    return int(live[:, 0].sum() + (live[:, 1:] & ~live[:, :-1]).sum())


# --------------------------------------------------------------------- #
# Kernel
# --------------------------------------------------------------------- #

def _head_loop(bh, body):
    """``body(h)`` for every head of the step: a loop whose body holds
    up to ``_HEAD_UNROLL`` heads side by side (their chains are
    independent, so the scheduler interleaves them) rather than bh
    copies of the body."""
    u = max(d for d in range(1, min(bh, _HEAD_UNROLL) + 1) if bh % d == 0)

    def group(g, carry):
        for i in range(u):
            body(g * u + i)
        return carry

    jax.lax.fori_loop(0, bh // u, group, 0)


def _walk_blocks(nlive_ref, rows_ref, base_ref, k_hbm, v_hbm, o_ref, k_buf,
                 v_buf, sem, ahead, *, P, begin, attend, finish):
    """The part of a grid step = one (stream, head block) that both
    bodies of the attend share: ALL of the stream's live blocks, P table
    slots at a time. The pools stay in HBM (``pl.ANY``); the step copies
    group g + 1's block tiles (every head of the block: one contiguous
    DMA a tile) into one half of ``k_buf`` / ``v_buf`` while
    ``attend(g, slot)`` computes on group g in the other, and
    issues a copy for a LIVE block only: a dead stream's step moves and
    computes nothing and emits exact zeros, matching the one-hot
    baseline's all-masked selector.  ``begin()`` empties the body's
    online-softmax state before a live stream's first group,
    ``finish()`` writes its output after the last.

    The copies run across grid steps too: during its LAST group a step
    starts the first group of the NEXT grid step (the next head block,
    else the next stream, where that one is live) into the half it has
    left, so a stream's first copies lie behind the stream before's
    compute and only a stream after a dead one starts cold. The grid is
    sequential and the buffers, the semaphores and ``ahead`` (SMEM:
    whether this step's first group is already in flight, and in which
    half) live across its steps.  The slots of the last group past the
    live count have their V rows zeroed (0 x finite); their K rows are
    whatever the buffer held, and lie wholly behind the mask."""
    s_idx, hb = pl.program_id(0), pl.program_id(1)
    bh = k_buf.shape[2]
    nlive = nlive_ref[s_idx]
    groups = pl.cdiv(nlive, P)

    @pl.when(jnp.logical_and(s_idx == 0, hb == 0))
    def _first_step():
        ahead[0] = 0
        ahead[1] = 0

    def tiles_of(s, hb, g, slot, act):
        """``act`` on the K and V copy of every live slot of group g of
        stream s, head block hb (into buffer half ``slot``)."""
        heads = pl.ds(hb * bh, bh)

        def one(p, carry):
            row = rows_ref[s, g * P + p] + base_ref[0]
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                act(pltpu.make_async_copy(hbm.at[row, heads],
                                          buf.at[slot, p], sem.at[slot]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(P, nlive_ref[s] - g * P), one, 0)

    # The grid step after this one, and whether it has anything to copy.
    streams = pl.num_programs(0)
    wrap = hb + 1 == pl.num_programs(1)
    s_next = jnp.where(wrap, s_idx + 1, s_idx)
    hb_next = jnp.where(wrap, 0, hb + 1)
    next_live = jnp.logical_and(
        s_next < streams, nlive_ref[jnp.minimum(s_next, streams - 1)] > 0)

    def group(g, carry):
        slot = jax.lax.rem(ahead[1] + g, 2)

        @pl.when(g + 1 < groups)
        def _next():
            tiles_of(s_idx, hb, g + 1, 1 - slot, lambda dma: dma.start())

        @pl.when(jnp.logical_and(g + 1 == groups, next_live))
        def _next_step():
            tiles_of(s_next, hb_next, 0, 1 - slot, lambda dma: dma.start())

        tiles_of(s_idx, hb, g, slot, lambda dma: dma.wait())

        def zero_v(p, carry):
            v_buf[slot, p] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
            return carry
        jax.lax.fori_loop(jnp.minimum(P, nlive - g * P), P, zero_v, 0)

        attend(g, slot)
        return carry

    @pl.when(groups == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(groups > 0)
    def _live():
        begin()

        @pl.when(ahead[0] == 0)
        def _cold():
            tiles_of(s_idx, hb, 0, ahead[1], lambda dma: dma.start())

        jax.lax.fori_loop(0, groups, group, 0)
        # What the last group started for the next step, and where.
        ahead[1] = jax.lax.rem(ahead[1] + groups, 2)
        ahead[0] = next_live.astype(jnp.int32)
        finish()


def _empty_state(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _pattn_kernel(nlive_ref, rows_ref, base_ref, lim_ref, q_ref, k_hbm,
                  v_hbm, o_ref, k_buf, v_buf, sem, ahead, m_scr, l_scr,
                  acc_scr, *, scale, bs, K, D, P):
    """The body of a step of FEW query rows (decode, verify), shaped for
    its copies: ``_walk_blocks`` with m / l / acc the standard
    online-softmax carry, updated once a group per head.

    A head's K/V tile lies in the buffer as the pool holds it,
    lane-dense ``[bs/f, f*D]``: position t of the block at row t // f,
    lanes (t % f)*D.. . Nothing re-tiles it; a group's P tiles are
    stacked into one ``[P*bs/f, f*D]`` operand (whole vregs once
    widened). Each query row comes f times (``_paged_local`` lays copy i
    into lanes i*D.. of an otherwise zero ``f*D``-wide row), so ONE
    full-lane contraction against the stack gives copy i the scores of
    the positions with t % f == i — column c of copy i is position
    ``g*P*bs + c*f + i`` of the stream — and one against the V stack
    gives it their weighted sum in lanes i*D.. . Each copy keeps its own
    online-softmax state (row ``i*K + k`` of its head) and the copies
    are merged when the stream's last group is done. With f == 1
    (head_dim >= 128) this is the plain kernel.

    The mask is one vector compare: ``lim_ref`` holds, per score row,
    the last attendable position less the copy's lane offset i, so a
    column is allowed iff ``g*P*bs + c*f <= lim`` (and, where the plan
    has a window, a second column with the FIRST attendable position:
    one more compare).

    Positions here count from the first block the plan lists: the whole
    context for an unbounded table, the first block in reach for a
    window's ring. Grouped heads never show here: a K/V head's ``group``
    query heads ride as ``group * K`` query rows of that one head
    (``_paged_local``), so the contraction is ``[group*K, D] x [D,
    keys]`` a K/V head and a block's tile is copied once for all of
    them."""
    bh, fK, fD = q_ref.shape[1:]
    f = fD // D
    N = P * (bs // f)

    def attend(g, slot):
        # Inclusive per-row position mask; the final partial block
        # contributes exactly its written rows, and verify's K=k+1 rows
        # get their per-row causal offsets here.
        col = jax.lax.broadcasted_iota(jnp.int32, (fK, N), 1) * f \
            + g * (P * bs)
        if lim_ref.shape[2] == 1:
            allowed = col <= lim_ref[0]               # [fK, 1] -> [fK, N]
        else:
            # A window: the row's first attendable position beside its
            # last (``_plan_window``).
            edges = lim_ref[0]
            allowed = jnp.logical_and(col <= edges[:, 0:1],
                                      col >= edges[:, 1:2])

        def stack(buf, h):
            # In-VMEM dequant: bf16 pool tiles upcast at the registers,
            # scores and the accumulator stay fp32 throughout. One load
            # of the head's P tiles whatever P is (a kernel body is traced
            # op by op on every start).
            return buf[slot, :, h].astype(jnp.float32).reshape(N, fD)

        def head(h):
            q_h = q_ref[0, h].astype(jnp.float32)     # [f*K, f*D]
            s = jax.lax.dot_general(
                q_h, stack(k_buf, h), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(allowed, s, NEG_INF)
            m_prev = m_scr[h, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # A copy may have seen no attendable position yet (context
            # shorter than its first one): keep its state empty rather
            # than exp(NEG_INF - NEG_INF) = 1 a column.
            p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
            l_scr[h, :, 0:1] = (l_scr[h, :, 0:1] * alpha
                                + jnp.sum(p, axis=1, keepdims=True))
            pv = jax.lax.dot_general(
                p, stack(v_buf, h), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_scr[h] = acc_scr[h] * alpha + pv
            m_scr[h, :, 0:1] = m_new

        _head_loop(bh, head)

    # Merge each query row's f copies (softmax over the union of their
    # positions); a row that could attend nothing keeps l == 0 and emits
    # zeros.
    def merge(h):
        copies = [slice(i * K, (i + 1) * K) for i in range(f)]
        m_fin = m_scr[h, copies[0], 0:1]
        for rows in copies[1:]:
            m_fin = jnp.maximum(m_fin, m_scr[h, rows, 0:1])
        l_fin = jnp.zeros_like(m_fin)
        out = jnp.zeros((K, D), jnp.float32)
        for i, rows in enumerate(copies):
            w = jnp.exp(m_scr[h, rows, 0:1] - m_fin)
            l_fin = l_fin + w * l_scr[h, rows, 0:1]
            out = out + w * acc_scr[h, rows, i * D:(i + 1) * D]
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0, h] = (out / l_safe).astype(o_ref.dtype)

    _walk_blocks(nlive_ref, rows_ref, base_ref, k_hbm, v_hbm, o_ref, k_buf,
                 v_buf, sem, ahead, P=P,
                 begin=lambda: _empty_state(m_scr, l_scr, acc_scr),
                 attend=attend, finish=lambda: _head_loop(bh, merge))


def _row_bands(R: int) -> int:
    """Query rows a band of the chunk body holds: two bands a head where
    each is whole packed tiles (16 rows of bf16) — two independent chains
    for the scheduler to lay side by side, one's products under the
    other's exponentials.  On the v5e at 512 rows x 512 keys (cell 14's
    run, ms a chunk's attend): one band 7.75, two 7.35, four 10.6; at 448
    rows (cell 11's): 0.531 / 0.522 / 0.728 (PERF.md section 6, PR 65)."""
    return R // 2 if R % 32 == 0 else R


def _pattn_chunk_kernel(nlive_ref, rows_ref, base_ref, edge_ref, lim_ref,
                        q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem,
                        ahead, m_scr, l_scr, acc_scr, *, scale, bs, P):
    """The body of a step of MANY query rows (a run of a prefill chunk:
    ``_DENSE_ROWS`` or more a K/V head), shaped for its products, not for
    its copies: ``_walk_blocks`` as ``_pattn_kernel`` — the same plan, the
    same copies of live blocks only, the same mask and output — and its
    own arithmetic.  It declines a folded pool (head_dim 64: ``f`` > 1,
    whose query rows ride ``f`` times in zero-padded lanes); such a chunk
    stays on ``_pattn_kernel``.

    1. Operands go into the MXU as stored (at the wider of q's dtype and
       the pool's: bf16 x bf16 wherever both are), fp32 accumulation; the
       probabilities go in at that dtype too.  On the chip that IS
       ``_pattn_kernel``'s arithmetic — a float32 product at default
       precision is one bf16 pass there, its operands rounded on the way
       in, and at equal tiles the two bodies' outputs are equal bit for
       bit (PERF.md section 6, PR 65) — without its converts.  m, l, the
       exponentials, the rescale and the accumulator stay fp32.
    2. m and l lie with every lane of a row holding the row's value: whole
       vregs in and out, no one-lane slice of a 128-lane row.
    3. A group is ``_CHUNK_KEYS`` keys (``_tile_rule``), so the
       ``[rows, D]`` accumulator's rescale and the m / l update are paid
       once per that many keys; a head's rows go in two bands
       (``_row_bands``).
    4. The mask is applied in the groups some row's edge lies in and
       nowhere else: ``edge_ref`` holds, per stream, the least last and
       (a window) the greatest first attendable position of its live
       rows, so one scalar compare a group picks the body without the
       iota, the compares and the selects.  A row that has seen nothing
       yet counts its masked columns (exp(0) each) until its first real
       score's alpha = 0 drops them; one that never sees any (a dead row
       of the chunk) is zeroed at the end, as ``_pattn_kernel`` emits
       it."""
    s_idx = pl.program_id(0)
    bh, R, D = q_ref.shape[1:]
    N = P * bs
    band = _row_bands(R)
    window = lim_ref.shape[2] == 2
    lanes = m_scr.shape[2]
    dtype = jnp.promote_types(q_ref.dtype, k_buf.dtype)

    def wide(x, n):
        """``x [rows, lanes]``, every lane its row's value, ``n`` wide."""
        if n % lanes == 0:
            return x if n == lanes else pltpu.repeat(x, n // lanes, axis=1)
        return x[:, 0:1]

    def attend(g, slot):
        first = g * N
        clear = first + (N - 1) <= edge_ref[s_idx, 0]
        if window:
            clear = jnp.logical_and(clear, first >= edge_ref[s_idx, 1])

        def heads(masked):
            def head(h):
                k = k_buf[slot, :, h].reshape(N, D).astype(dtype)
                v = v_buf[slot, :, h].reshape(N, D).astype(dtype)
                for r0 in range(0, R, band):
                    rows = slice(r0, r0 + band)
                    s = jax.lax.dot_general(
                        q_ref[0, h, rows].astype(dtype), k,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
                    if masked:
                        col = jax.lax.broadcasted_iota(
                            jnp.int32, (band, N), 1) + first
                        edges = lim_ref[0, rows]
                        allowed = col <= edges[:, 0:1]
                        if window:
                            allowed = jnp.logical_and(
                                allowed, col >= edges[:, 1:2])
                        s = jnp.where(allowed, s, NEG_INF)
                    m_prev = m_scr[h, rows]
                    m_new = jnp.maximum(
                        m_prev, jnp.max(s, axis=1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.exp(s - wide(m_new, N))
                    l_scr[h, rows] = l_scr[h, rows] * alpha \
                        + jnp.sum(p, axis=1, keepdims=True)
                    pv = jax.lax.dot_general(
                        p.astype(dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    acc_scr[h, rows] = acc_scr[h, rows] * wide(alpha, D) + pv
                    m_scr[h, rows] = m_new
            _head_loop(bh, head)

        pl.when(clear)(lambda: heads(False))
        pl.when(jnp.logical_not(clear))(lambda: heads(True))

    def emit(h):
        seen = jnp.logical_and(m_scr[h, :, 0:1] > 0.5 * NEG_INF,
                               lim_ref[0][:, 0:1] >= 0)
        l = l_scr[h, :, 0:1]
        out = acc_scr[h] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, h] = jnp.where(seen, out, 0.0).astype(o_ref.dtype)

    _walk_blocks(nlive_ref, rows_ref, base_ref, k_hbm, v_hbm, o_ref, k_buf,
                 v_buf, sem, ahead, P=P,
                 begin=lambda: _empty_state(m_scr, l_scr, acc_scr),
                 attend=attend, finish=lambda: _head_loop(bh, emit))


# One step's VMEM: the rule below keeps its reckoning under this, well
# inside the 16 MiB a v5e kernel gets without asking for more.
_VMEM_BUDGET = 8 * 2 ** 20
# ... and the kernel asks for no more than that default.
_VMEM_LIMIT = 16 * 2 ** 20


def _fold(D: int, bs: int) -> int:
    """Positions of a block side by side in the pool's 128 lanes
    (``kv_cache.kv_fold``, which imports this module)."""
    return math.gcd(128 // D, bs) if D < 128 and 128 % D == 0 else 1


def _step_vmem_bytes(bh: int, P: int, K: int, D: int, bs: int,
                     itemsize: int, q_itemsize: int) -> int:
    """What one grid step holds in VMEM at tile (bh, P): both halves of
    the K and V buffers and the pipeline's two buffers of the q / limit /
    output blocks (sublanes padded to the packed tile, lanes to 128),
    the fp32 scratch, and the fp32 values of one head in flight."""
    f = _fold(D, bs)
    fK, fD, bsf = f * K, max(f * D, 128), bs // f
    pad = lambda rows, size: -(-rows // (32 // size)) * (32 // size)  # noqa: E731
    kv = 2 * P * bh * pad(bsf, itemsize) * fD * itemsize
    q = bh * pad(fK, q_itemsize) * fD * q_itemsize
    out = bh * pad(K, q_itemsize) * 128 * q_itemsize
    lim = pad(fK, 4) * 128 * 4
    scratch = 3 * bh * pad(fK, 4) * fD * 4
    live = (2 * P * bsf * fD + 4 * pad(fK, 4) * max(P * bsf, 128)) * 4
    return 2 * (kv + q + out + lim) + scratch + live


# What a group of a step of few query rows copies, K and V tiles together,
# where the table is that long. On the v5e at head_dim 128, blocks of 64,
# 4 K/V heads of 8 query rows (PERF.md section 6, PR 40): 0.25 / 0.5 / 1 /
# 2 MiB a group (2 / 4 / 8 / 16 slots) read 44 / 49 / 78 / 86% of the
# bandwidth over long tables and 39 / 41 / 59 / 60% over a window's walks
# of 33 blocks; the copies alone stop at 89%, and 4 MiB would not fit.
_GROUP_BYTES = 2 ** 21
# Query rows from which a step's products fill the MXU: such a step (a
# prefill chunk) is bound by them, not by the copies behind them.
_DENSE_ROWS = 128
# Keys a group of such a step holds (``_pattn_chunk_kernel``): the
# ``[rows, D]`` accumulator's rescale and the m / l update are paid once a
# group.  On the v5e at cell 14's run (512 query rows a K/V head, blocks of
# 128, 70k cached rows; ms a chunk's attend, ``_pattn_kernel`` 28.6), four
# heads a step: 128 / 256 / 512 keys 12.9 / 9.1 / 7.75 in one row band, 512 /
# 1,024 keys 7.35 / 7.32 in two; heads a step at 512 keys, 1 / 2 / 4: 10.1 /
# 8.6 / 7.75 (8: 7.07 at a reckoning of 15.9 MiB, over the budget below);
# runs of twice the rows, two heads a step: 7.35 (PERF.md section 6, PR 65).
_CHUNK_KEYS = 512
# What the chunk body's reckoning may reach (``_chunk_vmem_bytes`` counts a
# band's score tile and its exponentials too, which ``_step_vmem_bytes``
# leaves to its margin): compiled for a described v5e inside ``_VMEM_LIMIT``
# at every cell's shape (tests/test_tpu_compile.py).
_CHUNK_VMEM_BUDGET = 12 * 2 ** 20


def _dense(K: int, D: int, bs: int) -> bool:
    """Whether a step of K query rows a K/V head takes the chunk body
    (``_pattn_chunk_kernel``): a function of the shapes alone."""
    return K >= _DENSE_ROWS and _fold(D, bs) == 1


def _chunk_vmem_bytes(bh: int, P: int, K: int, D: int, bs: int,
                      itemsize: int, q_itemsize: int) -> int:
    """``_step_vmem_bytes`` of the chunk body: both halves of the K and V
    buffers, the pipeline's two buffers of the q / limit / output blocks,
    the fp32 state (m and l a full 128 lanes a row) and a band's values in
    flight (scores, exponentials, the probabilities as the MXU takes
    them, their product)."""
    Dl = -(-D // 128) * 128
    pad = lambda rows, size: -(-rows // (32 // size)) * (32 // size)  # noqa: E731
    kv = 2 * P * bh * pad(bs, itemsize) * Dl * itemsize
    q = bh * pad(K, q_itemsize) * Dl * q_itemsize
    lim = pad(K, 4) * 128 * 4
    scratch = bh * pad(K, 4) * (2 * 128 + Dl) * 4
    live = _row_bands(K) * (P * bs * (8 + max(itemsize, q_itemsize))
                            + Dl * 4)
    return 2 * (kv + 2 * q + lim) + scratch + live


def _tile_rule(K: int, nH: int, D: int, bs: int, J: int, itemsize: int,
               q_itemsize: int = 2):
    """(heads a step, table slots a step) from the shapes. Slots: as
    many as give the score tile its 128 lanes (P * bs/f positions-rows),
    no more than the table is wide. Heads: the most that divide nH and
    keep the step under ``_VMEM_BUDGET`` — all of them for decode and
    verify, fewer for a prefill chunk whose f*K query rows carry 128-lane
    fp32 state each. Then, for a step of few query rows (decode, verify),
    slots again: doubled while a group copies under ``_GROUP_BYTES``, the
    table has them and the step stays under the budget — what such a step
    costs is its sequencing and the latency of its copies, a group each,
    so it wants its bytes in few groups (wide heads in long blocks reach
    128 lanes with a fifth of the bytes narrow ones in short blocks
    do).

    A step the chunk body takes (``_dense``) is bound by its products and
    its softmax arithmetic: slots for ``_CHUNK_KEYS`` keys a group, and
    the most heads that keep ``_chunk_vmem_bytes`` under
    ``_CHUNK_VMEM_BUDGET``."""
    f = _fold(D, bs)
    if _dense(K, D, bs):
        P = max(1, min(J, _CHUNK_KEYS // bs))
        return max(bh for bh in range(1, nH + 1) if nH % bh == 0 and (
            bh == 1 or _chunk_vmem_bytes(bh, P, K, D, bs, itemsize,
                                         q_itemsize) <= _CHUNK_VMEM_BUDGET
        )), P
    fits = lambda bh, P: _step_vmem_bytes(  # noqa: E731
        bh, P, K, D, bs, itemsize, q_itemsize) <= _VMEM_BUDGET
    P = max(1, min(J, 128 // max(1, bs // f)))
    bh = nH
    while bh > 1 and (nH % bh or not fits(bh, P)):
        bh -= 1
    tile = 2 * bh * bs * D * itemsize            # a slot's K and V tiles
    while (f * K < _DENSE_ROWS and P * tile < _GROUP_BYTES
           and 2 * P <= J and fits(bh, 2 * P)):
        P *= 2
    return bh, P


def _pool_rows(pool):
    """[L, G, B, nH, bs/f, f*D] -> [L*G*B, nH, bs/f, f*D]: every block
    tile of the stacked pool as one row of a 4-D array (a bitcast), so a
    kernel's index map names a tile by ONE precomputed index."""
    return pool.reshape((-1,) + pool.shape[3:])


class AttendPlan(NamedTuple):
    """What the attend needs of the tables and positions, which no layer
    changes: ``_paged_forward`` builds it once an execution.

    nlive [G, Q]: live blocks per stream (0 = dead stream).
    rows  [G, Q, J]: the pool tile (``group*B + block``, layer 0) of
          every block the stream walks, in walking order.
    lim   [G, Q, f*K, 1]: per score row, last attendable position less
          the copy's lane offset (see ``_pattn_kernel``), counted from
          the first block of ``rows``; ``[..., 2]`` with a window: the
          first attendable position beside it.

    The per-K/V-head form (``_plan_heads_local``) keeps an axis of K/V
    heads behind Q in all three (``rows [G, Q, nH, J]``): the plan's RANK
    says which form it is, and ``paged_attention`` reads it there."""
    nlive: jax.Array
    rows: jax.Array
    lim: jax.Array


def _plan_local(block_tables, positions, *, B, bs, f, reach=None, group=1):
    """Per-shard plan: G = groups this shard owns, so ``group*B`` is the
    shard's own tile row (block ids are group-local by construction).
    ``group`` query heads a K/V head: each position's row comes that many
    times (row ``m*K + k`` of a K/V head is query head ``m`` of its group
    at position k).  ``reach``: see ``_plan_window``."""
    G, Q, J = block_tables.shape
    bt = block_tables.astype(jnp.int32)
    pos = positions.astype(jnp.int32)
    if group > 1:
        pos = jnp.tile(pos, (1, 1, group))
    if reach is not None:
        return _plan_window(bt, pos, B=B, bs=bs, f=f, reach=reach)
    # Live block count per stream: the table's rows are a dense prefix
    # (blocks append in order), so ceil((max pos + 1)/bs) of them are
    # live, as far as the prefix goes; a dead leading entry marks the
    # whole stream inactive.
    dead = bt < 0
    prefix = jnp.where(dead.any(axis=2), jnp.argmax(dead, axis=2), J)
    nlive = jnp.minimum(jnp.clip(jnp.max(pos, axis=2) // bs + 1, 0, J),
                        prefix)
    shard_group = jnp.arange(G, dtype=jnp.int32)[:, None, None]
    rows = shard_group * B + jnp.maximum(bt, 0)
    # A row attends no further than the stream's live blocks reach (a
    # prefill chunk's padding rows lie past the table: they attend what
    # there is, stay finite, and nothing reads them).
    reach = jnp.minimum(pos, (nlive * bs - 1)[:, :, None])
    lim = reach[:, :, None, :] - jnp.arange(f, dtype=jnp.int32)[:, None]
    return AttendPlan(nlive, rows, lim.reshape(G, Q, -1, 1))


def _plan_window(bt, pos, *, B, bs, f, reach):
    """The plan of a class of layers whose rows attend ``reach`` positions
    back, themselves included (a sliding window): the table is a RING,
    logical block j of a stream at slot ``j % J``, and holds only blocks in
    reach.  The walk starts at the first block any live row (``pos >= 0``)
    reaches and ends at the last row's; ``rows`` lists those blocks in
    order, and both edges of the mask count from the first."""
    G, Q, J = bt.shape
    alive = pos >= 0
    last = jnp.max(pos, axis=2) // bs                           # -1: dead
    low = jnp.min(jnp.where(alive, jnp.maximum(pos - reach + 1, 0),
                            jnp.iinfo(jnp.int32).max), axis=2)
    first = jnp.where(last >= 0, low // bs, 0)
    walk = (first[:, :, None] + jnp.arange(J, dtype=jnp.int32)) % J
    blocks = jnp.take_along_axis(bt, walk, axis=2)
    # A stream whose newest block is dead is inactive (a dead table row).
    newest = jnp.take_along_axis(bt, (jnp.maximum(last, 0) % J)[:, :, None],
                                 axis=2)[:, :, 0]
    nlive = jnp.where((last >= 0) & (newest >= 0),
                      jnp.clip(last - first + 1, 0, J), 0)
    shard_group = jnp.arange(G, dtype=jnp.int32)[:, None, None]
    rows = shard_group * B + jnp.maximum(blocks, 0)
    origin = (first * bs)[:, :, None]
    copy = jnp.arange(f, dtype=jnp.int32)[:, None]
    hi = jnp.minimum(pos - origin, (nlive * bs - 1)[:, :, None])
    lo = pos - reach + 1 - origin
    lim = jnp.stack([hi[:, :, None, :] - copy, lo[:, :, None, :] - copy],
                    axis=-1)
    return AttendPlan(nlive, rows, lim.reshape(G, Q, -1, 2))


def _pool_geometry(pool_shape, D):
    """(B, bs, f) of a pool as held, [L, G, B, nH, bs/f, f*D]."""
    f = pool_shape[5] // D
    return pool_shape[2], pool_shape[4] * f, f


def _plan_heads_local(chosen, count, fill, *, B, bs, f, group):
    """The plan of an attend whose every K/V HEAD walks blocks of its own
    (a selection: ``ops/sparse_select.py``): chosen [G, Q, nH, J] the
    group-local block ids a head of a stream walks, in walking order (its
    ``count`` [G, Q, nH] first slots; the stream's NEWEST block last, the
    only one that may be partly filled); fill [G, Q, K]: a query row's
    offset in that last block (-1: a row that attends nothing).  Each
    (stream, K/V head) becomes a stream of the kernel over the pool seen as
    ``B * nH`` one-head tiles (``_paged_heads_local``): a step of a head
    copies and attends ITS blocks' tiles of that head only, and the mask's
    one compare bites in the walk's last block alone."""
    G, Q, nH, J = chosen.shape
    shard_group = jnp.arange(G, dtype=jnp.int32)[:, None, None, None]
    head = jnp.arange(nH, dtype=jnp.int32)[None, None, :, None]
    rows = (shard_group * B + jnp.maximum(chosen.astype(jnp.int32), 0)) \
        * nH + head
    fill = jnp.tile(fill.astype(jnp.int32), (1, 1, group))   # [G, Q, grp*K]
    reach = jnp.where(fill[:, :, None] >= 0,
                      (count[..., None] - 1) * bs + fill[:, :, None], -1)
    lim = reach[:, :, :, None, :] - jnp.arange(f, dtype=jnp.int32)[:, None]
    return AttendPlan(count.astype(jnp.int32), rows,
                      lim.reshape(G, Q, nH, -1, 1))


def attend_plan(block_tables, positions, pool, head_dim: int, *,
                mesh=None, reach: Optional[int] = None,
                group: int = 1, count=None) -> AttendPlan:
    """The per-execution index work of ``paged_attention``: block_tables
    [G, Q, J], positions [G, Q, K] (-1: a row that attends nothing),
    ``pool`` the stacked pool as held.  ``group``: query heads a K/V head
    of the pool.  ``reach``: the table is a window's ring
    (``_plan_window``).  Under a dp mesh each shard plans its own
    groups.

    The per-K/V-head form (``count`` given): block_tables [G, Q, nH, J] the
    blocks EACH head of a stream walks, ``count`` [G, Q, nH] how many, and
    ``positions`` each query row's offset in the walk's last block
    (``_plan_heads_local``); the plan says so by its rank."""
    B, bs, f = _pool_geometry(pool.shape, head_dim)
    if count is not None:
        fn = _on_mesh(
            functools.partial(_plan_heads_local, B=B, bs=bs, f=f,
                              group=group), mesh,
            lambda dpn, mpn: (P(dpn), P(dpn), P(dpn)),
            lambda dpn, mpn: AttendPlan(P(dpn), P(dpn), P(dpn)))
        return fn(block_tables, count, positions)
    fn = _on_mesh(
        functools.partial(_plan_local, B=B, bs=bs, f=f, reach=reach,
                          group=group), mesh,
        lambda dpn, mpn: (P(dpn), P(dpn)),
        lambda dpn, mpn: AttendPlan(P(dpn), P(dpn), P(dpn)))
    return fn(block_tables, positions)


def _paged_local(q, pool_k, pool_v, layer, nlive, rows, lim, *, scale,
                 tiles):
    """Per-shard kernel entry: shapes are LOCAL (G = groups this shard
    owns, nH = heads this shard owns). q [G, Q, K, nH, D], the stacked
    lane-dense pools [L, G, B, nH, bs/f, f*D], ``layer`` a traced int32
    scalar, the shard's ``AttendPlan``.

    q and the output ride head-major ([GQ, nH, K, D]) so a (bh, K, D)
    tile's last two dims span the array's: a head block in the
    second-minor position must be a multiple of 8 or all of nH on the
    TPU."""
    G, Q, K0, nQ, D = q.shape
    B, bs, f = _pool_geometry(pool_k.shape, D)
    nH, bsf, fD = pool_k.shape[3:]
    J = rows.shape[2]
    GQ = G * Q
    # Grouped heads: the ``grp`` query heads of a K/V head are grp * K0
    # query rows of that head (row m*K0 + k, as the plan lays ``lim``).
    grp = nQ // nH
    K = grp * K0
    bh, P_ = tiles or _tile_rule(K, nH, D, bs, J, pool_k.dtype.itemsize,
                                 q.dtype.itemsize)
    # Each query row f times, copy i in lanes i*D.. of a zero row (see
    # the kernel): [GQ, nH, f*K, f*D].
    if grp == 1:
        q = jnp.swapaxes(q.reshape(GQ, K, nH, D), 1, 2)
    else:
        q = q.reshape(GQ, K0, nH, grp, D).transpose(0, 2, 3, 1, 4) \
            .reshape(GQ, nH, K, D)
    q = (q[:, :, None, :, None, :] *
         jnp.eye(f, dtype=q.dtype)[:, None, :, None]
         ).reshape(GQ, nH, f * K, fD)
    # The layer's offset into the tile rows rides as one more scalar; a
    # last group may name slots past the table (never live: padded).
    base = (layer * (G * B)).astype(jnp.int32).reshape(1)
    rows = jnp.pad(rows.reshape(GQ, J), ((0, 0), (0, -J % P_)))

    def _stream_map(s, h, *scalars_p):
        return (s, h, 0, 0)

    def _lim_map(s, h, *scalars_p):
        return (s, 0, 0)

    kv_buf = pltpu.VMEM((2, P_, bh, bsf, fD), pool_k.dtype)
    edges = lim.shape[-1]            # 1, or 2 with a window's lower edge
    scalars = [nlive.reshape(GQ), rows, base]
    body, static = _pattn_kernel, dict(K=K, D=D)
    if _dense(K, D, bs):
        # Per stream, where its rows' masks bite: the least last and (a
        # window) the greatest first attendable position of its live rows.
        edge = lim.reshape(GQ, K, edges)
        alive = edge[..., :1] >= 0
        far = jnp.iinfo(jnp.int32).max
        scalars.append(jnp.concatenate(
            [jnp.min(jnp.where(alive, edge[..., :1], far), axis=1),
             jnp.max(jnp.where(alive, edge[..., 1:], -far), axis=1)], axis=1))
        body, static = _pattn_chunk_kernel, {}
    out = pl.pallas_call(
        functools.partial(body, scale=scale, bs=bs, P=P_, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(GQ, nH // bh),
            in_specs=[pl.BlockSpec((1, f * K, edges), _lim_map),
                      pl.BlockSpec((1, bh, f * K, fD), _stream_map),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, bh, K, D), _stream_map)],
            scratch_shapes=[
                kv_buf, kv_buf, pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((bh, f * K, 128), jnp.float32),
                pltpu.VMEM((bh, f * K, 128), jnp.float32),
                pltpu.VMEM((bh, f * K, fD), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((GQ, nH, K, D), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        name=body.__name__,
        interpret=_interpret(),
    )(*scalars, lim.reshape(GQ, f * K, edges), q,
      _pool_rows(pool_k), _pool_rows(pool_v))
    if grp == 1:
        return jnp.swapaxes(out[0], 1, 2).reshape(G, Q, K, nH, D)
    return out[0].reshape(GQ, nH, grp, K0, D).transpose(0, 3, 1, 2, 4) \
        .reshape(G, Q, K0, nQ, D)


def _paged_heads_local(q, pool_k, pool_v, layer, nlive, rows, lim, *, scale,
                       tiles):
    """``_paged_local`` under a per-K/V-head plan (``_plan_heads_local``):
    the pools seen as ``B * nH`` tiles of ONE head each (a bitcast), every
    (stream, K/V head) a stream of the kernel with the head's ``group``
    query heads as its query rows.  The kernel is the same."""
    G, Q, K0, nQ, D = q.shape
    L, _, B, nH, bsf, fD = pool_k.shape
    grp = nQ // nH
    qh = q.reshape(G, Q, K0, nH, grp, D).transpose(0, 1, 3, 2, 4, 5) \
        .reshape(G, Q * nH, K0, grp, D)
    one_head = lambda p: p.reshape(L, G, B * nH, 1, bsf, fD)   # noqa: E731
    streams = lambda a: a.reshape((G, Q * nH) + a.shape[3:])   # noqa: E731
    out = _paged_local(qh, one_head(pool_k), one_head(pool_v), layer,
                       streams(nlive), streams(rows), streams(lim),
                       scale=scale, tiles=tiles)
    return out.reshape(G, Q, nH, K0, grp, D).transpose(0, 1, 3, 2, 4, 5) \
        .reshape(G, Q, K0, nQ, D)


def _on_mesh(local_fn, mesh, in_specs, out_specs):
    """``local_fn`` under shard_map (manual over ALL mesh axes) when the
    mesh spans more than one device: GSPMD cannot partition a
    pallas_call, and group-local block ids make each shard's kernel
    self-contained — zero communication, the same locality argument the
    one-hot contraction relied on."""
    if mesh is None or math.prod(mesh.shape.values()) <= 1:
        return local_fn
    dpn = DP_AXIS if DP_AXIS in mesh.axis_names else None
    mpn = MP_AXIS if MP_AXIS in mesh.axis_names else None
    return comm.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs(dpn, mpn),
        out_specs=out_specs(dpn, mpn), axis_names=set(mesh.axis_names),
        # No collective inside: nothing for the vma checker to check,
        # and a pallas out_shape carries no vma.
        check_vma=False)


def _pool_spec(dpn, mpn):
    """[L, G, B, nH, bs/f, f*D]: groups over dp, heads over mp."""
    return P(None, dpn, None, mpn, None, None)


def paged_attention(q, pool_k, pool_v, layer, block_tables=None,
                    positions=None, *, scale, tiles=None, mesh=None,
                    plan: Optional[AttendPlan] = None):
    """Table-driven paged attention over one layer of the block pool.

    q:            [G, Q, K, nQ, D] — Q streams per group, K query rows
                  per stream (1 decode / k+1 verify / chunk prefill).
    pool_k/v:     [L, G, B, nH, bs/f, f*D] — the WHOLE stacked pool in
                  the lane-dense layout it is born in
                  (``kv_cache.PagedKVCacheSpec.shape``); nH K/V heads,
                  ``nQ // nH`` query heads each (head h reads K/V head
                  ``h // group``).
    layer:        int32 scalar — which layer of the pool to read.
    block_tables: [G, Q, J] int32 group-local block ids (DEAD_BLOCK for
                  unallocated tail entries).
    positions:    [G, Q, K] int32 inclusive last attendable position per
                  query row.
    plan:         ``attend_plan(block_tables, positions, ...)`` where the
                  caller built it once for all its layers (then the two
                  are not read here); a window or grouped heads come
                  through it (its ``reach`` / ``group``), and so does the
                  per-K/V-head form (its ``count``: a plan with an axis
                  of heads, ``_paged_heads_local``).
    tiles:        (heads a step, table slots a step) instead of the
                  shape rule's: the tests' handle on the tiling.

    Returns [G, Q, K, nH, D] in q's dtype. When ``mesh`` spans dp/mp the
    call runs under shard_map (see ``_on_mesh``)."""
    if pltpu is None:  # pragma: no cover - pallas TPU support missing
        raise RuntimeError("pallas TPU backend unavailable; run with "
                           "inference.paged_kernel=false")
    if plan is None:
        plan = attend_plan(block_tables, positions, pool_k, q.shape[-1],
                           mesh=mesh, group=q.shape[3] // pool_k.shape[3])
    # (a plan of ``attend_plan``'s per-K/V-head form: every head of a stream
    # walks blocks of its own, and heads are not sharded over a model axis)
    per_head = plan.rows.ndim == 4
    if per_head and mesh is not None and mesh.shape.get(MP_AXIS, 1) > 1:
        raise NotImplementedError("a per-head plan over a model axis")
    fn = _on_mesh(
        functools.partial(_paged_heads_local if per_head else _paged_local,
                          scale=scale, tiles=tiles), mesh,
        lambda dpn, mpn: (P(dpn, None, None, mpn, None),
                          _pool_spec(dpn, mpn), _pool_spec(dpn, mpn), P(),
                          P(dpn), P(dpn), P(dpn)),
        lambda dpn, mpn: P(dpn, None, None, mpn, None))
    return fn(q, pool_k, pool_v, jnp.asarray(layer, jnp.int32), *plan)


# --------------------------------------------------------------------- #
# The write: new rows into the donated pool, in place
# --------------------------------------------------------------------- #

# Rows of a block tile a run step reads, selects a row into and writes back
# at a time: the sublanes of one packed bf16 vreg (an fp32 tile's two).
_WRITE_GROUP = 16
# A stream's new rows ride a run step's VMEM as ONE block (fp32, both pools,
# two buffers each): a stream of more rows than this many bytes hold is
# written as several streams of consecutive rows.
_WRITE_ROWS_BYTES = 2 ** 20


def write_runs(K: int, block_size: int, one_block: bool = False) -> int:
    """Grid steps a stream of ``K`` consecutive rows takes in the write: one
    a RUN (the rows of the stream that lie in one block) — every block the
    rows can touch from any start, or one where the caller says they lie in
    ONE block (``one_block``); a row a step where a stream brings one."""
    return 1 if K == 1 or one_block else (K - 1) // block_size + 2


def _stream_rows(K: int, num_heads: int, lanes: int) -> int:
    """Rows of a stream of ``K`` a run step holds in VMEM at once
    (``_WRITE_ROWS_BYTES``): K, or a divisor of it."""
    rows = K
    while num_heads * rows * lanes * 4 > _WRITE_ROWS_BYTES and rows % 2 == 0:
        rows //= 2
    return rows


def write_step_counts(first_pos, rows, *, K: int, block_size: int,
                      one_block: bool = False, num_heads: int = 1,
                      head_dim: int = 128):
    """(rows, runs, grid steps) of ONE layer's write — host integers, no
    device work: ``first_pos`` / ``rows`` [streams], the position of each
    stream's first row and how many of its ``K`` rows are live (0: a dead
    stream).  A run is the live rows of a stream that lie in one block (what
    a live step lands); the steps are the static grid's, dead ones included
    (``write_runs`` a stream, each of at most ``_stream_rows`` rows).
    ``num_heads`` is what one shard holds."""
    first_pos = np.asarray(first_pos, np.int64).reshape(-1)
    rows = np.asarray(rows, np.int64).reshape(-1)
    last = first_pos + rows - 1
    runs = np.where(rows > 0, last // block_size - first_pos // block_size
                    + 1, 0)
    f = _fold(head_dim, block_size)
    part = _stream_rows(K, num_heads, f * head_dim)
    steps = len(rows) * (K // part) * write_runs(part, block_size, one_block)
    return int(rows.sum()), int(runs.sum()), int(steps)


def _kv_write_kernel(tile_ref, off_ref, *refs, D, K, N=1):
    """One grid step = one (group, RUN): the consecutive rows of a stream
    that lie in one block (``K`` rows a stream, ``N`` steps each; K = 1: a
    row).  The run's block tile ``[nH, bs/f, f*D]`` (or the aligned part of
    it that holds the run, where the caller knows one does: ``off_ref`` then
    counts from the part's start) is read, the run's rows put in their
    places, the tile written back.  Steps that name the same tile one after
    another (a dead step rides the tile of a live neighbour and changes
    nothing) keep it in VMEM: it is loaded from the pool on the first visit
    only and goes back when the block changes.

    K = 1 (``refs``: rows k, rows v, the pools in and out): the row
    ``[nH, 1, f*D]``, f copies side by side in the lanes, is selected into
    the whole tile at offset ``off_ref[g, r]`` (< 0: a dead row).

    K > 1 (``refs``: start, count[, part], then the same): the stream's rows
    are ONE fp32 block ``[nH, K, f*D]`` (a row a sublane, which a loop can
    name; exact) and the run is rows ``start .. start + |count|`` of it.
    ``count < 0`` says the run IS a block — all of its rows, live, from a
    sublane-aligned start: one aligned store as the tile lays them, no
    select.  Else a loop over the run's rows selects each into the
    ``_WRITE_GROUP`` tile rows that hold its offset ``off_ref[g, row]``
    (< 0: dead, no hit).  Selected in fp32 (exact for every pool dtype):
    the v5e has no 16-bit vector select."""
    *run, nk_ref, nv_ref, k_in, v_in, k_out, v_out = refs
    g, r = pl.program_id(0), pl.program_id(1)
    nH, bsf, fD = k_out.shape[1:]
    f = fD // D
    first = jnp.logical_or(
        r == 0, tile_ref[g, r] != tile_ref[g, jnp.maximum(r - 1, 0)])
    for part_ref in run[2:]:            # (which part of the tile, if a part)
        first = jnp.logical_or(
            first, part_ref[g, r] != part_ref[g, jnp.maximum(r - 1, 0)])

    @pl.when(first)
    def _load():
        k_out[...] = k_in[...]
        v_out[...] = v_in[...]

    def select(row_of, off, rows=None):
        """The row ``row_of(new)`` over the tile's offset ``off``, in the
        tile rows ``rows`` (a slice; None: the whole tile)."""
        n, at, at_row = bsf, (0,), jax.lax.div(off, f)
        if rows is not None:
            n, at, at_row = rows.size, (0, slice(None), rows), \
                at_row - rows.start
        hit = jax.lax.broadcasted_iota(jnp.int32, (nH, n, fD), 1) == at_row
        if f > 1:
            lane = jax.lax.broadcasted_iota(jnp.int32, (nH, n, fD), 2)
            hit = jnp.logical_and(
                hit, jax.lax.div(lane, D) == jax.lax.rem(off, f))
        for new, out in ((nk_ref, k_out), (nv_ref, v_out)):
            row = row_of(new).astype(jnp.float32)        # [nH, 1, f*D]
            cur = out[at].astype(jnp.float32)
            out[at] = jnp.where(hit, row, cur).astype(out.dtype)

    if K == 1:
        off = off_ref[g, r]

        @pl.when(off >= 0)
        def _write():
            select(lambda new: new[0, 0], off)
        return

    start, count = run[0][g, r], run[1][g, r]
    base = (r if N == 1 else r // N) * K    # the stream's first row
    group = _WRITE_GROUP if bsf % _WRITE_GROUP == 0 else bsf

    if f == 1 and K >= bsf:
        @pl.when(count < 0)
        def _whole():
            rows = pl.ds(pl.multiple_of(start, 8), bsf)
            for new, out in ((nk_ref, k_out), (nv_ref, v_out)):
                out[0] = new[0, 0, :, rows, :].astype(out.dtype)

    def one(i, carry):
        k = start + i
        off = off_ref[g, base + k]

        @pl.when(off >= 0)
        def _write():
            rows = None if group == bsf else pl.ds(
                pl.multiple_of(jax.lax.div(off, f * group) * group, group),
                group)
            select(lambda new: new[0, 0, :, pl.ds(k, 1), :], off, rows)
        return carry

    jax.lax.fori_loop(0, jnp.maximum(count, 0), one, 0)


def _write_local(pool_k, pool_v, k_new, v_new, layer, blk, off, *,
                 stream_rows, one_block):
    """Per-shard write: pools [L, G, B, nH, bs/f, f*D]; k_new/v_new
    [G, R, nH, D]; blk/off [G, R] (group-local ids, DEAD_BLOCK = -1); R is
    streams x ``stream_rows`` consecutive rows each."""
    _, G, B, nH, bsf, fD = pool_k.shape
    R, D = blk.shape[1], k_new.shape[-1]
    f = fD // D
    bs = bsf * f
    K = _stream_rows(stream_rows, nH, fD)
    N = write_runs(K, bs, one_block)
    # A step moves the run's block tile both ways — or, where every run lies
    # in ONE aligned group of ``_WRITE_GROUP`` tile rows (a block of a model
    # of blocks: K divides the group's positions), that PART of it alone.
    part = _WRITE_GROUP * f
    parts = bsf // _WRITE_GROUP if K > 1 and one_block and part % K == 0 \
        and bsf % _WRITE_GROUP == 0 else 1
    live = blk >= 0
    offs = jnp.where(live, off if parts == 1 else off % part, -1) \
        .astype(jnp.int32)
    if K == 1:
        run_live, run_blk, scalars = live, blk, (offs,)
    else:
        # The runs of every stream, from its first row's offset alone (the
        # rows' positions are consecutive): run n holds the rows of the
        # stream's n-th block, [first, end) of its K.
        S = R // K
        n = jax.lax.broadcasted_iota(jnp.int32, (1, 1, N), 2)
        off0 = off.reshape(G, S, K)[:, :, :1]
        first = jnp.clip(n * bs - off0, 0, K)
        end = jnp.clip((n + 1) * bs - off0, 0, K)
        k = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, K), 3)
        mine = jnp.logical_and(
            jnp.logical_and(k >= first[..., None], k < end[..., None]),
            live.reshape(G, S, 1, K))
        n_live = mine.sum(-1)                                   # [G, S, N]
        run_blk = jnp.where(mine, blk.reshape(G, S, 1, K), -1).max(-1) \
            .reshape(G, S * N)
        run_live = (n_live > 0).reshape(G, S * N)
        count = jnp.where(n_live > 0, end - first, 0)
        if f == 1:
            # (a run that IS a block, from a start the fp32 rows' sublanes
            # align with: the kernel's one store)
            whole = jnp.logical_and(n_live == bs, first % 8 == 0)
            count = jnp.where(whole, -count, count)
        scalars = (offs, first.reshape(G, S * N).astype(jnp.int32),
                   count.reshape(G, S * N).astype(jnp.int32))
    # Every grid step needs SOME block to hold; a dead step takes the
    # nearest live one's before it (else the first live one's, else block
    # 0), so each block is still one contiguous run of steps and a dead
    # step's tile is written back as it was read.
    idx = jnp.where(run_live, jax.lax.broadcasted_iota(
        jnp.int32, run_blk.shape, 1), -1)
    src = jax.lax.cummax(idx, axis=1)
    src = jnp.where(src >= 0, src, jnp.argmax(run_live, axis=1)[:, None])
    eb = jnp.maximum(jnp.take_along_axis(run_blk, src, axis=1), 0)
    group = jnp.arange(G, dtype=jnp.int32)[:, None]
    tiles = ((layer * G + group) * B + eb).astype(jnp.int32)   # [G, steps]
    if parts > 1:
        # (the part of its tile a run lies in; a dead step rides its live
        # neighbour's part as it rides its tile)
        scalars += (jnp.take_along_axis(
            (off0 // part).reshape(G, -1), src, axis=1).astype(jnp.int32),)

    def rows(new):
        # The row as it lies in a tile: f copies side by side in the
        # lanes (the kernel's select keeps the one at the row's offset).
        new = new.astype(pool_k.dtype)
        if K == 1:
            return jnp.tile(new, (1, 1, 1, f))[:, :, :, None, :]
        new = new.astype(jnp.float32).reshape(G, R // K, K, nH, D)
        return jnp.tile(new.transpose(0, 1, 3, 2, 4), (1, 1, 1, 1, f))

    def _row_map(g, r, *_):
        return (g, r if N == 1 else r // N, 0, 0, 0)

    def _pool_map(g, r, tiles_p, *more):
        return (tiles_p[g, r], 0, more[3][g, r] if parts > 1 else 0, 0)

    row_spec = pl.BlockSpec((1, 1, nH, K, fD), _row_map)
    pool_spec = pl.BlockSpec((1, nH, bsf // parts, fD), _pool_map)
    flat_k, flat_v = _pool_rows(pool_k), _pool_rows(pool_v)
    out_k, out_v = pl.pallas_call(
        functools.partial(_kv_write_kernel, D=D, K=K, N=N),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(scalars),
            grid=(G, tiles.shape[1]),
            in_specs=[row_spec, row_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec]),
        out_shape=[jax.ShapeDtypeStruct(flat_k.shape, flat_k.dtype),
                   jax.ShapeDtypeStruct(flat_v.shape, flat_v.dtype)],
        # operands: tiles, the scalars, rows_k, rows_v, pool_k, pool_v
        input_output_aliases={3 + len(scalars): 0, 4 + len(scalars): 1},
        name="_kv_write_kernel",
        interpret=_interpret(),
    )(tiles, *scalars, rows(k_new), rows(v_new), flat_k, flat_v)
    return out_k.reshape(pool_k.shape), out_v.reshape(pool_v.shape)


# The layers of a program hand the write the same shapes: under ``jax.jit``
# the run grid's index work and kernel are traced and lowered ONCE a program
# and called a layer (XLA inlines the calls) — a start pays a kernel
# instance's ~0.2 s of host time once, not a layer.  (A row a step is left
# as it lowers: the programs of a model of tokens keep their text.)
_write_runs_local = jax.jit(_write_local,
                            static_argnames=("stream_rows", "one_block"))


def paged_write(pool_k, pool_v, k_new, v_new, layer, blk, off, *,
                mesh=None, stream_rows: int = 1, one_block: bool = False):
    """Write R rows per group into one layer of both pools, in place.

    pool_k/v: [L, G, B, nH, bs/f, f*D] (donated by the caller's jit: the
    call aliases them to its outputs and touches a block tile each way per
    RUN of rows); k_new/v_new: [G, R, nH, D]; layer: int32 scalar; blk/off:
    [G, R] — rows with blk == DEAD_BLOCK write nowhere. Rows of one block
    must be consecutive and no block may be named by two separate runs of
    rows (the allocator's invariant: a writable block has one owner, and a
    stream's positions ascend).

    ``stream_rows``: the R rows are streams of this many rows each at
    CONSECUTIVE positions (``off`` counts up by one a row, dead rows'
    too, and starts again at 0 in the next block: a prefill chunk, a
    verify's drafts, a block of a model of blocks) — a grid step is then a
    run of a stream's rows in one block, ``write_runs`` steps a stream,
    not a row; ``one_block``: every stream's rows lie in ONE block (its
    first row at a multiple of ``stream_rows``, which divides the block).
    The pools come out byte for byte what a row a step leaves (1: rows of
    no stated order).  Returns (pool_k', pool_v')."""
    if pltpu is None:  # pragma: no cover - pallas TPU support missing
        raise RuntimeError("pallas TPU backend unavailable")
    fn = _on_mesh(
        functools.partial(_write_local if stream_rows == 1
                          else _write_runs_local, stream_rows=stream_rows,
                          one_block=one_block), mesh,
        lambda dpn, mpn: (_pool_spec(dpn, mpn), _pool_spec(dpn, mpn),
                          P(dpn, None, mpn, None), P(dpn, None, mpn, None),
                          P(), P(dpn), P(dpn)),
        lambda dpn, mpn: (_pool_spec(dpn, mpn), _pool_spec(dpn, mpn)))
    out = fn(pool_k, pool_v, k_new, v_new, jnp.asarray(layer, jnp.int32),
             blk, off)
    return out[0], out[1]


__all__ = ["paged_attention", "attend_plan", "AttendPlan", "paged_write",
           "write_runs", "write_step_counts",
           "paged_kernel_enabled", "attend_step_counts", "attend_cold_steps",
           "attend_flops_per_token", "attend_hbm_bytes_per_token"]

"""Flash attention — Pallas TPU kernels with custom VJP and block-sparsity.

The TPU-native replacement for the reference's fused attention core inside
the transformer kernel (csrc/transformer/softmax_kernels.cu +
strided_batch_gemm.h: QK^T → scale+mask softmax → AV, with saved softmax
output replayed in backward) AND its Triton block-sparse kernels
(ops/sparse_attention/trsrc/matmul.tr, softmax_fwd.tr). On TPU the dense
[S,S] fp32 score tensor is the HBM bottleneck, so we never materialize it:
the classic flash pattern computes attention block-by-block in VMEM with a
running (max, sum) softmax, and the backward recomputes scores per block
from the saved logsumexp — the same memory story as the reference's
``attn_dropout_checkpoint`` knob taken to its limit.

One kernel family serves three modes via static specialization:
- dense bidirectional (layout=None, causal=False)
- causal (score tiles above the diagonal are skipped: by static row bands
  inside the one-block kernels, by grid steps on the multi-block path)
- block-sparse (an int32 layout [H, nQ, nK] gates each (q-block, k-block)
  pair — the splash-attention pattern; masked blocks skip their matmuls)

Layout, two forms, chosen from (S, Sk, nH, dH, layout) alone (`tile_lanes`):
- IN PLACE over [B, S, nH*dH], which is the projections' own [B, S, nH, dH]
  for free: where ONE block covers the sequence (S = 1024 and below,
  `_pick_block`: every cell of the benchmark, BERT's 128 / 512) and a lane
  tile of 128 holds whole heads — two 64-wide heads side by side (nH
  even), or one head of 128 or 256.  A grid step is one tile of one batch
  row, its heads run through one traced body (`_each_head`), a head's
  q k^T and do v^T contract over all the tile's lanes with the neighbour's
  lanes zeroed in one operand, and its p v, ds k, ds^T q, p^T do come out
  tile-wide.  Nothing is transposed on the way in or out, and a fused
  [B, S, 3H] projection is read three times at three lane offsets
  (`flash_attention_qkv`).  A single 64-wide head cannot be a block of
  [B, S, nH, 64]: Mosaic wants a block's last two dims divisible by
  (8, 128) or equal to the array's, and (1, 64) of (nH, 64) is neither.
- [BH, S, D] (batch×heads flattened by `_to_bh`, a transpose each way) for
  everything else: a layout, a longer S, an odd head count, dH 32 or 96.
  Grid is (BH, q_blocks, k_blocks); the innermost (k) dimension iterates
  sequentially on TPU so VMEM scratch carries the running softmax state
  across k-blocks of one q-block; one block is one grid step a head with
  no running state.
`lowered` counts the calls by form.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

try:  # TPU backend bits are importable everywhere; interpret=True runs on CPU
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


import os

_BLOCK_TARGET = int(os.environ.get("DS_FLASH_BLOCK", "1024"))
# Which kernels a dense call runs.  A sequence `_pick_block` covers with
# ONE block (S of 128, 256, 512 or DS_FLASH_BLOCK; every cell of the
# benchmark: S = 1024) has no grid over positions: the forward is
# `_fwd_kernel`'s whole-row body (``band`` > 0: one grid step a head, a
# direct softmax) and the backward is `_bwd_fused_kernel`; both skip the
# masked half of a causal square by static row bands (`_BAND`, below), not
# by grid steps.  The multi-block grid path (running softmax in scratch,
# `_run_pred` skipping whole blocks above the diagonal) and the split
# `_bwd_dq_kernel` / `_bwd_dkv_kernel` serve a longer S (or one only
# smaller blocks divide), a layout, or a non-default autotuned tile only.
# DS_FLASH_BLOCK_BWD is the block of those SPLIT causal backward kernels
# (finer blocks trade grid-step overhead for blocks skipped).  Its default
# of 512 comes from a gpt2-large sweep on a v5e (commit 5ade0a3) from
# before the fused backward was the default (bwd 1024/512/256/128 ->
# 207.5/201.7/215.5/259.2 ms fwd+bwd; a forward at 512 cost +14 ms in grid
# steps); no cell runs that path and it is unmeasured on this tree.
# 0 = follow DS_FLASH_BLOCK.
_BLOCK_TARGET_BWD = int(os.environ.get("DS_FLASH_BLOCK_BWD", "512"))

# Rows of one band of the whole-sequence causal kernels.  Band i holds the
# query rows [i*_BAND, (i+1)*_BAND) and reads the keys [0, (i+1)*_BAND)
# only, so of T = S/_BAND bands' T*T score tiles T*(T+1)/2 are computed:
# at S = 1024 a band of 128 / 256 / 512 computes 56.25 / 62.5 / 75% of the
# square.  Chosen once on the chip (PERF.md section 6, PR 35); the choice
# follows what the code can observe (``causal`` and S), nothing a user sets.
_BAND = 256


def _row_band(S: int, Sk: int, causal: bool) -> int:
    """Rows a band when ONE block covers the sequence: `_BAND` for a causal
    self-attention square of at least two bands, else S (one band: the
    whole rectangle, every non-causal caller)."""
    if causal and S == Sk and S % _BAND == 0 and S >= 2 * _BAND:
        return _BAND
    return S


def computed_scores(S: int, Sk: int, causal: bool, blocks=None) -> int:
    """Scores one head tile COMPUTES in each of the forward and the
    backward (what the MXU and the vector unit pass over, masked or not):
    the bands' rectangles where one block covers the sequence, else the
    (bq, bk) blocks the grid does not skip."""
    bq, bk = blocks or (_pick_block(S), _pick_block(Sk))
    if (bq, bk) == (S, Sk):
        band = _row_band(S, Sk, causal)
        return sum(band * (r0 + band if band < S else Sk)
                   for r0 in range(0, S, band))
    return bq * bk * sum(1 for qi in range(S // bq) for kj in range(Sk // bk)
                         if not causal or kj * bk < (qi + 1) * bq)


def _cost(BH: int, D: int, scores: int, matmuls: int, operands):
    """A call's `pl.CostEstimate`, for XLA's scheduler: ``matmuls`` MXU
    passes and one exp a computed score, every operand moved once."""
    return pl.CostEstimate(
        flops=2 * matmuls * BH * scores * D, transcendentals=BH * scores,
        bytes_accessed=sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
                           for x in operands))


def _pick_block(s: int, target: int = 0) -> int:
    target = target or _BLOCK_TARGET
    for b in (target, 512, 256, 128):
        if b <= s and s % b == 0:
            return b
    return s  # small sequences: single block


def _pick_block_bwd(s: int, causal: bool) -> int:
    if not causal:       # no blocks to skip: finer only adds overhead
        return _pick_block(s)
    return _pick_block(s, _BLOCK_TARGET_BWD or _BLOCK_TARGET)


def _block_candidates(s: int):
    """Legal kernel blocks for a sequence of length s: the power-of-two
    grid the heuristic targets draw from, each dividing s."""
    return tuple(b for b in (128, 256, 512, 1024)
                 if b <= s and s % b == 0) or (s,)


def _resolve_blocks(kernel: str, q, k, causal: bool, heur, run_at):
    """Route a (bq, bk) pick through ops.autotune.  ``run_at(tile)``
    executes the real kernel pinned to a candidate tile (the measure);
    DS_AUTOTUNE=0 / CPU return ``heur`` — today's _BLOCK_TARGET
    heuristics (and their env overrides) bit-for-bit.  fwd and bwd
    resolve under separate kernel keys: the causal-bwd tile trade (finer
    blocks skip real compute) is real and shape-dependent."""
    from . import autotune
    BH, S, D = q.shape
    Sk = k.shape[1]
    cands = [(cq, ck) for cq in _block_candidates(S)
             for ck in _block_candidates(Sk)]
    measure = autotune.measure_from_runner(run_at) \
        if autotune.search_allowed() else None
    return autotune.resolve(kernel, (BH, S, Sk, D, int(causal)),
                            str(q.dtype), heur, cands, measure)


def _run_pred(causal: bool, qi, kj, bq: int, bk: int, layout_block=None):
    """Static-or-traced predicate for whether a (q,k) block pair runs."""
    conds = []
    if causal:
        conds.append(kj * bk < (qi + 1) * bq)
    if layout_block is not None:
        conds.append(layout_block != 0)
    if not conds:
        return True
    pred = conds[0]
    for c in conds[1:]:
        pred = jnp.logical_and(pred, c)
    return pred


def _dropout_keep(seed, bh, qi, kj, bq: int, bk: int, rate: float,
                  transposed: bool = False):
    """Regenerable dropout keep-mask for one (q-block, k-block) tile.

    A stateless position hash (murmur3 finalizer over
    ``seed ^ bh`` and the global (q, k) element index) rather than a
    sequential PRNG stream: forward and both backward kernels regenerate
    the exact same mask from the seed in whichever block orientation they
    iterate — the TPU-native replacement for the reference's *saved*
    dropout masks replayed in backward (ops/transformer/transformer.py:
    330-466, csrc/transformer/dropout_kernels.cu).
    """
    # Written in lax primitives: a kernel body is traced on every start, a
    # `jnp` operator on a tracer costs ~0.3 ms of dispatch where the
    # primitive costs 0.05, and the banded kernels call this once a band.
    u32 = np.uint32

    def position(dim, block, size):
        at = lax.broadcasted_iota(jnp.uint32, shape, dim)
        if isinstance(block, int):           # a static band: no add at 0
            return lax.add(at, u32(block * size)) if block else at
        return lax.add(at, lax.convert_element_type(
            lax.mul(block, np.int32(size)), jnp.uint32))
    shape = (bk, bq) if transposed else (bq, bk)
    qdim, kdim = (1, 0) if transposed else (0, 1)
    qpos, kpos = position(qdim, qi, bq), position(kdim, kj, bk)
    # Element id mixed with the (seed, head) stream id; uint32 wraparound is
    # fine (stays deterministic).
    stream = lax.bitwise_xor(
        lax.convert_element_type(seed, jnp.uint32),
        lax.mul(lax.convert_element_type(bh, jnp.uint32), u32(0x85EBCA6B)))
    x = lax.add(lax.add(lax.mul(qpos, u32(0x9E3779B9)), kpos), stream)
    x = lax.bitwise_xor(x, lax.shift_right_logical(x, u32(16)))
    x = lax.mul(x, u32(0x85EBCA6B))
    x = lax.bitwise_xor(x, lax.shift_right_logical(x, u32(13)))
    x = lax.mul(x, u32(0xC2B2AE35))
    x = lax.bitwise_xor(x, lax.shift_right_logical(x, u32(16)))
    # keep iff uniform[0,1) >= rate. Mosaic has no uint32->f32 cast; use the
    # top 24 bits via int32 (exact in f32).
    top = lax.convert_element_type(lax.shift_right_logical(x, u32(8)),
                                   jnp.int32)
    u = lax.mul(lax.convert_element_type(top, jnp.float32),
                np.float32(1.0 / 16777216.0))
    return lax.ge(u, np.float32(rate))


def _causal_mask(s, qi, kj, bq: int, bk: int, transposed: bool = False):
    # Narrow iotas broadcast in the compare: one [bq,bk] pass instead of
    # materializing two full-tile index planes.
    if transposed:
        krows = jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0) + kj * bk
        qcols = jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1) + qi * bq
        return jnp.where(qcols >= krows, s, NEG_INF)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0) + qi * bq
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) + kj * bk
    return jnp.where(rows >= cols, s, NEG_INF)


# --------------------------------------------------------------------- #
# Forward kernel
# --------------------------------------------------------------------- #
# The unrolled band bodies below are written in lax primitives for the
# reason `_dropout_keep` gives: what they emit is what the `jnp` spelling
# emitted, at a sixth of the tracing time a start.
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _keep_scaled(keep, x, dropout: float):
    """x / (1 - dropout) where kept, 0 where dropped."""
    return lax.select(keep, lax.mul(x, np.float32(1.0 / (1.0 - dropout))),
                      lax.full_like(x, 0.0))


def _causal_mask_band(s, r0: int):
    """Causal mask of one row band: ``s`` [band, r0 + band] holds query rows
    r0.. against keys 0.., so only its last [band, band] sub-tile crosses
    the diagonal; the columns left of it are kept by construction."""
    band, w = s.shape
    below = lax.ge(lax.broadcasted_iota(jnp.int32, (band, 1), 0),
                   lax.broadcasted_iota(jnp.int32, (1, band), 1))
    tile = lax.slice_in_dim(s, r0, w, axis=1)
    diag = lax.select(below, tile, lax.full_like(tile, NEG_INF))
    if r0 == 0:
        return diag
    return lax.concatenate([lax.slice_in_dim(s, 0, r0, axis=1), diag], 1)


def _own_lanes(head, head_dim: int, lanes: int):
    """[1, lanes] mask of the lanes head ``head`` of a tile holds
    (``lanes // head_dim`` heads side by side); None where the tile is one
    head and there is nothing to part."""
    if head_dim == lanes:
        return None
    lane = lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    return lane // head_dim == head


def _own(mine, x, other=None):
    """``x`` on the head's own lanes of a tile and zeros (or ``other``) on
    its neighbour's.  A contraction over the tile's lanes with ONE operand
    so zeroed is the head's own product: the zeros add nothing."""
    if mine is None:
        return x
    return lax.select(lax.broadcast_in_dim(mine, x.shape, (0, 1)), x,
                      lax.full_like(x, 0) if other is None else other)


def _fwd_bands(q_ref, k_ref, v_ref, o_ref, lse_ref, seed, bh, mine, qi, kj, *,
               scale: float, causal: bool, bq: int, bk: int, dropout: float,
               band: int):
    """One head through the whole-row forward.  Refs are [rows, lanes] and
    lse [rows] (no head dim); ``mine`` (`_own_lanes`) is None where the
    lanes are the head's alone ([BH, S, D] operands, or a head of a tile's
    width) and else parts the head from its neighbour in the tile."""
    def scores(r0):
        w = r0 + band if band < bq else bk
        s = lax.mul(_dot(_own(mine, q_ref[r0:r0 + band]), k_ref[:w], _NT),
                    np.float32(scale))
        if causal:
            s = _causal_mask_band(s, r0) if band < bq else \
                _causal_mask(s, qi, kj, bq, bk)
        return s

    def finish(r0, s):
        w = s.shape[1]
        v = v_ref[:w]
        m = lax.expand_dims(lax.reduce_max(s, (1,)), (1,))
        p = lax.exp(lax.sub(s, m))
        l = lax.expand_dims(lax.reduce_sum(p, (1,)), (1,))
        if dropout > 0.0:
            # Under bands the grid is one step a head: (qi, kj) is
            # (0, 0) and the tile starts at global (r0, 0).
            at = (r0 // band, 0) if band < bq else (qi, kj)
            keep = _dropout_keep(seed, bh, *at, band, w, dropout)
            p = _keep_scaled(keep, p, dropout)
        pv = _dot(lax.convert_element_type(p, v.dtype), v, _NN)
        l_safe = lax.select(lax.eq(l, np.float32(0.0)),
                            lax.full_like(l, 1.0), l)
        o = lax.convert_element_type(lax.div(pv, l_safe), o_ref.dtype)
        # ``pv`` is as wide as the tile: the head's lanes go into the
        # output tile, the neighbour's stay what they are.
        o_ref[r0:r0 + band] = o if mine is None else \
            _own(mine, o, o_ref[r0:r0 + band])
        lse_ref[r0:r0 + band] = lax.add(
            lax.squeeze(m, (1,)), lax.log(lax.squeeze(l_safe, (1,))))

    # The row max is a barrier a band (all of s before any exp): issue
    # each band's score matmul ahead of the band before's softmax and
    # the MXU works through it (one band ahead measured best at this
    # `_BAND`).  Widest band first, as the backward.
    starts = range(0, bq, band)[::-1]
    s = scores(starts[0])
    for r0, ahead in zip(starts, [*starts[1:], None]):
        s_ahead = None if ahead is None else scores(ahead)
        finish(r0, s)
        s = s_ahead


def _each_head(tile, tile_heads: int, lanes: int, body):
    """Run ``body(head, bh, mine)`` for the heads of lane tile ``tile``
    through ONE traced body: a loop over the head-in-tile index, the lane
    mask computed from it.  The loop is unrolled where it is LOWERED (the
    body is still traced once): the scheduler then runs the second head's
    first matmuls under the first head's tail (-0.35 us a head in the
    backward on a v5e, PERF.md section 6, PR 47)."""
    def step(head, carry):
        body(head, tile * tile_heads + head,
             _own_lanes(head, lanes // tile_heads, lanes))
        return carry
    if tile_heads == 1:
        step(0, None)
    else:
        lax.fori_loop(0, tile_heads, step, None, unroll=True)


def _fwd_kernel(*refs, scale: float, causal: bool, bq: int, bk: int,
                has_layout: bool, dropout: float = 0.0, band: int = 0,
                tile_heads: int = 0):
    if has_layout and dropout > 0.0:
        (layout_ref, seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    elif has_layout:
        (layout_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    elif dropout > 0.0:
        (seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    bh, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    if band:
        # One k-block covers the whole row (S = 1024 and below): each
        # band of query rows sees all of its keys at once, so no running
        # softmax, no scratch round-trips — a direct softmax + PV, one grid
        # step a head (or a lane tile of heads, `_flash_fwd_in_place`).
        # ``band`` < bq (`_row_band`: causal, the q-block is the whole
        # square) unrolls static row bands that read the keys up to their
        # own diagonal tile only; ``band`` == bq is one band over the whole
        # [bq, bk] rectangle.
        seed = seed_ref[0, 0] if dropout > 0.0 else None
        bands = functools.partial(
            _fwd_bands, scale=scale, causal=causal, bq=bq, bk=bk,
            dropout=dropout, band=band)
        if not tile_heads:      # [BH, S, D] operands: the step is the head
            bands(q_ref, k_ref, v_ref, o_ref, lse_ref, seed, bh, None,
                  qi, kj)
            return
        _each_head(bh, tile_heads, q_ref.shape[-1],
                   lambda head, bh, mine: bands(
                       q_ref, k_ref, v_ref, o_ref, lse_ref.at[head], seed,
                       bh, mine, qi, kj))
        return

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = _run_pred(causal, qi, kj, bq, bk,
                    _layout_gate(layout_ref, qi, kj) if has_layout else None)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                       # [BQ, D]
        k = k_ref[0]                       # [BK, D]
        v = v_ref[0]                       # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [BQ, BK]
        if causal:
            s = _causal_mask(s, qi, kj, bq, bk)

        m_prev = m_scr[:, 0:1]                            # [BQ, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                   # [BQ, 1]
        p = jnp.exp(s - m_new)                            # [BQ, BK]
        # l (the softmax normalizer) accumulates the UNdropped p: dropout
        # applies to the normalized weights w = p/l, so dropping p before
        # the PV matmul while normalizing by the full l is exactly
        # w' = mask * w / keep.
        l_new = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if dropout > 0.0:
            keep = _dropout_keep(seed_ref[0, 0], bh, qi, kj, bq, bk, dropout)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout)), 0.0)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [BQ, D]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:, 0:1] = m_new
        l_scr[:, 0:1] = l_new

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:, 0] + jnp.log(l_safe[:, 0]))


def _pad_layout(layout):
    """Pad [H, nQ, nK] to TPU tile multiples (8, 128) on the last two dims
    so the gate can ride a (1, 8, 128) VMEM block; the kernel reads
    [0, qi % 8, kj % 128] from the (qi // 8, kj // 128) block."""
    H, nQ, nK = layout.shape
    pq = (-nQ) % 8
    pk = (-nK) % 128
    if pq or pk:
        layout = jnp.pad(layout, ((0, 0), (0, pq), (0, pk)))
    return layout


def _layout_gate(layout_ref, qi, kj):
    """Read one int gate out of the (1, 8, 128) layout tile. Dynamic scalar
    indexing into VMEM doesn't lower on TPU; a masked VPU reduction does."""
    tile = layout_ref[0]                                   # [8, 128] int32
    r = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0) == (qi % 8)
    c = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1) == (kj % 128)
    return jnp.sum(jnp.where(jnp.logical_and(r, c), tile, 0))


def _seed_spec():
    """(1,1) int32 dropout seed rides SMEM (scalar memory)."""
    if pltpu is not None and jax.default_backend() == "tpu":
        return pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.BlockSpec((1, 1), lambda *_: (0, 0))


def _seed_arr(seed):
    return jnp.asarray(seed, jnp.int32).reshape(1, 1)


def _layout_spec(num_heads: int, role: str):
    """BlockSpec for the padded layout; bh grid index → head index."""
    if role == "fwd" or role == "dq":
        return pl.BlockSpec((1, 8, 128),
                            lambda b, i, j: (b % num_heads, i // 8, j // 128))
    # dkv grid is (BH, nK, nQ)
    return pl.BlockSpec((1, 8, 128),
                        lambda b, j, i: (b % num_heads, i // 8, j // 128))


def _qkv_spec(blk: int, D: int, role: str, head=1):
    """Block spec for a q/k/v/do/dq/dk/dv operand over [BH, S, D] arrays.
    ``role``: 'q' indexes the q-block dim, 'k' the k-block dim; '*T'
    variants are for the dkv grid whose program ids are (bh, kj, qi).
    ``head=None`` squeezes the head dim out of the kernel's ref ([blk, D]).

    The operands came through `_to_bh`.  A per-head block (1, blk, 1, D) of
    the native [B, S, nH, D] is not open to Mosaic (a block's last two
    dims: divisible by (8, 128) or the array's own); a block of 128 LANES
    of [B, S, nH*D] is, and where one block covers the sequence the
    kernels take that instead (`_tile_spec`)."""
    idx = {"q": lambda b, i, j: (b, i, 0),
           "k": lambda b, i, j: (b, j, 0),
           "qT": lambda b, j, i: (b, i, 0),
           "kT": lambda b, j, i: (b, j, 0)}[role]
    return pl.BlockSpec((head, blk, D), idx)


def _fwd_blocks(BH: int, S: int, Sk: int, D: int, dtype, scale: float,
                causal: bool):
    """(bq, bk) of a dense forward over [BH, S, D]: the autotune registry's
    pick, else one block as far as `_pick_block` goes."""
    def run_at(tile):
        return _flash_fwd(jnp.zeros((BH, S, D), dtype),
                          jnp.zeros((BH, Sk, D), dtype),
                          jnp.zeros((BH, Sk, D), dtype),
                          None, scale, causal, _blocks=tile)
    return _resolve_blocks(
        "flash_fwd", jax.ShapeDtypeStruct((BH, S, D), dtype),
        jax.ShapeDtypeStruct((BH, Sk, D), dtype), causal,
        (_pick_block(S), _pick_block(Sk)), run_at)


def _flash_fwd(q, k, v, layout, scale: float, causal: bool,
               dropout: float = 0.0, seed=None, _blocks=None):
    """q,k,v: [BH, S, D]; layout int32 [H, nQ, nK] or None.
    → (o [BH,S,D], lse [BH,1,S] f32)."""
    BH, S, D = q.shape
    Sk = k.shape[1]
    has_layout = layout is not None
    if has_layout:
        # Kernel blocks must match the layout's block granularity.
        bq = bk = S // layout.shape[-1]
    elif _blocks is not None:
        bq, bk = _blocks
    else:
        bq, bk = _fwd_blocks(BH, S, Sk, D, q.dtype, scale, causal)
    grid = (BH, S // bq, Sk // bk)
    # One k-block: the whole-row body, in `_row_band` row bands where the
    # one q-block is the causal square too.
    band = 0 if has_layout or bk != Sk else \
        _row_band(S, Sk, causal) if bq == S else bq

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, has_layout=has_layout,
                               dropout=dropout, band=band)
    # The band path sees [rows, D] refs (no head dim: it slices rows a band
    # and an int index is five times a slice's tracing cost).
    head = None if band else 1
    in_specs = [
        _qkv_spec(bq, D, "q", head),
        _qkv_spec(bk, D, "k", head),
        _qkv_spec(bk, D, "k", head),
    ]
    args = (q, k, v)
    if dropout > 0.0:
        in_specs = [_seed_spec()] + in_specs
        args = (_seed_arr(seed),) + args
    if has_layout:
        in_specs = [_layout_spec(layout.shape[0], "fwd")] + in_specs
        args = (_pad_layout(layout),) + args
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            _qkv_spec(bq, D, "q", head),
            pl.BlockSpec((head, head, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        cost_estimate=None if has_layout else _cost(
            BH, D, computed_scores(S, Sk, causal, (bq, bk)), 2, (q, k, v, q)),
        name="_fwd_kernel",
        interpret=_interpret(),
    )(*args)
    return o, lse


# --------------------------------------------------------------------- #
# Backward kernels
# --------------------------------------------------------------------- #
def _bwd_dq_kernel(*refs, scale: float, causal: bool, bq: int, bk: int,
                   has_layout: bool, dropout: float = 0.0):
    refs = list(refs)
    layout_ref = refs.pop(0) if has_layout else None
    seed_ref = refs.pop(0) if dropout > 0.0 else None
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
     acc_scr) = refs
    bh, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = _run_pred(causal, qi, kj, bq, bk,
                    _layout_gate(layout_ref, qi, kj) if has_layout else None)

    @pl.when(run)
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0]                                    # [BQ, D]
        lse = lse_ref[0, 0][:, None]                      # [BQ, 1]
        delta = delta_ref[0, 0][:, None]                  # [BQ, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, kj, bq, bk)
        p = jnp.exp(s - lse)                              # softmax [BQ, BK]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [BQ, BK]
        if dropout > 0.0:
            # d/dw of w' = mask*w/keep: route do·v^T through the regenerated
            # mask. delta = rowsum(do*o) already equals sum_j p_j g_j.
            keep = _dropout_keep(seed_ref[0, 0], bh, qi, kj, bq, bk, dropout)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout)), 0.0)
        ds = p * (dp - delta) * scale
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale: float, causal: bool, bq: int, bk: int,
                    has_layout: bool, dropout: float = 0.0):
    refs = list(refs)
    layout_ref = refs.pop(0) if has_layout else None
    seed_ref = refs.pop(0) if dropout > 0.0 else None
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
     dk_scr, dv_scr) = refs
    bh, kj, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = _run_pred(causal, qi, kj, bq, bk,
                    _layout_gate(layout_ref, qi, kj) if has_layout else None)

    @pl.when(run)
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][None, :]                      # [1, BQ]
        delta = delta_ref[0, 0][None, :]                  # [1, BQ]
        # s2[i, j] = k_i · q_j (transposed score block)
        s2 = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [BK, BQ]
        if causal:
            s2 = _causal_mask(s2, qi, kj, bq, bk, transposed=True)
        p2 = jnp.exp(s2 - lse)                            # [BK, BQ] = p.T
        if dropout > 0.0:
            keep2 = _dropout_keep(seed_ref[0, 0], bh, qi, kj, bq, bk,
                                  dropout, transposed=True)
            inv = 1.0 / (1.0 - dropout)
            p2_drop = jnp.where(keep2, p2 * inv, 0.0)     # = w'.T * l ... w'
        else:
            p2_drop = p2
        dv_scr[:] += jax.lax.dot_general(
            p2_drop.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp2 = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [BK, BQ] = dp.T
        if dropout > 0.0:
            dp2 = jnp.where(keep2, dp2 * inv, 0.0)
        ds2 = p2 * (dp2 - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds2.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_bands(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dk_acc, dv_acc, seed, bh, mine, *, scale: float, causal: bool,
               S: int, band: int, dropout: float, assign: bool,
               delta_from_o: bool = False):
    """One head through the whole-sequence backward: band r0 replays
    [band, r0 + band] scores, writes its dq rows once and adds its dk / dv
    rows [0, r0 + band) into ``dk_acc`` / ``dv_acc`` (float32 scratch, or
    the outputs themselves where one band is the whole square).
    ``delta_ref`` holds delta = rowsum(do * o) [S], or (``delta_from_o``)
    o itself as wide as do, and the band takes the sum.
    ``assign``: nothing has written the accumulators, so the widest band,
    which covers every key row, assigns them.  ``mine`` as in `_fwd_bands`:
    q and do carry the head's lanes only, so dk and dv come out zero on
    the neighbour's lanes and the heads of a tile add into one
    accumulator; dq is as wide as k and its own lanes are selected."""
    # Widest band first.
    for r0 in reversed(range(0, S, band)):
        w = r0 + band
        q, do = _own(mine, q_ref[r0:w]), _own(mine, do_ref[r0:w])
        k, v = k_ref[:w], v_ref[:w]
        lse = lax.expand_dims(lse_ref[r0:w], (1,))             # [band, 1]
        if delta_from_o:    # over the lanes ``do`` kept: the head's own
            delta = lax.reduce_sum(lax.mul(
                lax.convert_element_type(do, jnp.float32),
                lax.convert_element_type(delta_ref[r0:w], jnp.float32)), (1,))
        else:
            delta = delta_ref[r0:w]
        delta = lax.expand_dims(delta, (1,))                   # [band, 1]
        s = lax.mul(_dot(q, k, _NT), np.float32(scale))        # [band, w]
        if causal:
            s = _causal_mask_band(s, r0)
        p = lax.exp(lax.sub(s, lse))                           # the replay
        dp = _dot(do, v, _NT)                                  # [band, w]
        if dropout > 0.0:
            keep = _dropout_keep(seed, bh, r0 // band, 0, band, w, dropout)
            p_drop = _keep_scaled(keep, p, dropout)
            dp = _keep_scaled(keep, dp, dropout)
        else:
            p_drop = p
        dv = _dot(lax.convert_element_type(p_drop, do.dtype), do, _TN)
        ds = lax.mul(lax.mul(p, lax.sub(dp, delta)), np.float32(scale))
        dsc = lax.convert_element_type(ds, q.dtype)
        dq = lax.convert_element_type(_dot(dsc, k, _NN), dq_ref.dtype)
        dq_ref[r0:w] = dq if mine is None else _own(mine, dq, dq_ref[r0:w])
        dk = _dot(dsc, q, _TN)                                 # [w, lanes]
        if assign and w == S:
            dk_acc[:] = lax.convert_element_type(dk, dk_acc.dtype)
            dv_acc[:] = lax.convert_element_type(dv, dv_acc.dtype)
        else:
            dk_acc[:w] = lax.add(dk_acc[:w], dk)
            dv_acc[:w] = lax.add(dv_acc[:w], dv)


def _bwd_fused_kernel(*refs, scale: float, causal: bool, S: int, band: int,
                      dropout: float = 0.0, tile_heads: int = 0):
    """Whole-sequence fused backward: when one block covers S, compute the
    score/softmax replay ONCE and emit dq, dk, dv together — the split
    dq/dkv kernels each redo the s/p/exp work in their own iteration
    order (6 matmuls + 2 softmax replays vs 5 + 1 here).

    ``band`` < S (`_row_band`: causal) unrolls static row bands as the
    forward does (`_bwd_bands`), dk / dv in float32 scratch cast once at
    the end.  ``band`` == S is one band over the whole square and writes
    all three directly.  ``tile_heads`` (`_flash_bwd_in_place`): the refs
    are a lane tile of that many heads, which share the scratch, and o
    stands where delta stood."""
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout > 0.0 else None
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dq_ref, dk_ref, dv_ref) = refs[:9]
    dk_acc, dv_acc = refs[9:] or (dk_ref, dv_ref)
    seed = seed_ref[0, 0] if dropout > 0.0 else None
    bands = functools.partial(
        _bwd_bands, scale=scale, causal=causal, S=S, band=band,
        dropout=dropout)
    if not tile_heads:
        # [BH, S, D] operands: the step is the head, its lanes are its own,
        # and its widest band ASSIGNS the accumulators (nothing zeroes
        # them).  delta [S] is XLA's rowsum(do * o).
        bands(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
              dk_acc, dv_acc, seed, pl.program_id(0), None, assign=True)
    else:
        # A lane tile, and ``delta_ref`` the tile of o itself.
        if tile_heads > 1:
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)
        _each_head(pl.program_id(0), tile_heads, q_ref.shape[-1],
                   lambda head, bh, mine: bands(
                       q_ref, k_ref, v_ref, do_ref, lse_ref.at[head],
                       delta_ref, dq_ref, dk_acc, dv_acc, seed, bh, mine,
                       assign=tile_heads == 1, delta_from_o=True))
    if dk_acc is not dk_ref:
        dk_ref[:] = lax.convert_element_type(dk_acc[:], dk_ref.dtype)
        dv_ref[:] = lax.convert_element_type(dv_acc[:], dv_ref.dtype)


def _flash_bwd_fused(q, k, v, lse, do, delta, scale, causal, dropout, seed):
    BH, S, D = q.shape
    band = _row_band(S, S, causal)
    # No head dim in the kernel's refs: [S, D] and, for lse / delta, [S].
    full = pl.BlockSpec((None, S, D), lambda b: (b, 0, 0))
    row = pl.BlockSpec((None, None, S), lambda b: (b, 0, 0))
    in_specs = [full, full, full, full, row, row]
    args = (q, k, v, do, lse, delta)
    if dropout > 0.0:
        in_specs = [_seed_spec()] + in_specs
        args = (_seed_arr(seed),) + args
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          S=S, dropout=dropout, band=band),
        grid=(BH,),
        in_specs=in_specs,
        out_specs=[full] * 3,
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, S, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((S, D), jnp.float32)] * 2
        if band < S else [],
        cost_estimate=_cost(BH, D, computed_scores(S, S, causal, (S, S)), 5,
                            (q, k, v, do, q, k, v)),
        name="_bwd_fused_kernel",
        interpret=_interpret(),
    )(*args)


# --------------------------------------------------------------------- #
# The same two kernels IN PLACE over [B, S, nH*dH]
# --------------------------------------------------------------------- #
# Trace-time count of the calls that reach a kernel, by the operand layout
# they were lowered with: `in_place` (below) against `relayout` (`_to_bh`
# on the way in and its inverse on the way out).
lowered = {"in_place": 0, "relayout": 0}


def tile_lanes(S: int, Sk: int, nH: int, dH: int, layout=None) -> int:
    """Lanes of a head tile where the whole-sequence kernels read q, k, v
    and write o where the projections leave them, [B, S, nH*dH]; 0 where
    they cannot and the operands go through `_to_bh`.

    A block's last dim has to be a multiple of 128 lanes (or the array's
    own), which ONE 64-wide head of [B, S, nH, 64] never is; two of them
    side by side are, and so is a head of 128 or 256.  The rest is what
    these kernels are: no layout, one block over the sequence
    (`_pick_block`), the fused backward."""
    if layout is not None or S != Sk or _pick_block(S) != S or \
            os.environ.get("DS_FLASH_FUSED_BWD", "1") != "1":
        return 0
    return 128 if dH == 64 and nH % 2 == 0 else dH if dH % 128 == 0 else 0


def _tile_spec(S: int, lanes: int, tiles: int, at: int = 0):
    """Lane tile ``i % tiles`` of batch row ``i // tiles``: [S, lanes] of a
    [B, S, n * lanes] array, ``at`` tiles along it (the k and v thirds of a
    fused [B, S, 3H] projection)."""
    return pl.BlockSpec((None, S, lanes),
                        lambda i, *_: (i // tiles, 0, at + i % tiles))


def _tile_rows_spec(tile_heads: int, S: int):
    """The rows of lse / delta of a tile's heads: [tile_heads, S] of
    [B*nH, 1, S]."""
    return pl.BlockSpec((tile_heads, None, S), lambda i, *_: (i, 0, 0))


def _thirds(qkv, S: int, H: int, lanes: int):
    """(q, k, v) and their tile specs: three [B, S, H] arrays, or ONE fused
    [B, S, 3H] projection handed to the kernel three times, at three lane
    offsets."""
    tiles = H // lanes
    at = (0, 0, 0) if len(qkv) == 3 else (0, tiles, 2 * tiles)
    return tuple(qkv) * (3 // len(qkv)), \
        [_tile_spec(S, lanes, tiles, a) for a in at]


def _flash_fwd_in_place(qkv, nH: int, lanes: int, scale: float, causal: bool,
                        dropout: float, seed):
    """``qkv`` as `_thirds` takes it -> (o [B, S, H], lse [B*nH, 1, S]).
    A grid step is one lane tile: ``tile_heads`` heads of a batch row."""
    B, S, width = qkv[0].shape
    H = width * len(qkv) // 3
    tiles, tile_heads = H // lanes, lanes * nH // H
    args, in_specs = _thirds(qkv, S, H, lanes)
    if dropout > 0.0:
        in_specs = [_seed_spec()] + in_specs
        args = (_seed_arr(seed),) + args
    o = jax.ShapeDtypeStruct((B, S, H), qkv[0].dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=S,
                          bk=S, has_layout=False, dropout=dropout,
                          band=_row_band(S, S, causal),
                          tile_heads=tile_heads),
        grid=(B * tiles, 1, 1),
        in_specs=in_specs,
        out_specs=[_tile_spec(S, lanes, tiles),
                   _tile_rows_spec(tile_heads, S)],
        out_shape=[o, jax.ShapeDtypeStruct((B * nH, 1, S), jnp.float32)],
        # The running-softmax scratch of the grid path: not used here.
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)] * 3,
        cost_estimate=_cost(B * nH, H // nH,
                            computed_scores(S, S, causal, (S, S)), 2, [o] * 4),
        name="_fwd_kernel",
        interpret=_interpret(),
    )(*args)


def _flash_bwd_in_place(qkv, o, lse, do, nH: int, lanes: int, scale: float,
                        causal: bool, dropout: float, seed):
    """-> the gradients in ``qkv``'s own form: (dq, dk, dv), or the one
    [B, S, 3H] of a fused projection."""
    B, S, H = o.shape
    tiles, tile_heads = H // lanes, lanes * nH // H
    band = _row_band(S, S, causal)
    tile = _tile_spec(S, lanes, tiles)
    # No delta = rowsum(do * o) from XLA: over [B, S, nH*dH] it is a
    # reduction within lanes, which costs XLA a relayout of its own; the
    # kernel takes o's tile and the sum is a band's side work there.
    args, in_specs = _thirds(qkv, S, H, lanes)
    args += (do, lse, o)
    in_specs += [tile, _tile_rows_spec(tile_heads, S), tile]
    if dropout > 0.0:
        in_specs = [_seed_spec()] + in_specs
        args = (_seed_arr(seed),) + args
    grads = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          S=S, dropout=dropout, band=band,
                          tile_heads=tile_heads),
        grid=(B * tiles,),
        in_specs=in_specs,
        out_specs=[tile] * 3,
        out_shape=[jax.ShapeDtypeStruct((B, S, H), o.dtype)] * 3,
        scratch_shapes=[pltpu.VMEM((S, lanes), jnp.float32)] * 2
        if band < S or tile_heads > 1 else [],
        cost_estimate=_cost(B * nH, H // nH,
                            computed_scores(S, S, causal, (S, S)), 5, [o] * 8),
        name="_bwd_fused_kernel",
        interpret=_interpret(),
    )(*args)
    return tuple(grads) if len(qkv) == 3 else \
        (jnp.concatenate(grads, axis=-1),)


def _flash_bwd(q, k, v, o, lse, do, layout, scale: float, causal: bool,
               dropout: float = 0.0, seed=None, _blocks=None):
    BH, S, D = q.shape
    Sk = k.shape[1]
    has_layout = layout is not None
    if has_layout:
        bq = bk = S // layout.shape[-1]
    elif _blocks is not None:
        bq, bk = _blocks
    else:
        bq, bk = _pick_block_bwd(S, causal), _pick_block_bwd(Sk, causal)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True).transpose(0, 2, 1)  # [BH, 1, S]

    if _blocks is None and not has_layout and S == Sk and \
            _pick_block(S) == S and \
            os.environ.get("DS_FLASH_FUSED_BWD", "1") == "1":
        return _flash_bwd_fused(q, k, v, lse, do, delta, scale, causal,
                                dropout, seed)

    if _blocks is None and not has_layout:
        def run_at(tile):
            z = lambda s: jnp.zeros(s.shape, s.dtype)  # noqa: E731
            lse0 = jnp.zeros((BH, 1, S), jnp.float32)
            return _flash_bwd(z(q), z(k), z(v), z(o), lse0, z(do), None,
                              scale, causal, _blocks=tile)
        bq, bk = _resolve_blocks("flash_bwd", q, k, causal, (bq, bk),
                                 run_at)

    dq_specs = [
        _qkv_spec(bq, D, "q"),
        _qkv_spec(bk, D, "k"),
        _qkv_spec(bk, D, "k"),
        _qkv_spec(bq, D, "q"),
        pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
    ]
    dq_args = (q, k, v, do, lse, delta)
    if dropout > 0.0:
        dq_specs = [_seed_spec()] + dq_specs
        dq_args = (_seed_arr(seed),) + dq_args
    if has_layout:
        dq_specs = [_layout_spec(layout.shape[0], "dq")] + dq_specs
        dq_args = (_pad_layout(layout),) + dq_args
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, has_layout=has_layout,
                          dropout=dropout),
        grid=(BH, S // bq, Sk // bk),
        in_specs=dq_specs,
        out_specs=_qkv_spec(bq, D, "q"),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        name="_bwd_dq_kernel",
        interpret=_interpret(),
    )(*dq_args)

    dkv_specs = [
        _qkv_spec(bq, D, "qT"),
        _qkv_spec(bk, D, "kT"),
        _qkv_spec(bk, D, "kT"),
        _qkv_spec(bq, D, "qT"),
        pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i)),
        pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i)),
    ]
    dkv_args = (q, k, v, do, lse, delta)
    if dropout > 0.0:
        dkv_specs = [_seed_spec()] + dkv_specs
        dkv_args = (_seed_arr(seed),) + dkv_args
    if has_layout:
        dkv_specs = [_layout_spec(layout.shape[0], "dkv")] + dkv_specs
        dkv_args = (_pad_layout(layout),) + dkv_args
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, has_layout=has_layout,
                          dropout=dropout),
        grid=(BH, Sk // bk, S // bq),
        in_specs=dkv_specs,
        out_specs=[
            _qkv_spec(bk, D, "kT"),
            _qkv_spec(bk, D, "kT"),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        name="_bwd_dkv_kernel",
        interpret=_interpret(),
    )(*dkv_args)
    return dq, dk, dv


# --------------------------------------------------------------------- #
# custom_vjp wrappers (dense/causal and block-sparse variants)
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, seed, scale: float, causal: bool, dropout: float = 0.0):
    o, _ = _flash_fwd(q, k, v, None, scale, causal, dropout, seed)
    return o


def _tag_residuals(o, lse):
    """Name the flash residuals so remat policies can elect to SAVE them
    (``save_only_these_names``): pallas outputs aren't ``dot_general``s, so
    under ``checkpoint_dots`` the whole forward kernel would re-run in
    backward. transformer._remat_policy("dots_flash") keys on these names."""
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(o, "flash_out"), checkpoint_name(lse, "flash_lse")


def _flash_vjp_fwd(q, k, v, seed, scale, causal, dropout):
    o, lse = _flash_fwd(q, k, v, None, scale, causal, dropout, seed)
    o, lse = _tag_residuals(o, lse)
    return o, (q, k, v, seed, o, lse)


def _flash_vjp_bwd(scale, causal, dropout, res, do):
    q, k, v, seed, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, None, scale, causal,
                            dropout, seed)
    return dq, dk, dv, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _flash_in_place(qkv, seed, nH: int, lanes: int, scale: float,
                    causal: bool, dropout: float = 0.0):
    return _flash_fwd_in_place(qkv, nH, lanes, scale, causal, dropout,
                               seed)[0]


def _flash_in_place_vjp_fwd(qkv, seed, nH, lanes, scale, causal, dropout):
    o, lse = _tag_residuals(*_flash_fwd_in_place(
        qkv, nH, lanes, scale, causal, dropout, seed))
    return o, (qkv, seed, o, lse)


def _flash_in_place_vjp_bwd(nH, lanes, scale, causal, dropout, res, do):
    qkv, seed, o, lse = res
    return _flash_bwd_in_place(qkv, o, lse, do, nH, lanes, scale, causal,
                               dropout, seed), None


_flash_in_place.defvjp(_flash_in_place_vjp_fwd, _flash_in_place_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_sparse(q, k, v, layout, seed, scale: float, causal: bool,
                  dropout: float = 0.0):
    o, _ = _flash_fwd(q, k, v, layout, scale, causal, dropout, seed)
    return o


def _flash_sparse_vjp_fwd(q, k, v, layout, seed, scale, causal, dropout):
    o, lse = _flash_fwd(q, k, v, layout, scale, causal, dropout, seed)
    o, lse = _tag_residuals(o, lse)
    return o, (q, k, v, layout, seed, o, lse)


def _flash_sparse_vjp_bwd(scale, causal, dropout, res, do):
    q, k, v, layout, seed, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, layout, scale, causal,
                            dropout, seed)
    return dq, dk, dv, None, None


_flash_sparse.defvjp(_flash_sparse_vjp_fwd, _flash_sparse_vjp_bwd)


def _lut_fits_smem(layout, budget_bytes: int = 384 * 1024) -> bool:
    """Flattened-nnz LUTs must fit TPU scalar memory (~1 MB on v5e; leave
    headroom), and every row/column must have >=1 active block (else its
    output block would never be written by the nnz-grid kernel)."""
    import numpy as np
    lay = np.asarray(layout) != 0
    row_cnt = lay.sum(-1)
    col_cnt = lay.sum(-2)
    if (row_cnt == 0).any() or (col_cnt == 0).any():
        return False
    H = lay.shape[0]
    nnz = int(lay.reshape(H, -1).sum(-1).max())
    # qid+kid+kmask ([H, NNZ] each) for both orientations + the two nnz
    # vectors (conservative: k-widening only shrinks NNZ).
    bytes_needed = 4 * H * (6 * nnz + 2)
    return bytes_needed <= budget_bytes


def _to_bh(x):
    B, S, nH, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * nH, S, D)


def _split_qkv(qkv, num_heads: int):
    """q, k, v [B, S, nH, dH] cut out of a fused projection [B, S, 3*nH*dH]."""
    B, S, _ = qkv.shape
    return tuple(x.reshape(B, S, num_heads, -1)
                 for x in jnp.split(qkv, 3, axis=-1))


def _dropout_seed(attn_dropout: float, rng, deterministic: bool):
    """(rate the kernels run at, the keep-mask's int32 seed)."""
    dropout = float(attn_dropout) if (attn_dropout > 0.0 and not deterministic
                                      and rng is not None) else 0.0
    seed = jax.random.bits(rng, (), jnp.uint32).astype(jnp.int32) \
        if dropout > 0.0 else jnp.zeros((), jnp.int32)
    return dropout, seed


def _in_place(qkv, nH: int, causal: bool, attn_dropout: float, rng,
              deterministic: bool):
    """Attention [B, S, H] of ``qkv`` — (q, k, v), each [B, S, H], or the
    one fused projection ([B, S, 3H],) — read where it lies, or None where
    the shapes (`tile_lanes`) or a tile the autotuner recorded send the
    call through `_to_bh`."""
    B, S, width = qkv[0].shape
    D = width * len(qkv) // 3 // nH
    scale = 1.0 / math.sqrt(D)
    lanes = tile_lanes(S, qkv[-1].shape[1], nH, D)
    if not lanes or _fwd_blocks(B * nH, S, S, D, qkv[0].dtype, scale,
                                causal) != (S, S):
        return None
    lowered["in_place"] += 1
    dropout, seed = _dropout_seed(attn_dropout, rng, deterministic)
    return _flash_in_place(qkv, seed, nH, lanes, scale, causal, dropout)


def flash_attention_qkv(qkv: jnp.ndarray, num_heads: int,
                        mask: Optional[jnp.ndarray] = None,
                        causal: bool = False, attn_dropout: float = 0.0,
                        rng=None, deterministic: bool = True) -> jnp.ndarray:
    """`flash_attention` of a FUSED projection: qkv [B, S, 3*nH*dH] as the
    projection's GEMM leaves it (q | k | v along the last dim, heads
    within each) -> [B, S, nH*dH].  Where the kernels run in place the
    thirds are never cut out: the kernel takes the one array three times,
    and the gradient comes back as one [B, S, 3*nH*dH]."""
    B, S, _ = qkv.shape
    if mask is None and S % 128 == 0:
        o = _in_place((qkv,), num_heads, causal, attn_dropout, rng,
                      deterministic)
        if o is not None:
            return o
    return flash_attention(*_split_qkv(qkv, num_heads), mask=mask,
                           causal=causal, attn_dropout=attn_dropout, rng=rng,
                           deterministic=deterministic).reshape(B, S, -1)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    mask: Optional[jnp.ndarray] = None, causal: bool = False,
                    attn_dropout: float = 0.0, rng=None,
                    deterministic: bool = True,
                    layout: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Drop-in for models.transformer.dense_attention: q,k,v [B,S,nH,dH].

    ``layout`` [nH, S//block, S//block] int32 enables block-sparse mode.
    Attention dropout runs IN-KERNEL (mask regenerated in backward from the
    seed — see _dropout_keep); only additive masks and non-128-aligned
    sequences fall back to the dense path (the reference keeps a non-fused
    path for the same cases, transformer.py:153).
    """
    B, S, nH, D = q.shape
    layout_block = None
    if layout is not None:
        if layout.ndim != 3 or layout.shape[0] != nH or \
                layout.shape[-2] != layout.shape[-1] or \
                S % layout.shape[-1] != 0:
            raise ValueError(
                f"layout shape {layout.shape} incompatible with "
                f"{nH} heads / seq {S}: need (num_heads, S//block, S//block)")
        # The Pallas path needs 128-aligned kernel blocks; a sparse layout
        # fixes the block to S // n_blocks, which must itself be 128-aligned.
        layout_block = S // layout.shape[-1]
    if mask is not None or S % 128 != 0 \
            or (layout_block is not None and layout_block % 128 != 0):
        from ..models.transformer import dense_attention
        if layout is not None:
            mask = _layout_to_mask(layout, S, mask)
        return dense_attention(q, k, v, mask=mask, causal=causal,
                               attn_dropout=attn_dropout, rng=rng,
                               deterministic=deterministic)
    if layout is None:
        o = _in_place(tuple(x.reshape(*x.shape[:2], nH * D)
                            for x in (q, k, v)),
                      nH, causal, attn_dropout, rng, deterministic)
        if o is not None:
            return o.reshape(B, S, nH, D)
    lowered["relayout"] += 1
    scale = 1.0 / math.sqrt(D)
    dropout, seed = _dropout_seed(attn_dropout, rng, deterministic)
    qt, kt, vt = _to_bh(q), _to_bh(k), _to_bh(v)
    if layout is None:
        o = _flash(qt, kt, vt, seed, scale, causal, dropout)
    elif not isinstance(layout, jax.core.Tracer) and \
            _lut_fits_smem(layout):
        # Concrete layout (the normal case): LUT-driven kernels touch only
        # the live blocks — compute/bandwidth scale with nnz, not S^2
        # (reference csrc/sparse_attention LUT design; see sparse_flash.py).
        from .sparse_flash import sparse_flash_attention
        o = sparse_flash_attention(qt, kt, vt, layout, causal=causal,
                                   scale=scale, seed=seed, dropout=dropout)
    else:
        # Traced layout, or LUTs too large for SMEM (e.g. global-attention
        # rows at huge S make max-nnz ~ nK): full-grid gated kernel.
        o = _flash_sparse(qt, kt, vt, jnp.asarray(layout, jnp.int32),
                          seed, scale, causal, dropout)
    return o.reshape(B, nH, S, D).transpose(0, 2, 1, 3)


def _layout_to_mask(layout, seq_len: int, mask):
    """Expand a block layout to an additive [1, nH, S, S] element mask
    (dense-fallback semantics of the sparse path)."""
    layout = jnp.asarray(layout)
    block = seq_len // layout.shape[-1]
    elem = jnp.repeat(jnp.repeat(layout, block, axis=-2), block, axis=-1)
    add = jnp.where(elem[None] != 0, 0.0, NEG_INF).astype(jnp.float32)
    return add if mask is None else add + mask


_SAID_DENSE = False


def auto_attention(q, k, v, mask=None, causal=False, attn_dropout=0.0,
                   rng=None, deterministic=True):
    """Best attention for the current backend: flash kernels on TPU, plain
    XLA dense elsewhere (Pallas interpret mode is for correctness tests,
    not speed)."""
    if jax.default_backend() == "tpu":
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               attn_dropout=attn_dropout, rng=rng,
                               deterministic=deterministic)
    from ..models.transformer import dense_attention
    global _SAID_DENSE
    if not _SAID_DENSE:
        _SAID_DENSE = True
        from ..utils.logging import logger
        logger.info(f"auto_attention: backend is {jax.default_backend()!r}, "
                    "not tpu — dense XLA attention, no flash kernel")
    return dense_attention(q, k, v, mask=mask, causal=causal,
                           attn_dropout=attn_dropout, rng=rng,
                           deterministic=deterministic)


def auto_attention_qkv(qkv, num_heads: int, mask=None, causal=False,
                       attn_dropout=0.0, rng=None, deterministic=True):
    """`auto_attention` of a fused projection qkv [B, S, 3*nH*dH] ->
    [B, S, nH*dH]: on TPU the flash kernels read it where it lies
    (`flash_attention_qkv`)."""
    if jax.default_backend() == "tpu":
        return flash_attention_qkv(qkv, num_heads, mask=mask, causal=causal,
                                   attn_dropout=attn_dropout, rng=rng,
                                   deterministic=deterministic)
    return auto_attention(*_split_qkv(qkv, num_heads), mask=mask,
                          causal=causal, attn_dropout=attn_dropout, rng=rng,
                          deterministic=deterministic
                          ).reshape(*qkv.shape[:2], -1)

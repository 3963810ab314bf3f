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

Layout: kernels run over [BH, S, D] (batch×heads flattened, head_dim last).
Grid is (BH, q_blocks, k_blocks); the innermost (k) dimension iterates
sequentially on TPU so VMEM scratch carries the running softmax state
across k-blocks of one q-block.  A sequence one block covers (S = 1024
and below, `_pick_block`) is one grid step a head with no running state.
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


def _fwd_kernel(*refs, scale: float, causal: bool, bq: int, bk: int,
                has_layout: bool, dropout: float = 0.0, band: int = 0):
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
        # step a head.  ``band`` < bq (`_row_band`: causal, the q-block is
        # the whole square) unrolls static row bands that read the keys up
        # to their own diagonal tile only; ``band`` == bq is one band over
        # the whole [bq, bk] rectangle.
        # Refs are [rows, D] here and lse [rows] (`_flash_fwd`: no head dim).
        seed = seed_ref[0, 0] if dropout > 0.0 else None

        def scores(r0):
            w = r0 + band if band < bq else bk
            s = lax.mul(_dot(q_ref[r0:r0 + band], k_ref[:w], _NT),
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
            o_ref[r0:r0 + band] = lax.convert_element_type(
                lax.div(pv, l_safe), o_ref.dtype)
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

    NOTE a native-4D [B, S, nH, D] variant (per-head blocks (1, blk, 1, D)
    to skip the host-side transposes) was tried and REVERTED: Mosaic
    requires the last two block dims divisible by (8, 128) or equal to the
    array dims, which a 1-of-nH head block can never satisfy."""
    idx = {"q": lambda b, i, j: (b, i, 0),
           "k": lambda b, i, j: (b, j, 0),
           "qT": lambda b, j, i: (b, i, 0),
           "kT": lambda b, j, i: (b, j, 0)}[role]
    return pl.BlockSpec((head, blk, D), idx)


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
        def run_at(tile):
            return _flash_fwd(jnp.zeros((BH, S, D), q.dtype),
                              jnp.zeros((BH, Sk, D), k.dtype),
                              jnp.zeros((BH, Sk, D), v.dtype),
                              None, scale, causal, _blocks=tile)
        bq, bk = _resolve_blocks(
            "flash_fwd", q, k, causal,
            (_pick_block(S), _pick_block(Sk)), run_at)
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


def _bwd_fused_kernel(*refs, scale: float, causal: bool, S: int, band: int,
                      dropout: float = 0.0):
    """Whole-sequence fused backward: when one block covers S, compute the
    score/softmax replay ONCE and emit dq, dk, dv together — the split
    dq/dkv kernels each redo the s/p/exp work in their own iteration
    order (6 matmuls + 2 softmax replays vs 5 + 1 here).

    ``band`` < S (`_row_band`: causal) unrolls static row bands as the
    forward does: band r0 replays [band, r0 + band] scores, writes its dq
    rows once and adds its dk / dv rows [0, r0 + band) into float32
    scratch, cast once at the end.  ``band`` == S is one band over the whole
    square and writes all three directly."""
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout > 0.0 else None
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dq_ref, dk_ref, dv_ref) = refs[:9]
    bh = pl.program_id(0)
    seed = seed_ref[0, 0] if dropout > 0.0 else None
    # Widest band first: it covers every key row, so it ASSIGNS the
    # accumulators and nothing has to zero them.
    for r0 in reversed(range(0, S, band)):
        w = r0 + band
        q, do = q_ref[r0:w], do_ref[r0:w]
        k, v = k_ref[:w], v_ref[:w]
        lse = lax.expand_dims(lse_ref[r0:w], (1,))             # [band, 1]
        delta = lax.expand_dims(delta_ref[r0:w], (1,))         # [band, 1]
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
        dq_ref[r0:w] = lax.convert_element_type(_dot(dsc, k, _NN),
                                                dq_ref.dtype)
        dk = _dot(dsc, q, _TN)                                 # [w, D]
        if band == S:
            dk_ref[:] = lax.convert_element_type(dk, dk_ref.dtype)
            dv_ref[:] = lax.convert_element_type(dv, dv_ref.dtype)
            return
        dk_scr, dv_scr = refs[9:]
        if w == S:
            dk_scr[:] = dk
            dv_scr[:] = dv
        else:
            dk_scr[:w] = lax.add(dk_scr[:w], dk)
            dv_scr[:w] = lax.add(dv_scr[:w], dv)
    dk_ref[:] = lax.convert_element_type(dk_scr[:], dk_ref.dtype)
    dv_ref[:] = lax.convert_element_type(dv_scr[:], dv_ref.dtype)


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
    dropout = float(attn_dropout) if (attn_dropout > 0.0 and not deterministic
                                      and rng is not None) else 0.0
    if mask is not None or S % 128 != 0 \
            or (layout_block is not None and layout_block % 128 != 0):
        from ..models.transformer import dense_attention
        if layout is not None:
            mask = _layout_to_mask(layout, S, mask)
        return dense_attention(q, k, v, mask=mask, causal=causal,
                               attn_dropout=attn_dropout, rng=rng,
                               deterministic=deterministic)
    scale = 1.0 / math.sqrt(D)
    seed = jax.random.bits(rng, (), jnp.uint32).astype(jnp.int32) \
        if dropout > 0.0 else jnp.zeros((), jnp.int32)
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

"""LUT-driven block-sparse flash attention — only active blocks are touched.

The layout-gated kernels in flash_attention.py iterate the FULL (q,k) block
grid and gate the compute, so HBM loads and grid overhead still scale
O(S^2). This module is the reference's actual design point
(csrc/sparse_attention/utils.cpp builds LUTs for its Triton kernels,
sdd_segment :14-117), taken to the splash-attention form: the layout
flattens into ONE list of active (q-block, k-block) pairs per head, and the
Pallas grid iterates exactly those nnz steps — scalar-prefetch index maps
pick each step's blocks, the online-softmax state resets on q-row
transitions, and the output block flushes when the row advances. Compute,
bandwidth, AND grid steps all scale with nnz; there is no padding to the
widest row (global-attention rows cost only their own entries).

Forward and dq iterate the row-major pair list; dkv iterates the
column-major list (state carried per k block). Dropout composes via the
same stateless position hash as the dense kernels (keyed by the ACTUAL
block indices read from the LUT), so masks agree across fwd/dq/dkv.

Requirement: every q-block row and k-block column of the layout must have
at least one active block (else its output block would never be written);
``build_flat_luts`` returns None in that case and the caller falls back to
the gated kernel.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .flash_attention import (NEG_INF, _causal_mask, _dropout_keep,
                              _interpret)

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None


def build_flat_luts(layout: np.ndarray, widen: int = 1, qwiden: int = 1):
    """layout [H, nQ, nK] -> (qid, kid, nnz, kmask, qidT, kidT, nnzT,
    kmaskT) int32 arrays ([H, NNZ] / [H]), row-major for fwd/dq and
    column-major for dkv; padded tails repeat the last pair. None if any
    row/column is empty.

    ``widen``/``qwiden`` > 1 coarsen the K/Q dimensions by those factors:
    one LUT entry covers a ``qwiden x widen`` super-tile of base blocks
    (qid/kid index WIDE blocks) and ``kmask`` is a per-entry bitmask of
    which sub-blocks are live — bit ``sq * widen + sk`` for sub-row sq,
    sub-col sk; dead sub-blocks are softmax-masked in-kernel. Banded
    layouts (local attention) coarsen nearly for free in BOTH dims, and
    each grid step's matmuls grow ``qwiden*widen``x — amortizing the fixed
    per-step sequencing cost that dominates at head-dim 64, and deepening
    the MXU tiles (a 128-row step at D=64 underfills the systolic array;
    qwiden=2+ feeds it 256+ rows). Padded tail entries carry kmask=0, so
    they are hard no-ops."""
    lay = np.asarray(layout) != 0
    H, nQ, nK = lay.shape
    if (lay.sum(-1) == 0).any() or (lay.sum(-2) == 0).any():
        return None
    w, qw = int(widen), int(qwiden)
    if nK % w != 0 or nQ % qw != 0 or qw * w > 31:
        return None
    nK2, nQ2 = nK // w, nQ // qw
    # bits[h, q2, k2]: bit (sq * w + sk) = live(sub-row sq, sub-col sk)
    sub = lay.reshape(H, nQ2, qw, nK2, w).transpose(0, 1, 3, 2, 4)
    flat = sub.reshape(H, nQ2, nK2, qw * w)
    bits = (flat.astype(np.int64) <<
            np.arange(qw * w, dtype=np.int64)).sum(-1).astype(np.int32)

    def flatten(mask, bit_lookup):   # row-major active pairs per head
        pairs = [np.argwhere(mask[h]) for h in range(H)]
        nnz = np.asarray([len(p) for p in pairs], np.int32)
        NNZ = int(nnz.max())
        rid = np.zeros((H, NNZ), np.int32)
        cid = np.zeros((H, NNZ), np.int32)
        bm = np.zeros((H, NNZ), np.int32)
        for h, p in enumerate(pairs):
            rid[h, :len(p)] = p[:, 0]
            cid[h, :len(p)] = p[:, 1]
            bm[h, :len(p)] = bit_lookup(h, p[:, 0], p[:, 1])
            rid[h, len(p):] = p[-1, 0]
            cid[h, len(p):] = p[-1, 1]
            # kmask stays 0 on the padded tail: a hard no-op
        return rid, cid, nnz, bm

    lay2 = bits != 0
    qid, kid, nnz, kmask = flatten(lay2, lambda h, q, k2: bits[h, q, k2])
    kidT, qidT, nnzT, kmaskT = flatten(
        lay2.transpose(0, 2, 1), lambda h, k2, q: bits[h, q, k2])
    return qid, kid, nnz, kmask, qidT, kidT, nnzT, kmaskT


# --------------------------------------------------------------------- #
# Kernels — grid (BH, NNZ); state carries across same-row steps
# --------------------------------------------------------------------- #
def _submask(s, bits, bq: int, bk: int, qwiden: int, widen: int,
             transposed: bool = False):
    """NEG_INF-mask the sub-blocks of a qwiden x widen super-tile whose
    LUT bit is 0. s: [bq, bk] (or [bk, bq] transposed); bit index is
    sub_q * widen + sub_k."""
    if widen == 1 and qwiden == 1:
        return s
    subq, subk = bq // qwiden, bk // widen
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    sq = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis) // subq
    sk = jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis) // subk
    live = jax.lax.shift_right_logical(bits, sq * widen + sk) & 1
    return jnp.where(live == 1, s, NEG_INF)


def _sfwd_kernel(qid_ref, kid_ref, nnz_ref, kmask_ref, q_ref, k_ref, v_ref,
                 seed_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                 *, scale, causal, bq, bk, nH, dropout, widen, qwiden):
    bh, n = pl.program_id(0), pl.program_id(1)
    h = bh % nH
    qi = qid_ref[h, n]
    kj = kid_ref[h, n]
    prev_qi = qid_ref[h, jnp.maximum(n - 1, 0)]
    new_row = jnp.logical_or(n == 0, qi != prev_qi)
    active = n < nnz_ref[h]

    @pl.when(jnp.logical_and(new_row, active))
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(active)
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, kj, bq, bk)
        s = _submask(s, kmask_ref[h, n], bq, bk, qwiden, widen)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if dropout > 0.0:
            keep = _dropout_keep(seed_ref[0, 0], bh, qi, kj, bq, bk, dropout)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout)), 0.0)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:, 0:1] = m_new
        l_scr[:, 0:1] = l_new

    # Finalize only on the row's LAST active step (one divide/log/store per
    # row; the flush to HBM happens when the output block index advances).
    nj = pl.num_programs(1)
    next_qi = qid_ref[h, jnp.minimum(n + 1, nj - 1)]
    row_last = jnp.logical_or(n == nnz_ref[h] - 1,
                              jnp.logical_and(active, next_qi != qi))

    @pl.when(row_last)
    def _finalize():
        l_new = l_scr[:, 0:1]
        m_new = m_scr[:, 0:1]
        l_safe = jnp.where(l_new == 0.0, 1.0, l_new)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_new[:, 0] + jnp.log(l_safe[:, 0])


def _sdq_kernel(qid_ref, kid_ref, nnz_ref, kmask_ref, q_ref, k_ref, v_ref,
                do_ref, lse_ref, delta_ref, seed_ref, dq_ref, acc_scr,
                *, scale, causal, bq, bk, nH, dropout, widen, qwiden):
    bh, n = pl.program_id(0), pl.program_id(1)
    h = bh % nH
    qi = qid_ref[h, n]
    kj = kid_ref[h, n]
    prev_qi = qid_ref[h, jnp.maximum(n - 1, 0)]
    new_row = jnp.logical_or(n == 0, qi != prev_qi)
    active = n < nnz_ref[h]

    @pl.when(jnp.logical_and(new_row, active))
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(active)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, kj, bq, bk)
        s = _submask(s, kmask_ref[h, n], bq, bk, qwiden, widen)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout > 0.0:
            keep = _dropout_keep(seed_ref[0, 0], bh, qi, kj, bq, bk, dropout)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout)), 0.0)
        ds = p * (dp - delta) * scale
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    nj = pl.num_programs(1)
    next_qi = qid_ref[h, jnp.minimum(n + 1, nj - 1)]
    row_last = jnp.logical_or(n == nnz_ref[h] - 1,
                              jnp.logical_and(active, next_qi != qi))

    @pl.when(row_last)
    def _store():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _sfused_bwd_kernel(kidT_ref, qidT_ref, nnzT_ref, kmaskT_ref, q_ref,
                       k_ref, v_ref, do_ref, lse_ref, delta_ref, seed_ref,
                       dk_ref, dv_ref, dqp_ref, dk_scr, dv_scr, *, scale,
                       causal, bq, bk, nH, dropout, widen, qwiden):
    """Fused backward: ONE column-major pass emits dk, dv AND per-step dq
    partials (segment-summed by q-row outside the kernel). Compared to
    the split dq+dkv pair this computes s/p/dp/ds once instead of twice —
    the per-block cost that dominates at head-dim 64 — and drops a whole
    kernel's per-step fixed cost. The dense kernels' fused whole-S
    backward is the same idea; here the partial-sum trick stands in for
    whole-S row coverage (a k-column's steps touch arbitrary q rows, so
    dq cannot be accumulated in scratch across them)."""
    bh, n = pl.program_id(0), pl.program_id(1)
    h = bh % nH
    kj = kidT_ref[h, n]
    qi = qidT_ref[h, n]
    prev_kj = kidT_ref[h, jnp.maximum(n - 1, 0)]
    new_col = jnp.logical_or(n == 0, kj != prev_kj)
    active = n < nnzT_ref[h]

    @pl.when(jnp.logical_and(new_col, active))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(active)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0, 0][None, :]
        delta = delta_ref[0, 0][None, :]
        s2 = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s2 = _causal_mask(s2, qi, kj, bq, bk, transposed=True)
        s2 = _submask(s2, kmaskT_ref[h, n], bq, bk, qwiden, widen,
                      transposed=True)
        p2 = jnp.exp(s2 - lse)
        if dropout > 0.0:
            keep2 = _dropout_keep(seed_ref[0, 0], bh, qi, kj, bq, bk,
                                  dropout, transposed=True)
            inv = 1.0 / (1.0 - dropout)
            p2_drop = jnp.where(keep2, p2 * inv, 0.0)
        else:
            p2_drop = p2
        dv_scr[:] += jax.lax.dot_general(
            p2_drop.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp2 = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout > 0.0:
            dp2 = jnp.where(keep2, dp2 * inv, 0.0)
        ds2 = p2 * (dp2 - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds2.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dq partial for THIS step's q rows: ds^T @ k, shipped per step
        # (garbage on inactive tail steps is routed to a dump segment by
        # the host-built segment ids, never summed into a real row).
        dqp_ref[0, 0] = jax.lax.dot_general(
            ds2.astype(k.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dqp_ref.dtype)

    nj = pl.num_programs(1)
    next_kj = kidT_ref[h, jnp.minimum(n + 1, nj - 1)]
    col_last = jnp.logical_or(n == nnzT_ref[h] - 1,
                              jnp.logical_and(active, next_kj != kj))

    @pl.when(col_last)
    def _store():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _sdkv_kernel(kidT_ref, qidT_ref, nnzT_ref, kmaskT_ref, q_ref, k_ref,
                 v_ref, do_ref, lse_ref, delta_ref, seed_ref, dk_ref, dv_ref,
                 dk_scr, dv_scr, *, scale, causal, bq, bk, nH, dropout,
                 widen, qwiden):
    bh, n = pl.program_id(0), pl.program_id(1)
    h = bh % nH
    kj = kidT_ref[h, n]
    qi = qidT_ref[h, n]
    prev_kj = kidT_ref[h, jnp.maximum(n - 1, 0)]
    new_col = jnp.logical_or(n == 0, kj != prev_kj)
    active = n < nnzT_ref[h]

    @pl.when(jnp.logical_and(new_col, active))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(active)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0, 0][None, :]
        delta = delta_ref[0, 0][None, :]
        s2 = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s2 = _causal_mask(s2, qi, kj, bq, bk, transposed=True)
        s2 = _submask(s2, kmaskT_ref[h, n], bq, bk, qwiden, widen,
                      transposed=True)
        p2 = jnp.exp(s2 - lse)
        if dropout > 0.0:
            keep2 = _dropout_keep(seed_ref[0, 0], bh, qi, kj, bq, bk,
                                  dropout, transposed=True)
            inv = 1.0 / (1.0 - dropout)
            p2_drop = jnp.where(keep2, p2 * inv, 0.0)
        else:
            p2_drop = p2
        dv_scr[:] += jax.lax.dot_general(
            p2_drop.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp2 = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout > 0.0:
            dp2 = jnp.where(keep2, dp2 * inv, 0.0)
        ds2 = p2 * (dp2 - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds2.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    nj = pl.num_programs(1)
    next_kj = kidT_ref[h, jnp.minimum(n + 1, nj - 1)]
    col_last = jnp.logical_or(n == nnzT_ref[h] - 1,
                              jnp.logical_and(active, next_kj != kj))

    @pl.when(col_last)
    def _store():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# --------------------------------------------------------------------- #
# pallas_call wrappers
# --------------------------------------------------------------------- #
def _sparse_fwd(q, k, v, qid, kid, nnz, kmask, seed, scale, causal, nH, bq,
                bk, dropout, widen, qwiden):
    BH, S, D = q.shape
    NNZ = qid.shape[-1]
    kernel = functools.partial(_sfwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nH=nH, dropout=dropout,
                               widen=widen, qwiden=qwiden)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(BH, NNZ),
            in_specs=[
                pl.BlockSpec((1, bq, D),
                             lambda b, n, qid, kid, nnz, km:
                             (b, qid[b % nH, n], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, n, qid, kid, nnz, km:
                             (b, kid[b % nH, n], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, n, qid, kid, nnz, km:
                             (b, kid[b % nH, n], 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D),
                             lambda b, n, qid, kid, nnz, km:
                             (b, qid[b % nH, n], 0)),
                pl.BlockSpec((1, 1, bq),
                             lambda b, n, qid, kid, nnz, km:
                             (b, 0, qid[b % nH, n])),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
        ],
        name="_sfwd_kernel",
        interpret=_interpret(),
    )(qid, kid, nnz, kmask, q, k, v, seed)
    return o, lse


def _sparse_bwd_fused(q, k, v, o, lse, do, luts, seed, scale, causal, nH,
                      bq, bk, dropout, widen, qwiden):
    """One column-major pass for dk+dv+dq-partials, then a scatter-add
    over q rows. Vs the split dq+dkv pair: s/p/dp/ds computed once per
    block instead of twice, and one kernel's per-step fixed cost gone."""
    qid, kid, nnz, kmask, qidT, kidT, nnzT, kmaskT = luts
    BH, S, D = q.shape
    NNZT = kidT.shape[-1]
    nQ2 = S // bq
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True).transpose(0, 2, 1)  # [BH,1,S]

    dk, dv, dqp = pl.pallas_call(
        functools.partial(_sfused_bwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nH=nH, dropout=dropout, widen=widen,
                          qwiden=qwiden),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(BH, NNZT),
            in_specs=[
                pl.BlockSpec((1, bq, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, qi[b % nH, n], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, ki[b % nH, n], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, ki[b % nH, n], 0)),
                pl.BlockSpec((1, bq, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, qi[b % nH, n], 0)),
                pl.BlockSpec((1, 1, bq),
                             lambda b, n, ki, qi, nz, km:
                             (b, 0, qi[b % nH, n])),
                pl.BlockSpec((1, 1, bq),
                             lambda b, n, ki, qi, nz, km:
                             (b, 0, qi[b % nH, n])),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, ki[b % nH, n], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, ki[b % nH, n], 0)),
                pl.BlockSpec((1, 1, bq, D),
                             lambda b, n, ki, qi, nz, km: (b, n, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((BH, k.shape[1], D), k.dtype),
            jax.ShapeDtypeStruct((BH, v.shape[1], D), v.dtype),
            jax.ShapeDtypeStruct((BH, NNZT, bq, D), jnp.float32),
        ],
        name="_sfused_bwd_kernel",
        interpret=_interpret(),
    )(kidT, qidT, nnzT, kmaskT, q, k, v, do, lse, delta, seed)

    # Route each step's dq partial to its q row; tail steps (n >= nnzT[h],
    # whose dqp blocks are unwritten garbage) go to a dump segment.
    steps = jnp.arange(NNZT)[None, :]                       # [1, NNZT]
    ids = jnp.where(steps < nnzT[:, None], qidT, nQ2)       # [nH, NNZT]

    def seg(dqp_bh, ids_h):
        out = jnp.zeros((nQ2 + 1, bq, D), jnp.float32)
        return out.at[ids_h].add(dqp_bh)[:nQ2]

    dq = jax.vmap(seg)(dqp, ids[jnp.arange(BH) % nH])
    return dq.reshape(BH, S, D).astype(q.dtype), dk, dv


def _sparse_bwd(q, k, v, o, lse, do, luts, seed, scale, causal, nH, bq, bk,
                dropout, widen, qwiden):
    # DS_SPARSE_FUSED_BWD=1 opts into the fused single-pass backward.
    # Measured v5e (S=32768, d=0.023): fused 16.7/15.5 ms (q2k4/q1k4) vs
    # split 15.2/16.2 — the f32 dq-partials traffic + segment scatter
    # offsets the saved s/p recompute, so the split pair stays default.
    # Kept because the balance flips where HBM is faster relative to the
    # per-step fixed cost (larger D, future chips).
    import os
    if os.environ.get("DS_SPARSE_FUSED_BWD", "0") == "1":
        return _sparse_bwd_fused(q, k, v, o, lse, do, luts, seed, scale,
                                 causal, nH, bq, bk, dropout, widen, qwiden)
    qid, kid, nnz, kmask, qidT, kidT, nnzT, kmaskT = luts
    BH, S, D = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True).transpose(0, 2, 1)  # [BH,1,S]

    dq = pl.pallas_call(
        functools.partial(_sdq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nH=nH, dropout=dropout, widen=widen,
                          qwiden=qwiden),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(BH, qid.shape[-1]),
            in_specs=[
                pl.BlockSpec((1, bq, D),
                             lambda b, n, qi, ki, nz, km:
                             (b, qi[b % nH, n], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, n, qi, ki, nz, km:
                             (b, ki[b % nH, n], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, n, qi, ki, nz, km:
                             (b, ki[b % nH, n], 0)),
                pl.BlockSpec((1, bq, D),
                             lambda b, n, qi, ki, nz, km:
                             (b, qi[b % nH, n], 0)),
                pl.BlockSpec((1, 1, bq),
                             lambda b, n, qi, ki, nz, km:
                             (b, 0, qi[b % nH, n])),
                pl.BlockSpec((1, 1, bq),
                             lambda b, n, qi, ki, nz, km:
                             (b, 0, qi[b % nH, n])),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec(
                (1, bq, D),
                lambda b, n, qi, ki, nz, km: (b, qi[b % nH, n], 0)),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        name="_sdq_kernel",
        interpret=_interpret(),
    )(qid, kid, nnz, kmask, q, k, v, do, lse, delta, seed)

    dk, dv = pl.pallas_call(
        functools.partial(_sdkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nH=nH, dropout=dropout, widen=widen,
                          qwiden=qwiden),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(BH, kidT.shape[-1]),
            in_specs=[
                pl.BlockSpec((1, bq, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, qi[b % nH, n], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, ki[b % nH, n], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, ki[b % nH, n], 0)),
                pl.BlockSpec((1, bq, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, qi[b % nH, n], 0)),
                pl.BlockSpec((1, 1, bq),
                             lambda b, n, ki, qi, nz, km:
                             (b, 0, qi[b % nH, n])),
                pl.BlockSpec((1, 1, bq),
                             lambda b, n, ki, qi, nz, km:
                             (b, 0, qi[b % nH, n])),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, ki[b % nH, n], 0)),
                pl.BlockSpec((1, bk, D),
                             lambda b, n, ki, qi, nz, km:
                             (b, ki[b % nH, n], 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((BH, k.shape[1], D), k.dtype),
            jax.ShapeDtypeStruct((BH, v.shape[1], D), v.dtype),
        ],
        name="_sdkv_kernel",
        interpret=_interpret(),
    )(kidT, qidT, nnzT, kmaskT, q, k, v, do, lse, delta, seed)
    return dq, dk, dv


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(12, 13, 14, 15, 16, 17, 18, 19))
def _sparse_flash(q, k, v, qid, kid, nnz, kmask, qidT, kidT, nnzT, kmaskT,
                  seed, scale, causal, nH, bq, bk, dropout, widen, qwiden):
    o, _ = _sparse_fwd(q, k, v, qid, kid, nnz, kmask, seed, scale, causal,
                       nH, bq, bk, dropout, widen, qwiden)
    return o


def _sparse_vjp_fwd(q, k, v, qid, kid, nnz, kmask, qidT, kidT, nnzT, kmaskT,
                    seed, scale, causal, nH, bq, bk, dropout, widen, qwiden):
    o, lse = _sparse_fwd(q, k, v, qid, kid, nnz, kmask, seed, scale, causal,
                         nH, bq, bk, dropout, widen, qwiden)
    from .flash_attention import _tag_residuals
    o, lse = _tag_residuals(o, lse)
    return o, (q, k, v, qid, kid, nnz, kmask, qidT, kidT, nnzT, kmaskT,
               seed, o, lse)


def _sparse_vjp_bwd(scale, causal, nH, bq, bk, dropout, widen, qwiden, res,
                    do):
    (q, k, v, qid, kid, nnz, kmask, qidT, kidT, nnzT, kmaskT, seed, o,
     lse) = res
    dq, dk, dv = _sparse_bwd(
        q, k, v, o, lse, do,
        (qid, kid, nnz, kmask, qidT, kidT, nnzT, kmaskT), seed,
        scale, causal, nH, bq, bk, dropout, widen, qwiden)
    return (dq, dk, dv) + (None,) * 9


_sparse_flash.defvjp(_sparse_vjp_fwd, _sparse_vjp_bwd)


# Per-grid-step fixed cost (Mosaic sequencing latency), expressed in
# block-compute units: one unit = a 128x128 tile's work, so at base block
# b the fixed cost is ALPHA_128 * (128/b)^2 units. The auto picker
# charges candidate super-tile (qw, kw) a cost of
# nnz_{qw,kw} * (alpha + qw*kw + QW_PENALTY*(qw-1)) and takes the
# cheapest. Round-5 calibration from the v5e BigBird sweep (S=32768,
# D=64, block=128, fwd+bwd): 1x1/1x4/2x2/2x4/4x2/2x8/4x4 ->
# 19.4/16.2/17.2/15.7/17.7/18.9/18.3 ms fits t = steps*(3.75us +
# 0.49us*blocks) => alpha ~= 7.7; the residual q-widening overhead (row
# state grows with bq; measured q2k2 > q1k4 despite equal model cost) is
# the QW_PENALTY term. The law also names the remaining ceiling: per
# 128x128 block ~0.49us across three passes is MXU time on shallow
# D=64-contraction dots — cutting it further needs a fused backward (one
# s/p computation feeding dq+dk+dv, as the dense kernel does) rather
# than better tiling.
_WIDEN_ALPHA_128 = 7.7
_QW_PENALTY = 1.0


def pick_widen(layout: np.ndarray, block: int = 128,
               choices=(1, 2, 4, 8)) -> int:
    """K-only tiling pick (kept for API compatibility): pick_tile with
    q_choices=(1,)."""
    return pick_tile(layout, block=block, k_choices=tuple(choices),
                     q_choices=(1,))[1]


def supertile_nnz(layout: np.ndarray, qw: int, kw: int) -> int:
    """Occupied qw x kw super-tiles of a [H, nQ, nK] layout (= grid steps
    per full pass at that tiling)."""
    lay = np.asarray(layout) != 0
    H, nQ, nK = lay.shape
    return int(lay.reshape(H, nQ // qw, qw, nK // kw, kw)
               .any(axis=(2, 4)).sum())


def pick_tile(layout: np.ndarray, block: int = 128,
              k_choices=(1, 2, 4, 8), q_choices=(1, 2)):
    """(qwiden, kwiden) minimizing the calibrated step-cost model (see
    _WIDEN_ALPHA_128). Banded layouts coarsen nearly for free in both
    dimensions, so the optimum moves to super-tiles whose compute drowns
    the fixed per-step cost; q_choices stops at 2 because measured
    q-widening overhead outgrows its step savings beyond that."""
    lay = np.asarray(layout) != 0
    H, nQ, nK = lay.shape
    alpha = _WIDEN_ALPHA_128 * (128.0 / max(block, 1)) ** 2
    cands = {}
    for qw in q_choices:
        if nQ % qw != 0:
            continue
        for kw in k_choices:
            if nK % kw != 0 or qw * kw > 31:
                continue
            cands[(qw, kw)] = supertile_nnz(lay, qw, kw) * \
                (alpha + qw * kw + _QW_PENALTY * (qw - 1))
    if not cands:
        return (1, 1)
    lo = min(cands.values())
    # The model cannot order near-ties (its residuals are ~8%); among
    # those, the LARGEST super-tile measures fastest (deeper MXU work per
    # step) — v5e sweep: q2k4 beats q1k4/q2k2 despite equal model cost.
    near = [t for t, c in cands.items() if c <= 1.08 * lo]
    return max(near, key=lambda t: (t[0] * t[1], t[1]))


def sparse_flash_attention(q, k, v, layout, *, causal=False, scale,
                           seed=None, dropout: float = 0.0,
                           widen: int = 0, qwiden: int = 0):
    """q,k,v: [BH, S, D] (batch*heads flattened); layout: CONCRETE
    [nH, nQ, nK] array with no empty rows/columns. Grid steps == nnz of
    the (possibly super-tiled) layout.

    ``widen``/``qwiden``: 0 = auto (pick_tile cost model;
    DS_SPARSE_WIDEN / DS_SPARSE_QWIDEN override), else explicit k/q
    coarsening factors."""
    import os
    BH, S, D = q.shape
    nH = int(layout.shape[0])
    bq = S // layout.shape[1]
    bk = k.shape[1] // layout.shape[2]
    lay_np = np.asarray(layout)
    if widen == 0:
        widen = int(os.environ.get("DS_SPARSE_WIDEN", "0"))
    if qwiden == 0:
        qwiden = int(os.environ.get("DS_SPARSE_QWIDEN", "0"))
    if widen == 0 and qwiden == 0:
        # The cost-model pick routes through ops.autotune: on TPU the
        # first compile of a (shape, layout) key times the legal
        # super-tile grid (fwd pass — the bwd kernels share the tiling)
        # and caches the winner; DS_AUTOTUNE=0 / CPU keep the calibrated
        # pick_tile model bit-for-bit.
        from . import autotune
        heur = pick_tile(lay_np, block=bk)
        nQ, nK = int(layout.shape[1]), int(layout.shape[2])
        cands = [(qw, kw) for qw in (1, 2) for kw in (1, 2, 4, 8)
                 if nQ % qw == 0 and nK % kw == 0 and qw * kw <= 31]
        measure = None
        if autotune.search_allowed():
            def run_at(tile):
                return sparse_flash_attention(
                    jnp.zeros((BH, S, D), q.dtype),
                    jnp.zeros(k.shape, k.dtype),
                    jnp.zeros(v.shape, v.dtype), lay_np, causal=causal,
                    scale=scale, qwiden=tile[0], widen=tile[1])
            measure = autotune.measure_from_runner(run_at)
        nnz = int((lay_np != 0).sum())
        qwiden, widen = autotune.resolve(
            "sparse_flash", (BH, S, D, nH, nQ, nK, nnz, int(causal)),
            str(q.dtype), heur, cands, measure)
    # Pinning one factor explicitly leaves the other at 1 (not auto):
    # callers sweeping a single dimension get exactly that dimension.
    widen = widen or 1
    qwiden = qwiden or 1
    req = (qwiden, widen)
    if layout.shape[2] % widen != 0 or widen > 31:
        widen = 1          # non-dividing/overwide: plain 1-wide LUTs
    if layout.shape[1] % qwiden != 0 or qwiden * widen > 31:
        qwiden = 1
    if (qwiden, widen) != req:
        from ..utils.logging import logger
        logger.warning(
            f"sparse_flash_attention: requested super-tile q{req[0]}xk"
            f"{req[1]} does not fit this layout (divisibility or the "
            f"31-bit mask cap); running q{qwiden}xk{widen}")
    luts = build_flat_luts(lay_np, widen=widen, qwiden=qwiden)
    if luts is None:
        raise ValueError("layout has an empty q-block row or k-block "
                         "column; caller should use the gated kernel")
    (qid, kid, nnz, kmask, qidT, kidT, nnzT, kmaskT) = \
        (jnp.asarray(a) for a in luts)
    seed = jnp.zeros((1, 1), jnp.int32) if seed is None \
        else jnp.asarray(seed, jnp.int32).reshape(1, 1)
    return _sparse_flash(q, k, v, qid, kid, nnz, kmask, qidT, kidT, nnzT,
                         kmaskT, seed, scale, causal, nH, bq * qwiden,
                         bk * widen, float(dropout), widen, qwiden)

"""Pallas paged-attention: decode attend does O(context) work, not O(pool).

The serving tier's paged KV cache (inference/kv_cache.py) stores K/V in a
block pool ``[G, B, nH, bs, D]`` and the baseline ``paged_attend`` scores
each query against ALL B pool blocks, then routes through the one-hot
block-table selector — per-token attend FLOPs and HBM bytes scale with
pool CAPACITY, not the request's live context. This module is the real
kernel the one-hot contraction stood in for: the host-built block tables
ride in as scalar-prefetch indices (the sparse_flash.py flattened-LUT
pattern) and the grid iterates, per (stream, head block), only that
stream's ceil(context/bs) live blocks — each step a dynamic-slice load of
one ``[bs, D]`` K/V tile straight from the pool, online-softmax
accumulation in fp32 scratch, and an inclusive position mask so the final
partial block contributes exactly its written rows.

Shapes follow the one-hot path exactly: q is ``[G, Q, K, nH, D]`` where K
is the query rows PER STREAM — 1 for plain decode, k+1 for speculative
verify, the chunk width for chunked prefill. All K rows of a stream share
its block table; ``positions[g, q, k]`` is each row's inclusive last
attendable position (per-row causal offsets), so all three serving paths
run the SAME kernel with no specialization.

Static-shape discipline: the grid is ``(G*Q, nH/bh, J)`` with J the block-
table WIDTH (max_blocks_per_slot) — a compile-time constant — and steps
beyond a stream's live count are predicated off with ``pl.when`` while
their index maps clamp to the last live block (the TPU pipeline elides
the repeated copy). Compute and HBM traffic scale with ceil(context/bs);
the compiled shape never changes, so the serving engine's zero-recompile
sentinel holds. bf16 pools (``kv_cache_dtype: bf16``) dequantize in-VMEM:
tiles are upcast to fp32 at the register level, accumulation is fp32, and
only the final output drops back to q's dtype.

The head-block tile ``bh`` resolves through the PR-16 autotuner
(``resolve("paged_attn", ...)``); on CPU the heuristic answers and the
kernel runs in interpret mode — which is how the dp=8 CPU-mesh tier-1
proves logit parity against the one-hot baseline.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from . import autotune
from .flash_attention import NEG_INF, _interpret
from ..parallel import comm
from ..parallel.topology import DP_AXIS, MP_AXIS

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

_ENV_KNOB = "DS_PAGED_KERNEL"


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
    both sides, so the kernel/one-hot ratio is conservative)."""
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
    reads the whole pool; the kernel reads ceil(ctx/bs) tiles."""
    keys = _attend_keys(block_size, context, pool_blocks)
    return (2 * keys * int(num_heads) * int(head_dim)
            * int(kv_itemsize) * int(num_layers))


# --------------------------------------------------------------------- #
# Kernel
# --------------------------------------------------------------------- #

def _pattn_kernel(bt_ref, pos_ref, nlive_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, bs, bh, K):
    """One grid step = one (stream, head block, table slot j). Scratch
    rows are [bh, K] flattened — head h2's K query rows live at
    ``h2*K:(h2+1)*K`` — and persist across the j sweep (innermost grid
    axis), the standard online-softmax carry."""
    s_idx = pl.program_id(0)
    j = pl.program_id(2)
    nlive = nlive_ref[s_idx, 0]
    active = jnp.logical_and(j < nlive, bt_ref[s_idx, j] >= 0)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(active)
    def _compute():
        # Inclusive per-row position mask: key column t of this block is
        # position j*bs + t; row k attends it iff it is <= pos[k]. The
        # final partial block contributes exactly its written rows, and
        # verify's K=k+1 rows get their per-row causal offsets here.
        # (The per-row positions are SMEM scalars; they are laid into
        # an int32 [K, bs] tile by row select — Mosaic cannot
        # concatenate K boolean rows.)
        col = jax.lax.broadcasted_iota(jnp.int32, (K, bs), 1) + j * bs
        row = jax.lax.broadcasted_iota(jnp.int32, (K, bs), 0)
        pos = jnp.zeros((K, bs), jnp.int32)
        for kk in range(K):
            pos = jnp.where(row == kk, pos_ref[s_idx, kk], pos)
        allowed = col <= pos
        qs = q_ref[0]       # [bh, K, D]
        ks = k_ref[0, 0]    # [bh, bs, D]
        vs = v_ref[0, 0]
        for h2 in range(bh):
            # In-VMEM dequant: bf16 pool tiles upcast at the registers,
            # scores and the accumulator stay fp32 throughout.
            q_h = qs[h2].astype(jnp.float32)
            k_h = ks[h2].astype(jnp.float32)
            v_h = vs[h2].astype(jnp.float32)
            s = jax.lax.dot_general(
                q_h, k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(allowed, s, NEG_INF)
            rows = slice(h2 * K, (h2 + 1) * K)
            m_prev = m_scr[rows, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = (l_scr[rows, 0:1] * alpha
                     + jnp.sum(p, axis=1, keepdims=True))
            pv = jax.lax.dot_general(
                p, v_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_scr[rows] = acc_scr[rows] * alpha + pv
            m_scr[rows, 0:1] = m_new
            l_scr[rows, 0:1] = l_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        # Streams with no live blocks (dead table rows — inactive slots
        # in the uniform group-batched program) keep l == 0 and emit
        # zeros, matching the one-hot baseline's all-masked selector.
        for h2 in range(bh):
            rows = slice(h2 * K, (h2 + 1) * K)
            l_fin = l_scr[rows, 0:1]
            l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
            o_ref[0, h2] = (acc_scr[rows] / l_safe).astype(o_ref.dtype)


def _heuristic_bh(num_heads: int, K: int) -> int:
    """Head-block tile default: fold heads into one grid step while the
    fp32 scratch stays within one sublane tile (bh*K <= 8 rows) — small
    K (plain decode) amortizes per-step sequencing across heads, large K
    (chunked prefill) already fills the step."""
    bh = 1
    while (bh * 2 <= num_heads and num_heads % (bh * 2) == 0
           and bh * 2 * K <= 8):
        bh *= 2
    return bh


def _paged_call(q, pool_k, pool_v, bt, pos, nlive, *, scale, bh):
    """The pallas_call on flattened streams: q [GQ, K, nH, D], pools
    [G, B, nH, bs, D], scalar-prefetch bt [GQ, J] / pos [GQ, K] /
    nlive [GQ, 1] (all int32, group-LOCAL block ids).

    q and the output ride head-major ([GQ, nH, K, D]) so a (bh, K, D)
    tile's last two dims span the array's: a head block in the
    second-minor position must be a multiple of 8 or all of nH on the
    TPU, which nH=20 / bh=4 is not."""
    GQ, K, nH, D = q.shape
    q = jnp.swapaxes(q, 1, 2)
    G, B, _, bs, _ = pool_k.shape
    J = bt.shape[1]
    Q = GQ // G

    def _kv_map(s, h, j, bt_p, pos_p, nl_p):
        # Steps past the live count clamp to the LAST live block — the
        # revisited index lets the TPU pipeline skip the HBM copy, so
        # masked steps cost sequencing only, not bandwidth. max(.., 0)
        # guards dead rows (nlive == 0 streams never compute anyway).
        jj = jnp.minimum(j, jnp.maximum(nl_p[s, 0] - 1, 0))
        return (s // Q, jnp.maximum(bt_p[s, jj], 0), h, 0, 0)

    grid = (GQ, nH // bh, J)
    out = pl.pallas_call(
        functools.partial(_pattn_kernel, scale=scale, bs=bs, bh=bh, K=K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bh, K, D),
                             lambda s, h, j, bt_p, pos_p, nl_p:
                             (s, h, 0, 0)),
                pl.BlockSpec((1, 1, bh, bs, D), _kv_map),
                pl.BlockSpec((1, 1, bh, bs, D), _kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, bh, K, D),
                             lambda s, h, j, bt_p, pos_p, nl_p:
                             (s, h, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bh * K, 128), jnp.float32),
                pltpu.VMEM((bh * K, 128), jnp.float32),
                pltpu.VMEM((bh * K, D), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((GQ, nH, K, D), q.dtype)],
        name="_pattn_kernel",
        interpret=_interpret(),
    )(bt, pos, nlive, q, pool_k, pool_v)
    return jnp.swapaxes(out[0], 1, 2)


def _paged_local(q, pool_k, pool_v, block_tables, positions, *, scale,
                 block_heads):
    """Per-shard kernel entry: shapes are LOCAL (G = groups this shard
    owns, nH = heads this shard owns). Block-table ids are group-local
    by construction (the allocator only hands a slot blocks from its own
    group), so no cross-shard indexing exists to fix up."""
    G, Q, K, nH, D = q.shape
    B, bs = pool_k.shape[1], pool_k.shape[3]
    J = block_tables.shape[2]
    GQ = G * Q
    q2 = q.reshape(GQ, K, nH, D)
    bt2 = block_tables.reshape(GQ, J).astype(jnp.int32)
    pos2 = positions.reshape(GQ, K).astype(jnp.int32)
    # Live block count per stream: the table's rows are a dense prefix
    # (blocks append in order), so ceil((max pos + 1)/bs) of them are
    # live; a dead leading entry marks the whole stream inactive.
    nblk = jnp.clip(jnp.max(pos2, axis=1) // bs + 1, 0, J)
    nlive = jnp.where(bt2[:, 0] < 0, 0, nblk)[:, None].astype(jnp.int32)

    if block_heads:
        bh = int(block_heads)
    else:
        heur = _heuristic_bh(nH, K)
        cands = [c for c in (1, 2, 4, 8, 16)
                 if c <= nH and nH % c == 0 and c * K <= 512]
        measure = None
        if autotune.search_allowed():
            def run_at(v):
                # Concrete stand-ins, never the (possibly traced)
                # operands: every table slot live, so all J blocks load.
                return _paged_call(
                    jnp.zeros(q2.shape, q2.dtype),
                    jnp.zeros(pool_k.shape, pool_k.dtype),
                    jnp.zeros(pool_v.shape, pool_v.dtype),
                    jnp.zeros(bt2.shape, jnp.int32),
                    jnp.full(pos2.shape, J * bs - 1, jnp.int32),
                    jnp.full(nlive.shape, J, jnp.int32),
                    scale=scale, bh=v)
            measure = autotune.measure_from_runner(run_at)
        bh = autotune.resolve("paged_attn", (GQ, K, nH, D, B, bs, J),
                              str(q.dtype), heur, cands, measure)
    out = _paged_call(q2, pool_k, pool_v, bt2, pos2, nlive, scale=scale,
                      bh=bh)
    return out.reshape(G, Q, K, nH, D)


def paged_attention(q, pool_k, pool_v, block_tables, positions, *, scale,
                    block_heads: int = 0, mesh=None):
    """Table-driven paged attention over the block pool.

    q:            [G, Q, K, nH, D] — Q streams per group, K query rows
                  per stream (1 decode / k+1 verify / chunk prefill).
    pool_k/v:     [G, B, nH, bs, D] one layer's block pool.
    block_tables: [G, Q, J] int32 group-local block ids (DEAD_BLOCK for
                  unallocated tail entries).
    positions:    [G, Q, K] int32 inclusive last attendable position per
                  query row.

    Returns [G, Q, K, nH, D] in q's dtype. When ``mesh`` spans dp/mp the
    call runs under shard_map (manual over ALL mesh axes): GSPMD cannot
    partition a pallas_call, and group-local block ids make each shard's
    kernel self-contained — zero communication, the same locality
    argument the one-hot contraction relied on."""
    if pltpu is None:  # pragma: no cover - pallas TPU support missing
        raise RuntimeError("pallas TPU backend unavailable; run with "
                           "inference.paged_kernel=false")
    if mesh is not None and math.prod(mesh.shape.values()) > 1:
        dpn = DP_AXIS if DP_AXIS in mesh.axis_names else None
        mpn = MP_AXIS if MP_AXIS in mesh.axis_names else None
        fn = comm.shard_map(
            functools.partial(_paged_local, scale=scale,
                              block_heads=block_heads),
            mesh=mesh,
            in_specs=(P(dpn, None, None, mpn, None),
                      P(dpn, None, mpn, None, None),
                      P(dpn, None, mpn, None, None),
                      P(dpn), P(dpn)),
            out_specs=P(dpn, None, None, mpn, None),
            axis_names=set(mesh.axis_names),
            # No collective inside: nothing for the vma checker to
            # check, and a pallas out_shape carries no vma.
            check_vma=False)
        return fn(q, pool_k, pool_v, block_tables, positions)
    return _paged_local(q, pool_k, pool_v, block_tables, positions,
                        scale=scale, block_heads=block_heads)


__all__ = ["paged_attention", "paged_kernel_enabled",
           "attend_flops_per_token", "attend_hbm_bytes_per_token"]

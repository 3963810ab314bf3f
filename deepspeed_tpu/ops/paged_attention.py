"""Pallas paged-attention: decode attend does O(context) work, not O(pool).

The serving tier's paged KV cache (inference/kv_cache.py) stores K/V in a
stacked block pool (logically ``[L, G, B, nH, bs, D]``, held lane-dense —
see ``kv_cache.kv_fold``) and the baseline ``paged_attend`` scores each
query against ALL B pool blocks of a layer, then routes through the one-hot
block-table selector — per-token attend FLOPs and HBM bytes scale with
pool CAPACITY, not the request's live context. This module is the real
kernel the one-hot contraction stood in for: the host-built block tables
ride in as scalar-prefetch indices (the sparse_flash.py flattened-LUT
pattern) and the grid iterates, per (stream, head block), only that
stream's ceil(context/bs) live blocks — each step a dynamic-slice load of
one block's K/V tile straight from the WHOLE stacked pool (one more
scalar-prefetch table names each (stream, slot)'s tile with the layer
folded in: no layer is ever sliced out of the pool, relaid or copied
around the call), online-softmax
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

``paged_write`` is the other half of the same mechanism: new K/V rows are
written INTO the donated pool where it lies (an aliased ``pallas_call``
whose scalar-prefetched tile index and offset pick each row's block tile;
read-modify-write of that one tile in VMEM), so a step's cost is O(rows
written), never O(pool).
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

def _pattn_kernel(bt_ref, pos_ref, nlive_ref, rows_ref, q_ref, k_ref, v_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, scale, bs, bh, K, D):
    """One grid step = one (stream, head block, table slot j). Scratch
    rows persist across the j sweep (innermost grid axis), the standard
    online-softmax carry.

    A head's K/V tile arrives as the pool holds it, lane-dense
    ``[bs/f, f*D]``: position t of the block at row t // f, lanes
    (t % f)*D.. . Nothing re-tiles it. Instead each query row comes f
    times (``_paged_call`` lays copy i into lanes i*D.. of an otherwise
    zero ``f*D``-wide row), so ONE full-lane contraction against the tile
    gives copy i the scores of the positions with t % f == i, and one
    against the V tile gives it their weighted sum in lanes i*D.. . Each
    copy keeps its own online-softmax state — head h2's f*K rows live at
    ``h2*f*K + i*K + k`` — and the copies are merged when the stream's
    last block is done. With f == 1 (head_dim >= 128) this is the plain
    kernel."""
    s_idx = pl.program_id(0)
    j = pl.program_id(2)
    nlive = nlive_ref[s_idx, 0]
    active = jnp.logical_and(j < nlive, bt_ref[s_idx, j] >= 0)
    f = k_ref.shape[-1] // D
    bsf, fK = bs // f, f * K

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(active)
    def _compute():
        # Inclusive per-row position mask: column c of copy i is
        # position j*bs + c*f + i of the stream; query row k attends it
        # iff it is <= pos[k]. The final partial block contributes
        # exactly its written rows, and verify's K=k+1 rows get their
        # per-row causal offsets here. (The per-row positions are SMEM
        # scalars; they are laid into an int32 tile by row select —
        # Mosaic cannot concatenate K boolean rows.)
        row = jax.lax.broadcasted_iota(jnp.int32, (fK, bsf), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (fK, bsf), 1)
        if f > 1:
            col = col * f + jax.lax.div(row, K)
            row = jax.lax.rem(row, K)
        col = col + j * bs
        pos = jnp.zeros((fK, bsf), jnp.int32)
        for kk in range(K):
            pos = jnp.where(row == kk, pos_ref[s_idx, kk], pos)
        allowed = col <= pos
        qs = q_ref[0]           # [bh, f*K, f*D]
        ks = k_ref[0]           # [bh, bs/f, f*D]
        vs = v_ref[0]
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
            rows = slice(h2 * fK, (h2 + 1) * fK)
            m_prev = m_scr[rows, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # A copy may have seen no attendable position yet (context
            # shorter than its first one): keep its state empty rather
            # than exp(NEG_INF - NEG_INF) = 1 a column.
            p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
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
        # Merge each query row's f copies (softmax over the union of
        # their positions). Streams with no live blocks (dead table rows
        # — inactive slots in the uniform group-batched program) keep
        # l == 0 and emit zeros, matching the one-hot baseline's
        # all-masked selector.
        for h2 in range(bh):
            copies = [slice(h2 * fK + i * K, h2 * fK + (i + 1) * K)
                      for i in range(f)]
            m_fin = m_scr[copies[0], 0:1]
            for rows in copies[1:]:
                m_fin = jnp.maximum(m_fin, m_scr[rows, 0:1])
            l_fin = jnp.zeros_like(m_fin)
            out = jnp.zeros((K, D), jnp.float32)
            for i, rows in enumerate(copies):
                w = jnp.exp(m_scr[rows, 0:1] - m_fin)
                l_fin = l_fin + w * l_scr[rows, 0:1]
                out = out + w * acc_scr[rows, i * D:(i + 1) * D]
            l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
            o_ref[0, h2] = (out / l_safe).astype(o_ref.dtype)


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


def _pool_rows(pool):
    """[L, G, B, nH, bs/f, f*D] -> [L*G*B, nH, bs/f, f*D]: every block
    tile of the stacked pool as one row of a 4-D array (a bitcast), so a
    kernel's index map names a tile by ONE precomputed index."""
    return pool.reshape((-1,) + pool.shape[3:])


def _paged_call(q, pool_k, pool_v, layer, bt, pos, nlive, *, scale, bh):
    """The pallas_call on flattened streams: q [GQ, K, nH, D], the
    stacked lane-dense pools [L, G, B, nH, bs/f, f*D], ``layer`` a
    traced int32 scalar, scalar-prefetch bt [GQ, J] / pos [GQ, K] /
    nlive [GQ, 1] (all int32, group-LOCAL block ids).

    q and the output ride head-major ([GQ, nH, K, D]) so a (bh, K, D)
    tile's last two dims span the array's: a head block in the
    second-minor position must be a multiple of 8 or all of nH on the
    TPU, which nH=20 / bh=4 is not."""
    GQ, K, nH, D = q.shape
    _, G, B, _, bsf, fD = pool_k.shape
    f = fD // D
    bs = bsf * f
    J = bt.shape[1]
    Q = GQ // G
    # Each query row f times, copy i in lanes i*D.. of a zero row (see
    # the kernel): [GQ, nH, f*K, f*D].
    q = jnp.swapaxes(q, 1, 2)
    q = (q[:, :, None, :, None, :] *
         jnp.eye(f, dtype=q.dtype)[:, None, :, None]
         ).reshape(GQ, nH, f * K, fD)
    # The pool tile of every (stream, table slot), as a fourth
    # scalar-prefetch table, so the K/V index maps are one SMEM read a
    # step. Steps past the live count clamp to the LAST live block — the
    # revisited index lets the TPU pipeline skip the HBM copy, so masked
    # steps cost sequencing only, not bandwidth. max(.., 0) guards dead
    # rows (nlive == 0 streams never compute anyway).
    jj = jnp.minimum(jnp.arange(J, dtype=jnp.int32)[None],
                     jnp.maximum(nlive - 1, 0))
    blk = jnp.maximum(jnp.take_along_axis(bt, jj, axis=1), 0)
    group = (jnp.arange(GQ, dtype=jnp.int32) // Q)[:, None]
    rows = (layer * G + group) * B + blk                     # [GQ, J]

    def _kv_map(s, h, j, bt_p, pos_p, nl_p, rows_p):
        return (rows_p[s, j], h, 0, 0)

    def _q_map(s, h, j, bt_p, pos_p, nl_p, rows_p):
        return (s, h, 0, 0)

    grid = (GQ, nH // bh, J)
    out = pl.pallas_call(
        functools.partial(_pattn_kernel, scale=scale, bs=bs, bh=bh, K=K,
                          D=D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bh, f * K, fD), _q_map),
                pl.BlockSpec((1, bh, bsf, fD), _kv_map),
                pl.BlockSpec((1, bh, bsf, fD), _kv_map),
            ],
            out_specs=[pl.BlockSpec((1, bh, K, D), _q_map)],
            scratch_shapes=[
                pltpu.VMEM((bh * f * K, 128), jnp.float32),
                pltpu.VMEM((bh * f * K, 128), jnp.float32),
                pltpu.VMEM((bh * f * K, fD), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((GQ, nH, K, D), q.dtype)],
        name="_pattn_kernel",
        interpret=_interpret(),
    )(bt, pos, nlive, rows.astype(jnp.int32), q, _pool_rows(pool_k),
      _pool_rows(pool_v))
    return jnp.swapaxes(out[0], 1, 2)


def _paged_local(q, pool_k, pool_v, layer, block_tables, positions, *,
                 scale, block_heads):
    """Per-shard kernel entry: shapes are LOCAL (G = groups this shard
    owns, nH = heads this shard owns). Block-table ids are group-local
    by construction (the allocator only hands a slot blocks from its own
    group), so no cross-shard indexing exists to fix up."""
    G, Q, K, nH, D = q.shape
    B, bsf, fD = pool_k.shape[2], pool_k.shape[4], pool_k.shape[5]
    bs = bsf * (fD // D)
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
            one_layer = (1,) + tuple(pool_k.shape[1:])

            def run_at(v):
                # Concrete stand-ins, never the (possibly traced)
                # operands: one layer of pool, every table slot live, so
                # all J blocks load.
                return _paged_call(
                    jnp.zeros(q2.shape, q2.dtype),
                    jnp.zeros(one_layer, pool_k.dtype),
                    jnp.zeros(one_layer, pool_v.dtype),
                    jnp.int32(0),
                    jnp.zeros(bt2.shape, jnp.int32),
                    jnp.full(pos2.shape, J * bs - 1, jnp.int32),
                    jnp.full(nlive.shape, J, jnp.int32),
                    scale=scale, bh=v)
            measure = autotune.measure_from_runner(run_at)
        bh = autotune.resolve("paged_attn", (GQ, K, nH, D, B, bs, J),
                              str(q.dtype), heur, cands, measure)
    out = _paged_call(q2, pool_k, pool_v, layer, bt2, pos2, nlive,
                      scale=scale, bh=bh)
    return out.reshape(G, Q, K, nH, D)


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


def paged_attention(q, pool_k, pool_v, layer, block_tables, positions, *,
                    scale, block_heads: int = 0, mesh=None):
    """Table-driven paged attention over one layer of the block pool.

    q:            [G, Q, K, nH, D] — Q streams per group, K query rows
                  per stream (1 decode / k+1 verify / chunk prefill).
    pool_k/v:     [L, G, B, nH, bs/f, f*D] — the WHOLE stacked pool in
                  the lane-dense layout it is born in
                  (``kv_cache.PagedKVCacheSpec.shape``).
    layer:        int32 scalar — which layer of the pool to read.
    block_tables: [G, Q, J] int32 group-local block ids (DEAD_BLOCK for
                  unallocated tail entries).
    positions:    [G, Q, K] int32 inclusive last attendable position per
                  query row.

    Returns [G, Q, K, nH, D] in q's dtype. When ``mesh`` spans dp/mp the
    call runs under shard_map (see ``_on_mesh``)."""
    if pltpu is None:  # pragma: no cover - pallas TPU support missing
        raise RuntimeError("pallas TPU backend unavailable; run with "
                           "inference.paged_kernel=false")
    fn = _on_mesh(
        functools.partial(_paged_local, scale=scale,
                          block_heads=block_heads), mesh,
        lambda dpn, mpn: (P(dpn, None, None, mpn, None),
                          _pool_spec(dpn, mpn), _pool_spec(dpn, mpn), P(),
                          P(dpn), P(dpn)),
        lambda dpn, mpn: P(dpn, None, None, mpn, None))
    return fn(q, pool_k, pool_v, jnp.asarray(layer, jnp.int32),
              block_tables, positions)


# --------------------------------------------------------------------- #
# The write: new rows into the donated pool, in place
# --------------------------------------------------------------------- #

def _kv_write_kernel(rows_ref, off_ref, nk_ref, nv_ref, k_in, v_in, k_out,
                     v_out, *, D):
    """One grid step = one (group, row): the row's block tile
    ``[nH, bs/f, f*D]`` is read, the row's position overwritten, the tile
    written back. Consecutive rows of one block (a prefill chunk, a
    verify stream) revisit the same output tile, which then stays in
    VMEM: it is loaded from the pool on the first visit only and goes
    back when the block changes. Dead rows (offset < 0) ride the block
    of a live neighbour and change nothing."""
    g, r = pl.program_id(0), pl.program_id(1)
    off = off_ref[g, r]
    first = jnp.logical_or(
        r == 0, rows_ref[g, r] != rows_ref[g, jnp.maximum(r - 1, 0)])

    @pl.when(first)
    def _load():
        k_out[...] = k_in[...]
        v_out[...] = v_in[...]

    @pl.when(off >= 0)
    def _write():
        nH, bsf, fD = k_out.shape[1:]
        f = fD // D
        hit = jax.lax.broadcasted_iota(jnp.int32, (nH, bsf, fD), 1) == \
            jax.lax.div(off, f)
        if f > 1:
            lane = jax.lax.broadcasted_iota(jnp.int32, (nH, bsf, fD), 2)
            hit = jnp.logical_and(
                hit, jax.lax.div(lane, D) == jax.lax.rem(off, f))
        for new, out in ((nk_ref, k_out), (nv_ref, v_out)):
            # Selected in fp32 (exact for every pool dtype): the v5e has
            # no 16-bit vector select.
            row = new[0, 0].astype(jnp.float32)          # [nH, 1, f*D]
            cur = out[0].astype(jnp.float32)
            out[0] = jnp.where(hit, row, cur).astype(out.dtype)


def _write_local(pool_k, pool_v, k_new, v_new, layer, blk, off):
    """Per-shard write: pools [L, G, B, nH, bs/f, f*D]; k_new/v_new
    [G, R, nH, D]; blk/off [G, R] (group-local ids, DEAD_BLOCK = -1)."""
    _, G, B, nH, bsf, fD = pool_k.shape
    R, D = blk.shape[1], k_new.shape[-1]
    f = fD // D
    # Every grid step needs SOME block to hold; a dead row takes the
    # nearest live row's before it (else the first live row's, else
    # block 0), so each block is still one contiguous run of steps and
    # a dead row's tile is written back as it was read.
    live = blk >= 0
    idx = jnp.where(live, jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1),
                    -1)
    src = jax.lax.cummax(idx, axis=1)
    src = jnp.where(src >= 0, src, jnp.argmax(live, axis=1)[:, None])
    eb = jnp.maximum(jnp.take_along_axis(blk, src, axis=1), 0)
    group = jnp.arange(G, dtype=jnp.int32)[:, None]
    tiles = ((layer * G + group) * B + eb).astype(jnp.int32)   # [G, R]
    offs = jnp.where(live, off, -1).astype(jnp.int32)

    def rows(new):
        # The row as it lies in a tile: f copies side by side in the
        # lanes (the kernel's select keeps the one at the row's offset).
        new = new.astype(pool_k.dtype)
        return jnp.tile(new, (1, 1, 1, f))[:, :, :, None, :]

    def _row_map(g, r, tiles_p, off_p):
        return (g, r, 0, 0, 0)

    def _pool_map(g, r, tiles_p, off_p):
        return (tiles_p[g, r], 0, 0, 0)

    row_spec = pl.BlockSpec((1, 1, nH, 1, fD), _row_map)
    pool_spec = pl.BlockSpec((1, nH, bsf, fD), _pool_map)
    flat_k, flat_v = _pool_rows(pool_k), _pool_rows(pool_v)
    out_k, out_v = pl.pallas_call(
        functools.partial(_kv_write_kernel, D=D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(G, R),
            in_specs=[row_spec, row_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec]),
        out_shape=[jax.ShapeDtypeStruct(flat_k.shape, flat_k.dtype),
                   jax.ShapeDtypeStruct(flat_v.shape, flat_v.dtype)],
        # operands: tiles, offs, rows_k, rows_v, pool_k, pool_v
        input_output_aliases={4: 0, 5: 1},
        name="_kv_write_kernel",
        interpret=_interpret(),
    )(tiles, offs, rows(k_new), rows(v_new), flat_k, flat_v)
    return out_k.reshape(pool_k.shape), out_v.reshape(pool_v.shape)


def paged_write(pool_k, pool_v, k_new, v_new, layer, blk, off, *,
                mesh=None):
    """Write R rows per group into one layer of both pools, in place.

    pool_k/v: [L, G, B, nH, bs/f, f*D] (donated by the caller's jit: the
    call aliases them to its outputs and touches R block tiles each);
    k_new/v_new: [G, R, nH, D]; layer: int32 scalar; blk/off: [G, R] —
    rows with blk == DEAD_BLOCK write nowhere. Rows of one block must be
    consecutive and no block may be named by two separate runs of rows
    (the allocator's invariant: a writable block has one owner, and a
    stream's positions ascend). Returns (pool_k', pool_v')."""
    if pltpu is None:  # pragma: no cover - pallas TPU support missing
        raise RuntimeError("pallas TPU backend unavailable")
    fn = _on_mesh(
        _write_local, mesh,
        lambda dpn, mpn: (_pool_spec(dpn, mpn), _pool_spec(dpn, mpn),
                          P(dpn, None, mpn, None), P(dpn, None, mpn, None),
                          P(), P(dpn), P(dpn)),
        lambda dpn, mpn: (_pool_spec(dpn, mpn), _pool_spec(dpn, mpn)))
    out = fn(pool_k, pool_v, k_new, v_new, jnp.asarray(layer, jnp.int32),
             blk, off)
    return out[0], out[1]


__all__ = ["paged_attention", "paged_write", "paged_kernel_enabled",
           "attend_flops_per_token", "attend_hbm_bytes_per_token"]

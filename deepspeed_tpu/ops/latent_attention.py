"""Paged latent attention (MLA, absorbed form): one cache row a token and
layer, read by every query head.

The latent family keeps ``[ckv | k_rope]`` (``C + R`` values, 512 + 64
published) per token and layer, not per-head K and V.  A block of ``bs``
positions is held as ONE lane-dense tile ``[bs/2, 2C + 2R]``: row ``r``
holds positions ``r`` and ``r + bs/2`` side by side,

    [ ckv(r) | ckv(r + bs/2) | k_rope(r) | k_rope(r + bs/2) ]

so neither the 512 nor the 64 columns are padded to the 128 lanes (1,152
lanes = 9 lane tiles; a 576-wide row padded to 640 would cost 11% of the
pool and of the attend's traffic) and every slice the kernel takes of a
tile is lane-aligned.  The pool is ``[L, G, B, 1, bs/2, 2C + 2R]``: the
same six axes as GPT-2's pools (``inference/kv_cache.py``), one "head".

``latent_attention`` follows ``ops.paged_attention._pattn_kernel``'s
design: the stacked pool stays in HBM, a grid step takes ONE stream (and
one tile of its query rows) through its live blocks, P blocks at a time,
copying group g + 1's tiles into one half of a VMEM buffer while it
attends group g in the other — and, during its last group, the first
group of the NEXT grid step (the stream's next row tile, else the next
stream, where that one is live) into the half it has left, so only the
first step of a call and a step after a dead one start their copies
cold (the grid is sequential; the buffers, the semaphores and an SMEM
carry live across its steps).  All heads of a query token are ROWS of one
product (``[K x nH, C + R] x [C + R, P x bs]`` scores, ``[K x nH, P x bs] x
[P x bs, C]`` values): one shared "K/V head" for every query head.  The
two halves of a tile are two column sets of one online softmax, so
nothing is ever re-tiled.  bf16 products, fp32 scores, softmax and
accumulation.  Which rows a step holds follows from the shapes (K rows a
stream: 1 decode, k + 1 verify, the chunk in prefill), and so does the
group (``slots_a_step``): a prefill row tile's sixteen slots computed
whole; for a step of few rows as many as copy
``paged_attention._GROUP_BYTES`` (32 at the published tile), waited for
by bytes (one wait stands for several tiles), and computed in ONE
online-softmax update over the narrowest of 32 / 16 / 8 / 4 slots that
holds the group's live ones.  What the v5e showed (PERF.md section 6, PR
42): the cache's columns are the MXU's weights, so a group costs 0.047
us a slot computed, live or not, whatever the query rows (32 or 64); an
update costs 0.35 us of latency whatever its width, so a loop of narrow
updates loses what it saves; starting and waiting for a tile cost the
scalar core 0.04 us, in series with the products.

``latent_write`` is ``paged_write``'s twin for this tile: new rows go
into the donated pool in place (aliased call, scalar-prefetched tile and
offset, one tile read-modify-written per run of rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from .flash_attention import NEG_INF, _interpret
from . import paged_attention as paged

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

# Query tokens a row tile holds, and the VMEM a step may ask for.
_ROW_TOKENS = 8
_VMEM_LIMIT = 48 * 2 ** 20


def latent_tile(block_size: int, latent_width: int):
    """One block's tile as held: (rows, lanes)."""
    if block_size % 2:
        raise ValueError("the latent tile folds a block's two halves: "
                         "block_size must be even")
    return block_size // 2, 2 * latent_width


def fold_rows(rows: jax.Array, C: int) -> jax.Array:
    """Logical rows ``[..., bs, C + R]`` -> tiles ``[..., bs/2, 2C + 2R]``."""
    h = rows.shape[-2] // 2
    lo, hi = rows[..., :h, :], rows[..., h:, :]
    return jnp.concatenate([lo[..., :C], hi[..., :C], lo[..., C:],
                            hi[..., C:]], axis=-1)


def logical_rows(tiles: jax.Array, C: int) -> jax.Array:
    """Tiles ``[..., bs/2, 2C + 2R]`` -> rows ``[..., bs, C + R]`` (tests,
    the one-hot path)."""
    R = tiles.shape[-1] // 2 - C
    lo = jnp.concatenate([tiles[..., :C], tiles[..., 2 * C:2 * C + R]], -1)
    hi = jnp.concatenate([tiles[..., C:2 * C], tiles[..., 2 * C + R:]], -1)
    return jnp.concatenate([lo, hi], axis=-2)


def slots_a_step(rows: int, table_width: int, tile, kv_lora: int,
                 itemsize: int):
    """(table slots a group copies, the widths a group is computed at) for
    a step of ``rows`` query rows over a table ``table_width`` wide whose
    blocks are held as ``tile`` = ``latent_tile(...)``.

    A step whose rows fill the MXU (a prefill chunk's row tile) is bound
    by its products: a group whose score tile is as wide as its value
    tile (``kv_lora`` columns a half), computed whole.  A step of few rows
    (decode, verify) is bound by the latency and the sequencing of its
    copies, a group each: slots doubled from there while a group copies
    under ``paged._GROUP_BYTES``, the table has them and both halves of
    the buffer stay under half of ``_VMEM_LIMIT``.  Such a step computes a
    group in ONE online-softmax update (a chain of products and
    reductions whose latency, 0.35 us on the v5e, is paid whatever its
    width) over the narrowest of the widths that holds the group's live
    slots: the group halved down to the slots that give the score tile
    its 128 lanes."""
    h, W = tile
    slots = max(1, min(kv_lora // h, table_width))
    if rows >= paged._DENSE_ROWS:
        return slots, (slots,)
    tile_bytes = h * W * itemsize
    while (slots * tile_bytes < paged._GROUP_BYTES
           and 2 * slots <= table_width
           and 8 * slots * tile_bytes <= _VMEM_LIMIT):
        slots *= 2
    widths = [slots]
    while widths[-1] % 2 == 0 and widths[-1] * h > 128:
        widths.append(widths[-1] // 2)
    return slots, tuple(widths)


def row_tokens(K: int) -> int:
    return K if K <= _ROW_TOKENS else _ROW_TOKENS


# --------------------------------------------------------------------- #
# The attend
# --------------------------------------------------------------------- #
def _attend_columns(q_ref, lim_ref, buf, m_scr, l_scr, acc_scr, half, pos0,
                    *, scale, h, C, R, N):
    """One online-softmax update over the first ``N // h`` slots of
    ``buf[half]``.  Column c of the lo set is position ``pos0 + (c // h)*2h
    + c % h`` of the stream, of the hi set that + h."""
    shift = h.bit_length() - 1
    col = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
    pos = pos0 + jax.lax.shift_left(
        jax.lax.shift_right_logical(col, shift), shift + 1) \
        + jnp.bitwise_and(col, h - 1)
    lim = lim_ref[0]                                       # [rows, 1]
    ok_lo, ok_hi = pos <= lim, pos + h <= lim              # [rows, N]

    qa = q_ref[0, :, 0:C]
    q_lo = q_ref[0, :, C:C + 2 * R]
    q_hi = q_ref[0, :, C + 2 * R:C + 4 * R]
    held = buf[half, 0:N, :]                               # one load
    c_lo, c_hi = held[:, 0:C], held[:, C:2 * C]            # [N, C]
    kr = held[:, 2 * C:2 * C + 2 * R]                      # [N, 2R]
    nt = (((1,), (1,)), ((), ()))

    def scores(c, q_r, ok):
        s = jax.lax.dot_general(qa, c, nt,
                                preferred_element_type=jnp.float32) \
            + jax.lax.dot_general(q_r, kr, nt,
                                  preferred_element_type=jnp.float32)
        return jnp.where(ok, s * scale, NEG_INF)
    s_lo, s_hi = scores(c_lo, q_lo, ok_lo), scores(c_hi, q_hi, ok_hi)
    m_prev = m_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.maximum(
        jnp.max(s_lo, axis=1, keepdims=True),
        jnp.max(s_hi, axis=1, keepdims=True)))
    alpha = jnp.exp(m_prev - m_new)
    p_lo = jnp.where(ok_lo, jnp.exp(s_lo - m_new), 0.0)
    p_hi = jnp.where(ok_hi, jnp.exp(s_hi - m_new), 0.0)
    l_scr[:, 0:1] = l_scr[:, 0:1] * alpha \
        + jnp.sum(p_lo, axis=1, keepdims=True) \
        + jnp.sum(p_hi, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha \
        + jnp.dot(p_lo.astype(c_lo.dtype), c_lo,
                  preferred_element_type=jnp.float32) \
        + jnp.dot(p_hi.astype(c_hi.dtype), c_hi,
                  preferred_element_type=jnp.float32)
    m_scr[:, 0:1] = m_new


def _latent_kernel(nl_ref, rows_ref, base_ref, lim_ref, q_ref, pool_hbm,
                   o_ref, buf, sem, ahead, m_scr, l_scr, acc_scr, *, scale,
                   h, C, R, widths):
    """One grid step = one (stream, tile of query rows).  ``buf`` is
    ``[2, P*h, 2C+2R]``: a half holds the P = ``widths[0]`` tiles of the
    group in flight, stacked, and a group is attended over the narrowest
    of ``widths`` that holds its live slots.  ``ahead`` (SMEM) is
    ``_pattn_kernel``'s carry: whether this step's first group is already
    in flight, and in which half."""
    s_idx, t_idx = pl.program_id(0), pl.program_id(1)
    P, narrow = widths[0], widths[-1]
    nlive = nl_ref[s_idx, t_idx]
    groups = pl.cdiv(nlive, P)

    @pl.when(jnp.logical_and(s_idx == 0, t_idx == 0))
    def _first_step():
        ahead[0] = 0
        ahead[1] = 0

    def slots(first, n):
        return pl.ds(pl.multiple_of(first * h, h), n * h)

    def start_tiles(s, t, g, half):
        """Start the copy of every live slot of group g of step (s, t)
        into buffer half ``half``."""
        def one(p, carry):
            row = rows_ref[s, g * P + p] + base_ref[0]
            pltpu.make_async_copy(pool_hbm.at[row], buf.at[half, slots(p, 1)],
                                  sem.at[half]).start()
            return carry
        jax.lax.fori_loop(0, jnp.minimum(P, nl_ref[s, t] - g * P), one, 0)

    def wait_tiles(half, live):
        """Wait for ``live`` tiles' bytes in ``half``: a DMA semaphore
        counts bytes, so one wait sized as ``narrow`` tiles stands for
        that many tiles' (a descriptor only sizes it)."""
        def some(n):
            def one(p, carry):
                held = buf.at[half, slots(0, n)]
                pltpu.make_async_copy(held, held, sem.at[half]).wait()
                return carry
            return one
        jax.lax.fori_loop(0, live // narrow, some(narrow), 0)
        jax.lax.fori_loop(0, jax.lax.rem(live, narrow), some(1), 0)

    # The grid step after this one, and whether it has anything to copy.
    streams = pl.num_programs(0)
    wrap = t_idx + 1 == pl.num_programs(1)
    s_next = jnp.where(wrap, s_idx + 1, s_idx)
    t_next = jnp.where(wrap, 0, t_idx + 1)
    next_live = jnp.logical_and(
        s_next < streams,
        nl_ref[jnp.minimum(s_next, streams - 1), t_next] > 0)

    def group(g, carry):
        half = jax.lax.rem(ahead[1] + g, 2)

        @pl.when(g + 1 < groups)
        def _next():
            start_tiles(s_idx, t_idx, g + 1, 1 - half)

        @pl.when(jnp.logical_and(g + 1 == groups, next_live))
        def _next_step():
            start_tiles(s_next, t_next, 0, 1 - half)

        live = jnp.minimum(P, nlive - g * P)
        wait_tiles(half, live)

        def zero(p, carry):
            # Slots of the width past the live count: masked columns,
            # but 0 x stale VMEM is not 0.
            buf[half, slots(p, 1), :] = jnp.zeros((h, buf.shape[2]),
                                                  buf.dtype)
            return carry

        for n, below in zip(widths, widths[1:] + (0,)):
            @pl.when(jnp.logical_and(live > below, live <= n))
            def _attend(n=n):
                jax.lax.fori_loop(live, n, zero, 0)
                _attend_columns(
                    q_ref, lim_ref, buf, m_scr, l_scr, acc_scr, half,
                    g * (P * 2 * h), scale=scale, h=h, C=C, R=R, N=n * h)
        return carry

    @pl.when(groups == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(groups > 0)
    def _live():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when(ahead[0] == 0)
        def _cold():
            start_tiles(s_idx, t_idx, 0, ahead[1])

        jax.lax.fori_loop(0, groups, group, 0)
        # What the last group started for the next step, and where.
        ahead[1] = jax.lax.rem(ahead[1] + groups, 2)
        ahead[0] = next_live.astype(jnp.int32)
        l_fin = l_scr[:, 0:1]
        o_ref[0] = (acc_scr[...] / jnp.where(l_fin == 0.0, 1.0, l_fin)
                    ).astype(o_ref.dtype)


def _latent_local(q_abs, q_rope, pool, layer, nlive, rows, lim, *, scale):
    """Per-shard entry.  q_abs [G, Q, K, nH, C], q_rope [G, Q, K, nH, R],
    pool [L, G, B, 1, h, 2C+2R], the shard's plan (``nlive`` [G, Q],
    ``rows`` [G, Q, J], ``lim`` [G, Q, K, 1])."""
    G, Q, K, nH, C = q_abs.shape
    R = q_rope.shape[-1]
    _, _, B, _, h, W = pool.shape
    assert W == 2 * (C + R) and h & (h - 1) == 0, (pool.shape, C, R)
    bs, J, GQ = 2 * h, rows.shape[2], G * Q
    kt = row_tokens(K)
    Kp = -(-K // kt) * kt
    nT, rt = Kp // kt, kt * nH
    P_, widths = slots_a_step(rt, J, (h, W), C, pool.dtype.itemsize)
    zeros = jnp.zeros_like(q_rope)
    q = jnp.concatenate([q_abs, q_rope, zeros, zeros, q_rope], axis=-1)
    q = jnp.pad(q.reshape(GQ, K, nH, C + 4 * R),
                ((0, 0), (0, Kp - K), (0, 0), (0, 0)))
    q = q.reshape(GQ, Kp * nH, C + 4 * R)
    reach = jnp.pad(lim.reshape(GQ, K), ((0, 0), (0, Kp - K)),
                    constant_values=-1)
    # Live blocks a tile of rows reaches (causal: an early tile of a
    # prefill chunk stops short of the stream's last blocks).
    top = reach.reshape(GQ, nT, kt).max(axis=2)
    nl = jnp.where(top < 0, 0, jnp.minimum(top // bs + 1,
                                           nlive.reshape(GQ, 1)))
    lim_rows = jnp.repeat(reach, nH, axis=1).reshape(GQ, Kp * nH, 1)
    base = (layer * (G * B)).astype(jnp.int32).reshape(1)
    rows = jnp.pad(rows.reshape(GQ, J), ((0, 0), (0, -J % P_)))

    def _row_map(s, t, nl_p, rows_p, base_p):
        return (s, t, 0)

    out = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, h=h, C=C, R=R,
                          widths=widths),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(GQ, nT),
            in_specs=[pl.BlockSpec((1, rt, 1), _row_map),
                      pl.BlockSpec((1, rt, C + 4 * R), _row_map),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, rt, C), _row_map)],
            scratch_shapes=[
                pltpu.VMEM((2, P_ * h, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((rt, 128), jnp.float32),
                pltpu.VMEM((rt, 128), jnp.float32),
                pltpu.VMEM((rt, C), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((GQ, Kp * nH, C), q_abs.dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        name="_latent_attn_kernel",
        interpret=_interpret(),
    )(nl.astype(jnp.int32), rows, base, lim_rows, q,
      pool.reshape(-1, h, W))
    return out[0].reshape(GQ, Kp, nH, C)[:, :K].reshape(G, Q, K, nH, C)


def latent_plan(block_tables, positions, pool, *, mesh=None
                ) -> paged.AttendPlan:
    """What the attend reads of the tables and positions, once an
    execution (``paged_attention.attend_plan``'s twin; ``lim`` [G, Q, K,
    1] is each row's last attendable position)."""
    B, h = pool.shape[2], pool.shape[4]
    fn = paged._on_mesh(
        functools.partial(paged._plan_local, B=B, bs=2 * h, f=1), mesh,
        lambda dpn, mpn: (P(dpn), P(dpn)),
        lambda dpn, mpn: paged.AttendPlan(P(dpn), P(dpn), P(dpn)))
    return fn(block_tables, positions)


def latent_attention(q_abs, q_rope, pool, layer, *, plan, scale, mesh=None):
    """``softmax((q_abs . ckv + q_rope . k_rope) * scale) ckv`` through the
    block tables, per query row: q_abs [G, Q, K, nH, C] (the query
    absorbed through ``wkv_b``'s key half), q_rope [G, Q, K, nH, R], the
    stacked latent pool, ``layer`` an int32 scalar.  Returns [G, Q, K, nH,
    C] (to go through ``wkv_b``'s value half).  Under a dp mesh each
    shard attends its own groups."""
    if pltpu is None:  # pragma: no cover
        raise RuntimeError("pallas TPU backend unavailable; run with "
                           "inference.paged_kernel=false")
    fn = paged._on_mesh(
        functools.partial(_latent_local, scale=scale), mesh,
        lambda dpn, mpn: (P(dpn), P(dpn), P(None, dpn), P(), P(dpn),
                          P(dpn), P(dpn)),
        lambda dpn, mpn: P(dpn))
    return fn(q_abs, q_rope, pool, jnp.asarray(layer, jnp.int32), *plan)


# --------------------------------------------------------------------- #
# The write
# --------------------------------------------------------------------- #
def _latent_write_kernel(tiles_ref, off_ref, new_ref, pool_in, pool_out, *,
                         C, R):
    """One grid step = one (group, row): see
    ``paged_attention._kv_write_kernel``.  The new row comes laid as
    ``[ckv | ckv | k_rope | k_rope]``; the half its offset names is
    kept."""
    g, r = pl.program_id(0), pl.program_id(1)
    off = off_ref[g, r]
    first = jnp.logical_or(
        r == 0, tiles_ref[g, r] != tiles_ref[g, jnp.maximum(r - 1, 0)])

    @pl.when(first)
    def _load():
        pool_out[...] = pool_in[...]

    @pl.when(off >= 0)
    def _write():
        h, W = pool_out.shape[1:]
        row = jax.lax.broadcasted_iota(jnp.int32, (h, W), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (h, W), 1)
        upper = jnp.logical_or(jnp.logical_and(lane >= C, lane < 2 * C),
                               lane >= 2 * C + R)
        hit = jnp.logical_and(row == jax.lax.rem(off, h),
                              upper == (off >= h))
        # Selected in fp32 (exact for every pool dtype): the v5e has no
        # 16-bit vector select.
        new = new_ref[0, 0].astype(jnp.float32)                # [1, W]
        pool_out[0] = jnp.where(hit, new, pool_out[0].astype(jnp.float32)
                                ).astype(pool_out.dtype)


def _latent_write_local(pool, new, layer, blk, off, *, C):
    """pool [L, G, B, 1, h, 2C+2R]; new [G, Rn, C+R]; blk/off [G, Rn]."""
    _, G, B, _, h, W = pool.shape
    R = W // 2 - C
    live = blk >= 0
    idx = jnp.where(live, jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1),
                    -1)
    src = jax.lax.cummax(idx, axis=1)
    src = jnp.where(src >= 0, src, jnp.argmax(live, axis=1)[:, None])
    eb = jnp.maximum(jnp.take_along_axis(blk, src, axis=1), 0)
    group = jnp.arange(G, dtype=jnp.int32)[:, None]
    tiles = ((layer * G + group) * B + eb).astype(jnp.int32)
    offs = jnp.where(live, off, -1).astype(jnp.int32)
    new = new.astype(pool.dtype)
    rows = jnp.concatenate([new[..., :C], new[..., :C], new[..., C:],
                            new[..., C:]], axis=-1)[:, :, None, :]
    flat = pool.reshape(-1, h, W)
    pool_spec = pl.BlockSpec((1, h, W),
                             lambda g, r, t_p, o_p: (t_p[g, r], 0, 0))
    out = pl.pallas_call(
        functools.partial(_latent_write_kernel, C=C, R=R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(G, blk.shape[1]),
            in_specs=[pl.BlockSpec((1, 1, 1, W),
                                   lambda g, r, t_p, o_p: (g, r, 0, 0)),
                      pool_spec],
            out_specs=pool_spec),
        out_shape=jax.ShapeDtypeStruct(flat.shape, flat.dtype),
        input_output_aliases={3: 0},      # tiles, offs, rows, pool
        name="_latent_write_kernel",
        interpret=_interpret(),
    )(tiles, offs, rows, flat)
    return out.reshape(pool.shape)


def latent_write(pool, new, layer, blk, off, *, kv_lora: int, mesh=None):
    """Write ``new [G, Rn, C + R]`` rows into layer ``layer`` of the
    donated latent pool at (block, offset), in place; rows with blk ==
    DEAD_BLOCK (-1) write nowhere.  Rows of one block must be consecutive
    (``paged_attention.paged_write``'s contract)."""
    if pltpu is None:  # pragma: no cover
        raise RuntimeError("pallas TPU backend unavailable")
    fn = paged._on_mesh(
        functools.partial(_latent_write_local, C=kv_lora), mesh,
        lambda dpn, mpn: (P(None, dpn), P(dpn), P(), P(dpn), P(dpn)),
        lambda dpn, mpn: P(None, dpn))
    return fn(pool, new, jnp.asarray(layer, jnp.int32), blk, off)


__all__ = ["latent_tile", "fold_rows", "logical_rows", "latent_plan",
           "latent_attention", "latent_write", "slots_a_step", "row_tokens"]

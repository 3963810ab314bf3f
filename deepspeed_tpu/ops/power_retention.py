"""Power retention (power 2): gated linear attention whose weights are
the SQUARED scaled scores, kept as a fixed-size recurrent state.

    A_ij = exp(G_i - G_j) (q_i . k_j / sqrt(D))^2   (j <= i, G = cumsum log g)
    y_i  = sum_j A_ij v_j / (sum_j A_ij + eps)

**The feature map.**  ``(a . b)^2 = sum_{i,j} a_i a_j b_i b_j`` is a dot
product of the pairwise products.  An unordered pair ``{i, j}`` is named
here by its WRAPPED DIAGONAL ``o = (i - j) mod D`` in ``0 .. D/2``:

    pair_products(a)[o, i] = a_i * a_{(i - o) mod D}

a lane-wise product of ``a`` with itself rotated by ``o``, so a row of
``D`` lanes per diagonal and nothing to gather.  Diagonal 0 holds the
squares, diagonals ``1 .. D/2 - 1`` each pair once (weight 2 in the
square), and diagonal ``D/2`` holds each of its ``D/2`` pairs TWICE (at
``i`` and at ``i + D/2``), which is exactly its weight 2 — so it takes
weight 1 (``pair_weights``).  With ``phi(a) = sqrt(w_o) pair_products(a)``,
``phi(a) . phi(b) = (a . b)^2`` exactly.  ``D (D + 1) / 2`` = 8,256
distinct pairs at D = 128 are HELD as ``(D/2 + 1) D`` = 8,320 rows (65 x
128: the half row of the last diagonal is held whole, its 64 pairs twice;
0.8% of the state) — every row of the state is a full 128-lane row and
every diagonal a whole tile.

**The state** of one stream, layer and K/V head, fp32:

    S [O*D, D]   row o*D + d, lane i :  sum_j decay * v_j[d] * kphi_j[o, i]
    z [R, D]     row of diagonal o, lane i :  sum_j decay * kphi_j[o, i]

with ``kphi = (w_o / D) pair_products(k)`` (the 1/sqrt(D) of both score
factors folded into the key side) and the query side unweighted.  ``S`` is
held value-dimension-major inside a diagonal so that the decode update is
``S[o] = g S[o] + v (x) kphi[o]`` with ``kphi[o]`` a LANE vector broadcast
over sublanes — no transposed feature is ever needed — and the read
``sum_i S[o][d, i] qphi[o, i]`` a lane-wise multiply-add.  ``z``'s
diagonals are grouped as the kernel tiles them (``T`` diagonals a tile,
each tile's rows padded to a multiple of 8 sublanes: 5 x 16 rows hold the
65 at D = 128; ``norm_logical`` / ``norm_held`` convert).

Three forms, equal in real arithmetic: ``retention_quadratic`` (the
definition), ``recurrent_update`` (one token a stream: decode; plain
``jax.numpy``, the off-TPU path and the kernel's reference),
``chunked_retention`` (a chunk of rows from a carried state: prefill).
``state_update`` is the decode KERNEL over the paged state pool: grid
(stream, K/V head, tile of diagonals), the pools aliased in and out, one
read and one write of every LIVE page's layer, the group's query heads
read against the new tile in the same pass; dead slots cost no DMA and no
work (their grid steps revisit the last live tile).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from .flash_attention import _interpret
from . import paged_attention as paged

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

_HI = lax.Precision.HIGHEST
_HIGH = lax.Precision.HIGH      # fp32 operands as three bf16 passes
_TILE_BYTES = 1 << 20           # a tile of the state a grid step holds
_VMEM_LIMIT = 48 * 2 ** 20


# --------------------------------------------------------------------- #
# Geometry and the feature map
# --------------------------------------------------------------------- #
def diagonals(D: int) -> int:
    """Wrapped diagonals held: ``D/2 + 1``."""
    return D // 2 + 1


def feature_width(D: int) -> int:
    """Distinct pairs ``D (D + 1) / 2``: what a roofline counts."""
    return D * (D + 1) // 2


def tile_diagonals(D: int) -> int:
    """Diagonals a kernel tile holds: the largest divisor of ``D/2 + 1``
    whose tile ``[T*D, D]`` fp32 stays under ``_TILE_BYTES`` (13 of 65 at
    D = 128: 852 KB)."""
    O = diagonals(D)
    fit = [t for t in range(1, O + 1)
           if O % t == 0 and t * D * D * 4 <= _TILE_BYTES]
    return max(fit)


def _norm_rows(D: int) -> Tuple[int, int, int]:
    """(tiles, diagonals a tile, rows a tile is padded to)."""
    T = tile_diagonals(D)
    return diagonals(D) // T, T, -(-T // 8) * 8


def state_tiles(num_kv_heads: int, D: int):
    """((name, one page-layer's tile as held [heads, rows, lanes]), ...)
    for ``ServedModel.cache_pools``."""
    nT, _, Tp = _norm_rows(D)
    return (("state", (num_kv_heads, diagonals(D) * D, D)),
            ("norm", (num_kv_heads, nT * Tp, D)))


def pair_weights(D: int) -> np.ndarray:
    """fp32 ``[O]``: how often the square counts a diagonal's products
    (1 for the squares, 2 between, 1 for the twice-held last)."""
    w = np.full(diagonals(D), 2.0, np.float32)
    w[0] = w[-1] = 1.0
    return w


def _rotated(a: jax.Array, o: int) -> jax.Array:
    """``jnp.roll(a, o, axis=-1)``.  The rotation by exactly half the
    lanes is written as a reversal of the two halves: XLA's TPU compiler
    aborts (``IsFusibleUnalignedDUS``) on that one concatenation when it
    stands alone over an array whose second-minor dimension is off the
    8-sublane tiling (a group's 5 query heads; chip compiler, PR 34)."""
    D = a.shape[-1]
    if D % 2 or o != D // 2:
        return jnp.roll(a, o, axis=-1)
    return a.reshape(a.shape[:-1] + (2, o))[..., ::-1, :].reshape(a.shape)


def pair_products(a: jax.Array) -> jax.Array:
    """``[..., D] -> [..., O, D]`` fp32: ``a_i a_{(i - o) mod D}``."""
    a = a.astype(jnp.float32)
    D = a.shape[-1]
    return a[..., None, :] * jnp.stack(
        [_rotated(a, o) for o in range(diagonals(D))], axis=-2)


def phi(a: jax.Array) -> jax.Array:
    """The symmetric feature map ``[..., D] -> [..., O, D]``:
    ``(phi(a) * phi(b)).sum() == (a . b)^2``."""
    w = jnp.sqrt(jnp.asarray(pair_weights(a.shape[-1])))
    return pair_products(a) * w[:, None]


def key_features(k: jax.Array) -> jax.Array:
    """What the state accumulates of a key: ``(w_o / D) pair_products``."""
    D = k.shape[-1]
    return pair_products(k) * jnp.asarray(pair_weights(D) / D)[:, None]


def norm_logical(z: jax.Array) -> jax.Array:
    """``[..., nT*Tp, D]`` as held -> ``[..., O, D]``."""
    D = z.shape[-1]
    nT, T, Tp = _norm_rows(D)
    z = z.reshape(z.shape[:-2] + (nT, Tp, D))[..., :T, :]
    return z.reshape(z.shape[:-3] + (nT * T, D))


def norm_held(z: jax.Array) -> jax.Array:
    """Inverse of ``norm_logical`` (padding rows zero)."""
    D = z.shape[-1]
    nT, T, Tp = _norm_rows(D)
    z = z.reshape(z.shape[:-2] + (nT, T, D))
    z = jnp.pad(z, [(0, 0)] * (z.ndim - 2) + [(0, Tp - T), (0, 0)])
    return z.reshape(z.shape[:-3] + (nT * Tp, D))


def pair_tensor(S: jax.Array, z: jax.Array) -> jax.Array:
    """What a state stands for, free of how it is held: ``[..., O*D, D]``
    and ``[..., R, D]`` (as held) -> ``M [..., D + 1, D, D]`` fp32 whose
    symmetric faces are ``M[d] = sum_t decay_t v_t[d] k_t k_t^T / D`` and,
    last, the normaliser's ``sum_t decay_t k_t k_t^T / D``, so that ``q^T
    M[d] q`` is a query's numerator and denominator.  Tests and the
    benchmark hold a page to a reference through it."""
    D = S.shape[-1]
    held = jnp.concatenate([_state_view(S).swapaxes(-2, -3),
                            norm_logical(z)[..., None, :, :]], axis=-3)
    held = held / jnp.asarray(pair_weights(D))[:, None]  # [.., D+1, O, D]
    i = np.broadcast_to(np.arange(D), (diagonals(D), D))
    j = (i - np.arange(diagonals(D))[:, None]) % D
    flat = held.reshape((-1,) + held.shape[-2:])
    M = jnp.zeros((flat.shape[0], D, D), jnp.float32)
    M = M.at[:, i, j].set(flat).at[:, j, i].set(flat)
    return M.reshape(S.shape[:-2] + (D + 1, D, D))


def _state_view(S: jax.Array) -> jax.Array:
    """``[..., O*D, D]`` -> ``[..., O, D(value), D(lane)]``."""
    D = S.shape[-1]
    return S.reshape(S.shape[:-2] + (S.shape[-2] // D, D, D))


def _grouped(q: jax.Array, num_kv_heads: int) -> jax.Array:
    """``[..., nH, D]`` -> ``[..., nKV, nH/nKV, D]``."""
    nH, D = q.shape[-2:]
    return q.reshape(q.shape[:-2] + (num_kv_heads, nH // num_kv_heads, D))


# --------------------------------------------------------------------- #
# The three forms in plain jax.numpy
# --------------------------------------------------------------------- #
def retention_quadratic(q, k, v, log_g, eps: float) -> jax.Array:
    """The definition, from an empty state: q ``[N, nH, D]``, k / v ``[N,
    nKV, D]``, log_g ``[N, nKV]`` -> y ``[N, nH, D]`` fp32."""
    N, nKV, D = k.shape
    qg = _grouped(q.astype(jnp.float32), nKV)
    s = jnp.einsum("ichd,jcd->chij", qg, k.astype(jnp.float32),
                   precision=_HI) / math.sqrt(D)
    G = jnp.cumsum(log_g.astype(jnp.float32), axis=0).T          # [nKV, N]
    causal = jnp.tril(jnp.ones((N, N), bool))
    decay = jnp.exp(jnp.where(causal, G[:, :, None] - G[:, None, :],
                              -jnp.inf))
    A = decay[:, None] * s * s
    num = jnp.einsum("chij,jcd->ichd", A, v.astype(jnp.float32),
                     precision=_HI)
    den = A.sum(-1).transpose(2, 0, 1)
    return (num / (den[..., None] + eps)).reshape(q.shape)


def recurrent_update(S, z, q, k, v, log_g, eps: float):
    """One token a stream.  S ``[N, nKV, O*D, D]``, z ``[N, nKV, R, D]``
    (as held); q ``[N, nH, D]``, k / v ``[N, nKV, D]``, log_g ``[N, nKV]``.
    Returns (y ``[N, nH, D]`` fp32, S', z')."""
    nKV = k.shape[-2]
    g = jnp.exp(log_g.astype(jnp.float32))
    kphi = key_features(k)                                  # [N, c, O, D]
    Sv = g[..., None, None, None] * _state_view(S) \
        + v.astype(jnp.float32)[..., None, :, None] * kphi[..., :, None, :]
    zl = g[..., None, None] * norm_logical(z) + kphi
    qphi = pair_products(_grouped(q, nKV))                  # [N, c, h, O, D]
    num = jnp.einsum("nchoi,ncodi->nchd", qphi, Sv, precision=_HI)
    den = jnp.einsum("nchoi,ncoi->nch", qphi, zl, precision=_HI)
    y = (num / (den[..., None] + eps)).reshape(q.shape)
    return y, Sv.reshape(S.shape), norm_held(zl)


def chunked_retention(S, z, q, k, v, log_g, live, eps: float):
    """A chunk of C rows of ONE stream from a carried state.  S ``[nKV,
    O*D, D]``, z ``[nKV, R, D]`` (as held); q ``[C, nH, D]``, k / v ``[C,
    nKV, D]``, log_g ``[C, nKV]``, live ``[C]`` bool: rows that are not
    live neither decay the state nor add to it (a last chunk's padding).
    Returns (y ``[C, nH, D]`` fp32, S', z').  One K/V head at a time
    (``lax.map``), so the query features of one group (``[C, nH/nKV, O, D]``
    fp32) are the largest temporary.  Products against the fp32 state run
    at ``_HIGH`` (three bf16 passes: 2^-16 relative), accumulation fp32."""
    C, nKV, D = k.shape
    live_f = live.astype(jnp.float32)
    log_g = log_g.astype(jnp.float32) * live_f[:, None]
    G = jnp.cumsum(log_g, axis=0)                                 # [C, nKV]
    causal = jnp.tril(jnp.ones((C, C), bool))
    scale = 1.0 / math.sqrt(D)

    def head(args):
        S_c, z_c, q_c, k_c, v_c, G_c = args
        # q_c [C, h, D]; k_c, v_c [C, D]; G_c [C]
        k_c = k_c.astype(jnp.float32) * live_f[:, None]
        v_c = v_c.astype(jnp.float32)
        qf = q_c.astype(jnp.float32)
        s = jnp.einsum("ihd,jd->hij", qf, k_c, precision=_HI) * scale
        decay = jnp.exp(jnp.where(causal, G_c[:, None] - G_c[None, :],
                                  -jnp.inf))
        A = decay[None] * s * s                                  # [h, C, C]
        qphi = pair_products(qf) * jnp.exp(G_c)[:, None, None, None]
        Sv, zl = _state_view(S_c), norm_logical(z_c)
        num = jnp.einsum("hij,jd->ihd", A, v_c, precision=_HIGH) \
            + jnp.einsum("ihol,odl->ihd", qphi, Sv, precision=_HIGH)
        den = A.sum(-1).T + jnp.einsum("ihol,ol->ih", qphi, zl,
                                       precision=_HIGH)
        # What the chunk leaves: every row's features decayed to its end.
        kphi = key_features(k_c) * jnp.exp(G_c[-1] - G_c)[:, None, None]
        S_new = jnp.exp(G_c[-1]) * Sv + jnp.einsum(
            "jd,jol->odl", v_c, kphi, precision=_HIGH)
        z_new = jnp.exp(G_c[-1]) * zl + kphi.sum(0)
        return (num / (den[..., None] + eps), S_new.reshape(S_c.shape),
                norm_held(z_new))

    y, S_new, z_new = lax.map(head, (
        S, z, _grouped(q, nKV).transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
        v.transpose(1, 0, 2), G.T))
    return y.transpose(1, 0, 2, 3).reshape(q.shape), S_new, z_new


# --------------------------------------------------------------------- #
# The decode kernel over the paged state pool
# --------------------------------------------------------------------- #
def _state_update_kernel(tile_ref, row_ref, n_ref, qq_ref, kk_ref, s_in,
                         z_in, s_out, z_out, y_out, d_out, kphi_scr,
                         qphi_scr, vcol_scr, *, D, T, Gq, rows_at_once):
    """One grid step = (stream s, K/V head c, tile t of T diagonals).

    qq_ref [2*Hp, D]: the group's query heads (rows 0..Gq-1) and, from row
    Hp, the same rotated by t*T lanes; kk_ref [8, D]: row 0 the key, 1 the
    key rotated by t*T, 2 the value, 3 the gate g on every lane.  s_in /
    s_out [T*D, D] and z_in / z_out [Tp, D]: this tile of the stream's
    page, layer and head (the same HBM: aliased).  y_out [D, Yw] (value
    dimension on sublanes, head h in lane h) and d_out [Hp, D] (lane-wise
    partial sums of the normaliser) are revisited over t and accumulate.
    """
    del tile_ref, row_ref
    s, t = pl.program_id(0), pl.program_id(2)
    n = n_ref[0]
    Hp = qq_ref.shape[0] // 2

    @pl.when(n == 0)
    def _nothing_live():
        # Every step maps to one tile (see the index maps) that the
        # pipeline writes back at the end: hand it back as it came.
        s_out[...] = s_in[...]
        z_out[...] = z_in[...]
        y_out[...] = jnp.zeros_like(y_out)
        d_out[...] = jnp.zeros_like(d_out)

    @pl.when(s < n)
    def _update():
        kk = kk_ref[...]
        g, k0, v = kk[3:4, :], kk[0:1, :], kk[2:3, :]             # [1, D]
        q0, qb = qq_ref[0:Hp, :], qq_ref[Hp:2 * Hp, :]

        @pl.when(t == 0)
        def _per_head():
            # The value as a column on every lane: [D(d), D] = v_d.
            vcol_scr[...] = jnp.broadcast_to(v, (D, D)).T
            y_out[...] = jnp.zeros_like(y_out)
            d_out[...] = jnp.zeros_like(d_out)

        dacc = jnp.zeros((Hp, D), jnp.float32)
        for oo in range(T):
            o = t * T + oo
            w = jnp.where(jnp.logical_or(o == 0, o == D // 2), 1.0, 2.0) / D
            roll = (lambda x: x) if oo == 0 else \
                functools.partial(pltpu.roll, shift=oo, axis=1)
            kphi = k0 * roll(kk)[1:2, :] * w                      # [1, D]
            qphi = q0 * roll(qb)                                  # [Hp, D]
            z_new = g * z_in[oo:oo + 1, :] + kphi
            z_out[oo:oo + 1, :] = z_new
            dacc = dacc + qphi * z_new
            kphi_scr[oo:oo + 1, :] = kphi
            qphi_scr[oo * Hp:(oo + 1) * Hp, :] = qphi
        if z_in.shape[0] > T:
            z_out[T:, :] = z_in[T:, :]
        d_out[...] += dacc

        R = rows_at_once
        lane = lax.broadcasted_iota(jnp.int32, (R, y_out.shape[1]), 1)
        for r0 in range(0, D, R):
            acc = [jnp.zeros((R, D), jnp.float32) for _ in range(Gq)]
            vcol = vcol_scr[r0:r0 + R, :]
            for oo in range(T):
                rows = slice(oo * D + r0, oo * D + r0 + R)
                new = g * s_in[rows, :] + vcol * kphi_scr[oo:oo + 1, :]
                s_out[rows, :] = new
                for h in range(Gq):
                    acc[h] = acc[h] + new * \
                        qphi_scr[oo * Hp + h:oo * Hp + h + 1, :]
            cols = jnp.zeros(lane.shape, jnp.float32)
            for h in range(Gq):
                cols = jnp.where(lane == h, jnp.sum(acc[h], axis=1,
                                                    keepdims=True), cols)
            y_out[r0:r0 + R, :] += cols


def _state_update_local(state, norm, layer, pages, q, k, v, log_g, *,
                        eps: float):
    """state [L, G, B, nKV, O*D, D], norm [L, G, B, nKV, R, D] (whole
    stacked pools); pages [G, Sg] (-1: no live stream in the slot); q [G,
    Sg, nH, D]; k / v [G, Sg, nKV, D]; log_g [G, Sg, nKV]."""
    L, G, B, nKV, OD, D = state.shape
    nT, T, Tp = _norm_rows(D)
    Sg = pages.shape[1]
    N = G * Sg
    Gq = q.shape[2] // nKV
    Hp = -(-Gq // 8) * 8
    Yw = max(D, Hp)             # lanes of the numerators' block

    # Live streams first, in slot order; the grid's dead steps (s >= n)
    # all map to the LAST live step's blocks: no DMA, no work.
    page = pages.reshape(N)
    live = page >= 0
    n = live.sum().astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    group = order // Sg
    tiles = ((layer * G + group) * B + jnp.maximum(page[order], 0)) * nKV

    def at(s, c, t, n_p):
        """(sorted stream, head, tile) a grid step works on."""
        dead = s >= n_p[0]
        s_ = jnp.minimum(s, jnp.maximum(n_p[0] - 1, 0))
        return (s_, jnp.where(dead, nKV - 1, c), jnp.where(dead, nT - 1, t))

    def pool_map(s, c, t, t_p, r_p, n_p):
        s_, c_, t_ = at(s, c, t, n_p)
        return (t_p[s_] + c_, t_, 0)

    def row_map(s, c, t, t_p, r_p, n_p):
        s_, c_, t_ = at(s, c, t, n_p)
        return (r_p[s_], c_, t_, 0, 0)

    def out_map(s, c, t, t_p, r_p, n_p):
        s_, c_, _ = at(s, c, t, n_p)
        return (r_p[s_], c_, 0, 0)

    # The small operands: a stream's rows by (head, tile), rotations of
    # t*T lanes made here so that every rotation in the kernel is static.
    f32 = jnp.float32
    qg = _grouped(q.reshape(N, -1, D).astype(f32), nKV)        # [N,c,Gq,D]
    kf, vf = k.reshape(N, nKV, D).astype(f32), v.reshape(N, nKV, D).astype(f32)
    gf = jnp.broadcast_to(jnp.exp(log_g.reshape(N, nKV, 1).astype(f32)),
                          (N, nKV, D))

    def by_tile(x):            # [..., D] -> [..., nT, D] rotated by t*T
        return jnp.stack([jnp.roll(x, t * T, axis=-1) for t in range(nT)],
                         axis=-2)

    def rows_of(parts, n):
        """``[N, c, nT, n, D]`` whose row r is ``parts[r]`` (``[N, c, nT,
        D]``), zeros elsewhere — selected by row index, so that no
        one-sublane piece is ever concatenated or sliced in."""
        row = lax.broadcasted_iota(jnp.int32, (1, 1, 1, n, 1), 3)
        out = jnp.zeros((N, nKV, nT, n, D), f32)
        for r, part in parts.items():
            out = jnp.where(row == r, part[:, :, :, None, :], out)
        return out

    def whole(x):              # [..., D] -> [..., nT, D], the same a tile
        return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (nT, D))
    qq = rows_of({**{h: whole(qg[:, :, h]) for h in range(Gq)},
                  **{Hp + h: by_tile(qg[:, :, h]) for h in range(Gq)}},
                 2 * Hp)
    kk = rows_of({0: whole(kf), 1: by_tile(kf), 2: whole(vf), 3: whole(gf)},
                 8)

    s_flat = state.reshape(L * G * B * nKV, OD, D)
    z_flat = norm.reshape(L * G * B * nKV, nT * Tp, D)
    s_spec = pl.BlockSpec((None, T * D, D), pool_map)
    z_spec = pl.BlockSpec((None, Tp, D), pool_map)
    kernel = functools.partial(
        _state_update_kernel, D=D, T=T, Gq=Gq,
        rows_at_once=min(D, 32))
    s_new, z_new, y, den = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N, nKV, nT),
            in_specs=[pl.BlockSpec((None, None, None, 2 * Hp, D), row_map),
                      pl.BlockSpec((None, None, None, 8, D), row_map),
                      s_spec, z_spec],
            out_specs=[s_spec, z_spec,
                       pl.BlockSpec((None, None, D, Yw), out_map),
                       pl.BlockSpec((None, None, Hp, D), out_map)],
            scratch_shapes=[pltpu.VMEM((Tp, D), f32),
                            pltpu.VMEM((T * Hp, D), f32),
                            pltpu.VMEM((D, D), f32)]),
        out_shape=[jax.ShapeDtypeStruct(s_flat.shape, f32),
                   jax.ShapeDtypeStruct(z_flat.shape, f32),
                   jax.ShapeDtypeStruct((N, nKV, D, Yw), f32),
                   jax.ShapeDtypeStruct((N, nKV, Hp, D), f32)],
        # tiles, rows, n, qq, kk, state, norm
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        name="_state_update_kernel",
        interpret=_interpret(),
    )(tiles.astype(jnp.int32), order, n.reshape(1), qq, kk, s_flat, z_flat)
    # Dead slots' rows were never written: zero them.
    num = y.transpose(0, 1, 3, 2)[:, :, :Gq]                   # [N,c,Gq,D]
    out = num / (den.sum(-1)[:, :, :Gq, None] + eps)
    out = jnp.where(live[:, None, None, None], out, 0.0)
    return (out.reshape(G, Sg, nKV * Gq, D), s_new.reshape(state.shape),
            z_new.reshape(norm.shape))


def state_update(state, norm, layer, pages, q, k, v, log_g, *, eps: float,
                 mesh=None):
    """The decode step of every live stream's page, in place (module
    docstring).  Returns (y [G, Sg, nH, D] fp32, state', norm')."""
    if pltpu is None:  # pragma: no cover
        raise RuntimeError("pallas TPU backend unavailable")
    fn = paged._on_mesh(
        functools.partial(_state_update_local, eps=eps), mesh,
        lambda dpn, mpn: (P(None, dpn), P(None, dpn), P(), P(dpn), P(dpn),
                          P(dpn), P(dpn), P(dpn)),
        lambda dpn, mpn: (P(dpn), P(None, dpn), P(None, dpn)))
    return fn(state, norm, jnp.asarray(layer, jnp.int32), pages, q, k, v,
              log_g)


def state_update_steps(live_streams: int, num_slots: int, num_kv_heads: int,
                       D: int) -> Tuple[int, int]:
    """(grid steps a layer's kernel sequences, those that do work)."""
    per = num_kv_heads * _norm_rows(D)[0]
    return num_slots * per, int(live_streams) * per


__all__ = ["diagonals", "feature_width", "tile_diagonals", "state_tiles",
           "pair_weights", "pair_products", "phi", "key_features",
           "norm_logical", "norm_held", "pair_tensor", "retention_quadratic",
           "recurrent_update", "chunked_retention", "state_update",
           "state_update_steps"]

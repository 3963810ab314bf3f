"""Kimi Delta Attention: a gated DELTA rule with a decay a CHANNEL, kept as a
fixed-size fp32 state a stream:

    S' = Diag(alpha_t) S_{t-1}                  (alpha_t = exp(g_t) in (0, 1)^dk)
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T    (= (I - beta k k^T) S' + beta k v^T)
    o_t = S_t^T q_t

for every head: ``q_t, k_t, g_t [dk]``, ``v_t [dv]``, ``beta_t`` a scalar in
(0, 1) — or in (0, 2) for a model that allows NEGATIVE EIGENVALUES
(``models/solar_open2.py``: along a unit key the transition's eigenvalue is
``1 - beta``, in (-1, 1), so the recurrence stays a contraction) — ``S [dk,
dv]``.  Unlike ``ops/ssm_scan.py`` and ``ops/power_retention.py``
(``S <- a S + outer product``) the new state depends on what the old one
HOLDS along the key: the write reads ``k^T S'`` first, then corrects it.  The
projections, the short convolutions, the gates and the output norm are the
model's (``models/kimi_linear.py``).

**The state** of one stream and layer is ``S [nh, dk, dv]`` float32, keys on
sublanes and values on lanes (``state_tile``): the decay scales ROWS (a
column operand), ``k^T S'`` and ``S^T q`` are sums over sublanes whose result
is a lane vector, and the correction is a column times a lane vector.

Three forms, equal in real arithmetic: ``recurrent_update`` (one token a
stream: decode; plain ``jax.numpy``, the off-TPU path and the kernel's
reference), ``chunked_delta_rule`` (a run of rows of one stream from a
carried state, ``chunk`` rows a step: prefill) and ``state_update``, the
decode KERNEL over the paged state pool.

**The chunked form** (the WY / UT form).  Inside a chunk from ``S_0``, with
``G_r = sum_{j<=r} g_j`` a channel and ``u_r`` the corrected write of row r,

    (I + A) U = Diag(beta) (V - (K . e^G) S_0),
    A[r, i]   = beta_r sum_c k_rc k_ic e^(G_rc - G_ic)          (i < r)
    o_r       = S_0^T (q_r . e^G_r) + sum_{i<=r} u_i P[r, i],
    P[r, i]   = sum_c q_rc k_ic e^(G_rc - G_ic)
    S_C       = Diag(e^G_C) S_0 + sum_i (k_i . e^(G_C - G_i)) u_i^T.

``A`` and ``P`` depend on no state, so they, the inverse ``T = (I + A)^-1``
and ``W = T beta (K . e^G)``, ``U_v = T beta V`` are built for every chunk at
once; the scan over chunks carries ``S`` through three products a chunk
(``U = U_v - W S``).  **No exponent is ever bounded**: every one the program
forms is a difference ``G_r - G_i`` with ``r >= i`` (never positive, so it
cannot overflow; it may underflow to 0 exactly where the true value is under
fp32's least).  The decay differs by channel, so ``e^(G_r - G_i)`` does not
factor into one matrix product without ``e^(-G_i)``; instead pairs inside a
diagonal sub-block of ``_SUB`` rows are formed exactly (``[sub, sub, dk]``),
and a sub-block's rows against the rows before it go through the cumulative
decay at the sub-block's START: ``e^(G_r - G_ref) . e^(G_ref - G_i)``, both
non-positive.  ``T`` is forward substitution inside the diagonal sub-blocks
(``_SUB`` steps for all of them together) and the block inverse ``T21 = -T22
A21 T11`` from there up, in float32 at ``Precision.HIGHEST``.  A row that is
not live has ``g = 0`` and ``beta = 0``: it neither decays the state nor
writes to it.  The scan's carried states ARE the stream's state at every
chunk boundary, so one of them can be handed back as a snapshot (``keep``).
With ``beta`` up to 2 the entries of ``A`` reach 2 and ``T`` grows with them:
against ``recurrent_update`` the outputs and states read 3-4 times what
``beta`` < 1 reads in float32 (1e-6 / 4e-6 where it reads 4e-7 / 1e-6; 2e-5
of a largest entry of 14 at ``beta`` on (1.9, 2) under the slowest decay),
the same relative to the state, which itself grows under a slow decay
(``tests/test_kda.py``, PR 64); nothing in the three forms assumes ``beta`` <
1.

Products against the fp32 state run on the vector unit in the kernel and at
``Precision.HIGH`` (three bf16 passes) on the matrix unit in the chunked form;
the pairs inside a diagonal sub-block and the inverse at ``HIGHEST``;
accumulation is fp32 everywhere.

**The decode kernel**: grid (stream, tile of heads), the pool aliased in and
out, one read and one write of every LIVE page's layer with the dependent
pass in between; dead slots cost no DMA and no work (their grid steps revisit
the last live tile).  A head needs three COLUMNS (``alpha``, ``k``, ``q``:
value c on every lane of sublane c): the tile's ``3 Ht`` lane vectors are the
rows of one square that is transposed ONCE a grid step, and a head's column
is one lane of it broadcast along the lanes.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from .flash_attention import _interpret
from . import paged_attention as paged

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

_HIGH = lax.Precision.HIGH          # fp32 operands as three bf16 passes
_HIGHEST = lax.Precision.HIGHEST
_SUB = 16                           # rows of a diagonal sub-block
_TILE_BYTES = 1 << 20               # a tile of the state a grid step holds
_ROWS = 32                          # state rows the kernel updates at once
_VMEM_LIMIT = 48 * 2 ** 20


def state_tile(num_heads: int, d_k: int, d_v: int) -> Tuple[int, int, int]:
    """One page-layer's tile as held ``[heads, rows, lanes]``: a head's
    ``[dk, dv]``, lane-dense at dv = 128."""
    return (num_heads, d_k, d_v)


def tile_heads(num_heads: int, d_k: int, d_v: int) -> int:
    """Heads a grid step of the kernel holds: the most whose tile stays
    under ``_TILE_BYTES`` and whose ``3 Ht`` column vectors fit one
    square of ``d_k`` rows."""
    fit = [t for t in range(1, num_heads + 1)
           if num_heads % t == 0 and t * d_k * d_v * 4 <= _TILE_BYTES
           and 3 * t <= d_k]
    return max(fit or [1])


# --------------------------------------------------------------------- #
# The plain forms
# --------------------------------------------------------------------- #
def recurrent_update(S, q, k, v, g, beta):
    """One token a stream.  S ``[M, nh, dk, dv]`` fp32; q / k / g ``[M, nh,
    dk]`` (g the LOG decay, <= 0); v ``[M, nh, dv]``; beta ``[M, nh]``.
    Returns (o ``[M, nh, dv]`` fp32, S')."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    S = jnp.exp(g)[..., None] * S
    r = jnp.sum(S * k[..., None], axis=-2)                      # k^T S'
    u = beta[..., None] * (v - r)
    S = S + k[..., None] * u[..., None, :]
    return jnp.sum(S * q[..., None], axis=-2), S


def _pairs(x, k, G):
    """``M[j, r, i] = sum_c x[j]_rc k_ic e^(G_rc - G_ic)`` for ``r >= i``, 0
    above the diagonal.  x ``[J, ..., C, dk]`` (J left operands against one
    ``k``), k / G ``[..., C, dk]`` -> ``[J, ..., C, C]``.  Every exponent
    formed is a difference of a later row's cumulative decay and an
    earlier one's (module docstring)."""
    f32 = jnp.float32
    C, dk = k.shape[-2:]
    sub = _SUB if C % _SUB == 0 else C
    ns = C // sub
    lead = k.shape[:-2]
    xs = x.reshape(x.shape[:1] + lead + (ns, sub, dk))
    ks = k.reshape(lead + (ns, sub, dk))
    Gs = G.reshape(lead + (ns, sub, dk))
    # inside a diagonal sub-block: exact pairwise products
    lower = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    e = jnp.exp(jnp.where(lower, Gs[..., :, None, :] - Gs[..., None, :, :],
                          -jnp.inf))                  # [..., ns, sub, sub, dk]
    diag = jnp.einsum("j...rc,...ic,...ric->j...ri", xs, ks, e,
                      precision=_HIGHEST)
    out = jnp.zeros(x.shape[:1] + lead + (ns, sub, ns, sub), f32)
    idx = jnp.arange(ns)
    out = out.at[..., idx, :, idx, :].set(
        jnp.moveaxis(diag, -3, 0))
    if ns > 1:
        # a sub-block's rows against every row before it, through the
        # cumulative decay where the sub-block starts
        ref = jnp.concatenate([jnp.zeros_like(Gs[..., :1, 0, :]),
                               Gs[..., :-1, sub - 1, :]], axis=-2)  # [.., ns, dk]
        x_dec = xs * jnp.exp(Gs - ref[..., None, :])
        before = (jnp.arange(C)[None, :] < (idx * sub)[:, None])    # [ns, C]
        k_dec = k[..., None, :, :] * jnp.exp(jnp.where(
            before[..., None], ref[..., None, :] - G[..., None, :, :],
            -jnp.inf))                                     # [..., ns, C, dk]
        off = jnp.einsum("j...arc,...aic->j...ari", x_dec, k_dec,
                         precision=_HIGH, preferred_element_type=f32)
        out = out + off.reshape(out.shape)
    return out.reshape(x.shape[:1] + lead + (C, C))


def unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower-triangular ``A [..., C, C]``:
    forward substitution inside diagonal sub-blocks of ``_SUB`` rows (all of
    them at once, ``_SUB - 1`` steps), then ``[[T11, 0], [-T22 A21 T11,
    T22]]`` block by block, doubling."""
    f32 = jnp.float32
    C = A.shape[-1]
    b = _SUB if C % _SUB == 0 and (C // _SUB) & (C // _SUB - 1) == 0 else C
    lead = A.shape[:-2]

    def diagonal_blocks(M, b):
        n = C // b
        idx = jnp.arange(n)
        return jnp.moveaxis(
            M.reshape(lead + (n, b, n, b))[..., idx, :, idx, :], 0, -3)
    D = diagonal_blocks(A, b)                              # [..., n, b, b]
    T = jnp.broadcast_to(jnp.eye(b, dtype=f32), D.shape)
    for r in range(1, b):
        # row r of the inverse: e_r - sum_{i<r} A[r, i] T[i]
        row = jnp.einsum("...i,...ij->...j", D[..., r, :r], T[..., :r, :],
                         precision=_HIGHEST)
        T = T.at[..., r, :].add(-row)
    while b < C:
        A2 = diagonal_blocks(A, 2 * b)                 # [..., n/2, 2b, 2b]
        T11, T22 = T[..., 0::2, :, :], T[..., 1::2, :, :]
        T21 = -jnp.einsum("...ij,...jk,...kl->...il", T22,
                          A2[..., b:, :b], T11, precision=_HIGHEST)
        top = jnp.concatenate([T11, jnp.zeros_like(T11)], axis=-1)
        T = jnp.concatenate(
            [top, jnp.concatenate([T21, T22], axis=-1)], axis=-2)
        b *= 2
    return T.reshape(lead + (C, C))


def chunked_delta_rule(S0, q, k, v, g, beta, *, chunk: int,
                       keep: Optional[jax.Array] = None):
    """A run of T rows of ONE stream from a carried state, ``chunk`` rows a
    step.  S0 ``[nh, dk, dv]`` fp32; q / k / g ``[T, nh, dk]``; v ``[T, nh,
    dv]``; beta ``[T, nh]`` (a row that is not live has g = 0 and beta = 0).
    ``keep``: a traced chunk index — the state as it stands after that chunk
    is returned too (the stream's state at row ``(keep + 1) * chunk - 1``).
    Returns (o ``[T, nh, dv]`` fp32, S after the last row, the kept state
    or None)."""
    T_, nh, dk = q.shape
    dv = v.shape[-1]
    if T_ % chunk:
        raise ValueError(f"chunked_delta_rule: {T_} rows in chunks of "
                         f"{chunk}")
    nc = T_ // chunk
    f32 = jnp.float32
    exact = dict(preferred_element_type=f32, precision=_HIGH)

    def split(a):                               # [T, nh, d] -> [nc, nh, C, d]
        a = a.astype(f32)
        return a.reshape((nc, chunk, nh) + a.shape[2:]).swapaxes(1, 2)
    q, k, v, g = split(q), split(k), split(v), split(g)
    beta = split(beta[..., None])                          # [nc, nh, C, 1]
    G = jnp.cumsum(g, axis=2)
    # -- what no state enters: every chunk at once
    M = _pairs(jnp.stack([k, q]), k, G)
    A = beta * jnp.tril(M[0], -1)
    Pm = M[1]
    Tm = unit_lower_inverse(A)
    eG = jnp.exp(G)
    W = jnp.einsum("...ri,...ic->...rc", Tm, beta * k * eG, **exact)
    Uv = jnp.einsum("...ri,...iv->...rv", Tm, beta * v, **exact)
    q_dec = q * eG
    G_end = G[:, :, -1:, :]
    k_end = k * jnp.exp(G_end - G)
    a_end = jnp.exp(G_end[:, :, 0, :])                         # [nc, nh, dk]

    def step(carry, rows):
        S, kept = carry
        i, W_c, Uv_c, P_c, q_c, k_c, a_c = rows
        U = Uv_c - jnp.einsum("hrc,hcv->hrv", W_c, S, **exact)
        o = jnp.einsum("hrc,hcv->hrv", q_c, S, **exact) \
            + jnp.einsum("hri,hiv->hrv", P_c, U, **exact)
        S = a_c[..., None] * S \
            + jnp.einsum("hic,hiv->hcv", k_c, U, **exact)
        if kept is not None:
            kept = jnp.where(i == keep, S, kept)
        return (S, kept), o

    (S, kept), o = lax.scan(
        step, (S0.astype(f32), None if keep is None else S0.astype(f32)),
        (jnp.arange(nc, dtype=jnp.int32), W, Uv, Pm, q_dec, k_end, a_end))
    return o.swapaxes(1, 2).reshape(T_, nh, dv), S, kept


# --------------------------------------------------------------------- #
# The decode kernel over the paged state pool
# --------------------------------------------------------------------- #
def _state_update_kernel(tile_ref, row_ref, n_ref, col_ref, lane_ref, s_in,
                         s_out, o_out, sq_scr, *, dk, Ht, Hp, R):
    """One grid step = (stream s, tile t of Ht heads).

    col_ref [3*Ht, dk]: rows 3 hh + (0, 1, 2) the head's ``alpha``, ``k``,
    ``q`` along the lanes; lane_ref [2*Hp, dv]: row hh the head's ``beta v``
    and row Hp + hh its ``beta`` on every lane.  s_in / s_out [Ht*dk, dv]:
    this tile of the stream's page and layer (the same HBM: aliased).
    o_out [Hp, dv]: row hh the head's ``S_t^T q``.
    """
    del tile_ref, row_ref
    s = pl.program_id(0)
    n = n_ref[0]

    @pl.when(n == 0)
    def _nothing_live():
        # Every step maps to one tile (see the index maps) that the
        # pipeline writes back at the end: hand it back as it came.
        s_out[...] = s_in[...]
        o_out[...] = jnp.zeros_like(o_out)

    @pl.when(s < n)
    def _update():
        dv = s_in.shape[1]
        # The tile's lane vectors as columns: ONE square transpose; column
        # j of it is row j of col_ref down the sublanes.
        sq_scr[...] = jnp.zeros_like(sq_scr)
        sq_scr[0:3 * Ht, :] = col_ref[...]
        cols = sq_scr[...].T                                     # [dk, dk]
        if Hp > Ht:
            o_out[Ht:, :] = jnp.zeros((Hp - Ht, dv), jnp.float32)
        for hh in range(Ht):
            def column(j):
                return jnp.broadcast_to(cols[:, j:j + 1], (dk, dv))
            a_col, k_col, q_col = (column(3 * hh + j) for j in range(3))
            bv = lane_ref[hh:hh + 1, :]                          # [1, dv]
            b = lane_ref[Hp + hh:Hp + hh + 1, :]
            # pass 1: decay the rows, read k^T S'
            acc = jnp.zeros((R, dv), jnp.float32)
            for r0 in range(0, dk, R):
                rows = slice(hh * dk + r0, hh * dk + r0 + R)
                dec = a_col[r0:r0 + R, :] * s_in[rows, :]
                s_out[rows, :] = dec
                acc = acc + dec * k_col[r0:r0 + R, :]
            u = bv - b * jnp.sum(acc, axis=0, keepdims=True)     # [1, dv]
            # pass 2: the correction, read S_t^T q
            acc = jnp.zeros((R, dv), jnp.float32)
            for r0 in range(0, dk, R):
                rows = slice(hh * dk + r0, hh * dk + r0 + R)
                new = s_out[rows, :] + k_col[r0:r0 + R, :] * u
                s_out[rows, :] = new
                acc = acc + new * q_col[r0:r0 + R, :]
            o_out[hh:hh + 1, :] = jnp.sum(acc, axis=0, keepdims=True)


def _state_update_local(state, layer, pages, q, k, v, g, beta):
    """state [L, Gd, Bp, nh, dk, dv] (the whole stacked pool); pages [Gd,
    Sg] (-1: no live stream in the slot); q / k / g [Gd, Sg, nh, dk]; v
    [Gd, Sg, nh, dv]; beta [Gd, Sg, nh]."""
    L, Gd, Bp, nh, dk, dv = state.shape
    Sg = pages.shape[1]
    Ns = Gd * Sg
    Ht = tile_heads(nh, dk, dv)
    nT = nh // Ht
    Hp = -(-Ht // 8) * 8
    R = _ROWS if dk % _ROWS == 0 else dk
    f32 = jnp.float32

    # Live streams first, in slot order; the grid's dead steps (s >= n)
    # all map to the LAST live step's blocks: no DMA, no work.
    page = pages.reshape(Ns)
    live = page >= 0
    n = live.sum().astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    group = order // Sg
    tiles = ((layer * Gd + group) * Bp + jnp.maximum(page[order], 0)) * nT

    def at(s, t, n_p):
        """(sorted stream, tile) a grid step works on."""
        dead = s >= n_p[0]
        return (jnp.minimum(s, jnp.maximum(n_p[0] - 1, 0)),
                jnp.where(dead, nT - 1, t))

    def pool_map(s, t, t_p, r_p, n_p):
        s_, t_ = at(s, t, n_p)
        return (t_p[s_] + t_, 0, 0)

    def head_map(s, t, t_p, r_p, n_p):
        s_, t_ = at(s, t, n_p)
        return (r_p[s_], t_, 0, 0)

    # The small operands, a stream's rows by tile.
    cols = jnp.stack([jnp.exp(g.astype(f32)), k.astype(f32), q.astype(f32)],
                     axis=-2).reshape(Ns, nT, 3 * Ht, dk)
    b = beta.astype(f32).reshape(Ns, nT, Ht, 1)
    pad = ((0, 0), (0, 0), (0, Hp - Ht), (0, 0))
    lanes = jnp.concatenate(
        [jnp.pad(b * v.astype(f32).reshape(Ns, nT, Ht, dv), pad),
         jnp.pad(jnp.broadcast_to(b, (Ns, nT, Ht, dv)), pad)], axis=2)

    s_flat = state.reshape(L * Gd * Bp * nT, Ht * dk, dv)
    s_spec = pl.BlockSpec((None, Ht * dk, dv), pool_map)
    kernel = functools.partial(_state_update_kernel, dk=dk, Ht=Ht, Hp=Hp,
                               R=R)
    s_new, o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(Ns, nT),
            in_specs=[pl.BlockSpec((None, None, 3 * Ht, dk), head_map),
                      pl.BlockSpec((None, None, 2 * Hp, dv), head_map),
                      s_spec],
            out_specs=[s_spec,
                       pl.BlockSpec((None, None, Hp, dv), head_map)],
            scratch_shapes=[pltpu.VMEM((dk, dk), f32)]),
        out_shape=[jax.ShapeDtypeStruct(s_flat.shape, f32),
                   jax.ShapeDtypeStruct((Ns, nT, Hp, dv), f32)],
        # tiles, rows, n, cols, lanes, state
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=_VMEM_LIMIT),
        name="_kda_state_update_kernel",
        interpret=_interpret(),
    )(tiles.astype(jnp.int32), order, n.reshape(1), cols, lanes, s_flat)
    # Dead slots' rows were never written: zero them.
    o = jnp.where(live[:, None, None], o[:, :, :Ht].reshape(Ns, nh, dv), 0.0)
    return o.reshape(Gd, Sg, nh, dv), s_new.reshape(state.shape)


def state_update(state, layer, pages, q, k, v, g, beta, *, mesh=None):
    """The decode step of every live stream's page, in place (module
    docstring).  Returns (o [Gd, Sg, nh, dv] fp32, state')."""
    if pltpu is None:  # pragma: no cover
        raise RuntimeError("pallas TPU backend unavailable")
    fn = paged._on_mesh(
        _state_update_local, mesh,
        lambda dpn, mpn: (P(None, dpn), P(), P(dpn), P(dpn), P(dpn), P(dpn),
                          P(dpn), P(dpn)),
        lambda dpn, mpn: (P(dpn), P(None, dpn)))
    return fn(state, jnp.asarray(layer, jnp.int32), pages, q, k, v, g, beta)


__all__ = ["state_tile", "tile_heads", "recurrent_update",
           "unit_lower_inverse", "chunked_delta_rule", "state_update"]

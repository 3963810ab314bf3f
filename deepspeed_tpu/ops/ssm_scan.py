"""The Mamba-2 state-space recurrence (scalar decay a head), kept as a
fixed-size fp32 state a stream:

    S_t = exp(a_t) S_{t-1} + dt_t x_t (outer) B_t       (a_t = dt_t A <= 0)
    y_t = S_t C_t

for every head ``h`` of ``nh``: ``x_t [P]`` the head's channels, ``B_t`` /
``C_t [N]`` its GROUP's (``nh / G`` heads a group), ``S [N, P]``.  The skip
``D x_t``, the gate and the norm are the model's (``models/falcon_h1.py``).

**The state** of one stream and layer is ``S [nh, N, P]`` float32, state
dimension on sublanes and head channels on lanes (``state_tile``): the
decode update is ``S = da S + B (x) dtx`` with ``dtx`` a LANE vector
broadcast over sublanes and ``B`` a column, and the read ``sum_n S[n, :]
C[n]`` a sum over sublanes whose result is a lane vector — ``y`` comes out
in the layout the next product wants, and nothing wider than a row is ever
transposed.

Three forms, equal in real arithmetic: ``recurrent_update`` (one token a
stream: decode; plain ``jax.numpy``, the off-TPU path and the kernel's
reference), ``chunked_scan`` (a chunk of rows of one stream from a carried
state, in sub-chunks of ``chunk`` rows: prefill; the state-space duality's
block form, whose carried states ARE the stream's state at every sub-chunk
boundary, so one of them can be handed back as a snapshot) and
``state_update``, the decode KERNEL over the paged state pool: grid (stream,
tile of heads), the pool aliased in and out, one read and one write of every
LIVE page's layer; dead slots cost no DMA and no work (their grid steps
revisit the last live tile).  Products against the fp32 state run on the
vector unit in the kernel and at ``Precision.HIGH`` (three bf16 passes) on
the matrix unit in the chunked form; accumulation is fp32 everywhere.
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

_HIGH = lax.Precision.HIGH      # fp32 operands as three bf16 passes
_TILE_BYTES = 2 << 20           # a tile of the state a grid step holds
_ROWS = 32                      # state rows the kernel updates at once
_VMEM_LIMIT = 48 * 2 ** 20


def state_tile(num_heads: int, d_state: int, d_head: int
               ) -> Tuple[int, int, int]:
    """One page-layer's tile as held ``[heads, rows, lanes]``: a head's
    ``[N, P]``, lane-dense at P = 128."""
    return (num_heads, d_state, d_head)


def tile_heads(num_heads: int, groups: int, d_state: int, d_head: int
               ) -> int:
    """Heads a grid step of the kernel holds, the most whose tile stays
    under ``_TILE_BYTES``: of ONE group where a group has several heads
    (they share B and C: Mamba-2), else several WHOLE one-head groups (every
    head its own B and C: a linear attention; a step of one 64 KB head would
    be all fixed cost)."""
    per = num_heads // groups
    span = per if per > 1 else num_heads
    fit = [t for t in range(1, span + 1)
           if span % t == 0 and t * d_state * d_head * 4 <= _TILE_BYTES]
    return max(fit or [1])


def _by_head(a: jax.Array, heads: int) -> jax.Array:
    """``[..., G, N]`` -> ``[..., nh, N]``: every head its group's row."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


# --------------------------------------------------------------------- #
# The plain forms
# --------------------------------------------------------------------- #
def recurrent_update(S, x, B, C, dt, da):
    """One token a stream.  S ``[M, nh, N, P]`` fp32; x ``[M, nh, P]``; B /
    C ``[M, G, N]``; dt, da ``[M, nh]`` (the step and the decay ``exp(dt
    A)``).  Returns (y ``[M, nh, P]`` fp32, S')."""
    f32 = jnp.float32
    nh = x.shape[-2]
    dtx = dt.astype(f32)[..., None] * x.astype(f32)             # [M, nh, P]
    S = da.astype(f32)[..., None, None] * S \
        + _by_head(B.astype(f32), nh)[..., :, None] * dtx[..., None, :]
    y = jnp.sum(S * _by_head(C.astype(f32), nh)[..., :, None], axis=-2)
    return y, S


def chunked_scan(S0, x, B, C, dt, a, *, chunk: int,
                 keep: Optional[jax.Array] = None):
    """A run of T rows of ONE stream from a carried state, ``chunk`` rows a
    step.  S0 ``[nh, N, P]`` fp32; x ``[T, nh, P]``; B / C ``[T, G, N]``;
    dt, a ``[T, nh]`` fp32 (the step and the log decay ``dt A``; a row that
    is not live has both 0: it neither decays the state nor adds to it).
    ``keep``: a traced sub-chunk index — the state as it stands after that
    sub-chunk is returned too (the stream's state at row ``(keep + 1) *
    chunk - 1``: a snapshot the scan computes anyway).  Returns (y ``[T, nh,
    P]`` fp32, S after the last row, the kept state or None).

    The decay matrices ``exp(cum_l - cum_s)`` (``[nh, chunk, chunk]``) are
    built a sub-chunk at a time inside the scan: the largest temporary."""
    T, nh, Pd = x.shape
    G, N = B.shape[1:]
    if T % chunk:
        raise ValueError(f"chunked_scan: {T} rows in sub-chunks of {chunk}")
    nc, k = T // chunk, nh // G
    f32 = jnp.float32
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    exact = dict(preferred_element_type=f32, precision=_HIGH)

    def step(carry, rows):
        S, kept = carry
        i, x_c, B_c, C_c, dt_c, a_c = rows
        x_c = x_c.astype(f32).reshape(chunk, G, k, Pd)
        B_c, C_c = B_c.astype(f32), C_c.astype(f32)
        cum = jnp.cumsum(a_c, axis=0)                           # [Q, nh]
        cum_h = cum.T.reshape(G, k, chunk)
        # rows of this sub-chunk against each other
        decay = jnp.exp(jnp.where(
            causal, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
        cb = jnp.einsum("lgn,sgn->gls", C_c, B_c, **exact)
        m = cb[:, None] * decay * dt_c.T.reshape(G, k, 1, chunk)
        y = jnp.einsum("gkls,sgkp->lgkp", m, x_c, **exact)
        # ... and against the state they started from
        Sg = S.reshape(G, k, N, Pd)
        y = y + jnp.exp(cum).reshape(chunk, G, k, 1) * jnp.einsum(
            "lgn,gknp->lgkp", C_c, Sg, **exact)
        # what the sub-chunk leaves: every row's step decayed to its end
        w = (jnp.exp(cum[-1] - cum) * dt_c).reshape(chunk, G, k, 1)
        Sg = jnp.exp(cum[-1]).reshape(G, k, 1, 1) * Sg + jnp.einsum(
            "sgn,sgkp->gknp", B_c, w * x_c, **exact)
        S = Sg.reshape(nh, N, Pd)
        if kept is not None:
            kept = jnp.where(i == keep, S, kept)
        return (S, kept), y.reshape(chunk, nh, Pd)

    split = lambda v: v.reshape((nc, chunk) + v.shape[1:])     # noqa: E731
    (S, kept), y = lax.scan(
        step, (S0.astype(f32), None if keep is None else S0.astype(f32)),
        (jnp.arange(nc, dtype=jnp.int32), split(x), split(B), split(C),
         split(dt.astype(f32)), split(a.astype(f32))))
    return y.reshape(T, nh, Pd), S, kept


# --------------------------------------------------------------------- #
# The decode kernel over the paged state pool
# --------------------------------------------------------------------- #
def _state_update_kernel(tile_ref, row_ref, n_ref, hx_ref, bc_ref, s_in,
                         s_out, y_out, *col_scr, N, Ht, Hp, R):
    """One grid step = (stream s, tile t of Ht heads).

    hx_ref [2*Hp, P]: row hh the head's ``dt x`` and row Hp + hh its decay
    on every lane.  s_in / s_out [Ht*N, P]: this tile of the stream's page
    and layer (the same HBM: aliased).  y_out [Hp, P]: row hh the head's
    ``S' C``.  B and C, which the update wants as COLUMNS (value n on every
    lane of sublane n), come in one of two forms, told apart by whether the
    call brought the two ``[N, P]`` scratches ``col_scr``:
    - the tile's heads are of ONE group and share them (Mamba-2): bc_ref
      [8, N], row 0 the group's B, row 1 its C, turned into columns in the
      scratches once a step;
    - every head is a group of its own (a linear attention; no scratch):
      bc_ref [2, N, Hl], ``[0, n, hh]`` head hh's B[n] and ``[1, n, hh]`` its
      C[n] — state dimension on sublanes, heads on lanes, so a head's B and
      C arrive as columns and nothing is transposed in the kernel.
    """
    del tile_ref, row_ref
    s = pl.program_id(0)
    n = n_ref[0]

    @pl.when(n == 0)
    def _nothing_live():
        # Every step maps to one tile (see the index maps) that the
        # pipeline writes back at the end: hand it back as it came.
        s_out[...] = s_in[...]
        y_out[...] = jnp.zeros_like(y_out)

    @pl.when(s < n)
    def _update():
        Pd = s_in.shape[1]
        if col_scr:
            for r0 in range(0, N, Pd):
                w = min(Pd, N - r0)
                for row, scr in zip((0, 1), col_scr):
                    lanes = bc_ref[row:row + 1, r0:r0 + w]         # [1, w]
                    scr[r0:r0 + w, :] = jnp.broadcast_to(lanes, (Pd, w)).T

        def column(which, hh, r0):
            """Rows r0 .. r0 + R of head hh's B (0) or C (1) column."""
            if col_scr:
                return col_scr[which][r0:r0 + R, :]
            return bc_ref[which, r0:r0 + R, hh:hh + 1]             # [R, 1]

        if Hp > Ht:
            y_out[Ht:, :] = jnp.zeros((Hp - Ht, Pd), jnp.float32)
        for hh in range(Ht):
            dtx = hx_ref[hh:hh + 1, :]                            # [1, P]
            da = hx_ref[Hp + hh:Hp + hh + 1, :]
            acc = jnp.zeros((R, Pd), jnp.float32)
            for r0 in range(0, N, R):
                rows = slice(hh * N + r0, hh * N + r0 + R)
                new = da * s_in[rows, :] + column(0, hh, r0) * dtx
                s_out[rows, :] = new
                acc = acc + new * column(1, hh, r0)
            y_out[hh:hh + 1, :] = jnp.sum(acc, axis=0, keepdims=True)


def _state_update_local(state, layer, pages, x, B, C, dt, da):
    """state [L, Gd, Bp, nh, N, P] (the whole stacked pool); pages [Gd,
    Sg] (-1: no live stream in the slot); x [Gd, Sg, nh, P]; B / C [Gd, Sg,
    G, N]; dt, da [Gd, Sg, nh]."""
    L, Gd, Bp, nh, N, Pd = state.shape
    G = B.shape[2]
    Sg = pages.shape[1]
    Ns = Gd * Sg
    Ht = tile_heads(nh, G, N, Pd)
    nT = nh // Ht
    Hp = -(-Ht // 8) * 8
    R = _ROWS if N % _ROWS == 0 else N
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

    def group_map(s, t, t_p, r_p, n_p):
        s_, t_ = at(s, t, n_p)
        return (r_p[s_], t_ * Ht * G // nh, 0, 0)

    # The small operands, a stream's rows by tile.
    dtx = (dt.astype(f32)[..., None] * x.astype(f32)).reshape(Ns, nT, Ht, Pd)
    dal = jnp.broadcast_to(da.astype(f32).reshape(Ns, nT, Ht, 1),
                           (Ns, nT, Ht, Pd))
    pad = ((0, 0), (0, 0), (0, Hp - Ht), (0, 0))
    hx = jnp.concatenate([jnp.pad(dtx, pad), jnp.pad(dal, pad)], axis=2)
    if G < nh or Ht == 1:
        # A tile's heads share one group's B and C: rows of an [8, N] block.
        bc = jnp.stack([B.astype(f32).reshape(Ns, G, N),
                        C.astype(f32).reshape(Ns, G, N)], axis=2)
        bc = jnp.pad(bc, ((0, 0), (0, 0), (0, 6), (0, 0)))      # [Ns,G,8,N]
        bc_spec = pl.BlockSpec((None, None, 8, N), group_map)
        col_scr = [pltpu.VMEM((N, Pd), f32), pltpu.VMEM((N, Pd), f32)]
    else:
        # Every head its own B and C: a tile's, as columns ([N, heads]).
        Hl = -(-Ht // 128) * 128
        bc = jnp.stack([B.astype(f32).reshape(Ns, nT, Ht, N),
                        C.astype(f32).reshape(Ns, nT, Ht, N)], axis=2)
        bc = jnp.pad(jnp.swapaxes(bc, 3, 4),
                     ((0, 0),) * 4 + ((0, Hl - Ht),))       # [Ns,nT,2,N,Hl]
        bc_spec = pl.BlockSpec((None, None, 2, N, Hl),
                               lambda *a: head_map(*a) + (0,))
        col_scr = []

    s_flat = state.reshape(L * Gd * Bp * nT, Ht * N, Pd)
    s_spec = pl.BlockSpec((None, Ht * N, Pd), pool_map)
    kernel = functools.partial(_state_update_kernel, N=N, Ht=Ht, Hp=Hp, R=R)
    s_new, y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(Ns, nT),
            in_specs=[pl.BlockSpec((None, None, 2 * Hp, Pd), head_map),
                      bc_spec, s_spec],
            out_specs=[s_spec,
                       pl.BlockSpec((None, None, Hp, Pd), head_map)],
            scratch_shapes=col_scr),
        out_shape=[jax.ShapeDtypeStruct(s_flat.shape, f32),
                   jax.ShapeDtypeStruct((Ns, nT, Hp, Pd), f32)],
        # tiles, rows, n, hx, bc, state
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=_VMEM_LIMIT),
        name="_ssm_state_update_kernel",
        interpret=_interpret(),
    )(tiles.astype(jnp.int32), order, n.reshape(1), hx, bc, s_flat)
    # Dead slots' rows were never written: zero them.
    y = jnp.where(live[:, None, None], y[:, :, :Ht].reshape(Ns, nh, Pd), 0.0)
    return y.reshape(Gd, Sg, nh, Pd), s_new.reshape(state.shape)


def state_update(state, layer, pages, x, B, C, dt, da, *, mesh=None):
    """The decode step of every live stream's page, in place (module
    docstring).  Returns (y [Gd, Sg, nh, P] fp32, state')."""
    if pltpu is None:  # pragma: no cover
        raise RuntimeError("pallas TPU backend unavailable")
    fn = paged._on_mesh(
        _state_update_local, mesh,
        lambda dpn, mpn: (P(None, dpn), P(), P(dpn), P(dpn), P(dpn), P(dpn),
                          P(dpn), P(dpn)),
        lambda dpn, mpn: (P(dpn), P(None, dpn)))
    return fn(state, jnp.asarray(layer, jnp.int32), pages, x, B, C, dt, da)


def state_update_steps(live_streams: int, num_slots: int, num_heads: int,
                       groups: int, d_state: int, d_head: int
                       ) -> Tuple[int, int]:
    """(grid steps a layer's kernel sequences, those that do work)."""
    per = num_heads // tile_heads(num_heads, groups, d_state, d_head)
    return num_slots * per, int(live_streams) * per


__all__ = ["state_tile", "tile_heads", "recurrent_update", "chunked_scan",
           "state_update", "state_update_steps"]

"""A decode step's short-filter rows, rewritten in place.

A ``per_stream`` class keeps, beside its state, the last ``held`` rows of
what a short (depthwise, causal) filter runs over — one page tile a stream
and layer, ``held`` rows of ``C`` channels row-major in rows of 128 lanes:
``[held * C/128, 128]`` (``inference/served.py``: ``filter_tile``, for
the lfm2, falcon-h1 and kimi-linear families).  A decode step hands every
stream ONE new row: the filter wants ``[held old rows | the new one]`` and
the page must hold rows ``1 .. held`` of that afterwards.  As plain
``jax.numpy`` (``served.filter_rows``) that is a gather of page tiles, a
select, a concatenate, a ``take_along_axis`` and a scatter a layer, and on the chip
the page tiles' own gather and scatter are the small part of it: the tile is
``[held * C/128, 128]`` and the filter's rows are ``[S, held + 1, C]``, so
every step between them is a physical relayout, and ``take_along_axis`` is a
second gather, of rows.  3.14 ms of a 22.6 ms iteration at 256 streams x 6
layers, 0.12 of it the filters (PERF.md section 5, PR 53 step 0).

``shift_rows`` does it with one Pallas call a layer.  The stacked pool stays
in HBM (aliased input -> output, as ``ops.kda.state_update`` keeps its
pool); the streams' page tiles are scalar-prefetched; a grid step serves
SEVERAL streams (``streams_a_step``): it starts every stream's page copy
HBM -> VMEM straight into the rows it returns, lays the new rows behind them
while the copies fly, waits, and copies rows ``1 .. held`` of each stream
back to its page — all of a step's copies in flight together (the pattern
of ``ops.paged_attention._pattn_kernel``), so a page costs its bytes.  The
kernel moves rows and computes nothing: what it returns and what it leaves
in a page are the bits the plain lines give.

A stream without a page (or with no live row) reads nothing and writes
NOTHING: its old rows come back as zeros.  (The plain lines read page 0
for it and drop its write by an out-of-range index; nobody uses either.)
A stream at position 0 (not ``carried``) reads nothing either — zeros — and
writes its page.

What the kernel asks of the shapes (``takes``): the tile in its 128-lane
form and a held row that is a whole number of the dtype's sublane tiles
(8 rows of fp32, 16 of bf16: a copy's VMEM side starts on a tile), the new
row in the pool's dtype.  Kimi-Linear's 12,288 channels are 96 rows, LFM2's
2,048 are 16; Falcon-H1's 5,120 are 40 rows of bf16, two and a half tiles:
it keeps the plain lines.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from .flash_attention import _interpret
from . import paged_attention as paged

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

_LANES = 128
# What a grid step's page copies may add up to, one way: a step of 32 pages
# of 72 KB has 2.25 MiB in flight; the rows it returns (double-buffered by
# the pipeline) and the new rows make it ~8 MiB of VMEM.
_STEP_BYTES = 4 * 2 ** 20
_VMEM_LIMIT = 32 * 2 ** 20


def takes(pool_shape, pool_dtype, held: int, new_dtype) -> bool:
    """Whether ``shift_rows`` can serve a pool ``[L, G, B, *tile]`` of
    ``held`` rows a page (module docstring)."""
    if pltpu is None or held < 1 or len(pool_shape) != 6:
        return False
    one, rows, lanes = pool_shape[3:]
    dtype = jnp.dtype(pool_dtype)
    sublanes = 8 * max(1, 4 // dtype.itemsize)
    return (one == 1 and lanes == _LANES and rows % held == 0
            and (rows // held) % sublanes == 0
            and jnp.dtype(new_dtype) == dtype)


def streams_a_step(streams: int, page_bytes: int) -> int:
    """Streams a grid step serves: the most that divide ``streams`` and
    keep a step's page copies under ``_STEP_BYTES``."""
    most = max(1, _STEP_BYTES // page_bytes)
    return max(n for n in range(1, min(streams, most) + 1)
               if streams % n == 0)


def _filter_rows_kernel(tile_ref, does_ref, new_ref, pool_in, rows_ref,
                        pool_out, sem, *, Sb, held):
    """One grid step = ``Sb`` streams.  ``tile_ref`` [Ns]: a stream's page
    tile in the flat pool; ``does_ref`` [Ns]: 0 the stream has no page, 1 it
    writes its page, 2 it reads it first.  ``new_ref`` [Sb, Cr, 128];
    ``rows_ref`` [held + 1, Sb, Cr, 128]: row by row, the old ones then the
    new one; ``pool_in`` / ``pool_out`` [tiles, held, Cr, 128]: the same HBM
    (aliased)."""
    base = pl.program_id(0) * Sb

    def page_in(j):
        return pltpu.make_async_copy(
            pool_in.at[tile_ref[base + j]], rows_ref.at[pl.ds(0, held), j],
            sem.at[0])

    def page_out(j):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(1, held), j], pool_out.at[tile_ref[base + j]],
            sem.at[1])

    def each(which, do):
        """``do(j)`` for the step's streams whose ``does`` is ``which``."""
        def one(j, carry):
            @pl.when(which(does_ref[base + j]))
            def _():
                do(j)
            return carry
        jax.lax.fori_loop(0, Sb, one, 0)

    def reads(does):
        return does == 2

    def writes(does):
        return does >= 1

    def zeros(j):
        rows_ref[0:held, j] = jnp.zeros((held,) + rows_ref.shape[2:],
                                        rows_ref.dtype)

    each(reads, lambda j: page_in(j).start())
    rows_ref[held] = new_ref[...]
    each(lambda does: does < 2, zeros)
    each(reads, lambda j: page_in(j).wait())
    each(writes, lambda j: page_out(j).start())
    each(writes, lambda j: page_out(j).wait())


def _shift_rows_local(pool, layer, pages, carried, new, *, held):
    """pool [L, Gd, Bp, 1, held * Cr, 128] (the whole stacked pool); pages
    [Gd, Sg] (-1: the stream writes nothing); carried [Gd, Sg]; new [Gd,
    Sg, C]."""
    L, Gd, Bp, _, R, lanes = pool.shape
    Cr = R // held
    Sg = pages.shape[1]
    Ns = Gd * Sg
    Sb = streams_a_step(Ns, R * lanes * pool.dtype.itemsize)
    page = pages.reshape(Ns)
    group = jnp.arange(Ns, dtype=jnp.int32) // Sg
    tiles = (layer * Gd + group) * Bp + jnp.maximum(page, 0)
    does = jnp.where(page >= 0, 1 + carried.reshape(Ns).astype(jnp.int32), 0)
    rows, flat = pl.pallas_call(
        functools.partial(_filter_rows_kernel, Sb=Sb, held=held),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(Ns // Sb,),
            in_specs=[pl.BlockSpec((Sb, Cr, lanes),
                                   lambda i, t_p, d_p: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((held + 1, Sb, Cr, lanes),
                                    lambda i, t_p, d_p: (0, i, 0, 0)),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((held + 1, Ns, Cr, lanes),
                                        pool.dtype),
                   jax.ShapeDtypeStruct((L * Gd * Bp, held, Cr, lanes),
                                        pool.dtype)],
        input_output_aliases={3: 1},      # tiles, does, new, pool
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="_filter_rows_kernel",
        interpret=_interpret(),
    )(tiles.astype(jnp.int32), does, new.reshape(Ns, Cr, lanes),
      pool.reshape(L * Gd * Bp, held, Cr, lanes))
    return (rows.reshape(held + 1, Gd, Sg, Cr * lanes),
            flat.reshape(pool.shape))


def shift_rows(pool, layer, pages, carried, new, *, held: int, mesh=None):
    """Every stream's ``held`` rows of layer ``layer`` of the donated pool
    read, the new row laid behind them and rows ``1 .. held`` written back,
    in place (module docstring): pages [G, Sg] int32 (-1: nothing to
    write), carried [G, Sg] bool, new [G, Sg, C] in the pool's dtype.
    Returns (rows [held + 1, G, Sg, C], row by row, pool').  Under a dp mesh each shard
    serves its own groups."""
    if pltpu is None:  # pragma: no cover
        raise RuntimeError("pallas TPU backend unavailable")
    fn = paged._on_mesh(
        functools.partial(_shift_rows_local, held=held), mesh,
        lambda dpn, mpn: (P(None, dpn), P(), P(dpn), P(dpn), P(dpn)),
        lambda dpn, mpn: (P(None, dpn), P(None, dpn)))
    return fn(pool, jnp.asarray(layer, jnp.int32), pages, carried, new)


__all__ = ["takes", "streams_a_step", "shift_rows"]

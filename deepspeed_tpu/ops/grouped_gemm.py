"""Grouped-GEMM expert FFN: one Pallas kernel over ``[E,C,H] x [E,H,F]``.

MoE expert compute was a batched ``jnp.einsum`` over the capacity-bucketed
dispatch buffer (``moe/layer.py``): two einsums plus separate bias-add and
GELU passes, each a full HBM round-trip of the ``[E, C, F]`` intermediate.
This module is the kernel-tier replacement (ROADMAP item 5's grouped-GEMM
rung; the reference's fused transformer kernels play the same role on
GPU): a grouped matmul whose grid runs experts x row blocks x col blocks,
accumulates on the MXU in fp32 (``preferred_element_type``), and fuses the
bias + GELU epilogue in-register via the exact ``_gelu_f32``/``_dgelu_f32``
forms ``fused_elementwise`` ships — so the up-projection's activation
never makes a separate pass over HBM.

Structure:

- ``_grouped_matmul(a [E,M,K], b [E,K,N], bias [E,N]?, act?)`` — the raw
  ``pallas_call`` (no autodiff).  Block sizes resolve through
  ``ops.autotune`` (kernel key ``grouped_gemm``) with the same 12 MiB
  VMEM budget math as ``fused_elementwise``; ``DS_AUTOTUNE=0`` or CPU
  pins the heuristic.  Epilogue numerics mirror ``bias_gelu``:
  ``z = round(acc + bias)`` once to the storage dtype, GELU evaluated in
  fp32 on z, rounded once at the output.
- ``grouped_ffn(x, w1, b1, w2, b2, exact)`` — the expert FFN as a
  ``jax.custom_vjp``: forward is two fused grouped GEMMs; backward
  RECOMPUTES the pre-activation from (x, w1, b1) instead of saving the
  ``[E, C, F]`` intermediate (the ``normalize_invertible`` idea again —
  no fp32 expert-wide residual ever materializes, which is what keeps
  the moe lint flagship's materialization pass clean), and expresses
  every gradient contraction as the SAME grouped kernel on swapped
  axes.
- ``grouped_swiglu(x, src, w_gate, w_up, w_down, tile_expert, tile_rows,
  n_live_tiles, tm=, act=)`` — the serving tier's DROPLESS gated FFN (no
  autodiff, no capacity): rows grouped by expert in tiles of ``tm``, each
  tile against its expert's ``[F, H]`` matrices, ``down(act(gate x) * up
  x)`` with the gate's activation static — ``"silu"``
  (``_gswiglu_kernel``: ``deepseek_v3``, ``afmoe``, ``lfm2_moe``,
  ``kimi_linear``) or ``"relu"`` (``_greglu_kernel``: ``smallthinker``);
  one grid, one tile rule, two kernel names so that a trace tells them
  apart.  The kernel brings a tile's rows in itself, ``x[src[...]]`` row by
  row out of the tokens held in VMEM: no padded ``[M, H]`` copy of the
  rows is ever written.  ``moe/share.py`` plans the rows and is its only
  caller.

Numerics contract (tests/test_moe.py): vs the einsum path, fp32 agrees
to a few f32 ulp (cross-program dot association — the PR-1 tolerance
class), bf16 to ~2 bf16 ulp (the fused epilogue rounds once where the
unfused chain rounds per op).  ``num_experts=1`` keeps its dense
bit-parity through the DEFAULT dispatch ("auto" = off on CPU, einsum);
with the kernel forced on it lands in the ulp class above.

Sharding: the kernel is shard-LOCAL.  Under ep > 1 it runs inside the
fully-manual ``expert`` shard_map scope on the ``[E/ep, ...]`` slices —
``pallas_call`` is opaque to GSPMD, and here every operand is already
device-local, so no collective moves (the ``materialization`` lint pass
gates that, same as the elementwise kernels).

Enable/disable mirrors ``TransformerConfig.fused_kernels``:
``MoEConfig.grouped_gemm`` is ``"auto"`` (TPU on / CPU off, overridable
with DS_GROUPED_GEMM=0/1) or forced True/False — True on CPU runs
interpret mode, which is how tier-1's dp=8 mesh exercises the kernel.
The knob is cfg-static: it changes the compiled program, never the
compiled signature, and checkpoints resume across it.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # TPU backend bits are importable everywhere; interpret=True on CPU
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

from . import autotune
from .fused_elementwise import _dgelu_f32, _gelu_f32

_LANE = 128
_VMEM_BUDGET = 12 * 2 ** 20          # same budget math as fused_elementwise
_ENV_KNOB = "DS_GROUPED_GEMM"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def grouped_gemm_enabled(flag="auto") -> bool:
    """Resolve ``MoEConfig.grouped_gemm`` to on/off — the same contract
    as ``fused_elementwise_enabled``: True/False forced, "auto" on
    exactly when the backend is TPU, DS_GROUPED_GEMM=0/1 overrides
    "auto" (the bench/ablation switch)."""
    if flag is True or flag is False:
        return bool(flag)
    env = os.environ.get(_ENV_KNOB)
    if env in ("0", "1"):
        return env == "1"
    return jax.default_backend() == "tpu"


def _pad_to(n: int, q: int) -> int:
    return -(-n // q) * q


def _tile_heuristic(M: int, K: int, N: int, itemsize: int
                    ) -> Tuple[int, int]:
    """(bm, bn): bn is the largest power-of-two column block <= 512 (and
    <= lane-padded N); bm starts at 128 — clamped down to the padded row
    count for small capacities so a C=40 bucket doesn't run a 128-row
    block 69% empty — then halves while the fp32 working set (a block +
    b block + acc) exceeds the VMEM budget."""
    Kp = _pad_to(K, _LANE)
    Np = _pad_to(N, _LANE)
    bn = 512
    while bn > _LANE and bn > Np:
        bn //= 2
    bm = 128
    while bm > 16 and bm >= 2 * _pad_to(M, bm // 2):
        bm //= 2
    while bm > 16 and 4 * (bm * Kp + Kp * bn + bm * bn) > _VMEM_BUDGET:
        bm //= 2
    return bm, bn


def _tile_candidates(M: int, K: int, N: int) -> Tuple[Tuple[int, int], ...]:
    Kp = _pad_to(K, _LANE)
    Np = _pad_to(N, _LANE)

    def fits(bm, bn):
        return 4 * (bm * Kp + Kp * bn + bm * bn) <= _VMEM_BUDGET

    out = []
    for bm in (16, 32, 64, 128, 256):
        for bn in (128, 256, 512):
            if bn <= Np and fits(bm, bn):
                out.append((bm, bn))
    return tuple(out)


def _gg_kernel(a_ref, b_ref, bias_ref, o_ref, *, act: Optional[str],
               has_bias: bool, out_dtype):
    """One (expert, row-block, col-block) grid step: fp32 MXU dot +
    fused epilogue. Epilogue rounding mirrors ``bias_gelu``: the
    bias sum rounds ONCE to the storage dtype before GELU reads it."""
    acc = jax.lax.dot_general(
        a_ref[0], b_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [bm, bn] f32
    if has_bias:
        z = (acc + bias_ref[0].astype(jnp.float32)).astype(out_dtype)
    else:
        z = acc.astype(out_dtype)
    if act is not None:
        z = _gelu_f32(z.astype(jnp.float32),
                      exact=(act == "gelu_exact")).astype(out_dtype)
    o_ref[0] = z


def _spec(block, index_map):
    if pltpu is not None and jax.default_backend() == "tpu":
        return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)
    return pl.BlockSpec(block, index_map)


def _grouped_matmul(a: jax.Array, b: jax.Array,
                    bias: Optional[jax.Array] = None,
                    act: Optional[str] = None,
                    out_dtype=None, _tile=None) -> jax.Array:
    """``out[e] = act(a[e] @ b[e] + bias[e])`` for every expert e.

    ``a``: [E, M, K]; ``b``: [E, K, N]; ``bias``: [E, N] or None; ``act``
    None | "gelu_tanh" | "gelu_exact".  fp32 accumulation, one fused
    epilogue, output in ``out_dtype`` (default ``a.dtype``).  ``_tile``
    is the autotune recursion guard (the measure runner pins it).
    """
    E, M, K = a.shape
    Eb, Kb, N = b.shape
    assert E == Eb and K == Kb, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype

    if _tile is None:
        bm, bn = _tile_heuristic(M, K, N, jnp.dtype(a.dtype).itemsize)
        measure = None
        if autotune.search_allowed():
            def runner(tile):
                da = jnp.zeros((E, M, K), a.dtype)
                db = jnp.zeros((E, K, N), b.dtype)
                dbias = None if bias is None else \
                    jnp.zeros((E, N), jnp.float32)
                return _grouped_matmul(da, db, dbias, act, out_dtype,
                                       _tile=tile)
            measure = autotune.measure_from_runner(runner)
        bm, bn = autotune.resolve(
            "grouped_gemm", (E, M, K, N), str(jnp.dtype(a.dtype)),
            (bm, bn), _tile_candidates(M, K, N), measure)
    else:
        bm, bn = _tile

    Mp, Kp, Np = _pad_to(M, bm), _pad_to(K, _LANE), _pad_to(N, bn)
    if (Mp, Kp) != (M, K):
        a = jnp.pad(a, ((0, 0), (0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        b = jnp.pad(b, ((0, 0), (0, Kp - K), (0, Np - N)))
    has_bias = bias is not None
    # Bias rides as [E, 1, Np]: its (1, bn) block then spans the
    # second-minor dim (a (1, bn) block over [E, Np] breaks the TPU
    # (8, 128) tiling rule).
    if has_bias:
        bias2 = bias.astype(jnp.float32)[:, None, :]
        if Np != N:
            bias2 = jnp.pad(bias2, ((0, 0), (0, 0), (0, Np - N)))
    else:  # dummy broadcast row (the _ln_forward no-residual idiom)
        bias2 = jnp.zeros((E, 1, Np), jnp.float32)

    kernel = functools.partial(_gg_kernel, act=act, has_bias=has_bias,
                               out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(E, Mp // bm, Np // bn),
        in_specs=[
            _spec((1, bm, Kp), lambda e, i, j: (e, i, 0)),
            _spec((1, Kp, bn), lambda e, i, j: (e, 0, j)),
            _spec((1, 1, bn), lambda e, i, j: (e, 0, j)),
        ],
        out_specs=_spec((1, bm, bn), lambda e, i, j: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Mp, Np), out_dtype),
        name="_gg_kernel",
        interpret=_interpret(),
    )(a, b, bias2)
    return out[:, :M, :N]


def _swap(x: jax.Array) -> jax.Array:
    return jnp.swapaxes(x, 1, 2)


def _act_name(exact: bool) -> str:
    return "gelu_exact" if exact else "gelu_tanh"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def grouped_ffn(x, w1, b1, w2, b2, exact: bool = False):
    """The expert FFN ``gelu(x @ w1 + b1) @ w2 + b2`` per expert, as two
    fused grouped GEMMs.  ``x``: [E, C, H]; ``w1``: [E, H, F]; ``b1``:
    [E, F]; ``w2``: [E, F, H]; ``b2``: [E, H].  Default GELU is the tanh
    approximation (``exact=True`` selects erf — ``cfg.gelu_exact``)."""
    h = _grouped_matmul(x, w1, bias=b1, act=_act_name(exact))
    return _grouped_matmul(h, w2, bias=b2)


def _gff_fwd(x, w1, b1, w2, b2, exact):
    # Residuals are the INPUTS only: the [E, C, F] pre-activation is
    # recomputed in the backward rather than saved (materialization-pass
    # clean; recompute is one grouped GEMM the bwd needs anyway).
    return grouped_ffn(x, w1, b1, w2, b2, exact), (x, w1, b1, w2, b2)


def _gff_bwd(exact, res, dy):
    x, w1, b1, w2, b2 = res
    z1 = _grouped_matmul(x, w1, bias=b1)               # [E, C, F] pre-act
    z32 = z1.astype(jnp.float32)
    h = _gelu_f32(z32, exact).astype(z1.dtype)
    dh = _grouped_matmul(dy, _swap(w2))                # [E, C, F]
    dz = (dh.astype(jnp.float32) *
          _dgelu_f32(z32, exact)).astype(z1.dtype)
    dw2 = _grouped_matmul(_swap(h), dy).astype(w2.dtype)
    db2 = jnp.sum(dy.astype(jnp.float32), axis=1).astype(b2.dtype)
    dw1 = _grouped_matmul(_swap(x), dz).astype(w1.dtype)
    db1 = jnp.sum(dz.astype(jnp.float32), axis=1).astype(b1.dtype)
    dx = _grouped_matmul(dz, _swap(w1))
    return dx, dw1, db1, dw2, db2


grouped_ffn.defvjp(_gff_fwd, _gff_bwd)


# --------------------------------------------------------------------- #
# Dropless grouped gated FFN, SiLU or ReLU gate (serving: an expert layer's
# held share)
# --------------------------------------------------------------------- #
_SWIGLU_VMEM_LIMIT = 100 * 2 ** 20
_SWIGLU_WEIGHTS_ROOM = 48 * 2 ** 20     # three weight blocks, two buffers each


def _gated_step(gate_act, te_ref, nl_ref, tr_ref, src_ref, x_ref, wg_ref,
                wu_ref, wd_ref, o_ref, acc_ref, rows_ref, x32_ref):
    """One grid step = one (row tile, F tile): the tile's rows all belong
    to expert ``te_ref[i]``; ``acc += (gate_act(x Wg^T) * (x Wu^T)) Wd``
    over the F tiles, written out at the last.  The first step widens the
    tokens ``x_ref`` into ``x32_ref`` (float32: one row is one sublane, which
    a loop can name; exact).  At its first F step a tile copies its
    ``tr_ref[i]`` live rows ``x32_ref[src_ref[i * tm + r]]`` into
    ``rows_ref``, and every F step reads them there; the rows past the live
    count keep whatever an earlier tile left (zeros before the first),
    which nothing reads.  A tile past the live count (``nl_ref[0]``)
    computes nothing and emits zeros."""
    i, j = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(1) - 1
    live = i < nl_ref[0]
    tm = rows_ref.shape[0]

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _first_step():
        rows_ref[...] = jnp.zeros_like(rows_ref)
        x32_ref[...] = x_ref[...].astype(jnp.float32)

    @pl.when(jnp.logical_and(live, j == 0))
    def _rows():
        def row(r, carry):
            rows_ref[pl.ds(r, 1), :] = x32_ref[pl.ds(src_ref[i * tm + r], 1),
                                               :]
            return carry
        jax.lax.fori_loop(0, tr_ref[i], row, 0)

    @pl.when(live)
    def _compute():
        x = rows_ref[...].astype(o_ref.dtype)
        nt = (((1,), (1,)), ((), ()))                    # x [tm,H] . w [tf,H]
        g = jax.lax.dot_general(x, wg_ref[0], nt,
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, wu_ref[0], nt,
                                preferred_element_type=jnp.float32)
        h = (gate_act(g) * u).astype(x.dtype)
        part = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _first():
            acc_ref[...] = part

        @pl.when(j > 0)
        def _rest():
            acc_ref[...] += part

        @pl.when(j == last)
        def _out():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    @pl.when(jnp.logical_and(jnp.logical_not(live), j == last))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gswiglu_kernel(*refs):
    """``_gated_step`` with a SiLU gate: ``silu(x Wg^T) * (x Wu^T)``."""
    _gated_step(lambda g: g * jax.nn.sigmoid(g), *refs)


def _greglu_kernel(*refs):
    """``_gated_step`` with a ReLU gate: ``relu(x Wg^T) * (x Wu^T)``
    (``smallthinker``'s experts).  Same grid, specs and tile rule; a name of
    its own, so that a trace tells the two products apart."""
    _gated_step(lambda g: jnp.maximum(g, 0.0), *refs)


_GATED_KERNELS = {"silu": _gswiglu_kernel, "relu": _greglu_kernel}


def _swiglu_f_tile(F: int, H: int, itemsize: int,
                   room: int = _SWIGLU_WEIGHTS_ROOM) -> int:
    """The widest F tile (a multiple of 128 that divides F, or F) whose
    three weight blocks, double-buffered, stay under ``room`` bytes: 48
    MiB, or what the tokens held in VMEM beside them leave."""
    tf = F
    while tf > 128 and (F % tf or tf % 128 or 6 * tf * H * itemsize > room):
        tf -= 128
    return tf if F % tf == 0 else F


def grouped_swiglu(x, src, w_gate, w_up, w_down, tile_expert, tile_rows,
                   n_live_tiles, *, tm: int, act: str = "silu"):
    """``out[r] = down_e(act(gate_e x[src[r]]) * up_e x[src[r]])`` (``act``:
    the gate's activation, ``"silu"`` or ``"relu"``, static) for rows
    grouped by expert: buffer row ``r`` of ``M = len(src)`` is token
    ``src[r]`` of ``x [T, H]``; row tile ``t`` (``tm`` rows) belongs to
    expert ``tile_expert[t]`` and holds ``tile_rows[t]`` rows, from its
    first; only the first ``n_live_tiles`` tiles hold any (the rest emit
    zeros and move no weight).  Weights ``[E, F, H]`` (an expert's ``[tf,
    H]`` tile is one contiguous run).  No capacity and nothing dropped: the
    caller plans a row for every routed pair (``moe/share.py``).  The
    kernel reads the rows through ``src`` itself — ``x`` sits in VMEM whole
    (and once more as float32, widened by the kernel's first step, so that
    a row is a sublane a loop can name; the products' operands are the same
    ``x.dtype`` values a gathered ``x[src]`` would hold) — so the ``[M, H]``
    copy is never made, and ``x`` itself is the call's operand, as it was
    the gather's: the program round the call keeps the neighbours it had
    (a float32 view made OUTSIDE let the compiler fold the producer of ``x``
    into each of its readers, and one cell's router then read it unrounded).
    Returns ``[M, H]`` in x's dtype, fp32 accumulation; what a live tile's
    rows PAST its ``tile_rows`` hold is finite and otherwise unspecified
    (they were never anybody's: ``share``'s ``pos`` points at held pairs
    only)."""
    T, H = x.shape
    M, = src.shape
    E, F, _ = w_gate.shape
    assert M % tm == 0, (M, tm)
    nt = M // tm
    # x (one buffer: its block never moves), its float32 copy and the tiles
    # (rows, acc, out) come out of the limit first; at the cells' widths the
    # weights still get their 48 MiB.
    held = (x.dtype.itemsize + 4) * T * H
    room = _SWIGLU_VMEM_LIMIT - held - 16 * tm * H - 8 * 2 ** 20
    tf = _swiglu_f_tile(F, H, jnp.dtype(w_gate.dtype).itemsize,
                        min(room, _SWIGLU_WEIGHTS_ROOM))
    nf = F // tf
    nl = jnp.asarray(n_live_tiles, jnp.int32).reshape(1)

    def tile_of(i, nl_p):
        return jnp.maximum(jnp.minimum(i, nl_p[0] - 1), 0)

    def w_map(i, j, te_p, nl_p, tr_p, src_p):
        # A dead tile names the block the last live step left in VMEM.
        return (te_p[tile_of(i, nl_p)],
                jnp.where(i < nl_p[0], j, nf - 1), 0)

    w_spec = pl.BlockSpec((1, tf, H), w_map)
    kernel = _GATED_KERNELS[act]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(nt, nf),
            in_specs=[pl.BlockSpec((T, H), lambda i, j, *_: (0, 0),
                                   pipeline_mode=pl.Buffered(1)),
                      w_spec, w_spec, w_spec],
            out_specs=pl.BlockSpec((tm, H), lambda i, j, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tm, H), jnp.float32),
                            pltpu.VMEM((tm, H), jnp.float32),
                            pltpu.VMEM((T, H), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((M, H), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_SWIGLU_VMEM_LIMIT),
        name=kernel.__name__,
        interpret=_interpret(),
    )(tile_expert.astype(jnp.int32), nl, tile_rows.astype(jnp.int32),
      src.astype(jnp.int32), x, w_gate, w_up, w_down)


__all__ = ["grouped_ffn", "grouped_gemm_enabled", "grouped_swiglu"]

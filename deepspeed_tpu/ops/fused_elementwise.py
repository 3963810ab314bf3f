"""The elementwise part of a transformer block: residual-add+LayerNorm
as Pallas row kernels, and the bias+GELU of the FFN up-projection as the
fp32 expression XLA fuses into the GEMMs beside it.

Why these exist (the non-GEMM part of the step): part of what a train
step spends outside its GEMMs is elementwise passes XLA
schedules as separate HBM round-trips — LayerNorm reads the residual
stream, computes mean/var in fp32, and writes it back; the residual add
that feeds it is another full read+write.  The reference attacked the
same class of overhead with fused CUDA transformer kernels
(``csrc/transformer/normalize_kernels.cu``, ``gelu_kernels.cu``).  A
LayerNorm's output is materialised before a GEMM either way, so a row
kernel that makes the one-pass property structural has something to
win; a bias+GELU sits BETWEEN two GEMMs, where a kernel of its own is a
read and a write of the ``[rows, F]`` tensor that fusion into a GEMM's
operand or output does without (PR 51: the two GELU kernels were 20.7 /
22.4 ms of the two train cells' steps, and are gone):

- ``fused_layer_norm``: LN over the last axis, fp32 statistics, one read
  of x and one write of y (fwd) — plus a custom-vjp backward kernel that
  RECOMPUTES mean/rstd in-block instead of saving them (the
  ``normalize_invertible`` idea: stats are rank-1 per row, recompute is
  cheaper than an HBM round-trip).
- ``fused_residual_layer_norm``: ``s = x + delta; y = LN(s)`` in one
  pass, returning BOTH (the residual stream continues from ``s``).  The
  backward fuses the LN input-gradient with the pass-through residual
  cotangent, so the residual stream's gradient is also one pass.
- ``bias_gelu``: ``gelu(y + bias)`` (tanh approximation by default,
  exact-erf behind a flag) in ``jax.numpy`` with the analytic derivative
  in its custom vjp — no saved activations beyond the matmul output that
  already exists.  Not a kernel and under no switch: every caller gets
  it (``models.transformer.gelu_dense_fn``).

Numerics contract (tests/test_fused_ln.py): all statistics and
transcendentals evaluate in fp32 exactly like the jnp reference
(``models.transformer.layer_norm`` / ``jax.nn.gelu``); fp32 tensors
agree with the reference to <= a few f32 ulp (cross-program reduction
association — the PR-1 FMA-contraction tolerance class), bf16 tensors to
<= 2 bf16 ulp (these paths round ONCE at the output where the
per-operation chain rounds per op — theirs is the more accurate value).

Sharding caveat (same class as ``ops/flash_attention``): a
``pallas_call`` is opaque to GSPMD, so under a mesh that shards
activations *declaratively* XLA gathers the operand around the kernel.
Every hot path that enables the LayerNorm kernels runs them where
tensors are already device-local: the ZeRO-2 engines' explicit shard_map
gradient path, the single-chip bench, and the serving decode/prefill
programs (slot-sharded caches enter via their own shard_map-free slot
math).  The ``materialization`` lint pass is the watchdog: an activation
gather around the kernel shows up as a tree-scale buffer and fails CI.
``bias_gelu`` is ordinary HLO and shards like any elementwise op.

Enable/disable (the LayerNorm kernels only): resolved per model config
(``TransformerConfig.fused_kernels``): ``"auto"`` = on when the backend
is TPU, off on CPU (interpret-mode Pallas is a correctness tool, not a
fast path); ``DS_FUSED_ELEMENTWISE=0/1`` overrides "auto" (the bench
ablation knob); ``True``/``False`` force — True on CPU runs the kernels
in interpret mode, which is how the tier-1 dp=8 mesh tests them.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

try:  # TPU backend bits are importable everywhere; interpret=True on CPU
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

from . import autotune

_LANE = 128
_SQRT_2_OVER_PI = math.sqrt(2.0 / math.pi)
_GELU_C = 0.044715
_ENV_KNOB = "DS_FUSED_ELEMENTWISE"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fused_elementwise_enabled(flag="auto") -> bool:
    """Resolve a config knob value to on/off: whether LayerNorm and
    residual-LayerNorm go through the Pallas kernels (nothing else
    listens to it since PR 51).

    ``True``/``False`` are forced; ``"auto"`` (the TransformerConfig
    default) is on exactly when the backend is TPU, overridable with
    DS_FUSED_ELEMENTWISE=0/1 (the bench/ablation switch).
    """
    if flag is True or flag is False:
        return bool(flag)
    env = os.environ.get(_ENV_KNOB)
    if env in ("0", "1"):
        return env == "1"
    return jax.default_backend() == "tpu"


_VMEM_BUDGET = 12 * 2 ** 20


def _geom(rows: int, H: int, n_bufs: int, kernel: str = None,
          dtype=None, runner=None, rb: int = None
          ) -> Tuple[int, int, int]:
    """(rows_pad, Hpad, rb): lane-pad H to a 128 multiple, pick the
    largest power-of-two row block whose ``n_bufs`` fp32 copies fit a
    conservative VMEM budget, pad rows to a block multiple.

    When ``kernel`` is given the row block resolves through
    ``ops.autotune`` (heuristic = the budget loop below, candidates =
    powers of two under the same budget); DS_AUTOTUNE=0 and CPU reduce
    to the heuristic bit-for-bit.  ``rb`` pins the block (the autotune
    measure runner's recursion guard)."""
    Hpad = -(-H // _LANE) * _LANE
    if rb is None:
        rb = 128
        while rb > 16 and rb * Hpad * 4 * n_bufs > _VMEM_BUDGET:
            rb //= 2
        if kernel is not None:
            cands = autotune.pow2_candidates(
                16, 256, lambda c: c * Hpad * 4 * n_bufs <= _VMEM_BUDGET)
            measure = autotune.measure_from_runner(runner) \
                if (runner is not None and autotune.search_allowed()) \
                else None
            rb = autotune.resolve(kernel, (rows, H, n_bufs),
                                  str(jnp.dtype(dtype or jnp.float32)),
                                  rb, cands, measure)
    rows_pad = -(-rows // rb) * rb
    return rows_pad, Hpad, rb


def _row_spec(rb: int, Hpad: int):
    if pltpu is not None and jax.default_backend() == "tpu":
        return pl.BlockSpec((rb, Hpad), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((rb, Hpad), lambda i: (i, 0))


def _whole_spec(Hpad: int):
    """(1, Hpad) broadcast block over a (1, Hpad) array (scale/bias
    rows): the block spans the array, which the TPU tiling accepts."""
    if pltpu is not None and jax.default_backend() == "tpu":
        return pl.BlockSpec((1, Hpad), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((1, Hpad), lambda i: (0, 0))


def _part_spec(Hpad: int):
    """Per-grid-step partial row: the partials array is [grid, 1, Hpad]
    so the block's last two dims span the array's (a (1, Hpad) block
    over [grid, Hpad] breaks the TPU (8, 128) tiling rule); the leading
    grid dim is squeezed and the kernel still sees (1, Hpad)."""
    if pltpu is not None and jax.default_backend() == "tpu":
        return pl.BlockSpec((None, 1, Hpad), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((None, 1, Hpad), lambda i: (i, 0, 0))


def _pad2(x2: jax.Array, rows_pad: int, Hpad: int) -> jax.Array:
    r, h = x2.shape
    if rows_pad > r or Hpad > h:
        x2 = jnp.pad(x2, ((0, rows_pad - r), (0, Hpad - h)))
    return x2


def _pad_row(v: jax.Array, Hpad: int) -> jax.Array:
    if Hpad > v.shape[0]:
        v = jnp.pad(v, (0, Hpad - v.shape[0]))
    return v.reshape(1, Hpad)


def _col_mask(shape, H: int):
    """True on real columns (H may be lane-padded)."""
    return lax.broadcasted_iota(jnp.int32, shape, 1) < H


# --------------------------------------------------------------------- #
# LayerNorm kernels
# --------------------------------------------------------------------- #
def _ln_stats(xs: jax.Array, H: int, Hpad: int, eps: float):
    """Row mean / rstd in fp32; pad columns are zero so they drop out of
    the mean for free, the variance masks them explicitly."""
    mean = jnp.sum(xs, axis=-1, keepdims=True) / H
    c = xs - mean
    if Hpad != H:
        c = jnp.where(_col_mask(c.shape, H), c, 0.0)
    var = jnp.sum(c * c, axis=-1, keepdims=True) / H
    return mean, lax.rsqrt(var + eps)


def _ln_fwd_kernel(x_ref, d_ref, scale_ref, bias_ref, *out_refs,
                   eps: float, H: int, Hpad: int, has_resid: bool,
                   out_dtype):
    """One row block: (optional residual add) + LayerNorm.

    The residual sum is rounded to the storage dtype BEFORE the
    statistics read it — bit-parity with the unfused ``x + attn`` (a
    bf16 add IS round(f32 sum)); the stats then widen back to fp32
    exactly like the reference ``layer_norm``.
    """
    x = x_ref[...].astype(jnp.float32)
    if has_resid:
        s_cast = (x + d_ref[...].astype(jnp.float32)).astype(out_dtype)
        out_refs[0][...] = s_cast
        xs = s_cast.astype(jnp.float32)
        y_out = out_refs[1]
    else:
        xs = x
        y_out = out_refs[0]
    mean, rstd = _ln_stats(xs, H, Hpad, eps)
    y = ((xs - mean) * rstd) * scale_ref[...].astype(jnp.float32) + \
        bias_ref[...].astype(jnp.float32)
    y_out[...] = y.astype(out_dtype)


def _ln_bwd_kernel(s_ref, scale_ref, dy_ref, gs_ref, dx_ref, dsc_ref,
                   dbi_ref, *, eps: float, H: int, Hpad: int,
                   has_gs: bool, out_dtype):
    """LN input-gradient + per-block dscale/dbias partials; mean/rstd
    recomputed in-block (rank-1 per row — cheaper than an HBM
    round-trip of saved stats).  ``gs`` is the residual-stream cotangent
    of the fused residual variant, added in the same pass."""
    s = s_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mean, rstd = _ln_stats(s, H, Hpad, eps)
    xhat = (s - mean) * rstd
    dxhat = dy * scale_ref[...].astype(jnp.float32)
    if Hpad != H:
        # dy's pad columns are zero by padding, but xhat's are not —
        # mask the terms that multiply xhat alone.
        dxhat = jnp.where(_col_mask(dxhat.shape, H), dxhat, 0.0)
    m1 = jnp.sum(dxhat, axis=-1, keepdims=True) / H
    m2 = jnp.sum(dxhat * xhat, axis=-1, keepdims=True) / H
    dx = (dxhat - m1 - xhat * m2) * rstd
    if has_gs:
        dx = dx + gs_ref[...].astype(jnp.float32)
    dx_ref[...] = dx.astype(out_dtype)
    dsc_ref[...] = jnp.sum(dy * xhat, axis=0, keepdims=True)
    dbi_ref[...] = jnp.sum(dy, axis=0, keepdims=True)


def _ln_forward(x, delta, scale, bias, eps: float, _rb: int = None):
    """Shared fwd driver: returns (s, y) — s is x when no residual."""
    shape, dtype = x.shape, x.dtype
    H = shape[-1]
    rows = int(math.prod(shape[:-1])) if len(shape) > 1 else 1
    has_resid = delta is not None

    def runner(rb_):
        dx = jnp.zeros((rows, H), dtype)
        dd = jnp.zeros((rows, H), dtype) if has_resid else None
        v = jnp.zeros((H,), jnp.float32)
        return _ln_forward(dx, dd, v, v, eps, _rb=rb_)

    rows_pad, Hpad, rb = _geom(rows, H, n_bufs=6 if has_resid else 5,
                               kernel="fused_ln_fwd", dtype=dtype,
                               runner=runner, rb=_rb)
    x2 = _pad2(x.reshape(rows, H), rows_pad, Hpad)
    args = [x2]
    if has_resid:
        args.append(_pad2(delta.reshape(rows, H), rows_pad, Hpad))
    else:
        args.append(jnp.zeros((1, Hpad), dtype))
    args.append(_pad_row(scale.astype(jnp.float32), Hpad))
    args.append(_pad_row(bias.astype(jnp.float32), Hpad))
    kernel = functools.partial(_ln_fwd_kernel, eps=eps, H=H, Hpad=Hpad,
                               has_resid=has_resid, out_dtype=dtype)
    n_out = 2 if has_resid else 1
    outs = pl.pallas_call(
        kernel,
        grid=(rows_pad // rb,),
        in_specs=[_row_spec(rb, Hpad),
                  _row_spec(rb, Hpad) if has_resid else _whole_spec(Hpad),
                  _whole_spec(Hpad), _whole_spec(Hpad)],
        out_specs=[_row_spec(rb, Hpad)] * n_out,
        out_shape=[jax.ShapeDtypeStruct((rows_pad, Hpad), dtype)] * n_out,
        name="_ln_fwd_kernel",
        interpret=_interpret(),
    )(*args)
    def unpad(a):
        return a[:rows, :H].reshape(shape)
    if has_resid:
        return unpad(outs[0]), unpad(outs[1])
    return x, unpad(outs[0])


def _ln_backward(s, scale, dy, gs, eps: float, _rb: int = None):
    """Shared bwd driver: (ds, dscale, dbias)."""
    shape, dtype = s.shape, s.dtype
    H = shape[-1]
    rows = int(math.prod(shape[:-1])) if len(shape) > 1 else 1
    has_gs = gs is not None

    def runner(rb_):
        d2 = jnp.zeros((rows, H), dtype)
        dg = jnp.zeros((rows, H), dtype) if has_gs else None
        v = jnp.zeros((H,), jnp.float32)
        return _ln_backward(d2, v, d2, dg, eps, _rb=rb_)

    rows_pad, Hpad, rb = _geom(rows, H, n_bufs=7 if has_gs else 6,
                               kernel="fused_ln_bwd", dtype=dtype,
                               runner=runner, rb=_rb)
    grid = rows_pad // rb
    s2 = _pad2(s.reshape(rows, H), rows_pad, Hpad)
    dy2 = _pad2(dy.reshape(rows, H), rows_pad, Hpad)
    gs2 = _pad2(gs.reshape(rows, H), rows_pad, Hpad) if has_gs \
        else jnp.zeros((1, Hpad), dtype)
    kernel = functools.partial(_ln_bwd_kernel, eps=eps, H=H, Hpad=Hpad,
                               has_gs=has_gs, out_dtype=dtype)
    dx, dsc, dbi = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[_row_spec(rb, Hpad), _whole_spec(Hpad),
                  _row_spec(rb, Hpad),
                  _row_spec(rb, Hpad) if has_gs else _whole_spec(Hpad)],
        out_specs=[_row_spec(rb, Hpad), _part_spec(Hpad),
                   _part_spec(Hpad)],
        out_shape=[jax.ShapeDtypeStruct((rows_pad, Hpad), dtype),
                   jax.ShapeDtypeStruct((grid, 1, Hpad), jnp.float32),
                   jax.ShapeDtypeStruct((grid, 1, Hpad), jnp.float32)],
        name="_ln_bwd_kernel",
        interpret=_interpret(),
    )(s2, _pad_row(scale.astype(jnp.float32), Hpad), dy2, gs2)
    ds = dx[:rows, :H].reshape(shape)
    dscale = jnp.sum(dsc[:, 0], axis=0)[:H].astype(scale.dtype)
    dbias = jnp.sum(dbi[:, 0], axis=0)[:H].astype(scale.dtype)
    return ds, dscale, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x, scale, bias, eps: float = 1e-5):
    """LayerNorm over the last axis, fp32 statistics, one fused pass.
    Drop-in for ``models.transformer.layer_norm``."""
    return _ln_forward(x, None, scale, bias, eps)[1]


def _fln_fwd(x, scale, bias, eps):
    y = _ln_forward(x, None, scale, bias, eps)[1]
    return y, (x, scale)


def _fln_bwd(eps, res, dy):
    x, scale = res
    return _ln_backward(x, scale, dy, None, eps)


fused_layer_norm.defvjp(_fln_fwd, _fln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_residual_layer_norm(x, delta, scale, bias, eps: float = 1e-5):
    """``s = x + delta; y = LN(s)`` in one pass; returns ``(s, y)``.

    ``s`` continues the residual stream, ``y`` feeds the next sublayer —
    the fusion the reference's ``normalize_invertible`` fused LN
    performs between every transformer sublayer.
    """
    return _ln_forward(x, delta, scale, bias, eps)


def _frln_fwd(x, delta, scale, bias, eps):
    s, y = _ln_forward(x, delta, scale, bias, eps)
    return (s, y), (s, scale)


def _frln_bwd(eps, res, cotangents):
    s, scale = res
    gs, gy = cotangents
    ds, dscale, dbias = _ln_backward(s, scale, gy, gs, eps)
    # d(x + delta)/dx == d(x + delta)/ddelta == identity: both inputs
    # receive the same combined cotangent.
    return ds, ds, dscale, dbias


fused_residual_layer_norm.defvjp(_frln_fwd, _frln_bwd)


# --------------------------------------------------------------------- #
# Bias + GELU: fp32 arithmetic for XLA to fuse (and for
# ops/grouped_gemm's in-kernel epilogue)
# --------------------------------------------------------------------- #
def _gelu_f32(z: jax.Array, exact: bool) -> jax.Array:
    if exact:
        return 0.5 * z * (1.0 + lax.erf(z / math.sqrt(2.0)))
    u = _SQRT_2_OVER_PI * (z + _GELU_C * z * z * z)
    return 0.5 * z * (1.0 + jnp.tanh(u))


def _dgelu_f32(z: jax.Array, exact: bool) -> jax.Array:
    if exact:
        phi = jnp.exp(-0.5 * z * z) / math.sqrt(2.0 * math.pi)
        return 0.5 * (1.0 + lax.erf(z / math.sqrt(2.0))) + z * phi
    u = _SQRT_2_OVER_PI * (z + _GELU_C * z * z * z)
    t = jnp.tanh(u)
    du = _SQRT_2_OVER_PI * (1.0 + 3.0 * _GELU_C * z * z)
    return 0.5 * (1.0 + t) + 0.5 * z * (1.0 - t * t) * du


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def bias_gelu(y, bias, exact: bool = False):
    """``gelu(y + bias)`` where ``y`` is the raw output of the FFN's
    up-projection GEMM: plain ``jax.numpy``, no kernel.  An elementwise
    expression is something XLA fuses into the GEMMs on either side of
    it; a ``pallas_call`` there cost a read and a write of ``y`` that
    existed only because a custom call cannot be fused (PR 51).  The
    arithmetic is the deleted kernel's: the bias sum rounds once to the
    storage dtype (what the unfused ``y + bias`` gives), the GELU is
    evaluated in fp32 (tanh form; ``exact`` selects erf) and rounds once
    at the end.  The backward keeps ``(y, bias)`` and nothing else,
    recomputes ``z``, rounds ``dy`` once and sums ``dbias`` from the
    fp32 ``dz``.  ``bias`` is ``[F]``, or anything that broadcasts
    against ``y``'s trailing axes."""
    return _bias_gelu(y, bias, exact)


def _bias_sum_f32(y, bias):
    return (y.astype(jnp.float32) + bias.astype(jnp.float32)).astype(
        y.dtype).astype(jnp.float32)


def _bias_gelu(y, bias, exact):
    return _gelu_f32(_bias_sum_f32(y, bias), exact).astype(y.dtype)


def _bias_gelu_fwd(y, bias, exact):
    return _bias_gelu(y, bias, exact), (y, bias)


def _bias_gelu_bwd(exact, res, g):
    y, bias = res
    dz = g.astype(jnp.float32) * _dgelu_f32(_bias_sum_f32(y, bias), exact)
    # The bias sum reads the fp32 ``dz``, over the axes the bias was
    # broadcast along (``[F]`` under ``[rows, F]``; an expert's
    # ``[E, 1, F]`` under ``[E, C, F]``).
    lead = dz.ndim - bias.ndim
    dbias = jnp.sum(dz, axis=tuple(range(lead)) + tuple(
        lead + i for i, n in enumerate(bias.shape)
        if n == 1 and dz.shape[lead + i] != 1)).reshape(bias.shape)
    # ``dy`` is read by two GEMMs (dx and dW).  Left to itself XLA writes
    # ``g`` out of the GEMM that makes it and evaluates ``g * gelu'(z)``
    # again in each reader's operand: three tanh passes and four reads of
    # ``y`` a layer, +3.2 / +1.7 ms a step in the train cells (PR 51).
    # The barrier makes ``dy`` what that GEMM writes, beside ``dbias``;
    # nothing of it is left in the compiled program.
    dy = lax.optimization_barrier(dz.astype(y.dtype))
    return dy, dbias.astype(bias.dtype)


bias_gelu.defvjp(_bias_gelu_fwd, _bias_gelu_bwd)


__all__ = ["fused_layer_norm", "fused_residual_layer_norm",
           "bias_gelu", "fused_elementwise_enabled"]

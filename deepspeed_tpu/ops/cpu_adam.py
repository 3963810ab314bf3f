"""DeepSpeedCPUAdam — host-resident Adam/AdamW for ZeRO-Offload.

Parity with reference ``ops/adam/cpu_adam.py:12`` (per-instance native
optimizer state keyed by opt_id, ``step`` with optional fused fp16 param
copy) on top of the C++ SIMD kernel in ``csrc/cpu_adam.cpp`` (reference
``csrc/adam/cpu_adam.cpp:21-147``). Falls back to a vectorized numpy
implementation of identical math when no compiler is available, so offload
works everywhere and the native path is a pure speedup.

All state is numpy fp32 in host RAM: masters (owned by the engine), moments
(owned here). The step optionally emits a bf16 staging copy in the same
pass — that buffer is what ``jax.device_put`` ships back to HBM.
"""
from __future__ import annotations

import ctypes
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .op_builder import cpu_adam_builder
from ..utils.logging import logger

_f32p = ctypes.POINTER(ctypes.c_float)
_u16p = ctypes.POINTER(ctypes.c_uint16)


def host_f32(x) -> np.ndarray:
    """Owned, writable, C-contiguous fp32 host copy of ``x``.

    np.asarray of a CPU-backend jax array is a ZERO-COPY read-only view of
    the jax buffer — handing that to the in-place SIMD kernel would mutate
    the caller's arrays behind XLA's back. Likewise a backend may hand
    back F-ordered views, whose flat layout must not leak into kernel
    state (flat-index pairing breaks across a serialization round-trip).
    """
    a = np.asarray(x, np.float32)
    if a.base is not None or not a.flags["OWNDATA"] \
            or not a.flags["C_CONTIGUOUS"] or not a.flags["WRITEABLE"]:
        a = np.array(a, np.float32, order="C")
    return a


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.ds_adam_step.argtypes = [
        _f32p, _f32p, _f32p, _f32p, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ctypes.c_float, ctypes.c_int32, ctypes.c_float]
    lib.ds_adam_step.restype = None
    lib.ds_adam_step_plus_copy.argtypes = [
        _f32p, _f32p, _f32p, _f32p, _u16p, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ctypes.c_float, ctypes.c_int32, ctypes.c_float]
    lib.ds_adam_step_plus_copy.restype = None
    lib.ds_grad_norm_sq.argtypes = [_f32p, ctypes.c_int64, ctypes.c_float]
    lib.ds_grad_norm_sq.restype = ctypes.c_double
    lib.ds_adam_step_bf16g.argtypes = [
        _f32p, _u16p, _f32p, _f32p, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ctypes.c_float, ctypes.c_int32, ctypes.c_float]
    lib.ds_adam_step_bf16g.restype = None
    lib.ds_adam_step_plus_copy_bf16g.argtypes = [
        _f32p, _u16p, _f32p, _f32p, _u16p, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ctypes.c_float, ctypes.c_int32, ctypes.c_float]
    lib.ds_adam_step_plus_copy_bf16g.restype = None
    lib.ds_grad_norm_sq_bf16.argtypes = [_u16p, ctypes.c_int64,
                                         ctypes.c_float]
    lib.ds_grad_norm_sq_bf16.restype = ctypes.c_double
    return lib


def _is_bf16(a) -> bool:
    """ml_dtypes.bfloat16 ndarray."""
    d = getattr(a, "dtype", None)
    return d is not None and getattr(d, "name", "") == "bfloat16"


_LIB: Optional[ctypes.CDLL] = None
_LIB_FAILED = False


def _native_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _LIB_FAILED
    if _LIB is None and not _LIB_FAILED:
        builder = cpu_adam_builder()
        if not builder.is_compatible():
            _LIB_FAILED = True
            logger.warning("cpu_adam: no C++ compiler; using numpy fallback")
        else:
            try:
                _LIB = _bind(builder.jit_load())
            except Exception as e:  # pragma: no cover
                _LIB_FAILED = True
                logger.warning(f"cpu_adam native build failed ({e}); "
                               "using numpy fallback")
    return _LIB


def _ptr(a: np.ndarray, ty=_f32p):
    return a.ctypes.data_as(ty)


class DeepSpeedCPUAdam:
    """Host Adam over a pytree of fp32 numpy masters (updated in place)."""

    def __init__(self, params: Dict[str, Any], lr: float = 1e-3,
                 betas: Tuple[float, float] = (0.9, 0.999), eps: float = 1e-8,
                 weight_decay: float = 0.0, adamw_mode: bool = True):
        import jax
        self.lr = float(lr)
        self.betas = (float(betas[0]), float(betas[1]))
        self.eps = float(eps)
        self.weight_decay = float(weight_decay)
        self.adamw_mode = bool(adamw_mode)
        self.step_count = 0
        leaves, self.treedef = jax.tree_util.tree_flatten(params)
        # Plain C-ordered zeros — zeros_like would inherit the (possibly
        # F-ordered) layout of backend views, see host_f32.
        self.exp_avg = [np.zeros(np.shape(l), np.float32) for l in leaves]
        self.exp_avg_sq = [np.zeros(np.shape(l), np.float32) for l in leaves]
        self._lib = _native_lib()

    @property
    def native(self) -> bool:
        return self._lib is not None

    def state_dict(self) -> Dict[str, Any]:
        return {"step": self.step_count, "exp_avg": list(self.exp_avg),
                "exp_avg_sq": list(self.exp_avg_sq)}

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        self.step_count = int(sd["step"])
        self.exp_avg = [host_f32(a) for a in sd["exp_avg"]]
        self.exp_avg_sq = [host_f32(a) for a in sd["exp_avg_sq"]]

    # ------------------------------------------------------------------ #
    def step(self, master_leaves, grad_leaves, lr: Optional[float] = None,
             grad_scale: float = 1.0, bf16_out: Optional[list] = None) -> None:
        """One optimizer step over flat leaf lists, in place.

        ``grad_scale`` folds the loss-scale inverse and clip coefficient
        into the kernel's gradient read (single pass). With ``bf16_out``
        (list of uint16 arrays, same shapes) the updated masters are also
        down-cast in the same pass (ds_adam_step_plus_copy parity).
        """
        self.step_count += 1
        self.step_leaves(master_leaves, grad_leaves,
                         range(len(master_leaves)), lr=lr,
                         grad_scale=grad_scale, bf16_out=bf16_out,
                         step=self.step_count)

    def step_leaves(self, master_leaves, grad_leaves, indices,
                    lr: Optional[float] = None, grad_scale: float = 1.0,
                    bf16_out: Optional[list] = None,
                    step: Optional[int] = None) -> None:
        """Per-bucket Adam: update ``master_leaves[i]`` for ``i`` in
        ``indices`` from ``grad_leaves[j]`` (the j-th grad pairs with the
        j-th index), in place.

        ``step`` is the bias-correction tick, passed EXPLICITLY so
        concurrent per-bucket callers share one optimizer step without
        racing on ``step_count`` — the bucketed offload pipeline updates
        ``step_count`` once, after every bucket has applied. Leaves are
        disjoint per bucket, so calls for different buckets are thread-safe
        (the native kernels and numpy both release the GIL for the heavy
        loops)."""
        t = int(self.step_count if step is None else step)
        lr = self.lr if lr is None else float(lr)
        b1, b2 = self.betas
        for j, i in enumerate(indices):
            p, g = master_leaves[i], grad_leaves[j]
            assert p.dtype == np.float32 and p.flags["C_CONTIGUOUS"], \
                "masters must be contiguous fp32"
            m, v = self.exp_avg[i], self.exp_avg_sq[i]
            if self._lib is not None and _is_bf16(g):
                # BF16 grads straight into the kernel: no host-side cast
                # pass, half the gradient read traffic.
                gb = np.ascontiguousarray(g).view(np.uint16)
                if bf16_out is not None:
                    self._lib.ds_adam_step_plus_copy_bf16g(
                        _ptr(p), _ptr(gb, _u16p), _ptr(m), _ptr(v),
                        _ptr(bf16_out[i], _u16p), p.size, t,
                        lr, b1, b2, self.eps, self.weight_decay,
                        int(self.adamw_mode), grad_scale)
                else:
                    self._lib.ds_adam_step_bf16g(
                        _ptr(p), _ptr(gb, _u16p), _ptr(m), _ptr(v), p.size,
                        t, lr, b1, b2, self.eps,
                        self.weight_decay, int(self.adamw_mode), grad_scale)
                continue
            g = np.ascontiguousarray(np.asarray(g, np.float32))
            if self._lib is not None:
                if bf16_out is not None:
                    self._lib.ds_adam_step_plus_copy(
                        _ptr(p), _ptr(g), _ptr(m), _ptr(v),
                        _ptr(bf16_out[i], _u16p), p.size, t,
                        lr, b1, b2, self.eps, self.weight_decay,
                        int(self.adamw_mode), grad_scale)
                else:
                    self._lib.ds_adam_step(
                        _ptr(p), _ptr(g), _ptr(m), _ptr(v), p.size,
                        t, lr, b1, b2, self.eps,
                        self.weight_decay, int(self.adamw_mode), grad_scale)
            else:
                self._numpy_step(p, g, m, v, lr, grad_scale, t)
                if bf16_out is not None:
                    bf16_out[i][...] = _f32_to_bf16_np(p)

    def _numpy_step(self, p, g, m, v, lr, grad_scale, t) -> None:
        b1, b2 = self.betas
        g = g * grad_scale
        if not self.adamw_mode and self.weight_decay:
            g = g + self.weight_decay * p
        m *= b1
        m += (1 - b1) * g
        v *= b2
        v += (1 - b2) * np.square(g)
        bc1 = 1 - b1 ** t
        bc2 = 1 - b2 ** t
        denom = np.sqrt(v) / np.sqrt(bc2) + self.eps
        if self.adamw_mode and self.weight_decay:
            p -= lr * self.weight_decay * p
        p -= (lr / bc1) * (m / denom)

    def grad_norm_sq(self, grad_leaves, grad_scale: float = 1.0) -> float:
        """Squared L2 norm of the (scaled) gradients, accumulated per leaf
        in list order (float64 partials). The per-bucket entry point: the
        bucketed offload path sums these partials in bucket-index order, so
        overlapped and serial execution of the SAME bucketing produce the
        identical double — the overflow vote and clip coefficient cannot
        diverge between the two modes."""
        acc = 0.0
        for g in grad_leaves:
            if self._lib is not None and _is_bf16(g):
                gb = np.ascontiguousarray(g).view(np.uint16)
                acc += float(self._lib.ds_grad_norm_sq_bf16(
                    _ptr(gb, _u16p), gb.size, grad_scale))
                continue
            g = np.ascontiguousarray(np.asarray(g, np.float32))
            if self._lib is not None:
                acc += float(self._lib.ds_grad_norm_sq(
                    _ptr(g), g.size, grad_scale))
            else:
                gd = g.astype(np.float64) * grad_scale
                acc += float(np.sum(gd * gd))
        return acc

    def grad_norm(self, grad_leaves, grad_scale: float = 1.0) -> float:
        """Global L2 norm of the (scaled) gradients, host-side."""
        return float(np.sqrt(self.grad_norm_sq(grad_leaves, grad_scale)))


def _f32_to_bf16_np(a: np.ndarray) -> np.ndarray:
    """fp32 -> bf16 bits with round-to-nearest-even (numpy fallback)."""
    x = a.view(np.uint32)
    lsb = (x >> 16) & 1
    rounded = x + 0x7FFF + lsb
    return (rounded >> 16).astype(np.uint16)

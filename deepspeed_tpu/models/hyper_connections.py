"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606): the residual path of a block kept
as ``n`` streams ``X [..., n, C]`` that every sublayer F reads through one
learned, input-dependent map and writes back through two more.

Per token and sublayer, with ``phi [nC, 2n + n*n]``, ``b [2n + n*n]`` and
``alpha [3]`` (all fp32; the columns are pre, post, then res row-major):

    u      = vec(X) * rsqrt(mean(vec(X)^2) + eps)        over all nC values
    H_pre  = sigmoid(alpha[0] * (u phi_pre) + b_pre)              [n]
    H_post = 2 * sigmoid(alpha[1] * (u phi_post) + b_post)        [n]
    M      = exp(clip(alpha[2] * mat(u phi_res) + b_res, lo, hi)) [n, n]
    iters times:  M /= colsum(M) + eps;  M /= rowsum(M) + eps
    h      = sum_j H_pre[j] X[j]              the sublayer's input
    X'[i]  = sum_j M[i, j] X[j] + H_post[i] F(norm(h))

``M`` is doubly stochastic as far as ``iters`` Sinkhorn-Knopp iterations
bring it (``res_error`` reads what they leave), so the streams' sum is
carried through a layer unchanged and only ``H_post`` scales what F adds.
The maps and the mixes are fp32; ``X`` is stored in the model's dtype.

The maps are laid out with the TOKENS on the minor axis (``[n, T]``,
``[n, n, T]``): twenty iterations over ``[T, 4, 4]`` would pad every 4 x 4
to a whole (8, 128) tile on a TPU.  The mixes are written as the ``n`` /
``n * n`` multiply-adds they are, elementwise over C, and never as a
contraction: a product over a width of 4 at the MXU's default precision
would round the maps to bf16.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax


class HyperConnections(NamedTuple):
    """The five published keys (``hc_mult`` streams, ``hc_sinkhorn_iters``,
    ``hc_eps``, ``mhc_h_res_clamp_min`` / ``_max``)."""
    mult: int
    iters: int
    eps: float
    clamp: Tuple[float, float]

    @property
    def columns(self) -> int:
        return 2 * self.mult + self.mult * self.mult


class Maps(NamedTuple):
    """One sublayer's maps for tokens ``[...]``: pre ``[n, ...]``, post
    ``[n, ...]``, res ``[n, n, ...]`` (fp32)."""
    pre: jax.Array
    post: jax.Array
    res: jax.Array


def param_shapes(hc: HyperConnections, width: int
                 ) -> Dict[str, Tuple[int, ...]]:
    """One sublayer's parameters (fp32) for streams of ``width``."""
    return {"phi": (hc.mult * width, hc.columns), "b": (hc.columns,),
            "alpha": (3,)}


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``m [n, n, ...]`` positive -> columns, then rows, normalised
    ``iters`` times (unrolled: the operands are a few vector registers)."""
    for _ in range(iters):
        m = m / (m.sum(axis=0, keepdims=True) + eps)
        m = m / (m.sum(axis=1, keepdims=True) + eps)
    return m


def maps(phi: jax.Array, b: jax.Array, alpha: jax.Array, X: jax.Array,
         hc: HyperConnections) -> Maps:
    """The three maps of one sublayer for ``X [..., n, C]``."""
    n = hc.mult
    lead = X.shape[:-2]
    xf = X.reshape(-1, n * X.shape[-1]).astype(jnp.float32)       # [T, nC]
    r = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1) + hc.eps)     # [T]
    # (x r) phi = (x phi) r: the product does not wait for the norm.  fp32
    # operands at the MXU's default precision are ONE bf16 pass on the chip.
    t = jnp.einsum("tc,cm->mt", xf, phi.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * r[None]     # [m, T]
    b = b.astype(jnp.float32)[:, None]
    pre = jax.nn.sigmoid(alpha[0] * t[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * t[n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(alpha[2] * t[2 * n:] + b[2 * n:], *hc.clamp))
    res = sinkhorn(res.reshape(n, n, -1), hc.iters, hc.eps)
    return Maps(pre.reshape((n,) + lead), post.reshape((n,) + lead),
                res.reshape((n, n) + lead))


def mix_in(m: Maps, X: jax.Array) -> jax.Array:
    """``h = sum_j H_pre[j] X[j]``: ``[..., n, C] -> [..., C]`` in X's
    dtype."""
    xf = X.astype(jnp.float32)
    h = sum(m.pre[j][..., None] * xf[..., j, :] for j in range(X.shape[-2]))
    return h.astype(X.dtype)


def mix_out(m: Maps, X: jax.Array, y: jax.Array) -> jax.Array:
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` in X's dtype."""
    n = X.shape[-2]
    xf, yf = X.astype(jnp.float32), y.astype(jnp.float32)
    rows = [sum(m.res[i, j][..., None] * xf[..., j, :] for j in range(n))
            + m.post[i][..., None] * yf for i in range(n)]
    return jnp.stack(rows, axis=-2).astype(X.dtype)


def expand(x: jax.Array, n: int) -> jax.Array:
    """The embedding copied to n streams: ``[..., C] -> [..., n, C]``."""
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (n, x.shape[-1]))


def collapse(X: jax.Array) -> jax.Array:
    """What the final norm reads: the streams' sum (fp32 inside)."""
    return X.astype(jnp.float32).sum(axis=-2).astype(X.dtype)


def res_error(m: Maps, live: jax.Array) -> jax.Array:
    """The largest deviation of a row or column sum of ``H_res`` from 1
    over the ``live [...]`` tokens (fp32 scalar)."""
    dev = jnp.maximum(jnp.abs(m.res.sum(axis=0) - 1.0).max(axis=0),
                      jnp.abs(m.res.sum(axis=1) - 1.0).max(axis=0))
    return jnp.max(jnp.where(live, dev, 0.0))


__all__ = ["HyperConnections", "Maps", "param_shapes", "sinkhorn", "maps",
           "mix_in", "mix_out", "expand", "collapse", "res_error"]

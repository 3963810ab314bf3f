"""Block primitives more than one model family is written in: RMS norm,
rotary angles and the rotate-half rotation, the gated-SiLU FFN, the compute-dtype product, and the
description of an expert layer's routing that ``moe/share.py`` reads.  A
family's own file (``deepseek_v3.py``, ``brumby.py``, ``afmoe.py``) holds
its config, its init and what only it has; none imports these from a
sibling.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Routing(NamedTuple):
    """An expert layer's rule (``moe/share.py``) in numbers: ``experts``
    routed experts of which ``per_tok`` are chosen a token, by ``rule``:

    - ``"sigmoid_bias"`` (``deepseek_v3``'s ``noaux_tc``; ``afmoe``,
      ``lfm2_moe``): sigmoid scores with a selection bias, in ``n_group``
      groups of which the ``topk_group`` best stay (1 / 1: no group limit);
      the weights are the scores at the chosen;
    - ``"softmax_topk"`` (``smallthinker``): the ``per_tok`` largest LOGITS,
      the weights a softmax over those; no bias, no groups.

    Either way the weights are divided by their sum where ``norm`` (their
    sum + ``norm_eps``: 1e-20 as ``deepseek_v3`` and ``afmoe`` publish it,
    1e-6 ``lfm2_moe``, 0 ``smallthinker``), times ``scale``; and ``held`` =
    (first, count), the share this program holds."""
    experts: int
    per_tok: int
    n_group: int
    topk_group: int
    norm: bool
    scale: float
    held: Tuple[int, int]
    norm_eps: float = 1e-20
    rule: str = "sigmoid_bias"


def rotary_cos_sin(inv_freq, positions: jax.Array, scale: float = 1.0):
    """fp32 ``scale`` x cos, sin ``[..., len(inv_freq)]`` of ``positions``
    x ``inv_freq``: rotary positions with whatever frequencies a family
    states."""
    ang = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rotary_inv_freq(theta: float, head_dim: int) -> np.ndarray:
    """float64 [head_dim / 2]: ``theta^(-2i / head_dim)``, unscaled."""
    return float(theta) ** (-np.arange(0, head_dim, 2, dtype=np.float64)
                            / head_dim)


def rope_half(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the pairs ``(i, i + D/2)`` of the last axis by frequency i
    (rotate-half).  cos/sin ``[..., D/2]`` broadcast against the halves;
    fp32 inside, x's dtype out."""
    xf = x.astype(jnp.float32)
    a, b = jnp.split(xf, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """``x * rsqrt(mean(x^2) + eps) * w`` in fp32, x's dtype out."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32)).astype(x.dtype)


def matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """Compute-dtype product, fp32 accumulation, x's dtype out."""
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)


def swiglu(x: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array
           ) -> jax.Array:
    """``down(silu(gate x) * up x)``, weights ``[in, out]``."""
    g = jnp.dot(x, gate.astype(x.dtype), preferred_element_type=jnp.float32)
    u = jnp.dot(x, up.astype(x.dtype), preferred_element_type=jnp.float32)
    return matmul((jax.nn.silu(g) * u).astype(x.dtype), down)


__all__ = ["rotary_cos_sin", "rotary_inv_freq", "rope_half", "rms_norm",
           "matmul", "swiglu"]

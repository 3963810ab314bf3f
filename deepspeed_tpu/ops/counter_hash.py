"""The stateless counter hash behind every regenerable random draw that
is a function of (a seed, an element's global index): the fused
optimizer's stochastic-rounding noise (``ops/fused_update``) and the
residual-dropout keep-masks (``models/transformer.dropout``). Plain
``jnp`` integer arithmetic, so it runs inside a Pallas kernel, in
interpret mode and as ordinary XLA ops alike, bit for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def hash_u32(x: jax.Array) -> jax.Array:
    """murmur3's 32-bit finalizer: a bijection of ``uint32`` in which
    every input bit flips every output bit with probability ~1/2."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> jnp.uint32(16))

"""Capability probe for backend-dependent features: the skip tracks what
the backend can actually compile, not a platform or version string."""
import functools

import jax


@functools.lru_cache(maxsize=None)
def fused_elementwise_skip_reason():
    """None when this backend can compile the fused elementwise Pallas
    kernels (interpret mode on CPU, native on TPU) — probed by building
    a minimal fused LayerNorm program, so the skip tracks actual
    capability, not a platform string."""
    try:
        import jax.numpy as jnp
        from deepspeed_tpu.ops.fused_elementwise import fused_layer_norm
        x = jnp.ones((8, 128), jnp.float32)
        s = jnp.ones((128,), jnp.float32)
        b = jnp.zeros((128,), jnp.float32)
        jax.jit(lambda x, s, b: fused_layer_norm(x, s, b)) \
            .lower(x, s, b).compile()
        return None
    except Exception as e:   # pragma: no cover - exotic backends only
        return ("fused elementwise Pallas kernels cannot compile on this "
                f"backend: {type(e).__name__}: {e}")


def fused_elementwise_supported() -> bool:
    return fused_elementwise_skip_reason() is None

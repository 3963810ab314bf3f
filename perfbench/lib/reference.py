"""GPT-2 in plain float32 ``jax.numpy``: the reference the system's
outputs are held to.

No kernels, no cache, no batching tricks, no remat; every matrix product
at ``highest`` precision (on a TPU a float32 product otherwise runs in
bf16 passes).  Follows the published model: token + position embedding,
pre-LayerNorm blocks (causal multi-head attention scaled by
1/sqrt(head), tanh-GELU FFN of width 4H), final LayerNorm, logits
through the tied embedding.  Departure: it has no dropout, so it is the
model at evaluation; the runners compare it with the program's
dropout-free forward (training) and with served logits and tokens.

It reads the parameter tree the program's ``gpt2_init`` produces (stacked
per-layer tensors under ``blocks``) and upcasts each tensor where it is
used, so bf16-served weights need no float32 copy of the model.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax


def _f32(a):
    return a.astype(jnp.float32)


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * _f32(scale) + _f32(bias)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def logits(params, tokens, *, num_heads: int, eps: float = 1e-5):
    """tokens int32 [B, S] -> float32 logits [B, S, V]."""
    with jax.default_matmul_precision("highest"):
        B, S = tokens.shape
        x = _f32(params["wte"])[tokens] + _f32(params["wpe"])[None, :S]
        H = x.shape[-1]
        dh = H // num_heads
        causal = jnp.tril(jnp.ones((S, S), jnp.bool_))

        def block(x, p):
            h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], eps)
            qkv = h @ _f32(p["qkv_kernel"]) + _f32(p["qkv_bias"])
            q, k, v = (t.reshape(B, S, num_heads, dh)
                       for t in jnp.split(qkv, 3, axis=-1))
            s = jnp.einsum("bsnd,btnd->bnst", q, k) / math.sqrt(dh)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jnp.einsum("bnst,btnd->bsnd", jax.nn.softmax(s, axis=-1), v)
            x = x + a.reshape(B, S, H) @ _f32(p["proj_kernel"]) \
                + _f32(p["proj_bias"])
            h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], eps)
            h = _gelu_tanh(h @ _f32(p["fc_kernel"]) + _f32(p["fc_bias"]))
            x = x + h @ _f32(p["fc_out_kernel"]) + _f32(p["fc_out_bias"])
            return x, None

        x, _ = lax.scan(block, x, params["blocks"])
        x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"], eps)
        return x @ _f32(params["wte"]).T


def next_token_loss(params, rows, *, num_heads: int, eps: float = 1e-5):
    """rows int32 [B, S + 1] -> per-row mean next-token cross-entropy
    [B] (inputs rows[:, :-1], targets rows[:, 1:])."""
    lg = logits(params, rows[:, :-1], num_heads=num_heads, eps=eps)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, rows[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked, axis=-1)

"""The ``brumby`` family (Brumby-14B-Base): every layer a POWER-RETENTION
layer — no softmax attention anywhere, so no K/V row is ever kept.  What a
layer keeps of a stream is a fixed-size recurrent state, whatever the
context length.

This module is the MODEL: its config from the published ``config.json``
keys, a seeded init and the projections every path shares.  The retention
itself (the feature map, the recurrent / chunked forms, the decode kernel)
is ``ops/power_retention.py``; how it is served (the state pool, the
snapshots) is ``inference/retention.py``.  Nothing here is imported unless
a configuration asks for it.

A layer (all norms ``x * rsqrt(mean(x^2) + eps) * w``)::

    h += Ret(norm1(h));  h += Wdown(silu(Wgate x) * Wup x), x = norm2(h)

    q = x Wq [nH x D]   k = x Wk [nKV x D]   v = x Wv [nKV x D]
    q, k: per-head RMS norm (weight [D]), then rotary positions, pairs
          (i, i + D/2), all D dimensions, ``rope_theta``, no scaling
    log g = log_sigmoid(x Wg + bg)  [nKV], float32: one gate a K/V head
    A_ij = exp(G_i - G_j) (q_i . k_j / sqrt(D))^p   (j <= i;  G = cumsum
           log g;  p = ``retention_power`` = 2, even: every weight >= 0)
    y_i  = sum_j A_ij v_j / (sum_j A_ij + eps);   Ret = concat_h(y) Wo

Query head ``h`` reads K/V head ``h // (nH / nKV)`` (grouped heads).

What the published config does NOT state and is assumed here (the
benchmark's configuration file lists each with its why): the power (2), the
gate's form (a linear map WITH a bias to one logit a K/V head, through
``log_sigmoid``), q/k norm and rotary kept from the Qwen3 parent whose keys
the config carries, ``eps``, the 1/sqrt(D).

Parameter tree (weights ``[in, out]``; per-layer tensors stacked on a
leading axis under ``layers``)::

    embed [V, H]   lm_head [V, H]   final_norm [H]
    layers: input_norm [H]  wq [H, nH*D]  wk [H, nKV*D]  wv [H, nKV*D]
            wg [H, nKV]  bg [nKV] (fp32)  q_norm [D]  k_norm [D]
            wo [nH*D, H]  post_norm [H]
            mlp_gate [H, I]  mlp_up [H, I]  mlp_down [I, H]
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict

import jax
import jax.numpy as jnp

import numpy as np

from .blocks import matmul, rms_norm, rotary_cos_sin, swiglu  # noqa: F401


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """The published keys (same names) plus the assumed ones (module
    docstring) and the seeded gate's half-life range."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    initializer_range: float = 0.02
    # assumed (not in the published config)
    retention_power: int = 2
    retention_eps: float = 1e-6
    # seeded init only: half-lives ln 2 / -log g of the heads' gates,
    # spread log-uniformly (as trained gated retention spreads them)
    gate_half_life_min: float = 64.0
    gate_half_life_max: float = 16384.0
    dtype: Any = jnp.bfloat16
    serving_module: ClassVar[str] = "deepspeed_tpu.inference.retention"

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads (grouped heads)")
        if self.retention_power != 2:
            raise NotImplementedError(
                "only power 2 is written: the feature map phi(a) . phi(b) "
                "= (a . b)^2 (ops/power_retention.py)")
        if self.head_dim % 2:
            raise ValueError("rotary pairs need an even head_dim")

    @classmethod
    def from_hf(cls, cfg: Dict[str, Any], **overrides) -> "BrumbyConfig":
        """From a ``config.json`` dict: every key this class names is
        taken as published (the inert ``sliding_window`` keys and the
        rest are not read)."""
        if cfg.get("rope_scaling"):
            raise NotImplementedError("no rope_scaling is written")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names and k != "dtype"}
        kw.update(overrides)
        return cls(**kw)

    @property
    def name(self) -> str:
        return f"brumby-h{self.hidden_size}-l{self.num_hidden_layers}"

    @property
    def group_size(self) -> int:
        """Query heads a K/V head."""
        return self.num_attention_heads // self.num_key_value_heads


def rope_cos_sin(cfg: BrumbyConfig, positions: jax.Array):
    """fp32 cos, sin ``[..., D/2]``: frequency i is ``rope_theta ** (-2i /
    D)`` over all D dimensions, no scaling."""
    D = cfg.head_dim
    return rotary_cos_sin(cfg.rope_theta ** (-np.arange(0, D, 2) / D),
                          positions)


def rope_half(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the pairs ``(i, i + D/2)`` of the last axis by frequency i
    (the Qwen / Llama convention).  cos/sin ``[..., D/2]`` broadcast
    against a half of ``x``; fp32 inside and out."""
    xf = x.astype(jnp.float32)
    half = xf.shape[-1] // 2
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def retention_projections(p: Dict[str, jax.Array], h: jax.Array,
                          positions: jax.Array, cfg: BrumbyConfig):
    """The projections ahead of the retention, for normed input ``h
    [..., H]`` at ``positions [...]``: (q [..., nH, D], k [..., nKV, D], v
    [..., nKV, D] in the compute dtype — q and k normed per head and
    rotated, in fp32, then rounded — and log g [..., nKV] fp32)."""
    nH, nKV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    lead = h.shape[:-1]
    q = matmul(h, p["wq"]).reshape(lead + (nH, D))
    k = matmul(h, p["wk"]).reshape(lead + (nKV, D))
    v = matmul(h, p["wv"]).reshape(lead + (nKV, D))
    cos, sin = rope_cos_sin(cfg, positions)
    cos, sin = cos[..., None, :], sin[..., None, :]
    q = rope_half(rms_norm(q, p["q_norm"], cfg.rms_norm_eps), cos, sin)
    k = rope_half(rms_norm(k, p["k_norm"], cfg.rms_norm_eps), cos, sin)
    gamma = jnp.dot(h, p["wg"].astype(h.dtype),
                    preferred_element_type=jnp.float32) + p["bg"]
    return (q.astype(h.dtype), k.astype(h.dtype), v,
            jax.nn.log_sigmoid(gamma))


def gate_bias(key: jax.Array, cfg: BrumbyConfig) -> jax.Array:
    """``bg [L, nKV]`` fp32 such that ``log_sigmoid(bg)`` = -ln 2 / T with
    the half-lives T stratified log-uniformly over the config's range (a
    jittered stratum a head, dealt in another order in every layer).
    With ``bg = 0`` every head would forget in two tokens and no
    comparison could tell a stale state from a right one."""
    L, n = cfg.num_hidden_layers, cfg.num_key_value_heads
    k_perm, k_jit = jax.random.split(key)
    strata = jax.vmap(lambda k: jax.random.permutation(k, n))(
        jax.random.split(k_perm, L)).astype(jnp.float32)
    u = (strata + jax.random.uniform(k_jit, (L, n))) / n
    lo, hi = math.log(cfg.gate_half_life_min), \
        math.log(cfg.gate_half_life_max)
    rate = math.log(2.0) / jnp.exp(lo + (hi - lo) * u)     # -log g
    return -jnp.log(jnp.expm1(rate))


def _layer_shapes(cfg: BrumbyConfig):
    H, I = cfg.hidden_size, cfg.intermediate_size
    nH, nKV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    return {"wq": (H, nH * D), "wk": (H, nKV * D), "wv": (H, nKV * D),
            "wg": (H, nKV), "wo": (nH * D, H), "mlp_gate": (H, I),
            "mlp_up": (H, I), "mlp_down": (I, H)}


def brumby_init(rng: jax.Array, cfg: BrumbyConfig) -> Dict[str, Any]:
    """Weights normal(0, initializer_range) in ``cfg.dtype``, norms 1, the
    gate's bias from ``gate_bias`` in fp32."""
    L, H, D = cfg.num_hidden_layers, cfg.hidden_size, cfg.head_dim
    std = cfg.initializer_range
    k_emb, k_head, k_layers, k_bias = jax.random.split(rng, 4)

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * std).astype(cfg.dtype)

    shapes = sorted(_layer_shapes(cfg).items())
    layers = {name: normal(k, (L,) + shape) for k, (name, shape) in
              zip(jax.random.split(k_layers, len(shapes)), shapes)}
    for name, width in (("input_norm", H), ("post_norm", H), ("q_norm", D),
                        ("k_norm", D)):
        layers[name] = jnp.ones((L, width), cfg.dtype)
    layers["bg"] = gate_bias(k_bias, cfg)
    return {"embed": normal(k_emb, (cfg.vocab_size, H)),
            "lm_head": normal(k_head, (cfg.vocab_size, H)),
            "final_norm": jnp.ones((H,), cfg.dtype), "layers": layers}


__all__ = ["BrumbyConfig", "brumby_init", "gate_bias", "rope_cos_sin",
           "rope_half", "retention_projections", "rms_norm", "matmul",
           "swiglu"]

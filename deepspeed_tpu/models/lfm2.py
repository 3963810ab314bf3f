"""The ``lfm2_moe`` family (Liquid LFM2-24B-A2B / LFM2-8B-A1B): most layers
a GATED SHORT CONVOLUTION, a depthwise causal filter of ``conv_L_cache``
taps between two input-dependent gates, which keeps a fixed-size state a
stream; every fourth or so a grouped-query ATTENTION layer with per-head
RMS norm on q and k and rotary positions; leading dense SwiGLU layers, then
expert layers routed by sigmoid scores with a selection bias (one group,
no shared expert).

This module is the MODEL: its config from the published ``config.json``
keys, a seeded init and the pieces every path shares.  How it is served
(K/V pages of the attention layers and a state a stream of the conv layers,
two KINDS of cache in one manager) is ``inference/lfm2.py``; the expert
layer is ``moe/share.py`` under this family's ``Routing``; RMS norm, the
gated FFN and the rotary angles are ``models/blocks.py``'s.  Nothing here
is imported unless a configuration asks for it.

Layer ``l`` is of ``layer_types[l]``; layers ``0 .. num_dense_layers - 1``
have the dense FFN.  With ``h`` the residual stream:

    h = h + Op_l(N1(h));  h = h + FFN_l(N2(h))
    conv(u): [B | C | X] = u W_in;  z = B * X;
             c_t = sum_j k[:, j] * z_{t - (L-1) + j}   (z_{<0} = 0; depthwise,
                   causal, k[:, L-1] on the current token);
             y = (C * c) W_out                 -- no position enters
    attn(u): q = qnorm(u Wq) [nH, D], k = knorm(u Wk) [nKV, D], v = u Wv;
             rotary (rotate-half) on q and k; causal softmax(q k^T /
             sqrt(D)) v, a K/V head serving nH / nKV query heads; o = . Wo

then one RMS norm (the family's ``embedding_norm``) and the head, TIED to
the embedding.  A stream's conv state at position t is ``(z_{t-L+2}, ..,
z_t)``: ``conv_L_cache - 1`` rows of ``hidden_size`` a layer.  What
``config.json`` does not say (the order of the split, which tap meets the
current token, the q/k norms, rotary on every attention layer, the tied
head, the router's ``+ 1e-6``) is from the ``lfm2_moe`` modeling code of
``transformers``.

Parameter tree (weights ``[in, out]``; the routed experts ``[E, F, H]`` so
that an expert's ``[tf, H]`` tile is one contiguous run of HBM; one dict a
layer, nothing stacked: the layers differ in kind, so the programs walk
them in a static loop and never slice a stack):

    embed [V, H] (also the head)   final_norm [H]
    layers[l]:
      op_norm / ffn_norm [H]
      conv:  w_in [H, 3H]  conv_k [H, L] (fp32)  w_out [H, H]
      attn:  wq [H, nH*D]  wk [H, nKV*D]  wv [H, nKV*D]  wo [nH*D, H]
             q_norm [D]  k_norm [D]
      dense: mlp_gate [H, I]  mlp_up [H, I]  mlp_down [I, H]
      moe:   router [H, E]  router_bias [E] (fp32)
             w_gate / w_up / w_down [E, F, H]
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (Routing, matmul, rms_norm, rope_half, rotary_cos_sin,
                     rotary_inv_freq)

CONV, FULL = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The published keys (same names; ``rope_theta`` out of
    ``rope_parameters``), the seeded init's spreads and the compute
    dtype."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    conv_bias: bool = False
    layer_types: Optional[Tuple[str, ...]] = None
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    router_bias_std: float = 0.1
    conv_filter_std: float = 0.5
    dtype: Any = jnp.bfloat16
    # Where the family's served-model implementation registers itself
    # (``inference.served.served_model`` imports it on first use).
    serving_module: ClassVar[str] = "deepspeed_tpu.inference.lfm2"

    def __post_init__(self):
        if self.conv_bias or not self.use_expert_bias \
                or self.conv_L_cache < 2:
            raise NotImplementedError(
                "lfm2_moe as written: a filter of two taps or more without "
                "a bias, a router with its selection bias")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads, and that hidden_size")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers lies in [0, "
                             "num_hidden_layers]")
        types = tuple(self.layer_types or ())
        if len(types) != self.num_hidden_layers or set(types) - {CONV, FULL}:
            raise ValueError(f"layer_types={types} does not name "
                             f"{self.num_hidden_layers} layers")
        object.__setattr__(self, "layer_types", types)

    @classmethod
    def from_hf(cls, cfg: Dict[str, Any], **overrides) -> "Lfm2Config":
        """From a ``config.json`` dict: every key this class names is taken
        as published.  A cut in depth keeps the published ``layer_types``
        whole in the file and takes of it the leading ``num_dense_layers``
        entries (the published dense layers are of one kind and count once
        each) and then the entries from the PUBLISHED ``num_dense_layers``
        on (``published`` in the dict; the file's own count where there is
        none), so that what follows the dense layers is the pattern's own
        start."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        rope = dict(cfg.get("rope_parameters") or {})
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError("lfm2_moe is written without rope "
                                      "scaling")
        if "rope_theta" in rope:
            kw["rope_theta"] = float(rope["rope_theta"])
        kw.update(overrides)
        types = kw.get("layer_types")
        if types is not None:
            n = int(kw.get("num_hidden_layers", cls.num_hidden_layers))
            dense = int(kw.get("num_dense_layers", cls.num_dense_layers))
            skip = int((cfg.get("published") or {}).get(
                "num_dense_layers", dense))
            kw["layer_types"] = tuple(types[:dense]) \
                + tuple(types[skip:skip + n - dense])
        return cls(**kw)

    @property
    def name(self) -> str:
        return (f"lfm2-h{self.hidden_size}-l{self.num_hidden_layers}"
                f"-e{self.num_experts}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def num_conv_layers(self) -> int:
        return sum(t == CONV for t in self.layer_types)

    @property
    def num_attention_layers(self) -> int:
        return self.num_hidden_layers - self.num_conv_layers

    @property
    def group(self) -> int:
        """Query heads a K/V head."""
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def routing(self) -> Routing:
        """The expert layers' rule as ``moe/share.py`` reads it; every
        expert is held."""
        return Routing(experts=self.num_experts,
                       per_tok=self.num_experts_per_tok, n_group=1,
                       topk_group=1, norm=self.norm_topk_prob,
                       scale=float(self.routed_scaling_factor),
                       held=(0, self.num_experts), norm_eps=1e-6)


def inv_freq(cfg: Lfm2Config) -> np.ndarray:
    """float64 [head_dim / 2]: ``theta^(-2i / head_dim)``, unscaled."""
    return rotary_inv_freq(cfg.rope_theta, cfg.head_dim)


def qkv(p: Dict[str, jax.Array], u: jax.Array, positions: jax.Array,
        cfg: Lfm2Config):
    """An attention layer's projections of normed input ``u [..., H]`` at
    ``positions [...]``: q ``[..., nH, D]`` and k ``[..., nKV, D]``, normed
    per head and rotated; v ``[..., nKV, D]``."""
    nH, nKV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = matmul(u, p["wq"]).reshape(u.shape[:-1] + (nH, D))
    k = matmul(u, p["wk"]).reshape(u.shape[:-1] + (nKV, D))
    v = matmul(u, p["wv"]).reshape(u.shape[:-1] + (nKV, D))
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    cos, sin = rotary_cos_sin(inv_freq(cfg), positions)
    return (rope_half(q, cos[..., None, :], sin[..., None, :]),
            rope_half(k, cos[..., None, :], sin[..., None, :]), v)


def conv_gates(p: Dict[str, jax.Array], u: jax.Array):
    """A conv layer's input projection of normed ``u [..., H]``: (z = B *
    X, what the filter runs over and the state keeps; C, the output's
    gate), both ``[..., H]`` in u's dtype."""
    b, c, x = jnp.split(matmul(u, p["w_in"]), 3, axis=-1)
    return b * x, c


def _layer_shapes(cfg: Lfm2Config, l: int) -> Dict[str, Tuple[int, ...]]:
    H, D = cfg.hidden_size, cfg.head_dim
    nH, nKV = cfg.num_attention_heads, cfg.num_key_value_heads
    if cfg.layer_types[l] == CONV:
        shapes = {"w_in": (H, 3 * H), "w_out": (H, H)}
    else:
        shapes = {"wq": (H, nH * D), "wk": (H, nKV * D), "wv": (H, nKV * D),
                  "wo": (nH * D, H)}
    if l < cfg.num_dense_layers:
        I = cfg.intermediate_size
        shapes.update(mlp_gate=(H, I), mlp_up=(H, I), mlp_down=(I, H))
    else:
        E, F = cfg.num_experts, cfg.moe_intermediate_size
        shapes.update(router=(H, E), w_gate=(E, F, H), w_up=(E, F, H),
                      w_down=(E, F, H))
    return shapes


def lfm2_init(rng: jax.Array, cfg: Lfm2Config) -> Dict[str, Any]:
    """Weights normal(0, initializer_range) in ``cfg.dtype``, norms 1, the
    router's selection bias normal(0, router_bias_std) in fp32 (NON-zero
    on purpose: choosing by ``s + b`` and weighting by ``s`` must be told
    apart) and the conv filter normal(0, conv_filter_std) in fp32 (EVERY
    tap carries weight: with near-zero older taps a stale or zeroed state
    would pass every comparison)."""
    H, D, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * std).astype(cfg.dtype)

    k_emb, k_layers = jax.random.split(rng)
    layers = []
    for l, key in enumerate(jax.random.split(k_layers,
                                             cfg.num_hidden_layers)):
        shapes = _layer_shapes(cfg, l)
        keys = jax.random.split(key, len(shapes) + 2)
        p = {name: normal(k, shape) for k, (name, shape)
             in zip(keys, sorted(shapes.items()))}
        p["op_norm"] = jnp.ones((H,), cfg.dtype)
        p["ffn_norm"] = jnp.ones((H,), cfg.dtype)
        if cfg.layer_types[l] == CONV:
            p["conv_k"] = jax.random.normal(
                keys[-2], (H, cfg.conv_L_cache), jnp.float32) \
                * cfg.conv_filter_std
        else:
            p["q_norm"] = jnp.ones((D,), cfg.dtype)
            p["k_norm"] = jnp.ones((D,), cfg.dtype)
        if l >= cfg.num_dense_layers:
            p["router_bias"] = jax.random.normal(
                keys[-1], (cfg.num_experts,), jnp.float32) \
                * cfg.router_bias_std
        layers.append(p)
    return {"embed": normal(k_emb, (cfg.vocab_size, H)),
            "final_norm": jnp.ones((H,), cfg.dtype),
            "layers": layers}


__all__ = ["Lfm2Config", "lfm2_init", "inv_freq", "rope_half", "qkv",
           "conv_gates", "CONV", "FULL"]

"""The ``afmoe`` family (Arcee Trinity Mini / Nano): grouped-query
attention whose layers alternate between a SLIDING WINDOW (with rotary
positions) and FULL causal attention (with no position encoding at all),
per-head RMS norm on q and k, a sigmoid output gate on the attention,
sandwich RMS norms around both halves of a block, leading dense SwiGLU
layers, then expert layers routed by sigmoid scores with a selection bias
(one group: no group limit) plus a shared expert.

This module is the MODEL: its config from the published ``config.json``
keys, a seeded init and the pieces every path shares.  How it is served
(two classes of cache layers in one manager, the paged attend with grouped
heads and a window) is ``inference/afmoe.py``; the expert layer is
``moe/share.py`` under this family's ``Routing``; RMS norm, the gated FFN
and the rotary angles are ``models/blocks.py``'s.  Nothing here is
imported unless a configuration asks for it.

Layer ``i`` is ``full_attention`` where ``(i + 1) %
global_attn_every_n_layers == 0``, else ``sliding_attention`` (the
published ``layer_types``); layers ``0 .. num_dense_layers - 1`` have the
dense FFN.  With ``h`` the residual stream:

    h = h + N2(Attn(N1(h)));  h = h + N4(FFN(N3(h)))
    Attn(x): q = qnorm(x Wq) [nH, D], k = knorm(x Wk) [nKV, D], v = x Wv;
             rotary (rotate-half) on q, k in SLIDING layers only;
             causal softmax(q k^T / sqrt(D)) over keys j <= i, and in a
             sliding layer i - j < sliding_window;
             o = (A * sigmoid(x Wg)) Wo

and the embedded row is multiplied by ``sqrt(hidden_size)``
(``mup_enabled``).  What ``config.json`` does not say (the sandwich norms,
the q/k norms, the gate, rotary in sliding layers only, the rotate-half
pairing) is from the ``afmoe`` modeling code of ``transformers``.

Parameter tree (weights ``[in, out]``; the routed experts ``[E, F, H]`` so
that an expert's ``[tf, H]`` tile is one contiguous run of HBM; one dict a
layer, nothing stacked: the layers differ in kind, so the programs walk
them in a static loop and never slice a stack):

    embed [V, H]   lm_head [V, H]   final_norm [H]
    layers[i]:
      input_norm / post_attn_norm / pre_mlp_norm / post_mlp_norm [H]
      wq [H, nH*D]  wk [H, nKV*D]  wv [H, nKV*D]  wg [H, nH*D]
      wo [nH*D, H]  q_norm [D]  k_norm [D]
      dense: mlp_gate [H, I]  mlp_up [H, I]  mlp_down [I, H]
      moe:   router [H, E]  router_bias [E] (fp32)
             w_gate / w_up / w_down [E, F, H]
             shared_gate [H, Fs]  shared_up [H, Fs]  shared_down [Fs, H]
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (Routing, matmul, rms_norm, rope_half, rotary_cos_sin,
                     rotary_inv_freq)

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The published keys (same names), the seeded init's two spreads and
    the compute dtype."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    score_func: str = "sigmoid"
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    layer_types: Optional[Tuple[str, ...]] = None
    hidden_act: str = "silu"
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    router_bias_std: float = 0.1
    dtype: Any = jnp.bfloat16
    # Where the family's served-model implementation registers itself
    # (``inference.served.served_model`` imports it on first use).
    serving_module: ClassVar[str] = "deepspeed_tpu.inference.afmoe"

    def __post_init__(self):
        if self.score_func != "sigmoid" or self.hidden_act != "silu" \
                or self.tie_word_embeddings:
            raise NotImplementedError(
                "afmoe as written: sigmoid router scores, SiLU gates, an "
                "untied head")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers lies in [0, "
                             "num_hidden_layers]")
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            object.__setattr__(self, "layer_types", tuple(
                FULL if (i + 1) % n == 0 else SLIDING
                for i in range(self.num_hidden_layers)))
        types = tuple(self.layer_types)
        if len(types) != self.num_hidden_layers \
                or set(types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types={types} does not name "
                             f"{self.num_hidden_layers} layers")
        object.__setattr__(self, "layer_types", types)

    @classmethod
    def from_hf(cls, cfg: Dict[str, Any], **overrides) -> "AfmoeConfig":
        """From a ``config.json`` dict: every key this class names is
        taken as published; of ``layer_types`` the first
        ``num_hidden_layers`` (a cut in depth keeps the list's start)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        if cfg.get("rope_scaling"):
            raise NotImplementedError("afmoe is written without rope_scaling")
        kw.update(overrides)
        if kw.get("layer_types") is not None:
            kw["layer_types"] = tuple(
                kw["layer_types"][:kw.get("num_hidden_layers",
                                          cls.num_hidden_layers)])
        return cls(**kw)

    @property
    def name(self) -> str:
        return (f"afmoe-h{self.hidden_size}-l{self.num_hidden_layers}"
                f"-e{self.num_experts}")

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

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
                       per_tok=self.num_experts_per_tok,
                       n_group=self.n_group, topk_group=self.topk_group,
                       norm=self.route_norm, scale=self.route_scale,
                       held=(0, self.num_experts))


def inv_freq(cfg: AfmoeConfig) -> np.ndarray:
    """float64 [head_dim / 2]: ``theta^(-2i / head_dim)``, unscaled."""
    return rotary_inv_freq(cfg.rope_theta, cfg.head_dim)


def qkvg(p: Dict[str, jax.Array], h: jax.Array, positions: jax.Array,
         cfg: AfmoeConfig, sliding: bool):
    """The projections ahead of the attend, for normed input ``h [..., H]``
    at ``positions [...]``: (q [..., nH, D] and k [..., nKV, D], normed per
    head and, in a sliding layer, rotated; v [..., nKV, D]; the output
    gate's logits [..., nH * D])."""
    nH, nKV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = matmul(h, p["wq"]).reshape(h.shape[:-1] + (nH, D))
    k = matmul(h, p["wk"]).reshape(h.shape[:-1] + (nKV, D))
    v = matmul(h, p["wv"]).reshape(h.shape[:-1] + (nKV, D))
    q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    if sliding:
        cos, sin = rotary_cos_sin(inv_freq(cfg), positions)
        q = rope_half(q, cos[..., None, :], sin[..., None, :])
        k = rope_half(k, cos[..., None, :], sin[..., None, :])
    return q, k, v, matmul(h, p["wg"])


def _layer_shapes(cfg: AfmoeConfig, dense: bool) -> Dict[str, Tuple[int, ...]]:
    H, D = cfg.hidden_size, cfg.head_dim
    nH, nKV = cfg.num_attention_heads, cfg.num_key_value_heads
    shapes = {"wq": (H, nH * D), "wk": (H, nKV * D), "wv": (H, nKV * D),
              "wg": (H, nH * D), "wo": (nH * D, H)}
    if dense:
        I = cfg.intermediate_size
        shapes.update(mlp_gate=(H, I), mlp_up=(H, I), mlp_down=(I, H))
    else:
        E, F = cfg.num_experts, cfg.moe_intermediate_size
        Fs = F * cfg.num_shared_experts
        shapes.update(router=(H, E), w_gate=(E, F, H), w_up=(E, F, H),
                      w_down=(E, F, H), shared_gate=(H, Fs),
                      shared_up=(H, Fs), shared_down=(Fs, H))
    return shapes


def afmoe_init(rng: jax.Array, cfg: AfmoeConfig) -> Dict[str, Any]:
    """Weights normal(0, initializer_range) in ``cfg.dtype``, norms 1, and
    the router's selection bias normal(0, router_bias_std) in fp32:
    NON-zero on purpose, so that choosing by ``s + b`` and weighting by
    ``s`` are distinguishable in every comparison."""
    H, D, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * std).astype(cfg.dtype)

    k_emb, k_head, k_layers = jax.random.split(rng, 3)
    layers = []
    for i, key in enumerate(jax.random.split(k_layers,
                                             cfg.num_hidden_layers)):
        dense = i < cfg.num_dense_layers
        shapes = _layer_shapes(cfg, dense)
        keys = jax.random.split(key, len(shapes) + 1)
        p = {name: normal(k, shape) for k, (name, shape)
             in zip(keys, sorted(shapes.items()))}
        for name in ("input_norm", "post_attn_norm", "pre_mlp_norm",
                     "post_mlp_norm"):
            p[name] = jnp.ones((H,), cfg.dtype)
        p["q_norm"] = jnp.ones((D,), cfg.dtype)
        p["k_norm"] = jnp.ones((D,), cfg.dtype)
        if not dense:
            p["router_bias"] = jax.random.normal(
                keys[-1], (cfg.num_experts,), jnp.float32) \
                * cfg.router_bias_std
        layers.append(p)
    return {"embed": normal(k_emb, (cfg.vocab_size, H)),
            "lm_head": normal(k_head, (cfg.vocab_size, H)),
            "final_norm": jnp.ones((H,), cfg.dtype),
            "layers": layers}


__all__ = ["AfmoeConfig", "afmoe_init", "inv_freq", "rope_half", "qkvg",
           "SLIDING", "FULL"]

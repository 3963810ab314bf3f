"""The ``smallthinker`` family (PowerInfer SmallThinker-21BA3B /
-4BA0.6B): EVERY layer an expert layer whose router reads the block's
normed INPUT — before the attention, not after it — over ReLU-gated
experts with no shared expert and no dense layer; grouped-query attention
whose layers are, by two published per-layer lists, a SLIDING WINDOW with
rotary positions (``sliding_window_layout[l] == 1``, ``rope_layout[l] ==
1``) or FULL causal attention with no position encoding at all (0 / 0);
two RMS norms a block; no q/k norm, no bias, no output gate.

This module is the MODEL: its config from the published ``config.json``
keys, a seeded init and the pieces every path shares.  How it is served
(two classes of cache layers in one manager, the routing planned ahead of
the attention) is ``inference/smallthinker.py``; the expert layer is
``moe/share.py`` under this family's ``Routing`` (rule ``softmax_topk``);
RMS norm and the rotary angles are ``models/blocks.py``'s.  Nothing here is
imported unless a configuration asks for it.

With ``h`` the residual stream, block ``l``:

    x = N_in(h)
    idx, w = the moe_num_active_primary_experts largest of x Wr (fp32),
             softmax over THOSE logits, / their sum (norm_topk_prob)
    h = h + Attn_l(x):  q = x Wq [nH, D], k = x Wk [nKV, D], v = x Wv;
             rotary (rotate-half, all D dimensions) on q, k where
             rope_layout[l]; causal softmax(q k^T / sqrt(D)) over keys
             s <= t, and where sliding_window_layout[l] t - s <
             sliding_window_size; o = A Wo
    z = N_post(h)
    h = h + sum_j w_j Wdown_e (relu(Wgate_e z) * (Wup_e z)),  e = idx_j

then a final RMS norm and the untied head.  The experts read ``z``; their
choice and weights came from ``x``.  What ``config.json`` does not say
(that the router's input is the normed ``x``, top-k before the softmax, the
window's convention, the rotate-half pairing, the ReLU on the gate branch
only) is from the ``smallthinker`` modeling code and the family's paper.

Parameter tree (weights ``[in, out]``; the experts ``[E, F, H]`` so that an
expert's ``[tf, H]`` tile is one contiguous run of HBM; one dict a layer,
nothing stacked: the layers differ in kind, so the programs walk them in a
static loop and never slice a stack):

    embed [V, H]   lm_head [V, H]   final_norm [H]
    layers[l]:
      input_norm / post_attn_norm [H]
      wq [H, nH*D]  wk [H, nKV*D]  wv [H, nKV*D]  wo [nH*D, H]
      router [H, E]   w_gate / w_up / w_down [E, F, H]
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (Routing, matmul, rope_half, rotary_cos_sin,
                     rotary_inv_freq)


@dataclasses.dataclass(frozen=True)
class SmallthinkerConfig:
    """The published keys (same names) and the compute dtype."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    # Per layer: 1 = rotary / a sliding window, 0 = none.  The published
    # lists read 0 1 1 1 repeated; by default every fourth layer from the
    # first is the position-free full layer.
    rope_layout: Optional[Tuple[int, ...]] = None
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    sliding_window_size: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    max_position_embeddings: int = 16384
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # Where the family's served-model implementation registers itself
    # (``inference.served.served_model`` imports it on first use).
    serving_module: ClassVar[str] = "deepspeed_tpu.inference.smallthinker"

    def __post_init__(self):
        if not self.moe_primary_router_apply_softmax \
                or self.tie_word_embeddings:
            raise NotImplementedError(
                "smallthinker as written: a softmax over the chosen "
                "logits, an untied head")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if not 0 < self.moe_num_active_primary_experts \
                <= self.moe_num_primary_experts:
            raise ValueError("moe_num_active_primary_experts lies in (0, "
                             "moe_num_primary_experts]")
        L = self.num_hidden_layers
        for name in ("rope_layout", "sliding_window_layout"):
            layout = getattr(self, name)
            if layout is None:
                layout = tuple(int(l % 4 != 0) for l in range(L))
            layout = tuple(int(v) for v in layout)
            if len(layout) != L or set(layout) - {0, 1}:
                raise ValueError(f"{name}={layout} does not say 0 or 1 for "
                                 f"{L} layers")
            object.__setattr__(self, name, layout)

    @classmethod
    def from_hf(cls, cfg: Dict[str, Any], **overrides
                ) -> "SmallthinkerConfig":
        """From a ``config.json`` dict: every key this class names is
        taken as published; of the two layouts the first
        ``num_hidden_layers`` entries (a cut in depth keeps the lists'
        start)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        if cfg.get("rope_scaling"):
            raise NotImplementedError(
                "smallthinker is written without rope_scaling")
        kw.update(overrides)
        L = kw.get("num_hidden_layers", cls.num_hidden_layers)
        for name in ("rope_layout", "sliding_window_layout"):
            if kw.get(name) is not None:
                kw[name] = tuple(kw[name][:L])
        return cls(**kw)

    @property
    def name(self) -> str:
        return (f"smallthinker-h{self.hidden_size}"
                f"-l{self.num_hidden_layers}"
                f"-e{self.moe_num_primary_experts}")

    # What ``inference.afmoe``'s expert counters read, under its names.
    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_experts(self) -> int:
        return self.moe_num_primary_experts

    @property
    def num_experts_per_tok(self) -> int:
        return self.moe_num_active_primary_experts

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
        return Routing(experts=self.moe_num_primary_experts,
                       per_tok=self.moe_num_active_primary_experts,
                       n_group=1, topk_group=1, norm=self.norm_topk_prob,
                       scale=1.0, held=(0, self.moe_num_primary_experts),
                       norm_eps=0.0, rule="softmax_topk")


def inv_freq(cfg: SmallthinkerConfig) -> np.ndarray:
    """float64 [head_dim / 2]: ``theta^(-2i / head_dim)``, unscaled."""
    return rotary_inv_freq(cfg.rope_theta, cfg.head_dim)


def qkv(p: Dict[str, jax.Array], x: jax.Array, positions: jax.Array,
        cfg: SmallthinkerConfig, rotary: bool):
    """The projections ahead of the attend, for normed input ``x [..., H]``
    at ``positions [...]``: q [..., nH, D] and k [..., nKV, D], rotated
    where the layer has rotary positions; v [..., nKV, D]."""
    nH, nKV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = matmul(x, p["wq"]).reshape(x.shape[:-1] + (nH, D))
    k = matmul(x, p["wk"]).reshape(x.shape[:-1] + (nKV, D))
    v = matmul(x, p["wv"]).reshape(x.shape[:-1] + (nKV, D))
    if rotary:
        cos, sin = rotary_cos_sin(inv_freq(cfg), positions)
        q = rope_half(q, cos[..., None, :], sin[..., None, :])
        k = rope_half(k, cos[..., None, :], sin[..., None, :])
    return q, k, v


def _layer_shapes(cfg: SmallthinkerConfig) -> Dict[str, Tuple[int, ...]]:
    H, D = cfg.hidden_size, cfg.head_dim
    nH, nKV = cfg.num_attention_heads, cfg.num_key_value_heads
    E, F = cfg.moe_num_primary_experts, cfg.moe_ffn_hidden_size
    return {"wq": (H, nH * D), "wk": (H, nKV * D), "wv": (H, nKV * D),
            "wo": (nH * D, H), "router": (H, E), "w_gate": (E, F, H),
            "w_up": (E, F, H), "w_down": (E, F, H)}


def smallthinker_init(rng: jax.Array, cfg: SmallthinkerConfig
                      ) -> Dict[str, Any]:
    """Every matrix normal(0, fan_in^-1/2), the embedding normal(0, 1),
    norms 1, in ``cfg.dtype``: on a unit-RMS input every product has
    unit-RMS outputs, so the router's logits, the attention scores (q . k /
    sqrt(D) of unit-variance q and k: there is no q/k norm to set their
    scale) and the head's logits all have a spread of about 1.  That is
    what makes a wrong rule SHOW: six chosen logits a unit apart give
    weights far from 1/6 each, where a flat normal(0, 0.02) at a small
    width would leave them equal and let a softmax over the wrong set
    pass.  (At the published width 2560^-1/2 = 0.0198: the usual 0.02.)"""
    H = cfg.hidden_size
    F = cfg.moe_ffn_hidden_size
    fan_in = {"wq": H, "wk": H, "wv": H, "router": H, "w_gate": H,
              "w_up": H, "w_down": F,
              "wo": cfg.num_attention_heads * cfg.head_dim}

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32)
                * std).astype(cfg.dtype)

    k_emb, k_head, k_layers = jax.random.split(rng, 3)
    layers = []
    for key in jax.random.split(k_layers, cfg.num_hidden_layers):
        shapes = _layer_shapes(cfg)
        keys = jax.random.split(key, len(shapes))
        p = {name: normal(k, shape, fan_in[name] ** -0.5)
             for k, (name, shape) in zip(keys, sorted(shapes.items()))}
        for name in ("input_norm", "post_attn_norm"):
            p[name] = jnp.ones((H,), cfg.dtype)
        layers.append(p)
    return {"embed": normal(k_emb, (cfg.vocab_size, H), 1.0),
            "lm_head": normal(k_head, (cfg.vocab_size, H), H ** -0.5),
            "final_norm": jnp.ones((H,), cfg.dtype),
            "layers": layers}


__all__ = ["SmallthinkerConfig", "smallthinker_init", "inv_freq", "qkv"]

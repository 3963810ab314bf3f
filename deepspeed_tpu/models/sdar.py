"""The ``sdar_moe`` family (JetLM SDAR-30B-A3B): a Qwen3-MoE layer —
grouped-query attention with a per-head RMS norm on q and k and rotary
positions, then an expert layer routed by a softmax over all experts, the
``num_experts_per_tok`` largest kept and divided by their sum, no shared
expert — under a BLOCK-CAUSAL attention mask, generated by diffusion over
blocks: positions come in blocks of ``block_length``; a row attends every
row of its own block and of the blocks before it, and a block is generated
by passes over its ``block_length`` positions that start as a mask token.

This module is the MODEL: its config from the published ``config.json``
keys, a seeded init, the projections ahead of the attend, and the
generation's unmasking rules (``unmask``), which are mathematics and not
serving.  How it is served (a stream's step is a pass over a block through
the paged cache) is ``inference/sdar.py``; the expert layer is
``moe/share.py`` under this family's ``Routing`` (``softmax_topk``: a
softmax over the chosen logits, which IS the published rule:
``softmax(l)_e / sum_chosen softmax(l) = exp(l_e) / sum_chosen exp(l)``).

With ``h`` the residual stream and ``B = block_length``:

    a = h + Wo Attn(N1(h));  h' = a + MoE(N2(a))
    Attn(x): q = qnorm(x Wq) [nH, D], k = knorm(x Wk) [nKV, D], v = x Wv;
             rotary (rotate-half) on q, k at the row's own position;
             softmax(q k^T / sqrt(D)) over the keys j with j // B <= i // B
    MoE(x):  p = softmax(x Wr) over all experts (fp32); the per_tok largest;
             weights p_e / (sum of the chosen p);
             expert e: W2_e (silu(W1_e x) * W3_e x)

Logit row ``i`` of a block predicts position ``i`` itself (no shift).  What
``config.json`` does not say (the q/k norms and their place before the
rotary, the mask, the rules below) is from the family's modeling code and
``generate.py``, from memory: the benchmark's configuration file lists each
under ``assumed``.

Generation of a block (``unmask``): a DENOISE pass forwards the block's
``B`` rows (the mask token where a position is undecided); at each
undecided position ``x0 = argmax`` (or a draw) and
its confidence ``c = softmax(logits)[x0]``; a rule unmasks some of them,
and an unmasked position is never masked again.  Once no mask is left a
COMMIT pass forwards the block once more and keeps its K/V rows.

- ``low_confidence_static``: the block's masked positions are split evenly
  over ``denoising_steps`` passes, the remainder to the first passes; a
  pass unmasks that many of the highest ``c``.  Stated without the block's
  history: pass ``i`` unmasks ``ceil(masked now / (denoising_steps - i))``
  (the two are the same numbers: ``tests/test_sdar_serving.py``).
- ``low_confidence_dynamic``: every masked position with ``c >
  confidence_threshold``, and if those are fewer than the static count of
  the pass, the static count of highest ``c``.

Parameter tree (weights ``[in, out]``; the routed experts ``[E, F, H]``;
one dict a layer):

    embed [V, H]   lm_head [V, H]   final_norm [H]
    layers[i]: input_norm / post_attn_norm [H]  q_norm / k_norm [D]
               wq [H, nH*D]  wk [H, nKV*D]  wv [H, nKV*D]  wo [nH*D, H]
               router [H, E]  w_gate / w_up / w_down [E, F, H]
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (Routing, matmul, rms_norm, rope_half, rotary_cos_sin,
                     rotary_inv_freq)

RULES = ("low_confidence_static", "low_confidence_dynamic")


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The published keys (same names); what generation needs and
    ``config.json`` does not carry (``block_length``, ``mask_token_id``;
    ``denoising_steps``, ``remasking``, ``confidence_threshold``: a
    request's, fixed here for an engine's lifetime because they shape its
    one compiled step); the seeded init's spread and the compute dtype."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    attention_bias: bool = False
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = False
    block_length: int = 4
    mask_token_id: int = 151669
    denoising_steps: int = 4
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    # Where the family's served-model implementation registers itself
    # (``inference.served.served_model`` imports it on first use).
    serving_module: ClassVar[str] = "deepspeed_tpu.inference.sdar"

    def __post_init__(self):
        object.__setattr__(self, "mlp_only_layers",
                           tuple(self.mlp_only_layers or ()))
        if self.hidden_act != "silu" or self.tie_word_embeddings \
                or self.attention_bias or not self.norm_topk_prob \
                or self.decoder_sparse_step != 1 or self.mlp_only_layers:
            raise NotImplementedError(
                "sdar_moe as written: SiLU gates, an untied head, no "
                "attention bias, norm_topk_prob, every layer an expert "
                "layer")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if self.remasking not in RULES:
            raise ValueError(f"remasking={self.remasking!r}: one of {RULES}")
        if not 1 <= self.denoising_steps or self.block_length < 1:
            raise ValueError("denoising_steps and block_length are positive")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("mask_token_id lies inside the vocabulary")

    @classmethod
    def from_hf(cls, cfg: Dict[str, Any], **overrides) -> "SdarConfig":
        """From a ``config.json`` dict: every key this class names is taken
        as published; ``assumed`` (the benchmark's configuration files)
        gives the sizes ``config.json`` does not."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        if cfg.get("rope_scaling") or cfg.get("use_sliding_window"):
            raise NotImplementedError(
                "sdar_moe is written without rope_scaling or a window")
        assumed = cfg.get("assumed") or {}
        kw.update({k: assumed[k] for k in (
            "block_length", "mask_token_id", "confidence_threshold",
            "initializer_range") if k in assumed})
        kw.update(overrides)
        return cls(**kw)

    @property
    def name(self) -> str:
        return (f"sdar-h{self.hidden_size}-l{self.num_hidden_layers}"
                f"-e{self.num_experts}-b{self.block_length}")

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def group(self) -> int:
        """Query heads a K/V head."""
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def routing(self) -> Routing:
        """The expert layers' rule as ``moe/share.py`` reads it (module
        docstring); every expert is held."""
        return Routing(experts=self.num_experts,
                       per_tok=self.num_experts_per_tok, n_group=1,
                       topk_group=1, norm=False, scale=1.0,
                       held=(0, self.num_experts), norm_eps=0.0,
                       rule="softmax_topk")


def inv_freq(cfg: SdarConfig) -> np.ndarray:
    """float64 [head_dim / 2]: ``theta^(-2i / head_dim)``, unscaled."""
    return rotary_inv_freq(cfg.rope_theta, cfg.head_dim)


def qkv(p: Dict[str, jax.Array], h: jax.Array, positions: jax.Array,
        cfg: SdarConfig):
    """The projections ahead of the attend, for normed input ``h [..., H]``
    at ``positions [...]``: q [..., nH, D] and k [..., nKV, D], normed per
    head and rotated; v [..., nKV, D]."""
    nH, nKV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = matmul(h, p["wq"]).reshape(h.shape[:-1] + (nH, D))
    k = matmul(h, p["wk"]).reshape(h.shape[:-1] + (nKV, D))
    v = matmul(h, p["wv"]).reshape(h.shape[:-1] + (nKV, D))
    q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    cos, sin = rotary_cos_sin(inv_freq(cfg), positions)
    return (rope_half(q, cos[..., None, :], sin[..., None, :]),
            rope_half(k, cos[..., None, :], sin[..., None, :]), v)


def static_count(masked: jax.Array, passes_done: jax.Array,
                 denoising_steps: int) -> jax.Array:
    """Positions the static schedule unmasks in this pass: ``masked`` still
    masked, ``passes_done`` denoise passes of the block behind it."""
    left = jnp.maximum(denoising_steps - passes_done, 1)
    return -(-masked // left)


def unmask(undecided: jax.Array, conf: jax.Array, passes_done: jax.Array,
           cfg: SdarConfig) -> jax.Array:
    """Which positions ``[S, B]`` (bool) one denoise pass unmasks, of the
    positions ``undecided [S, B]`` of each block, from the confidences
    ``conf [S, B]`` (fp32) of the pass's proposals.  ``passes_done [S]``:
    denoise passes of each block before this one.  A tie goes to the
    earlier position, as a stable descending sort has it."""
    c = jnp.where(undecided, conf.astype(jnp.float32), -jnp.inf)
    at = jnp.arange(undecided.shape[1])
    ahead = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None]) & (at[None, :] < at[:, None]))
    k = static_count(undecided.sum(-1), passes_done, cfg.denoising_steps)
    take = ahead.sum(-1) < k[:, None]
    if cfg.remasking == "low_confidence_dynamic":
        take = take | (c > cfg.confidence_threshold)
    return take & undecided


def _layer_shapes(cfg: SdarConfig) -> Dict[str, Tuple[int, ...]]:
    H, D = cfg.hidden_size, cfg.head_dim
    nH, nKV = cfg.num_attention_heads, cfg.num_key_value_heads
    E, F = cfg.num_experts, cfg.moe_intermediate_size
    return {"wq": (H, nH * D), "wk": (H, nKV * D), "wv": (H, nKV * D),
            "wo": (nH * D, H), "router": (H, E), "w_gate": (E, F, H),
            "w_up": (E, F, H), "w_down": (E, F, H)}


def sdar_init(rng: jax.Array, cfg: SdarConfig) -> Dict[str, Any]:
    """Weights normal(0, initializer_range) in ``cfg.dtype``, norms 1."""
    H, D, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * std).astype(cfg.dtype)

    k_emb, k_head, k_layers = jax.random.split(rng, 3)
    layers = []
    shapes = sorted(_layer_shapes(cfg).items())
    for key in jax.random.split(k_layers, cfg.num_hidden_layers):
        p = {name: normal(k, shape) for k, (name, shape)
             in zip(jax.random.split(key, len(shapes)), shapes)}
        p["input_norm"] = jnp.ones((H,), cfg.dtype)
        p["post_attn_norm"] = jnp.ones((H,), cfg.dtype)
        p["q_norm"] = jnp.ones((D,), cfg.dtype)
        p["k_norm"] = jnp.ones((D,), cfg.dtype)
        layers.append(p)
    return {"embed": normal(k_emb, (cfg.vocab_size, H)),
            "lm_head": normal(k_head, (cfg.vocab_size, H)),
            "final_norm": jnp.ones((H,), cfg.dtype),
            "layers": layers}


__all__ = ["SdarConfig", "sdar_init", "inv_freq", "qkv", "static_count",
           "unmask", "RULES"]

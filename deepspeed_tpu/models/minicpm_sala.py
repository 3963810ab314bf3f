"""The ``minicpm_sala`` family (OpenBMB MiniCPM-SALA, 9 B): a hybrid whose
layers are, by the published per-layer list ``mixer_types``, either

- ``"minicpm4"`` — grouped-query attention (32 query heads on 2 K/V heads)
  with NO position encoding, an RMS norm over each q and k head and a sigmoid
  OUTPUT GATE, which past ``sparse_dense_len`` tokens reads only
  ``sparse_topk`` blocks of ``sparse_block_size`` keys a query token and K/V
  head.  The blocks are chosen without any weight of their own (InfLLM-V2):
  the layer's own queries score mean-pooled copies of the layer's own keys;
- ``"lightning-attn"`` — a Lightning linear attention: 32 heads of 128 with
  a q and k of their own each, rotary positions, a FIXED decay a head and an
  fp32 state ``S [128, 128]`` a head in place of a cache; an RMS norm over
  each head's output, a sigmoid output gate.

This module is the MODEL: its config from the published ``config.json`` keys
(+ the sparse layers' sizes, which the published config does not carry: the
MiniCPM4 family's ``sparse_config`` convention), a seeded init and the pieces
every path shares.  How it is served is ``inference/minicpm_sala.py``; the
selection is ``ops/sparse_select.py``; the recurrence is ``ops/ssm_scan.py``'s.
Nothing here is imported unless a configuration asks for it.

With ``h [T, H]`` the residual stream and ``c = scale_depth /
sqrt(depth_scale_layers)`` (the PUBLISHED depth, whatever a cut keeps):

    x = N_in(h);   h = h + c * mixer(x)
    z = N_post(h); h = h + c * W_down(silu(W_gate z) * (W_up z))

the embedding times ``scale_emb``; a final RMS norm, the untied head, logits
/ (``hidden_size`` / ``dim_model_base``).

``minicpm4``: q = N_q(W_q x) [T, nH, D], k = N_k(W_k x) [T, nKV, D] (one
weight ``[D]`` each), v = W_v x; pooled keys ``c_j = mean(k[s*j .. s*j + w -
1])`` (``s`` = ``sparse_kernel_stride``, ``w`` = ``sparse_kernel_size``) for
every j whose window has ended; for the query at t (``n = t + 1`` tokens):
all blocks where ``n <= sparse_dense_len``, else the ``sparse_topk`` of
largest ``B[g, b]`` = max over the pooled keys whose windows touch block b
of (sum over the group's query heads of softmax_j(q . c_j / sqrt(D))), the
first ``sparse_init_blocks`` and the newest ``sparse_window_size /
sparse_block_size`` blocks forced; o = causal softmax attention over the
chosen blocks' keys; ``y = W_o (o * sigmoid(W_g x))``.

``lightning-attn``: q, k = rotary(N_q(W_q x)), rotary(N_k(W_k x)), v = W_v x,
each [T, 32, 128]; ``S_t = lam_h S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t /
sqrt(128)``, ``lam_h = exp(-2^(-8 (h + 1) / nh))``; ``y = W_o (N_o(o) *
sigmoid(W_g x))`` with ``N_o`` an RMS norm over each head's 128 outputs.

Parameter tree (weights ``[in, out]``, one dict a layer, nothing stacked: the
layers differ in kind):

    embed [V, H]   lm_head [V, H]   final_norm [H]
    layers[l]: input_norm / post_norm [H]; w_gate / w_up [H, F]; w_down [F, H]
      minicpm4:       wq [H, nH*D]  wk / wv [H, nKV*D]  wg [H, nH*D]
                      wo [nH*D, H]  q_norm / k_norm [D]
      lightning-attn: wq / wk / wv / wg [H, nh*d]  wo [nh*d, H]
                      q_norm / k_norm / o_norm [d]
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (matmul, rms_norm, rope_half, rotary_cos_sin,
                     rotary_inv_freq, swiglu)

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


@dataclasses.dataclass(frozen=True)
class MinicpmSalaConfig:
    """The published keys (same names), the sparse layers' sizes (the
    MiniCPM4 family's convention; ``perfbench/configs/minicpm-sala.json``
    lists them under ``assumed``) and the compute dtype.
    ``vocab_rows_held`` = the embedding / head rows held where that is not
    ``vocab_size`` (padded to the lane tiling: ids at or above
    ``vocab_size`` are never drawn and never sampled)."""
    vocab_size: int = 73448
    vocab_rows_held: int = 0
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_use_rope: bool = True
    attn_use_rope: bool = False
    qk_norm: bool = True
    use_output_gate: bool = True
    use_output_norm: bool = True
    attn_use_output_gate: bool = True
    # Entry l names layer l's mixer.  None: the published 1 : 3 period, a
    # sparse layer first.
    mixer_types: Optional[Tuple[str, ...]] = None
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    # The depth the residual scale is taken over: the PUBLISHED one, which a
    # configuration cut in depth keeps (None: ``num_hidden_layers``).
    depth_scale_layers: Optional[int] = None
    max_position_embeddings: int = 524288
    tie_word_embeddings: bool = False
    # InfLLM-V2's sizes (not in the published config).
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_window_size: int = 2048
    sparse_init_blocks: int = 1
    sparse_dense_len: int = 8192
    dtype: Any = jnp.bfloat16
    serving_module: ClassVar[str] = "deepspeed_tpu.inference.minicpm_sala"

    def __post_init__(self):
        L = self.num_hidden_layers
        types = self.mixer_types
        if types is None:
            types = tuple(SPARSE if l % 4 == 0 else LIGHTNING
                          for l in range(L))
        types = tuple(types)
        if len(types) != L or set(types) - {SPARSE, LIGHTNING}:
            raise ValueError(f"mixer_types={types} does not name "
                             f"{SPARSE!r} or {LIGHTNING!r} for {L} layers")
        object.__setattr__(self, "mixer_types", types)
        if self.depth_scale_layers is None:
            object.__setattr__(self, "depth_scale_layers", L)
        if not (self.qk_norm and self.use_output_gate
                and self.use_output_norm and self.attn_use_output_gate
                and self.lightning_use_rope and not self.attn_use_rope
                and not self.tie_word_embeddings
                and self.lightning_nkv == self.lightning_nh):
            raise NotImplementedError(
                "minicpm_sala as written: q/k norm, both output gates, the "
                "output norm, rotary on the Lightning layers only, an "
                "untied head, lightning_nkv == lightning_nh")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        s, w, b = (self.sparse_kernel_stride, self.sparse_kernel_size,
                   self.sparse_block_size)
        if w != 2 * s or b % s or self.sparse_window_size % b \
                or self.sparse_dense_len % b:
            raise ValueError(
                "the selection as written: sparse_kernel_size = 2 x "
                "sparse_kernel_stride, which divides sparse_block_size, "
                "which divides sparse_window_size and sparse_dense_len")
        if self.sparse_init_blocks + self.sparse_window_size // b \
                > self.sparse_topk:
            raise ValueError("the forced blocks outnumber sparse_topk")

    @classmethod
    def from_hf(cls, cfg: Dict[str, Any], **overrides
                ) -> "MinicpmSalaConfig":
        """From a ``config.json`` dict: every key this class names as
        published; ``assumed.sparse_config`` (kernel_size, kernel_stride,
        block_size, topk, window_size, init_blocks, dense_len) and
        ``assumed.vocab_rows_held`` where the dict carries them;
        ``published.num_hidden_layers`` as the depth of the residual
        scale."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        assumed = cfg.get("assumed") or {}
        if assumed.get("vocab_rows_held"):
            kw["vocab_rows_held"] = int(assumed["vocab_rows_held"])
        sparse = assumed.get("sparse_config") or {}
        kw.update({"sparse_" + k: v for k, v in sparse.items()
                   if "sparse_" + k in names})
        depth = (cfg.get("published") or {}).get("num_hidden_layers")
        if depth:
            kw["depth_scale_layers"] = int(depth)
        kw.update(overrides)
        if kw.get("mixer_types") is not None:
            kw["mixer_types"] = tuple(kw["mixer_types"])
        return cls(**kw)

    @property
    def name(self) -> str:
        return (f"minicpm-sala-h{self.hidden_size}"
                f"-l{self.num_hidden_layers}")

    @property
    def vocab_rows(self) -> int:
        return self.vocab_rows_held or self.vocab_size

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, t in enumerate(self.mixer_types)
                     if t == SPARSE)

    @property
    def lightning_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, t in enumerate(self.mixer_types)
                     if t == LIGHTNING)

    @property
    def group(self) -> int:
        """Query heads a K/V head."""
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def lightning_scale(self) -> float:
        return self.lightning_head_dim ** -0.5

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.depth_scale_layers)

    @property
    def logit_scale(self) -> float:
        return self.dim_model_base / self.hidden_size

    @property
    def pooled_a_block(self) -> int:
        """Pooled keys whose windows END in one block."""
        return self.sparse_block_size // self.sparse_kernel_stride

    @property
    def chosen_width(self) -> int:
        """Slots of a row's table of chosen blocks: ``sparse_topk``, or
        every block of a context no longer than ``sparse_dense_len``."""
        return max(self.sparse_topk,
                   self.sparse_dense_len // self.sparse_block_size)


def decay(cfg: MinicpmSalaConfig) -> np.ndarray:
    """float64 [lightning_nh]: ``lam_h = exp(-2^(-8 (h + 1) / nh))``,
    Lightning Attention's per-head slopes; the same in every layer."""
    nh = cfg.lightning_nh
    return np.exp(-np.exp2(-8.0 * (np.arange(nh) + 1) / nh))


def _heads(y: jax.Array, heads: int) -> jax.Array:
    return y.reshape(y.shape[:-1] + (heads, y.shape[-1] // heads))


def sparse_qkv(p, x: jax.Array, cfg: MinicpmSalaConfig):
    """A ``minicpm4`` layer's q [..., nH, D] and k [..., nKV, D], each head
    RMS-normed and without positions, and v [..., nKV, D]."""
    q = rms_norm(_heads(matmul(x, p["wq"]), cfg.num_attention_heads),
                 p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(_heads(matmul(x, p["wk"]), cfg.num_key_value_heads),
                 p["k_norm"], cfg.rms_norm_eps)
    return q, k, _heads(matmul(x, p["wv"]), cfg.num_key_value_heads)


def lightning_qkv(p, x: jax.Array, positions: jax.Array,
                  cfg: MinicpmSalaConfig):
    """A Lightning layer's q, k (each head RMS-normed, then rotated: all
    ``lightning_head_dim`` dimensions, rotate-half) and v, [..., nh, d]."""
    nh, d = cfg.lightning_nh, cfg.lightning_head_dim
    cos, sin = rotary_cos_sin(rotary_inv_freq(cfg.rope_theta, d), positions)
    cos, sin = cos[..., None, :], sin[..., None, :]
    q = rope_half(rms_norm(_heads(matmul(x, p["wq"]), nh), p["q_norm"],
                           cfg.rms_norm_eps), cos, sin)
    k = rope_half(rms_norm(_heads(matmul(x, p["wk"]), nh), p["k_norm"],
                           cfg.rms_norm_eps), cos, sin)
    return q, k, _heads(matmul(x, p["wv"]), nh)


def gated_out(p, o: jax.Array, x: jax.Array) -> jax.Array:
    """``W_o (o * sigmoid(W_g x))``: o [..., nH * D] in fp32 or x's dtype."""
    gate = jax.nn.sigmoid(jnp.dot(x, p["wg"].astype(x.dtype),
                                  preferred_element_type=jnp.float32))
    return matmul((o.astype(jnp.float32) * gate).astype(x.dtype), p["wo"])


def mlp(p, z: jax.Array) -> jax.Array:
    return swiglu(z, p["w_gate"], p["w_up"], p["w_down"])


def _layer_shapes(cfg: MinicpmSalaConfig, kind: str
                  ) -> Dict[str, Tuple[int, ...]]:
    H, F = cfg.hidden_size, cfg.intermediate_size
    out = {"w_gate": (H, F), "w_up": (H, F), "w_down": (F, H)}
    if kind == SPARSE:
        q = cfg.num_attention_heads * cfg.head_dim
        kv = cfg.num_key_value_heads * cfg.head_dim
        out.update(wq=(H, q), wk=(H, kv), wv=(H, kv), wg=(H, q), wo=(q, H))
    else:
        w = cfg.lightning_nh * cfg.lightning_head_dim
        out.update(wq=(H, w), wk=(H, w), wv=(H, w), wg=(H, w), wo=(w, H))
    return out


def minicpm_sala_init(rng: jax.Array, cfg: MinicpmSalaConfig
                      ) -> Dict[str, Any]:
    """Seeded weights that make a wrong rule SHOW.  Every matrix normal(0,
    std) in ``cfg.dtype``; the embedding normal(0, 1 / scale_emb) (unit rows
    after ``scale_emb``); the head such that logits have a spread of ~1 after
    ``logit_scale``; ``wo`` / ``w_down`` such that a branch's output has an
    RMS of ~1 / residual_scale (the residual scale brings it back to the
    stream's own: with the family's 0.02 the mixers would move the stream by
    a percent and a dropped gate or a wrong decay would pass any comparison);
    the q/k norm weights 2 (attention scores of spread ~4 and pooled-key
    scores of ~0.7: a softmax over pooled keys that is NOT flat, so the
    chosen blocks differ from head to head and from token to token); other
    norms 1."""
    H, F = cfg.hidden_size, cfg.intermediate_size
    c = cfg.residual_scale

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32)
                * std).astype(cfg.dtype)

    k_emb, k_head, k_layers = jax.random.split(rng, 3)
    layers = []
    for key, kind in zip(jax.random.split(k_layers, cfg.num_hidden_layers),
                         cfg.mixer_types):
        shapes = _layer_shapes(cfg, kind)
        keys = jax.random.split(key, len(shapes))
        p = {}
        for k, (name, shape) in zip(keys, sorted(shapes.items())):
            std = shape[0] ** -0.5
            if name in ("wo", "w_down"):
                std *= 0.5 / c
            p[name] = normal(k, shape, std)
        D = cfg.head_dim if kind == SPARSE else cfg.lightning_head_dim
        p["q_norm"] = jnp.full((D,), 2.0, cfg.dtype)
        p["k_norm"] = jnp.full((D,), 2.0, cfg.dtype)
        if kind == LIGHTNING:
            p["o_norm"] = jnp.ones((D,), cfg.dtype)
        for name in ("input_norm", "post_norm"):
            p[name] = jnp.ones((H,), cfg.dtype)
        layers.append(p)
    return {"embed": normal(k_emb, (cfg.vocab_rows, H), 1.0 / cfg.scale_emb),
            "lm_head": normal(k_head, (cfg.vocab_rows, H),
                              H ** -0.5 / cfg.logit_scale),
            "final_norm": jnp.ones((H,), cfg.dtype),
            "layers": layers}


__all__ = ["MinicpmSalaConfig", "minicpm_sala_init", "decay", "sparse_qkv",
           "lightning_qkv", "gated_out", "mlp", "SPARSE", "LIGHTNING"]

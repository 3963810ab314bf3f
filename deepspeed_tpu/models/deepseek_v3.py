"""The ``deepseek_v3`` family (DeepSeek-V3, GigaChat3 Ultra): latent
attention (MLA) with YaRN rotary positions, RMS norm, leading dense
SwiGLU layers, then expert layers routed by sigmoid scores with a
selection bias and a group limit, plus a shared expert.

This module is the MODEL: its config from the published ``config.json``
keys, a seeded init and the layer pieces every path shares (YaRN
frequencies, the rotary rotation, the latent projections; RMS norm, the
gated FFN and the plain products are ``models/blocks.py``'s, shared with
the other families).  How it is served (the paged latent cache, the absorbed
attend) is ``inference/latent.py``; how an expert layer that holds a
share of the experts routes and computes is ``moe/share.py``.  Nothing
here is imported unless a configuration asks for it.

Parameter tree (weights ``[in, out]`` like ``models.transformer.dense``;
the routed experts ``[E_held, F, H]`` so that an expert's ``[tf, H]``
tile is one contiguous run of HBM):

    embed [V, H]   lm_head [V, H]   final_norm [H]
    dense / moe : per-layer tensors stacked on a leading axis
      input_norm [H]  wq_a [H, q_lora]  q_norm [q_lora]
      wq_b [q_lora, nH*(nope+rope)]  wkv_a [H, kv_lora+rope]
      kv_norm [kv_lora]  wkv_b [kv_lora, nH*(nope+v)]  wo [nH*v, H]
      post_norm [H]
      dense: mlp_gate [H, I]  mlp_up [H, I]  mlp_down [I, H]
      moe:   router [H, E]  router_bias [E] (fp32)
             w_gate / w_up / w_down [E_held, F, H]
             shared_gate [H, Fs]  shared_up [H, Fs]  shared_down [Fs, H]
      with ``hc_mult`` = n > 0 (the ``xing4_0`` keys), for sub in attn, ffn:
             hc_<sub>_phi [n*H, 2n + n*n]  hc_<sub>_b [2n + n*n]
             hc_<sub>_alpha [3]  (all fp32)

``model_type: xing4_0`` is this family with FOUR residual streams: every
sublayer (attention after ``input_norm``, the FFN or expert layer after
``post_norm``) reads a learned mixture of the streams and writes back
through two more maps, one of them projected onto the doubly stochastic
matrices by Sinkhorn-Knopp iterations (``models/hyper_connections.py``
has the equations).  A config asks for it with ``hc_mult`` (+
``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min`` / ``_max``);
without the key the block is ``x + f(norm(x))`` on one stream and the
parameter tree has no ``hc_*`` leaf.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import hyper_connections as hc
from .blocks import Routing, matmul, rms_norm, rotary_cos_sin, swiglu


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """The published keys (same names), plus what a chip's share needs:
    ``held`` = (first, count) of the ``n_routed_experts`` routed experts
    this program holds (the router keeps its published width), and
    ``vocab_rows_held`` = the embedding / head rows held where that is
    not ``vocab_size`` (a slice padded to the MXU tiling: ids at or above
    ``vocab_size`` are never drawn and never sampled)."""
    vocab_size: int = 128256
    vocab_rows_held: int = 0
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 64
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    n_shared_experts: int = 1
    n_routed_experts: int = 256
    held: Tuple[int, int] = (0, 256)
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    # None (a published ``null``): a full-rank query, ``wq [H, nH*(nope+rope)]``
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 192
    rms_norm_eps: float = 1e-6
    rope_theta: float = 100000.0
    rope_factor: float = 64.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rope_original_max_position_embeddings: int = 4096
    # NoPE: the ``qk_rope_head_dim`` columns of q and of the cached row stay
    # in place, unrotated (the ``kimi_linear`` family's latent layers).
    mla_use_nope: bool = False
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    router_bias_std: float = 0.1
    # The ``xing4_0`` keys: ``hc_mult`` residual streams mixed by
    # Sinkhorn-projected maps round every sublayer
    # (``models/hyper_connections.py``); 0 = one stream, ``x + f(norm(x))``.
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    dtype: Any = jnp.bfloat16
    # Where the family's served-model implementation registers itself
    # (``inference.served.served_model`` imports it on first use).
    serving_module: ClassVar[str] = "deepspeed_tpu.inference.latent"

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(f"held={self.held} is no share of "
                             f"{self.n_routed_experts} routed experts")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")
        if not 0 < self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError("the stack is a dense prefix, then expert "
                             "layers: 0 < first_k_dense_replace < "
                             "num_hidden_layers")
        if self.hc_mult < 0 or self.hc_mult == 1:
            raise ValueError("hc_mult is 0 (one residual stream) or the "
                             "number of streams, at least 2")

    @classmethod
    def from_hf(cls, cfg: Dict[str, Any], **overrides) -> "DeepseekV3Config":
        """From a ``config.json`` dict: every key this class names is
        taken as published; ``rope_scaling`` is flattened; every routed
        expert is held unless ``held`` says otherwise."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        rs = cfg.get("rope_scaling") or {}
        if rs and rs.get("rope_type", rs.get("type")) != "yarn":
            raise NotImplementedError("only YaRN rope_scaling is written")
        for k in ("factor", "beta_fast", "beta_slow", "mscale",
                  "mscale_all_dim", "original_max_position_embeddings"):
            if k in rs:
                kw["rope_" + k] = rs[k]
        kw.update(overrides)
        if "n_routed_experts" in kw:
            kw.setdefault("held", (0, kw["n_routed_experts"]))
        return cls(**kw)

    @property
    def name(self) -> str:
        return (f"deepseek_v3-h{self.hidden_size}-l{self.num_hidden_layers}"
                f"-e{self.held[1]}of{self.n_routed_experts}"
                + (f"-hc{self.hc_mult}" if self.hc_mult else ""))

    @property
    def hyper(self) -> Optional[hc.HyperConnections]:
        """The residual maps' constants, or None for one stream."""
        if not self.hc_mult:
            return None
        return hc.HyperConnections(
            mult=self.hc_mult, iters=self.hc_sinkhorn_iters,
            eps=self.hc_eps, clamp=(float(self.mhc_h_res_clamp_min),
                                    float(self.mhc_h_res_clamp_max)))

    @property
    def vocab_rows(self) -> int:
        return self.vocab_rows_held or self.vocab_size

    @property
    def routing(self) -> Routing:
        """The expert layers' routing rule and share, as ``moe/share.py``
        reads it."""
        return Routing(experts=self.n_routed_experts,
                       per_tok=self.num_experts_per_tok,
                       n_group=self.n_group, topk_group=self.topk_group,
                       norm=self.norm_topk_prob,
                       scale=self.routed_scaling_factor, held=self.held)

    @property
    def num_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What the cache holds a token and layer: [ckv | k_rope]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim^-0.5 * m^2``, m = yarn_mscale(factor,
        mscale_all_dim) (the HF model folds YaRN's attention scaling into
        the softmax scale when ``mscale_all_dim`` is set)."""
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m


# ------------------------------------------------------------------ #
# YaRN rotary positions
# ------------------------------------------------------------------ #
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: DeepseekV3Config) -> np.ndarray:
    """float64 [rope_dim / 2]: interpolated (f / factor) below the
    ``beta_slow`` dimension, extrapolated (f) above ``beta_fast``'s, a
    linear ramp between (HF ``_compute_yarn_parameters``)."""
    dim, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    orig = cfg.rope_original_max_position_embeddings
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                       # 1 = extrapolate (f as it is)
    return freq / cfg.rope_factor * (1.0 - keep) + freq * keep


def rope_cos_sin(cfg: DeepseekV3Config, positions: jax.Array):
    """fp32 cos, sin ``[..., rope_dim / 2]`` at integer ``positions``;
    scaled by yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim) (1 for the published values)."""
    att = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return rotary_cos_sin(yarn_inv_freq(cfg), positions, att)


def rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array
                     ) -> jax.Array:
    """Rotate the pairs ``(2i, 2i+1)`` of the last axis by frequency i
    (``rope_interleave``, the family's default).  cos/sin broadcast
    against ``x[..., ::2]``; fp32 inside, x's dtype out."""
    xf = x.astype(jnp.float32)
    pairs = xf.reshape(xf.shape[:-1] + (xf.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ------------------------------------------------------------------ #
# Layer pieces
# ------------------------------------------------------------------ #
def latent_projections(p: Dict[str, jax.Array], h: jax.Array,
                       positions: jax.Array, cfg):
    """The projections ahead of the attend, for normed input ``h
    [..., H]`` at ``positions [...]``: (q_nope [..., nH, nope], q_rope
    [..., nH, rope] rotated, ckv [..., kv_lora] normed, k_rope [...,
    rope] rotated) — ``[ckv | k_rope]`` is the row the cache keeps.
    ``cfg``: a ``DeepseekV3Config``, or any config with its latent keys
    (``q_lora_rank`` None: ``wq`` alone; ``mla_use_nope``: nothing is
    rotated and ``positions`` is not read)."""
    nH, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                  cfg.qk_rope_head_dim)
    if cfg.q_lora_rank:
        cq = rms_norm(matmul(h, p["wq_a"]), p["q_norm"], cfg.rms_norm_eps)
        q = matmul(cq, p["wq_b"])
    else:
        q = matmul(h, p["wq"])
    q = q.reshape(h.shape[:-1] + (nH, dn + dr))
    kv = matmul(h, p["wkv_a"])
    ckv = rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_norm"],
                   cfg.rms_norm_eps)
    if cfg.mla_use_nope:
        return q[..., :dn], q[..., dn:], ckv, kv[..., cfg.kv_lora_rank:]
    cos, sin = rope_cos_sin(cfg, positions)
    q_rope = rope_interleaved(q[..., dn:], cos[..., None, :],
                              sin[..., None, :])
    k_rope = rope_interleaved(kv[..., cfg.kv_lora_rank:], cos, sin)
    return q[..., :dn], q_rope, ckv, k_rope


def wkv_b_split(p: Dict[str, jax.Array], cfg):
    """``wkv_b [kv_lora, nH*(nope+v)]`` -> (k part [kv_lora, nH, nope],
    v part [kv_lora, nH, v])."""
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                           cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


# ------------------------------------------------------------------ #
# Seeded init
# ------------------------------------------------------------------ #
def _attn_shapes(cfg: DeepseekV3Config) -> Dict[str, Tuple[int, ...]]:
    H, nH = cfg.hidden_size, cfg.num_attention_heads
    query = {"wq_a": (H, cfg.q_lora_rank),
             "wq_b": (cfg.q_lora_rank, nH * cfg.qk_head_dim)} \
        if cfg.q_lora_rank else {"wq": (H, nH * cfg.qk_head_dim)}
    return {
        **query,
        "wkv_a": (H, cfg.latent_width),
        "wkv_b": (cfg.kv_lora_rank,
                  nH * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (nH * cfg.v_head_dim, H),
    }


def _norm_shapes(cfg: DeepseekV3Config) -> Dict[str, Tuple[int, ...]]:
    q_norm = {"q_norm": (cfg.q_lora_rank,)} if cfg.q_lora_rank else {}
    return {"input_norm": (cfg.hidden_size,), **q_norm,
            "kv_norm": (cfg.kv_lora_rank,), "post_norm": (cfg.hidden_size,)}


_HC_SUBLAYERS = ("attn", "ffn")
_HC_BIAS_STD = 1.0


def _hc_init(key: jax.Array, n_layers: int, cfg: DeepseekV3Config
             ) -> Dict[str, jax.Array]:
    """The residual maps of ``n_layers`` layers, fp32: ``phi`` normal(0,
    initializer_range), ``b`` normal(0, 1), ``alpha`` 1 — ALIVE
    on purpose, as the router's bias is: under the papers' near-identity
    start (``alpha`` 0.01, ``H_res`` = I) a wrong or skipped map would pass
    every comparison."""
    out = {}
    shapes = hc.param_shapes(cfg.hyper, cfg.hidden_size)
    for i, sub in enumerate(_HC_SUBLAYERS):
        k_phi, k_b = jax.random.split(jax.random.fold_in(key, i))
        out[f"hc_{sub}_phi"] = jax.random.normal(
            k_phi, (n_layers,) + shapes["phi"], jnp.float32) \
            * cfg.initializer_range
        out[f"hc_{sub}_b"] = jax.random.normal(
            k_b, (n_layers,) + shapes["b"], jnp.float32) * _HC_BIAS_STD
        out[f"hc_{sub}_alpha"] = jnp.ones((n_layers,) + shapes["alpha"],
                                          jnp.float32)
    return out


def hc_maps(p: Dict[str, jax.Array], sub: str, X: jax.Array,
            cfg: DeepseekV3Config) -> hc.Maps:
    """The maps of layer ``p``'s sublayer ``sub`` ("attn" / "ffn") for
    ``X [..., n, H]``."""
    return hc.maps(p[f"hc_{sub}_phi"], p[f"hc_{sub}_b"],
                      p[f"hc_{sub}_alpha"], X, cfg.hyper)


def deepseek_v3_init(rng: jax.Array, cfg: DeepseekV3Config
                     ) -> Dict[str, Any]:
    """Weights normal(0, initializer_range) in ``cfg.dtype``, norms 1,
    and the router's selection bias normal(0, router_bias_std) in fp32:
    NON-zero on purpose, so that choosing by ``s + b`` and weighting by
    ``s`` are distinguishable in every comparison.  With ``hc_mult`` the
    residual maps' parameters (``_hc_init``), from keys of their own: the
    rest of the tree is what it is without them."""
    H, I, F = cfg.hidden_size, cfg.intermediate_size, \
        cfg.moe_intermediate_size
    Fs = F * cfg.n_shared_experts
    E, Eh = cfg.n_routed_experts, cfg.held[1]
    std = cfg.initializer_range

    def stack(key, n, shapes):
        keys = jax.random.split(key, len(shapes))
        return {name: (jax.random.normal(k, (n,) + shape, jnp.float32)
                       * std).astype(cfg.dtype)
                for k, (name, shape) in zip(keys, sorted(shapes.items()))}

    def norms(n):
        return {name: jnp.ones((n,) + shape, cfg.dtype)
                for name, shape in _norm_shapes(cfg).items()}

    k_emb, k_head, k_dense, k_moe, k_bias = jax.random.split(rng, 5)
    Ld, Le = cfg.num_dense_layers, cfg.num_moe_layers
    dense = stack(k_dense, Ld, dict(
        _attn_shapes(cfg), mlp_gate=(H, I), mlp_up=(H, I), mlp_down=(I, H)))
    moe = stack(k_moe, Le, dict(
        _attn_shapes(cfg), router=(H, E), w_gate=(Eh, F, H),
        w_up=(Eh, F, H), w_down=(Eh, F, H), shared_gate=(H, Fs),
        shared_up=(H, Fs), shared_down=(Fs, H)))
    moe["router_bias"] = jax.random.normal(
        k_bias, (Le, E), jnp.float32) * cfg.router_bias_std
    if cfg.hyper is not None:
        k_hc = jax.random.fold_in(rng, 5)
        dense.update(_hc_init(jax.random.fold_in(k_hc, 0), Ld, cfg))
        moe.update(_hc_init(jax.random.fold_in(k_hc, 1), Le, cfg))
    return {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_rows, H), jnp.float32)
                  * std).astype(cfg.dtype),
        "lm_head": (jax.random.normal(k_head, (cfg.vocab_rows, H),
                                      jnp.float32) * std).astype(cfg.dtype),
        "final_norm": jnp.ones((H,), cfg.dtype),
        "dense": dict(dense, **norms(Ld)),
        "moe": dict(moe, **norms(Le)),
    }


__all__ = ["DeepseekV3Config", "deepseek_v3_init", "yarn_inv_freq",
           "yarn_mscale", "rotary_cos_sin", "rope_cos_sin",
           "rope_interleaved", "rms_norm", "matmul", "swiglu",
           "latent_projections", "wkv_b_split", "hc_maps"]

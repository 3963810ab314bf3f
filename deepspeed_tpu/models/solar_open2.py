"""The ``solar_open2`` family (Solar-Open2-250B): three Kimi-Delta-Attention
layers — the gated delta rule of ``models/kimi_linear.py`` with a write
strength that reaches 2, so the transition ``I - beta k k^T`` has an
eigenvalue in (-1, 1) along the key (``kda_allow_neg_eigval``) — to one
grouped-query softmax attention layer with NO position encoding
(``use_rope: false``) and a sigmoid OUTPUT GATE (``use_gqa_gate``); every
layer an expert layer routed by sigmoid scores with a selection bias, plus a
shared expert.

This module is the MODEL: its config from the published ``config.json`` keys
and a seeded init.  The KDA mixer's pieces are ``models/kimi_linear.py``'s
(``kda_in``, ``kda_conv``, ``kda_qkv``, ``kda_gates``, ``kda_out``: they read
the ``kda_*`` fields of whatever config they are handed), the state's own
arithmetic is ``ops/kda.py``, the expert layer ``moe/share.py`` as it stands;
how the model is served (K/V pages beside a per-stream class) is
``inference/solar_open2.py``.  Nothing here is imported unless a
configuration asks for it.

Layer ``l`` (0-based, as the config counts): ``a = h + Mixer_l(RMSNorm(h))``;
``h' = a + MoE(RMSNorm(a))``.  ``Mixer_l`` is grouped-query attention for
``l`` in ``gqa_layers``, KDA otherwise (``gqa_interval`` of them behind each).

    KDA (nh heads of d): as ``models/kimi_linear.py``'s docstring, with
        beta = 2 sigmoid(x W_beta)                                (a head)
    GQA: q = x W_q [nH, D]   k = x W_k [nKV, D]   v = x W_v [nKV, D]
         (no bias, no q/k norm, no rotation)
         A = causal softmax(q k^T / sqrt(D)) v
         out = (A . sigmoid(x W_g)) W_o                  (W_g [H, nH D])

Parameter tree (weights ``[in, out]``; a layer is a dict in ``layers``, a
Python list: the layers differ in kind):

    embed [V, H]   lm_head [V, H]   final_norm [H]
    every layer: input_norm [H]  post_norm [H]
                 router [H, E]  router_bias [E] (fp32)
                 w_gate / w_up / w_down [E_held, F, H]
                 shared_gate / shared_up [H, Fs]  shared_down [Fs, H]
    a KDA layer: as ``models/kimi_linear.py``'s
    a GQA layer: wq [H, nH D]  wk [H, nKV D]  wv [H, nKV D]  wg [H, nH D]
                 wo [nH D, H]
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, Tuple

import jax
import jax.numpy as jnp

from . import kimi_linear as kl
from .blocks import Routing, matmul

KDA, GQA = "kda", "gqa"


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """The published keys (same names; ``linear_attn_config`` flattened to
    ``kda_*`` / ``short_conv_kernel_size``), what a chip's share needs
    (``held`` = (first, count) of the routed experts this program holds) and
    the compute dtype."""
    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240          # read by no layer (no dense FFN)
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    first_k_dense_replace: int = 0
    use_rope: bool = False
    gqa_interval: int = 3
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    use_gqa_gate: bool = True
    # linear_attn_config
    kda_num_heads: int = 64
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    # the expert layers
    n_routed_experts: int = 320
    held: Tuple[int, int] = (0, 320)
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 1048576
    dtype: Any = jnp.bfloat16
    # Where the family's served-model implementation registers itself
    # (``inference.served.served_model`` imports it on first use).
    serving_module: ClassVar[str] = "deepspeed_tpu.inference.solar_open2"

    def __post_init__(self):
        object.__setattr__(self, "gqa_layers", tuple(
            int(v) for v in self.gqa_layers))
        object.__setattr__(self, "held", tuple(int(v) for v in self.held))
        if (self.use_rope or not self.use_gqa_gate or self.kda_use_full_proj
                or self.tie_word_embeddings or self.first_k_dense_replace
                or self.short_conv_kernel_size < 2):
            raise NotImplementedError(
                "solar_open2 as written: no position encoding in the "
                "grouped-query layers and an output gate on them, low-rank "
                "KDA gates, an untied head, every layer an expert layer, a "
                "filter of two taps or more")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(f"held={self.held} is no share of "
                             f"{self.n_routed_experts} routed experts")

    @classmethod
    def from_hf(cls, cfg: Dict[str, Any], **overrides) -> "SolarOpen2Config":
        """From a ``config.json`` dict: every key this class names is taken
        as published; ``linear_attn_config`` is flattened; ``gqa_layers`` may
        name layers past ``num_hidden_layers`` (a cut in depth keeps the list
        whole and takes the entries under it); every routed expert is held
        unless ``held`` says otherwise."""
        if cfg.get("rope_scaling"):
            raise NotImplementedError("solar_open2 rotates nothing")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        lin = cfg.get("linear_attn_config") or {}
        if lin.get("num_kv_heads") not in (None, lin.get("num_heads")):
            raise NotImplementedError(
                "solar_open2 as written: a KDA key/value head a query head")
        for key, name in (("num_heads", "kda_num_heads"),
                          ("head_dim", "kda_head_dim"),
                          ("short_conv_kernel_size",
                           "short_conv_kernel_size")):
            if key in lin:
                kw[name] = lin[key]
        kw.update(overrides)
        if "n_routed_experts" in kw:
            kw.setdefault("held", (0, kw["n_routed_experts"]))
        return cls(**kw)

    @property
    def name(self) -> str:
        return (f"solar_open2-h{self.hidden_size}-l{self.num_hidden_layers}"
                f"-e{self.held[1]}of{self.n_routed_experts}")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """``GQA`` / ``KDA`` of layers 0 .. L - 1."""
        full = set(self.gqa_layers)
        return tuple(GQA if l in full else KDA
                     for l in range(self.num_hidden_layers))

    @property
    def num_kda_layers(self) -> int:
        return self.layer_kinds.count(KDA)

    @property
    def num_gqa_layers(self) -> int:
        return self.layer_kinds.count(GQA)

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def kda_width(self) -> int:
        """Channels of one of q, k, v: every head's ``kda_head_dim``."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the short filters run over and the cache keeps rows
        of: q~, k~ and v~ side by side."""
        return 3 * self.kda_width

    @property
    def group(self) -> int:
        """Query heads a K/V head."""
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def routing(self) -> Routing:
        return Routing(experts=self.n_routed_experts,
                       per_tok=self.num_experts_per_tok, n_group=1,
                       topk_group=1, norm=self.norm_topk_prob,
                       scale=float(self.routed_scaling_factor),
                       held=self.held)


def qkvg(p: Dict[str, jax.Array], u: jax.Array, cfg: SolarOpen2Config):
    """A grouped-query layer's projections of normed ``u [..., H]``: (q
    ``[..., nH, D]``, k and v ``[..., nKV, D]``, the output gate's logits
    ``[..., nH D]``); nothing is normed or rotated."""
    nH, nKV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = matmul(u, p["wq"]).reshape(u.shape[:-1] + (nH, D))
    k = matmul(u, p["wk"]).reshape(u.shape[:-1] + (nKV, D))
    v = matmul(u, p["wv"]).reshape(u.shape[:-1] + (nKV, D))
    return q, k, v, matmul(u, p["wg"])


# ------------------------------------------------------------------ #
# Seeded init
# ------------------------------------------------------------------ #
def _layer_stds(cfg: SolarOpen2Config, layer: int
                ) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """{tensor: (shape, std)} of layer ``layer``'s matrices: see
    ``solar_open2_init``."""
    H, F = cfg.hidden_size, cfg.moe_intermediate_size
    unit, out = 1.0 / math.sqrt(H), kl._BRANCH_RMS
    if cfg.layer_kinds[layer] == KDA:
        stds = kl.kda_stds(cfg)
    else:
        nH, nKV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        stds = {
            # (k of unit variance: the scores' spread is q's)
            "wq": ((H, nH * D), kl._SCORE_STD * unit),
            "wk": ((H, nKV * D), unit), "wv": ((H, nKV * D), unit),
            "wg": ((H, nH * D), unit),
            # (a handful of unit rows averaged, times sigmoid(unit gate))
            "wo": ((nH * D, H), out / (0.4 * math.sqrt(nH * D)))}
    Eh, Fs = cfg.held[1], F * cfg.n_shared_experts
    # (silu(a) * b of unit a, b has RMS ~0.6)
    stds.update({
        "router": ((H, cfg.n_routed_experts), unit),
        "w_gate": ((Eh, F, H), unit), "w_up": ((Eh, F, H), unit),
        "w_down": ((Eh, F, H), out / (0.6 * math.sqrt(F))),
        "shared_gate": ((H, Fs), unit), "shared_up": ((H, Fs), unit),
        "shared_down": ((Fs, H), out / (0.6 * math.sqrt(Fs)))})
    return stds


def solar_open2_init(rng: jax.Array, cfg: SolarOpen2Config) -> Dict[str, Any]:
    """Seeded weights in ``cfg.dtype`` (the filters, ``dt_bias``, ``A_log``
    and the router's bias fp32), norms 1, on ``kimi_linear_init``'s ranges
    and for its reasons (every projection normal(0, target / sqrt(fan_in));
    decays from a few tokens to thousands, differing inside a head; filters
    alive on every tap; a router bias that is not 0).  ``beta``'s logit is
    normal(0, 1.5) and ``beta`` twice its sigmoid: about HALF the writes
    land over 1, where the transition along the key changes sign — a flat
    init would leave that regime unvisited.  The grouped-query layers'
    scores have a spread of 4 (a query reads a handful of rows, as a trained
    model's does: at 1 a softmax over 100k rows is the rows' mean and WHAT
    is attended never shows) and their gate's logits of 1."""
    H = cfg.hidden_size

    def normal(key, shape, std, dtype=cfg.dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                * jnp.asarray(std, jnp.float32)).astype(dtype)

    k_emb, k_head, k_layers = jax.random.split(rng, 3)
    layers = []
    for l, key in enumerate(jax.random.split(k_layers,
                                             cfg.num_hidden_layers)):
        stds = _layer_stds(cfg, l)
        keys = jax.random.split(key, len(stds) + 4)
        p = {name: normal(k, shape, std) for k, (name, (shape, std))
             in zip(keys, sorted(stds.items()))}
        k_w, k_a, k_dt, k_bias = keys[len(stds):]
        if cfg.layer_kinds[l] == KDA:
            p.update(kl.kda_vectors(k_w, k_a, k_dt, cfg))
        p["router_bias"] = normal(k_bias, (cfg.n_routed_experts,),
                                  kl._ROUTER_BIAS_STD, jnp.float32)
        p["input_norm"] = jnp.ones((H,), cfg.dtype)
        p["post_norm"] = jnp.ones((H,), cfg.dtype)
        layers.append(p)
    return {
        "embed": normal(k_emb, (cfg.vocab_size, H), 1.0),
        "lm_head": normal(k_head, (cfg.vocab_size, H), 1.0 / math.sqrt(H)),
        "final_norm": jnp.ones((H,), cfg.dtype),
        "layers": layers}


__all__ = ["SolarOpen2Config", "solar_open2_init", "qkvg", "KDA", "GQA"]

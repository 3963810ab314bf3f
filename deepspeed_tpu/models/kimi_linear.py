"""The ``kimi_linear`` family (Kimi-Linear-48B-A3B): three Kimi-Delta-
Attention layers — a gated DELTA rule over a fixed-size fp32 state a stream,
with a decay a CHANNEL — to one latent-attention (MLA) layer with NO position
encoding and a full-rank query; a dense SwiGLU in the first layer, then
expert layers routed by sigmoid scores with a selection bias, plus a shared
expert.

This module is the MODEL: its config from the published ``config.json`` keys,
a seeded init and the KDA mixer's pieces every path shares (the fused q/k/v
projection, the three short filters, the two low-rank gates, the write
strength, the gated head norm).  The state's own arithmetic is
``ops/kda.py``; the latent layer's projections are
``models/deepseek_v3.latent_projections`` (``q_lora_rank`` None,
``mla_use_nope``); the expert layer is ``moe/share.py`` as it stands; how
the model is served (a latent class beside a per-stream class) is
``inference/kimi_linear.py``.  Nothing here is imported unless a
configuration asks for it.

Block ``l`` (1-based, as the config counts): ``h += Mixer_l(RMSNorm(h))``;
``h += FFN_l(RMSNorm(h))``.  ``Mixer_l`` is KDA for ``l`` in
``linear_attn_config.kda_layers``, latent attention for ``l`` in
``full_attn_layers``; ``FFN_l`` is dense for ``l <= first_k_dense_replace``.

The KDA mixer on normed ``x [T, H]`` (nh heads of d = ``head_dim``):

    [q~ | k~ | v~] = x W_qkv                 (three projections, side by side)
    q, k, v = silu(filter_j(.))              (a depthwise causal filter EACH,
                                              ``short_conv_kernel_size`` taps,
                                              the last on the current token)
    q = L2Norm_head(q) d^-0.5   k = L2Norm_head(k)
    g = -exp(A_log_h) softplus((x W_f_down) W_f_up + dt_bias)   (a CHANNEL)
    beta = sigmoid(x W_beta)                                    (a head)
    S_t = (I - beta k k^T) Diag(e^g) S_{t-1} + beta k v^T;  o = S_t^T q
    y = RMSNorm_head(o) . sigmoid((x W_g_down) W_g_up);  out = y W_o

Parameter tree (weights ``[in, out]``; a layer is a dict in ``layers``, a
Python list: the layers differ in kind):

    embed [V, H]   lm_head [V, H]   final_norm [H]
    every layer: input_norm [H]  post_norm [H]
    a KDA layer:  w_qkv [H, 3 nh d]  conv_w [3 nh d, taps] (fp32)
                  w_f_down [H, d]  w_f_up [d, nh d]  dt_bias [nh d] (fp32)
                  A_log [nh] (fp32)  w_beta [H, nh]
                  w_g_down [H, d]  w_g_up [d, nh d]  o_norm [d]
                  wo [nh d, H]
    a latent layer: wq [H, nH (nope + rope)]  wkv_a [H, kv_lora + rope]
                  kv_norm [kv_lora]  wkv_b [kv_lora, nH (nope + v)]
                  wo [nH v, H]
    a dense layer: mlp_gate / mlp_up [H, I]  mlp_down [I, H]
    an expert layer: router [H, E]  router_bias [E] (fp32)
                  w_gate / w_up / w_down [E_held, F, H]
                  shared_gate / shared_up [H, Fs]  shared_down [Fs, H]
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .blocks import Routing, matmul, rms_norm

KDA, LATENT = "kda", "latent"


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The published keys (same names; ``linear_attn_config`` flattened to
    ``kda_*`` / ``full_attn_layers`` / ``short_conv_kernel_size``), what a
    chip's share needs (``held`` = (first, count) of the routed experts this
    program holds: as ``DeepseekV3Config``) and the seeded init's ranges."""
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    # linear_attn_config
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15,
                                   17, 18, 19, 21, 22, 23, 25, 26)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    # the latent layers
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    # the expert layers
    num_experts: int = 256
    held: Tuple[int, int] = (0, 256)
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    num_expert_group: int = 1
    topk_group: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    model_max_length: int = 1048576
    dtype: Any = jnp.bfloat16
    # Where the family's served-model implementation registers itself
    # (``inference.served.served_model`` imports it on first use).
    serving_module: ClassVar[str] = "deepspeed_tpu.inference.kimi_linear"

    def __post_init__(self):
        for name in ("kda_layers", "full_attn_layers"):
            object.__setattr__(self, name, tuple(
                int(v) for v in getattr(self, name)))
        object.__setattr__(self, "held", tuple(int(v) for v in self.held))
        L = self.num_hidden_layers
        kinds = {l: KDA for l in self.kda_layers if l <= L}
        kinds.update({l: LATENT for l in self.full_attn_layers if l <= L})
        if sorted(kinds) != list(range(1, L + 1)) or set(
                self.kda_layers) & set(self.full_attn_layers):
            raise ValueError(
                "linear_attn_config: kda_layers and full_attn_layers "
                f"(1-based) name every layer up to {L} once")
        if (self.q_lora_rank or not self.mla_use_nope
                or self.tie_word_embeddings or self.moe_layer_freq != 1
                or self.moe_router_activation_func != "sigmoid"
                or self.num_attention_heads != self.num_key_value_heads
                or self.short_conv_kernel_size < 2):
            raise NotImplementedError(
                "kimi_linear as written: a full-rank query and no position "
                "encoding in the latent layers, an untied head, every layer "
                "past the dense prefix an expert layer routed by sigmoid "
                "scores, a filter of two taps or more")
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(f"held={self.held} is no share of "
                             f"{self.num_experts} routed experts")
        if not 0 <= self.first_k_dense_replace <= L:
            raise ValueError("first_k_dense_replace counts leading layers")

    @classmethod
    def from_hf(cls, cfg: Dict[str, Any], **overrides) -> "KimiLinearConfig":
        """From a ``config.json`` dict: every key this class names is taken
        as published; ``linear_attn_config`` is flattened (its lists may
        name layers past ``num_hidden_layers``: a cut in depth keeps them
        whole and takes the entries up to it); every routed expert is held
        unless ``held`` says otherwise."""
        if cfg.get("rope_scaling"):
            raise NotImplementedError("kimi_linear rotates nothing")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        lin = cfg.get("linear_attn_config") or {}
        for key, name in (("kda_layers", "kda_layers"),
                          ("full_attn_layers", "full_attn_layers"),
                          ("num_heads", "kda_num_heads"),
                          ("head_dim", "kda_head_dim"),
                          ("short_conv_kernel_size",
                           "short_conv_kernel_size")):
            if key in lin:
                kw[name] = lin[key]
        kw.update(overrides)
        if "num_experts" in kw:
            kw.setdefault("held", (0, kw["num_experts"]))
        return cls(**kw)

    @property
    def name(self) -> str:
        return (f"kimi_linear-h{self.hidden_size}-l{self.num_hidden_layers}"
                f"-e{self.held[1]}of{self.num_experts}")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """``KDA`` / ``LATENT`` of layers 1 .. L, 0-based here."""
        full = set(self.full_attn_layers)
        return tuple(LATENT if l in full else KDA
                     for l in range(1, self.num_hidden_layers + 1))

    @property
    def num_kda_layers(self) -> int:
        return self.layer_kinds.count(KDA)

    @property
    def num_latent_layers(self) -> int:
        return self.layer_kinds.count(LATENT)

    @property
    def num_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def kda_width(self) -> int:
        """Channels of one of q, k, v: every head's ``kda_head_dim``."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the short filters run over and the cache keeps rows
        of: q~, k~ and v~ side by side."""
        return 3 * self.kda_width

    # -- what the latent sublayer and the expert layer read ------------- #
    @property
    def max_position_embeddings(self) -> int:
        return self.model_max_length

    @property
    def num_experts_per_tok(self) -> int:
        return self.num_experts_per_token

    @property
    def hyper(self):
        """One residual stream (``inference/latent.py`` asks)."""
        return None

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What a latent layer's cache holds a token: [ckv | k_pe]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def routing(self) -> Routing:
        return Routing(experts=self.num_experts,
                       per_tok=self.num_experts_per_token,
                       n_group=self.num_expert_group,
                       topk_group=self.topk_group,
                       norm=self.moe_renormalize,
                       scale=self.routed_scaling_factor, held=self.held)


# ------------------------------------------------------------------ #
# The KDA mixer's pieces.  ``cfg``: any config with the ``kda_*`` fields,
# ``short_conv_kernel_size`` and ``rms_norm_eps`` (this family's, and
# ``models/solar_open2.py``'s, whose ``kda_allow_neg_eigval`` is set).
# ------------------------------------------------------------------ #
_L2_EPS = 1e-6


def kda_in(p: Dict[str, jax.Array], u: jax.Array, cfg) -> jax.Array:
    """``[q~ | k~ | v~]`` ``[..., conv_dim]`` of normed ``u [..., H]``: what
    the filters run over and the conv state keeps rows of."""
    return matmul(u, p["w_qkv"])


def kda_conv(p: Dict[str, jax.Array], rows: jax.Array, cfg) -> jax.Array:
    """The three short filters (one depthwise filter over their channels
    side by side) over ``rows [..., taps - 1 + K, conv_dim]`` (a stream's
    kept rows ahead of its K new ones): ``silu(sum_j w[:, j] rows[j : j +
    K])`` ``[..., K, conv_dim]``, the last tap on the current row, no bias;
    fp32 inside, the rows' dtype out."""
    taps = cfg.short_conv_kernel_size
    K = rows.shape[-2] - (taps - 1)
    mixed = sum(rows[..., j:j + K, :].astype(jnp.float32) * p["conv_w"][:, j]
                for j in range(taps))
    return jax.nn.silu(mixed).astype(rows.dtype)


def kda_qkv(mixed: jax.Array, cfg):
    """Filtered ``[..., conv_dim]`` -> fp32 (q ``[..., nh, d]`` L2-normed a
    head times ``d^-0.5``, k L2-normed, v as it is)."""
    nh, d = cfg.kda_num_heads, cfg.kda_head_dim
    x = mixed.astype(jnp.float32).reshape(mixed.shape[:-1] + (3, nh, d))
    q, k, v = x[..., 0, :, :], x[..., 1, :, :], x[..., 2, :, :]

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                 + _L2_EPS)
    return unit(q) * d ** -0.5, unit(k), v


def _low_rank(u, down, up):
    """``(u W_down) W_up`` in fp32 out of bf16 products: the inner
    activation in u's dtype, fp32 accumulation."""
    return jnp.dot(matmul(u, down), up.astype(u.dtype),
                   preferred_element_type=jnp.float32)


def kda_gates(p: Dict[str, jax.Array], u: jax.Array, cfg):
    """fp32 (g ``[..., nh, d]``, the LOG decay a channel, < 0; beta ``[...,
    nh]`` in (0, 1) — in (0, 2) for a config whose ``kda_allow_neg_eigval``
    is set: the write's strength then passes 1 and ``I - beta k k^T`` has a
    negative eigenvalue along the key) of normed ``u [..., H]``."""
    nh, d = cfg.kda_num_heads, cfg.kda_head_dim
    f = _low_rank(u, p["w_f_down"], p["w_f_up"]) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        f.reshape(f.shape[:-1] + (nh, d)))
    beta = jax.nn.sigmoid(jnp.dot(u, p["w_beta"].astype(u.dtype),
                                  preferred_element_type=jnp.float32))
    if getattr(cfg, "kda_allow_neg_eigval", False):
        beta = 2.0 * beta
    return g, beta


def kda_out(p: Dict[str, jax.Array], o: jax.Array, u: jax.Array,
            cfg) -> jax.Array:
    """``(RMSNorm_head(o) . sigmoid((u W_g_down) W_g_up)) W_o``: o fp32
    ``[..., nh, d]``, the norm over each head's d values with one weight of
    d; fp32 inside, u's dtype into the last product."""
    gate = jax.nn.sigmoid(_low_rank(u, p["w_g_down"], p["w_g_up"]))
    y = rms_norm(o, p["o_norm"], cfg.rms_norm_eps)
    y = (y.reshape(gate.shape) * gate).astype(u.dtype)
    return matmul(y, p["wo"])


# ------------------------------------------------------------------ #
# Seeded init
# ------------------------------------------------------------------ #
_A_RANGE = (1.0, 16.0)       # -A = exp(A_log), uniform, a head
_DT_RANGE = (1e-3, 1e-1)     # softplus(dt_bias), log-uniform, a channel
_FILTER_STD = 0.5            # every tap of the short filters
_BETA_LOGIT_STD = 1.5        # beta spreads over (0.1, 0.9)
_SCORE_STD = 4.0             # the latent layers' scores (see the init)
_ROUTER_BIAS_STD = 0.1
_BRANCH_RMS = 0.5            # a branch's contribution to the residual stream

def kda_stds(cfg) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """{tensor: (shape, std)} of a KDA mixer's matrices (any config with the
    ``kda_*`` fields): see ``kimi_linear_init``."""
    H, d, W = cfg.hidden_size, cfg.kda_head_dim, cfg.kda_width
    unit = 1.0 / math.sqrt(H)
    return {
        "w_qkv": ((H, cfg.conv_dim), unit),
        "w_f_down": ((H, d), unit), "w_f_up": ((d, W), d ** -0.5),
        "w_g_down": ((H, d), unit), "w_g_up": ((d, W), d ** -0.5),
        "w_beta": ((H, cfg.kda_num_heads), _BETA_LOGIT_STD * unit),
        # (a unit-RMS head times sigmoid(unit gate) has RMS ~0.55)
        "wo": ((W, H), _BRANCH_RMS / (0.55 * math.sqrt(W)))}


def kda_vectors(k_w, k_a, k_dt, cfg) -> Dict[str, jax.Array]:
    """A KDA mixer's fp32 leaves and its head norm from three keys: the
    filters' taps, ``A_log`` a head, ``dt_bias`` a channel, ``o_norm``
    (ranges: see ``kimi_linear_init``)."""
    lo, hi = _A_RANGE
    a_log = jnp.log(jax.random.uniform(k_a, (cfg.kda_num_heads,),
                                       jnp.float32, lo, hi))
    lo, hi = _DT_RANGE
    dt = jnp.exp(jax.random.uniform(k_dt, (cfg.kda_width,), jnp.float32,
                                    math.log(lo), math.log(hi)))
    return {
        "conv_w": jax.random.normal(
            k_w, (cfg.conv_dim, cfg.short_conv_kernel_size), jnp.float32)
        * jnp.asarray(_FILTER_STD, jnp.float32),
        "A_log": a_log,
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
        "o_norm": jnp.ones((cfg.kda_head_dim,), cfg.dtype)}


def _layer_stds(cfg: KimiLinearConfig, layer: int
                ) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """{tensor: (shape, std)} of layer ``layer``'s (0-based) matrices: see
    ``kimi_linear_init``."""
    H, I, F = (cfg.hidden_size, cfg.intermediate_size,
               cfg.moe_intermediate_size)
    unit, out = 1.0 / math.sqrt(H), _BRANCH_RMS
    if cfg.layer_kinds[layer] == KDA:
        stds = kda_stds(cfg)
    else:
        nH, C = cfg.num_attention_heads, cfg.kv_lora_rank
        stds = {
            # (k of unit variance: the scores' spread is q's)
            "wq": ((H, nH * cfg.qk_head_dim), _SCORE_STD * unit),
            "wkv_a": ((H, cfg.latent_width), unit),
            "wkv_b": ((C, nH * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                      C ** -0.5),
            "wo": ((nH * cfg.v_head_dim, H),
                   out / math.sqrt(nH * cfg.v_head_dim))}
    # (silu(a) * b of unit a, b has RMS ~0.6)
    if layer < cfg.num_dense_layers:
        stds.update({"mlp_gate": ((H, I), unit), "mlp_up": ((H, I), unit),
                     "mlp_down": ((I, H), out / (0.6 * math.sqrt(I)))})
    else:
        Eh, Fs = cfg.held[1], F * cfg.num_shared_experts
        down = out / (0.6 * math.sqrt(F))
        stds.update({
            "router": ((H, cfg.num_experts), unit),
            "w_gate": ((Eh, F, H), unit), "w_up": ((Eh, F, H), unit),
            "w_down": ((Eh, F, H), down),
            "shared_gate": ((H, Fs), unit), "shared_up": ((H, Fs), unit),
            "shared_down": ((Fs, H), out / (0.6 * math.sqrt(Fs)))})
    return stds


def kimi_linear_init(rng: jax.Array, cfg: KimiLinearConfig) -> Dict[str, Any]:
    """Seeded weights in ``cfg.dtype`` (the filters, ``dt_bias``, ``A_log``
    and the router's bias fp32), norms 1.

    Under a flat normal(0, 0.02) at the published widths the gates sit at
    one value (``beta`` = 0.5, one decay for every channel), q~ and k~ read
    0.02 and the delta term vanishes beside the write: a dropped correction,
    a decay a head or a stale page would pass any comparison (PR 48 found
    the same of Mamba-2).  So every projection is normal(0, target /
    sqrt(fan_in)): unit-variance q~, k~, v~, gate inputs, router logits and
    logits on a unit-RMS input, a unit-RMS embedding, each branch's
    contribution to the residual stream ``_BRANCH_RMS`` of it at most.
    The latent layers' scores have a spread of ``_SCORE_STD``: at 1 the
    softmax over hundreds of rows is so flat that a layer's output is the
    rows' mean (RMS ~N^-0.5) and WHAT is attended never shows — rotary
    switched on in both latent layers read 0.083 against the true reference
    where the served path's own rounding reads 0.055 (my chip run, PR 52 c1); at 4 a
    query reads a handful of rows, as a trained model's does.
    ``-A = exp(A_log)`` uniform on ``_A_RANGE`` a head and
    ``softplus(dt_bias)`` log-uniform on ``_DT_RANGE`` a CHANNEL
    (half-lives from a few tokens to thousands, differing inside a head);
    ``beta``'s logit normal(0, ``_BETA_LOGIT_STD``) so that it spreads over
    (0.1, 0.9); the filters' taps normal(0, ``_FILTER_STD``) on EVERY
    tap; the router's selection bias normal(0, ``_ROUTER_BIAS_STD``), so
    that choosing by ``s + b`` and weighting by ``s`` differ."""
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
            p.update(kda_vectors(k_w, k_a, k_dt, cfg))
        else:
            p["kv_norm"] = jnp.ones((cfg.kv_lora_rank,), cfg.dtype)
        if l >= cfg.num_dense_layers:
            p["router_bias"] = normal(k_bias, (cfg.num_experts,),
                                      _ROUTER_BIAS_STD, jnp.float32)
        p["input_norm"] = jnp.ones((H,), cfg.dtype)
        p["post_norm"] = jnp.ones((H,), cfg.dtype)
        layers.append(p)
    return {
        "embed": normal(k_emb, (cfg.vocab_size, H), 1.0),
        "lm_head": normal(k_head, (cfg.vocab_size, H), 1.0 / math.sqrt(H)),
        "final_norm": jnp.ones((H,), cfg.dtype),
        "layers": layers}


__all__ = ["KimiLinearConfig", "kimi_linear_init", "KDA", "LATENT",
           "kda_in", "kda_conv", "kda_qkv", "kda_gates", "kda_out",
           "kda_stds", "kda_vectors"]

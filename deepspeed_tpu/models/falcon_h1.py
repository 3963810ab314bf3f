"""The ``falcon_h1`` family (TII Falcon-H1 0.5B .. 34B): every layer's mixer
is the SUM of two branches over the same normed input — a Mamba-2
state-space mixer, which keeps a fixed-size fp32 STATE a stream, and
grouped-query attention, which keeps K/V rows a token — then a gated-SiLU
feed-forward part; every projection sits between muP MULTIPLIERS the
config publishes.

This module is the MODEL: its config from the published ``config.json``
keys, a seeded init and the pieces every path shares.  How it is served
(K/V pages AND a state a stream in EVERY layer, two kinds of cache of two
dtypes in one manager) is ``inference/falcon_h1.py``; the state-space
recurrence, its chunked form and its decode kernel are ``ops/ssm_scan.py``;
RMS norm, the product and the rotary angles are ``models/blocks.py``'s.
Nothing here is imported unless a configuration asks for it.

With ``h`` the residual stream (every multiplier is the config's key of
that name; P = ``mamba_d_head``, N = ``mamba_d_state``, nh =
``mamba_n_heads``, G = ``mamba_n_groups``):

    h0 = embed[tokens] * embedding_multiplier
    u  = RMSNorm(h; input_norm)
    attn(u): q, k, v = (u * attention_in_multiplier) Wq, Wk, Wv;
             k = k * key_multiplier;  rotary (rotate-half, every one of
             ``head_dim``'s pairs) on q and k;  causal softmax(q k^T /
             sqrt(D)) v, a K/V head serving nH / nKV query heads;
             a = (. Wo) * attention_out_multiplier
    ssm(u):  [z | xBC | dt] = ((u * ssm_in_multiplier) W_in) * mup_vector
             (widths d_ssm | d_ssm + 2 G N | nh; ``mup_vector`` =
             ``ssm_multipliers[0..4]`` over the segments z, x, B, C, dt);
             xBC = silu(conv(xBC) + conv_b): depthwise, causal,
             ``mamba_d_conv`` taps, the last on the current token;
             x [nh, P], B [G, N], C [G, N] = split(xBC);
             dt = softplus(dt + dt_bias);  A = -exp(A_log)   (a head each)
             S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t;
             y_t = S_t C_t + D x_t        (head j reads group j // (nh/G))
             y = RMSNorm over each of G groups of d_ssm / G (y * silu(z);
                 ssm_norm)                (``mamba_norm_before_gate`` false)
             m = (y W_out) * ssm_out_multiplier
    h = h + a + m
    g = RMSNorm(h; pre_ff_norm)
    h = h + (silu((g W_gate) * mlp_multipliers[0]) * (g W_up)) W_down
            * mlp_multipliers[1]

then ``final_norm`` and the head (untied) times ``lm_head_multiplier``.  A
stream's state of a layer is ``S [nh, N, P]`` (float32) and the last
``mamba_d_conv - 1`` rows of the projected ``xBC`` (before the filter).
What ``config.json`` does not say (the order of ``W_in``'s segments, where
the multipliers sit, the gated norm's grouping, ``mamba_use_mlp`` read as
"the block has its feed-forward part", no limit on dt) is from the
``falcon_h1`` modeling code of ``transformers``.

Parameter tree (weights ``[in, out]``, the head ``[V, H]``; one dict a
layer, walked in a static loop):

    embed [V, H]   lm_head [V, H]   final_norm [H]
    layers[l]:
      input_norm / pre_ff_norm [H]
      wq [H, nH*D]  wk / wv [H, nKV*D]  wo [nH*D, H]
      ssm_in [H, 2 d_ssm + 2 G N + nh]  ssm_out [d_ssm, H]  ssm_norm [d_ssm]
      conv_w [d_ssm + 2 G N, d_conv]  conv_b [d_ssm + 2 G N]      (fp32)
      A_log / D / dt_bias [nh]                                    (fp32)
      mlp_gate / mlp_up [H, I]  mlp_down [I, H]
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import rms_norm, rope_half, rotary_cos_sin, rotary_inv_freq


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The published keys (same names), the seeded init's ranges and the
    compute dtype."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    mamba_use_mlp: bool = True
    attention_bias: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    tie_word_embeddings: bool = False
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    max_position_embeddings: int = 262144
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    # the seeded init (``falcon_h1_init``)
    ssm_a_range: Tuple[float, float] = (1.0, 16.0)
    ssm_dt_range: Tuple[float, float] = (1e-3, 1e-1)
    conv_filter_std: float = 0.5
    conv_bias_std: float = 0.5
    branch_out_rms: float = 0.5
    dtype: Any = jnp.bfloat16
    # Where the family's served-model implementation registers itself
    # (``inference.served.served_model`` imports it on first use).
    serving_module: ClassVar[str] = "deepspeed_tpu.inference.falcon_h1"

    def __post_init__(self):
        for name in ("ssm_multipliers", "mlp_multipliers", "ssm_a_range",
                     "ssm_dt_range"):
            object.__setattr__(self, name, tuple(
                float(v) for v in getattr(self, name)))
        if (self.attention_bias or self.mlp_bias or self.projectors_bias
                or self.mamba_proj_bias or self.tie_word_embeddings
                or self.mamba_norm_before_gate or not self.mamba_rms_norm
                or not self.mamba_conv_bias or self.mamba_d_conv < 2
                or not self.mamba_use_mlp):
            raise NotImplementedError(
                "falcon_h1 as written: no projection biases, an untied "
                "head, a filter of two taps or more with its bias, the "
                "mixer's grouped RMS norm after the gate, the block's "
                "feed-forward part")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm \
                or self.mamba_n_heads % self.mamba_n_groups \
                or self.mamba_d_ssm % self.mamba_n_groups:
            raise ValueError(
                "mamba_d_ssm is mamba_n_heads heads of mamba_d_head, in "
                "mamba_n_groups equal groups")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers names z, x, B, C, dt; "
                             "mlp_multipliers the gate and the output")

    @classmethod
    def from_hf(cls, cfg: Dict[str, Any], **overrides) -> "FalconH1Config":
        """From a ``config.json`` dict: every key this class names is taken
        as published."""
        if cfg.get("rope_scaling"):
            raise NotImplementedError("falcon_h1 is written without rope "
                                      "scaling")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        kw.update(overrides)
        return cls(**kw)

    @property
    def name(self) -> str:
        return (f"falcon-h1-h{self.hidden_size}-l{self.num_hidden_layers}"
                f"-s{self.mamba_d_state}")

    @property
    def group(self) -> int:
        """Query heads a K/V head."""
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def ssm_bc_width(self) -> int:
        """Values of B (and of C) a token: every group's ``d_state``."""
        return self.mamba_n_groups * self.mamba_d_state

    @property
    def conv_dim(self) -> int:
        """Channels the short filter runs over: x, B and C."""
        return self.mamba_d_ssm + 2 * self.ssm_bc_width

    @property
    def ssm_in_width(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads

    @property
    def mup_vector(self) -> np.ndarray:
        """fp32 ``[ssm_in_width]``: ``ssm_multipliers`` over the segments
        z, x, B, C, dt of ``W_in``'s output."""
        widths = (self.mamba_d_ssm, self.mamba_d_ssm, self.ssm_bc_width,
                  self.ssm_bc_width, self.mamba_n_heads)
        return np.concatenate([np.full(w, m, np.float32) for w, m in
                               zip(widths, self.ssm_multipliers)])


def inv_freq(cfg: FalconH1Config) -> np.ndarray:
    """float64 [head_dim / 2]: ``theta^(-2i / head_dim)``, unscaled."""
    return rotary_inv_freq(cfg.rope_theta, cfg.head_dim)


def scaled_matmul(x: jax.Array, w: jax.Array, scale) -> jax.Array:
    """``(x w) * scale`` (a scalar or a vector over the output): the product
    in the compute dtype with fp32 accumulation, the multiplier on the fp32
    sum, one rounding to x's dtype."""
    y = jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)
    return (y * scale).astype(x.dtype)


def qkv(p: Dict[str, jax.Array], u: jax.Array, positions: jax.Array,
        cfg: FalconH1Config):
    """The attention branch's projections of normed input ``u [..., H]`` at
    ``positions [...]``: q ``[..., nH, D]`` and k ``[..., nKV, D]`` (times
    ``key_multiplier``), rotated; v ``[..., nKV, D]``."""
    nH, nKV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    m = cfg.attention_in_multiplier
    q = scaled_matmul(u, p["wq"], m).reshape(u.shape[:-1] + (nH, D))
    k = scaled_matmul(u, p["wk"], m * cfg.key_multiplier).reshape(
        u.shape[:-1] + (nKV, D))
    v = scaled_matmul(u, p["wv"], m).reshape(u.shape[:-1] + (nKV, D))
    cos, sin = rotary_cos_sin(inv_freq(cfg), positions)
    return (rope_half(q, cos[..., None, :], sin[..., None, :]),
            rope_half(k, cos[..., None, :], sin[..., None, :]), v)


def ssm_in(p: Dict[str, jax.Array], u: jax.Array, cfg: FalconH1Config):
    """The mixer's input projection of normed ``u [..., H]``: (z ``[...,
    d_ssm]``, the gate; xBC ``[..., conv_dim]``, what the filter runs over
    and the conv state keeps; dt ``[..., nh]`` fp32, before its bias)."""
    y = jnp.dot(u, p["ssm_in"].astype(u.dtype),
                preferred_element_type=jnp.float32) \
        * (cfg.ssm_in_multiplier * cfg.mup_vector)
    d = cfg.mamba_d_ssm
    return (y[..., :d].astype(u.dtype),
            y[..., d:d + cfg.conv_dim].astype(u.dtype),
            y[..., d + cfg.conv_dim:])


def ssm_conv(p: Dict[str, jax.Array], rows: jax.Array, cfg: FalconH1Config
             ) -> jax.Array:
    """The short filter over ``rows [..., taps - 1 + K, conv_dim]`` (a
    stream's kept rows ahead of its K new ones): ``silu(sum_j w[:, j] *
    rows[j : j + K] + conv_b)`` ``[..., K, conv_dim]``, the last tap on the
    current row; fp32 inside, the rows' dtype out."""
    taps = cfg.mamba_d_conv
    K = rows.shape[-2] - (taps - 1)
    mixed = sum(rows[..., j:j + K, :].astype(jnp.float32) * p["conv_w"][:, j]
                for j in range(taps)) + p["conv_b"]
    return jax.nn.silu(mixed).astype(rows.dtype)


def ssm_split(xbc: jax.Array, cfg: FalconH1Config):
    """Filtered ``xBC [..., conv_dim]`` -> x ``[..., nh, P]``, B and C
    ``[..., G, N]``."""
    d, w = cfg.mamba_d_ssm, cfg.ssm_bc_width
    lead = xbc.shape[:-1]
    G, N = cfg.mamba_n_groups, cfg.mamba_d_state
    return (xbc[..., :d].reshape(lead + (cfg.mamba_n_heads,
                                         cfg.mamba_d_head)),
            xbc[..., d:d + w].reshape(lead + (G, N)),
            xbc[..., d + w:].reshape(lead + (G, N)))


def ssm_steps(p: Dict[str, jax.Array], dt_raw: jax.Array):
    """(dt ``[..., nh]`` = softplus(dt_raw + dt_bias), log decay ``dt * A``
    with ``A = -exp(A_log)``), fp32."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    return dt, -dt * jnp.exp(p["A_log"])


def gated_norm(p: Dict[str, jax.Array], y: jax.Array, z: jax.Array,
               cfg: FalconH1Config, dtype) -> jax.Array:
    """``RMSNorm(y * silu(z))`` over each of ``mamba_n_groups`` groups of
    ``d_ssm / G`` channels: y fp32 ``[..., d_ssm]``, z the gate; fp32
    inside, ``dtype`` out."""
    G = cfg.mamba_n_groups
    g = y * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(g.shape[:-1] + (G, g.shape[-1] // G))
    w = p["ssm_norm"].astype(jnp.float32).reshape(g.shape[-2:])
    return rms_norm(g, w, cfg.rms_norm_eps).reshape(y.shape).astype(dtype)


def gated_mlp(p: Dict[str, jax.Array], g: jax.Array, cfg: FalconH1Config
              ) -> jax.Array:
    """``(silu((g W_gate) * m0) * (g W_up)) W_down * m1``."""
    a = jnp.dot(g, p["mlp_gate"].astype(g.dtype),
                preferred_element_type=jnp.float32) * cfg.mlp_multipliers[0]
    b = jnp.dot(g, p["mlp_up"].astype(g.dtype),
                preferred_element_type=jnp.float32)
    return scaled_matmul((jax.nn.silu(a) * b).astype(g.dtype),
                         p["mlp_down"], cfg.mlp_multipliers[1])


def _layer_stds(cfg: FalconH1Config) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """{tensor: (shape, std)} of a layer's matrices: see
    ``falcon_h1_init``."""
    H, I, D = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    nH, nKV = cfg.num_attention_heads, cfg.num_key_value_heads
    unit = 1.0 / math.sqrt(H)
    a_in, out = cfg.attention_in_multiplier, cfg.branch_out_rms
    return {
        # scores q . k / sqrt(D) of unit variance, q and k sharing it
        "wq": ((H, nH * D), unit / (a_in * math.sqrt(cfg.key_multiplier))),
        "wk": ((H, nKV * D), unit / (a_in * math.sqrt(cfg.key_multiplier))),
        "wv": ((H, nKV * D), unit / a_in),
        "wo": ((nH * D, H), out / (math.sqrt(nH * D)
                                   * cfg.attention_out_multiplier)),
        # unit z, x, B, C and dt before the filter, whatever the multipliers
        "ssm_in": ((H, cfg.ssm_in_width),
                   unit / (cfg.ssm_in_multiplier * cfg.mup_vector)),
        "ssm_out": ((cfg.mamba_d_ssm, H),
                    out / (math.sqrt(cfg.mamba_d_ssm)
                           * cfg.ssm_out_multiplier)),
        "mlp_gate": ((H, I), unit / cfg.mlp_multipliers[0]),
        "mlp_up": ((H, I), unit),
        # (silu(a) * b of unit a, b has RMS ~0.6)
        "mlp_down": ((I, H), out / (0.6 * math.sqrt(I)
                                    * cfg.mlp_multipliers[1])),
    }


def falcon_h1_init(rng: jax.Array, cfg: FalconH1Config) -> Dict[str, Any]:
    """Seeded weights in ``cfg.dtype`` (the filter, its bias and the
    per-head scalars fp32), norms 1.

    The published multipliers are forward scalings that a trained model's
    weights are sized against; under a flat normal(0, 0.02) at the published
    widths the key scores read 0.02 (uniform attention: K never matters),
    the state moves ``y`` by a third of a percent beside ``D x``, and the
    logits' spread is 0.011 — a dropped state, a stale page or a wrong
    multiplier would pass any comparison.  So every projection is normal(0,
    std) with std = target / (sqrt(fan_in) x the multipliers that meet its
    output): unit-variance scores, z, x, B, C, dt, gate and logits on a
    unit-RMS input, a unit-RMS embedding after its multiplier, and each
    branch's contribution to the residual stream ``branch_out_rms`` of it.
    And so that a dropped or stale part cannot pass: ``A = -exp(A_log)``
    with ``-A`` uniform on ``ssm_a_range`` and ``softplus(dt_bias)``
    log-uniform on ``ssm_dt_range`` (the Mamba-2 defaults: half-lives from
    a few tokens to thousands), ``D`` = 1, the filter's taps
    normal(0, ``conv_filter_std``) on EVERY tap and its bias
    normal(0, ``conv_bias_std``)."""
    H, nh = cfg.hidden_size, cfg.mamba_n_heads

    def normal(key, shape, std, dtype=cfg.dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                * jnp.asarray(std, jnp.float32)).astype(dtype)

    k_emb, k_head, k_layers = jax.random.split(rng, 3)
    stds = _layer_stds(cfg)
    layers = []
    for key in jax.random.split(k_layers, cfg.num_hidden_layers):
        keys = jax.random.split(key, len(stds) + 4)
        p = {name: normal(k, shape, std) for k, (name, (shape, std))
             in zip(keys, sorted(stds.items()))}
        k_w, k_b, k_a, k_dt = keys[len(stds):]
        p["conv_w"] = normal(k_w, (cfg.conv_dim, cfg.mamba_d_conv),
                             cfg.conv_filter_std, jnp.float32)
        p["conv_b"] = normal(k_b, (cfg.conv_dim,), cfg.conv_bias_std,
                             jnp.float32)
        lo, hi = cfg.ssm_a_range
        p["A_log"] = jnp.log(jax.random.uniform(
            k_a, (nh,), jnp.float32, lo, hi))
        lo, hi = cfg.ssm_dt_range
        dt = jnp.exp(jax.random.uniform(
            k_dt, (nh,), jnp.float32, math.log(lo), math.log(hi)))
        p["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
        p["D"] = jnp.ones((nh,), jnp.float32)
        p["input_norm"] = jnp.ones((H,), cfg.dtype)
        p["pre_ff_norm"] = jnp.ones((H,), cfg.dtype)
        p["ssm_norm"] = jnp.ones((cfg.mamba_d_ssm,), cfg.dtype)
        layers.append(p)
    return {
        "embed": normal(k_emb, (cfg.vocab_size, H),
                        1.0 / cfg.embedding_multiplier),
        "lm_head": normal(k_head, (cfg.vocab_size, H),
                          1.0 / (math.sqrt(H) * cfg.lm_head_multiplier)),
        "final_norm": jnp.ones((H,), cfg.dtype),
        "layers": layers}


__all__ = ["FalconH1Config", "falcon_h1_init", "inv_freq", "scaled_matmul",
           "qkv", "ssm_in", "ssm_conv", "ssm_split", "ssm_steps",
           "gated_norm", "gated_mlp"]

"""Transformer building blocks — pure-functional, shard-annotated.

Capability parity with the reference's fused transformer layer
(ops/transformer/transformer.py:468 DeepSpeedTransformerLayer and its CUDA
backend csrc/transformer/ds_transformer_cuda.cpp): QKV projection, scaled
masked softmax attention, output projection, residual + LayerNorm (pre- or
post-LN), GELU FFN, dropout — with the memory knobs
(attn_dropout_checkpoint / normalize_invertible / gelu_checkpoint,
transformer.py:39-151) expressed as jax.checkpoint remat policies instead of
hand-managed saved-tensor lists.

TPU-native design decisions:
- Params are plain dict pytrees; per-layer tensors are STACKED on a leading
  layer axis and the block is applied with ``lax.scan`` — one compilation of
  one block regardless of depth (XLA unrolls nothing).
- Attention math runs in fp32 (softmax stability) while matmuls stay in the
  compute dtype so they hit the MXU at full rate.
- Tensor parallelism is Megatron-style column→row sharding, expressed purely
  as PartitionSpec trees over the weights; GSPMD inserts the all-reduces.
- The attention inner product is pluggable (``attention_fn``) so dense, flash
  (Pallas), and block-sparse attention share the surrounding layer.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Shared transformer hyperparameters.

    Mirrors DeepSpeedTransformerConfig (reference transformer.py:39-151):
    batch/seq/hidden/heads/pre_layer_norm/dropout knobs; the checkpointing
    booleans map onto ``remat_policy``.

    ``hidden_dropout`` (after the attention projection and after the FFN)
    and ``dense_attention``'s ``attn_dropout`` draw their keep-masks from
    a counter hash of (the site's key, the element's GLOBAL index), not
    from a per-element threefry draw (``dropout`` below): every layout of an
    activation gets one mask, forward and rematerialized forward agree,
    and nothing per element is drawn from the key. Loss values under
    dropout differ from releases before PR 33, as under a reseeding.
    """
    hidden_size: int = 768
    num_heads: int = 12
    num_layers: int = 12
    intermediate_size: int = 0          # 0 → 4*hidden
    max_seq_length: int = 1024
    vocab_size: int = 50257
    type_vocab_size: int = 0            # >0 → BERT-style segment embeddings
    pre_layer_norm: bool = True         # GPT-2: True; original BERT: False
    hidden_dropout: float = 0.1
    attn_dropout: float = 0.1
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    # remat policy: "none" | "full" | "dots" | "attn" (≈ attn_dropout_checkpoint
    # + gelu_checkpoint territory in the reference)
    remat_policy: str = "none"
    causal: bool = False
    dtype: Any = jnp.bfloat16
    # scan_layers=True compiles one block and lax.scans it (fast compiles,
    # small code); False unrolls the layer loop, which lets XLA overlap
    # weight loads with compute across layer boundaries (better step time,
    # slower compile) — the usual TPU tradeoff.
    scan_layers: bool = True
    # True = erf-form GELU (HF BERT "gelu"); False = tanh approximation
    # (GPT-2 gelu_new, and what the reference's gelu_kernels.cu computes).
    gelu_exact: bool = False
    # Mixture-of-Experts: a ``deepspeed_tpu.moe.MoEConfig`` swaps the
    # dense FFN for the expert-parallel MoE FFN on every
    # ``moe_layer_freq``-th block (freq 1 = every block — the only form
    # the scanned layer stack supports; freq > 1 needs
    # ``scan_layers=False``, since mixed block programs cannot share one
    # scan body). None = dense everywhere (unchanged).
    # ``MoEConfig.grouped_gemm`` picks the expert-FFN program with the
    # same contract as ``fused_kernels`` below: "auto"/True/False,
    # DS_GROUPED_GEMM override, grouped Pallas kernel vs einsum pair
    # (ops/grouped_gemm) — cfg-static, resolved inside _moe_tokens.
    moe: Any = None
    moe_layer_freq: int = 1
    # Fused elementwise Pallas kernels (ops/fused_elementwise): LayerNorm
    # and residual-add+LayerNorm, nothing else (the FFN's bias+GELU is
    # one jnp expression for every value of this, PR 51). "auto" = on
    # when the backend is TPU (DS_FUSED_ELEMENTWISE=0/1 overrides);
    # True/False force — True on CPU runs interpret-mode Pallas (how the
    # dp=8 tier-1 mesh tests them). Static per config: flipping it
    # changes the program, not the compiled signature.
    fused_kernels: Any = "auto"

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads


# --------------------------------------------------------------------- #
# Primitive ops
# --------------------------------------------------------------------- #
def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    """LayerNorm in fp32 (the reference's normalize_kernels.cu does the same
    accumulation in fp32 even for fp16 activations)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def dense(x: jnp.ndarray, kernel: jnp.ndarray, bias: Optional[jnp.ndarray]) -> jnp.ndarray:
    y = x @ kernel.astype(x.dtype)
    if bias is not None:
        y = y + bias.astype(x.dtype)
    return y


def gelu(x: jnp.ndarray) -> jnp.ndarray:
    # tanh approximation — same curve the reference's gelu_kernels.cu uses.
    return jax.nn.gelu(x, approximate=True)


# --------------------------------------------------------------------- #
# cfg-resolved fused-kernel dispatch (ops/fused_elementwise)
# --------------------------------------------------------------------- #
def use_fused_kernels(cfg: "TransformerConfig") -> bool:
    """Whether LayerNorm / residual-LayerNorm run as Pallas kernels."""
    from ..ops.fused_elementwise import fused_elementwise_enabled
    return fused_elementwise_enabled(getattr(cfg, "fused_kernels", "auto"))


def layer_norm_fn(cfg: "TransformerConfig") -> Callable:
    """``(x, scale, bias) -> y``: the fused Pallas LayerNorm when the
    config enables it, the jnp reference otherwise.  The choice is
    static per config, so every caller (training block, serving
    decode/prefill) keeps ONE compiled signature either way."""
    if use_fused_kernels(cfg):
        from ..ops.fused_elementwise import fused_layer_norm
        return lambda x, scale, bias: fused_layer_norm(
            x, scale, bias, cfg.layer_norm_eps)
    return lambda x, scale, bias: layer_norm(
        x, scale, bias, cfg.layer_norm_eps)


def residual_layer_norm_fn(cfg: "TransformerConfig") -> Callable:
    """``(x, delta, scale, bias) -> (s, y)`` with ``s = x + delta`` and
    ``y = LN(s)`` — fused into one pass when enabled."""
    if use_fused_kernels(cfg):
        from ..ops.fused_elementwise import fused_residual_layer_norm
        return lambda x, delta, scale, bias: fused_residual_layer_norm(
            x, delta, scale, bias, cfg.layer_norm_eps)

    def unfused(x, delta, scale, bias):
        s = x + delta
        return s, layer_norm(s, scale, bias, cfg.layer_norm_eps)
    return unfused


def gelu_dense_fn(cfg: "TransformerConfig") -> Callable:
    """``(h, kernel, bias) -> gelu(h @ kernel + bias)`` — the FFN
    up-projection.  One function whatever ``cfg.fused_kernels`` says:
    the GEMM's output in the compute dtype (what ``checkpoint_dots``
    saves), then ``ops.fused_elementwise.bias_gelu``, fp32 arithmetic in
    ``jax.numpy`` that XLA fuses into this GEMM's output and the next
    GEMM's operand.  There is no shape at which a pass of its own over
    ``[rows, F]`` beats no pass, so nothing chooses here."""
    from ..ops.fused_elementwise import bias_gelu
    return lambda h, kernel, bias: bias_gelu(
        h @ kernel.astype(h.dtype), bias, cfg.gelu_exact)


_INDEX_SPACE = 1 << 32      # flat indices are ``uint32``


def _element_index(shape: Tuple[int, ...]
                   ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """``(low, high)``: every element's row-major index within the
    longest run of trailing dims that holds under 2**32 elements, and its
    index over the dims before them (``None`` where there are none).
    Built from iotas of the GLOBAL shape, so under GSPMD every layout of
    ``x`` numbers an element alike."""
    split, count = len(shape), 1
    while split and count * shape[split - 1] < _INDEX_SPACE:
        split -= 1
        count *= shape[split]

    def ravel(dims):
        idx, stride = None, 1
        for d in reversed(dims):
            term = lax.broadcasted_iota(jnp.uint32, shape, d) \
                * jnp.uint32(stride)
            idx = term if idx is None else idx + term
            stride *= shape[d]
        return idx

    low = ravel(range(split, len(shape)))
    if low is None:                         # a scalar
        low = jnp.zeros(shape, jnp.uint32)
    return low, ravel(range(split))


def dropout(x: jnp.ndarray, rate: float, rng: Optional[jax.Array],
            deterministic: bool) -> jnp.ndarray:
    """Inverted dropout whose keep-mask is a counter hash of (``rng``,
    the element's global index): two murmur3 finalizers keyed by two
    words drawn ONCE from ``rng``, an integer compare against
    ``rate * 2**32``. Nothing is drawn per element, so XLA fuses the
    mask into whatever consumes it and the bits never reach HBM; forward
    and rematerialized forward agree because both are this pure function
    of the same key. The second word enters between the rounds: with one
    round, two call sites' masks would be one sequence read at two
    offsets."""
    if deterministic or rate == 0.0 or rng is None:
        return x
    from ..ops.counter_hash import hash_u32
    with jax.named_scope("dropout"):
        words = jax.random.bits(rng, (2,), jnp.uint32)
        low, high = _element_index(x.shape)
        w0, w1 = words[0], words[1]
        if high is not None:
            w0 = w0 ^ (high * jnp.uint32(0x9E3779B9))
            w1 = w1 + high * jnp.uint32(0x85EBCA6B)
        h = hash_u32(hash_u32(low ^ w0) + w1)
        keep = h >= jnp.uint32(min(round(rate * 2 ** 32), 2 ** 32 - 1))
        return jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))


def dense_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    mask: Optional[jnp.ndarray], causal: bool,
                    attn_dropout: float = 0.0,
                    rng: Optional[jax.Array] = None,
                    deterministic: bool = True) -> jnp.ndarray:
    """Reference attention: QK^T → scale → mask → softmax → AV.

    q,k,v: [B, S, nH, dH]. mask: broadcastable to [B, 1, S, S] additive.
    Softmax in fp32 (csrc softmax_kernels.cu accumulates fp32 likewise).
    """
    dh = q.shape[-1]
    qt = jnp.einsum("bsnd,btnd->bnst", q, k).astype(jnp.float32)
    qt = qt / math.sqrt(dh)
    if causal:
        s, t = qt.shape[-2], qt.shape[-1]
        cmask = jnp.tril(jnp.ones((s, t), jnp.bool_))
        qt = jnp.where(cmask[None, None], qt, jnp.float32(-1e9))
    if mask is not None:
        qt = qt + mask.astype(jnp.float32)
    w = jax.nn.softmax(qt, axis=-1)
    w = dropout(w, attn_dropout, rng, deterministic)
    out = jnp.einsum("bnst,btnd->bsnd", w.astype(v.dtype), v)
    return out


AttentionFn = Callable[..., jnp.ndarray]


# --------------------------------------------------------------------- #
# One transformer block (stack-friendly)
# --------------------------------------------------------------------- #
def init_block_params(rng: jax.Array, cfg: TransformerConfig,
                      num_layers: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Initialize STACKED block params: every tensor has a leading layer
    axis — [L] for the shared attention/LN tensors; with ``cfg.moe`` the
    FFN tensors split into a dense stack ([n_dense]) and an expert stack
    ([n_moe, E, ...]), each covering only its own layers (no dead
    parameters on either side)."""
    L = num_layers if num_layers is not None else cfg.num_layers
    H, F = cfg.hidden_size, cfg.ffn_size
    std = cfg.initializer_range
    # GPT-2-style scaled init for residual-ending projections.
    proj_std = std / math.sqrt(2.0 * L)
    ks = jax.random.split(rng, 6)

    def norm(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s)

    params = {
        "ln1_scale": jnp.ones((L, H), jnp.float32),
        "ln1_bias": jnp.zeros((L, H), jnp.float32),
        "qkv_kernel": norm(ks[0], (L, H, 3 * H), std),
        "qkv_bias": jnp.zeros((L, 3 * H), jnp.float32),
        "proj_kernel": norm(ks[1], (L, H, H), proj_std),
        "proj_bias": jnp.zeros((L, H), jnp.float32),
        "ln2_scale": jnp.ones((L, H), jnp.float32),
        "ln2_bias": jnp.zeros((L, H), jnp.float32),
    }
    if cfg.moe is None:
        n_dense, n_moe = L, 0
    else:
        from ..moe.layer import moe_layer_indices
        n_moe = len(moe_layer_indices(L, cfg.moe_layer_freq))
        n_dense = L - n_moe
        if n_moe == 0:
            raise ValueError(
                f"cfg.moe is set but moe_layer_freq={cfg.moe_layer_freq} "
                f"selects no MoE layer out of {L} — use freq <= num_layers "
                "or drop cfg.moe")
    if n_dense > 0:
        params.update({
            "fc_kernel": norm(ks[2], (n_dense, H, F), std),
            "fc_bias": jnp.zeros((n_dense, F), jnp.float32),
            "fc_out_kernel": norm(ks[3], (n_dense, F, H), proj_std),
            "fc_out_bias": jnp.zeros((n_dense, H), jnp.float32),
        })
    if n_moe > 0:
        E = cfg.moe.num_experts
        params.update({
            "router_kernel": norm(ks[4], (n_moe, H, E), std),
            "moe_fc_kernel": norm(ks[5], (n_moe, E, H, F), std),
            "moe_fc_bias": jnp.zeros((n_moe, E, F), jnp.float32),
            "moe_out_kernel": norm(
                jax.random.fold_in(ks[5], 1), (n_moe, E, F, H), proj_std),
            "moe_out_bias": jnp.zeros((n_moe, E, H), jnp.float32),
        })
    return params


def block_param_shardings(mp_axis: str = "model") -> Dict[str, P]:
    """Megatron column→row TP over the stacked block params.

    QKV and FFN-in kernels are column-sharded (output features over mp);
    proj and FFN-out are row-sharded (input features over mp). GSPMD turns
    the row-sharded matmuls into partial sums + all-reduce — exactly the
    hand-written Megatron pattern the reference's mpu contract assumes
    (engine.py:79-80).
    """
    # Expert-FFN leaves (cfg.moe) get their specs from
    # deepspeed_tpu.moe.sharding.expert_block_shardings (the `expert`
    # axis on the E dim), merged by gpt2_moe_param_shardings.
    return {
        "ln1_scale": P(None, None), "ln1_bias": P(None, None),
        "qkv_kernel": P(None, None, mp_axis), "qkv_bias": P(None, mp_axis),
        "proj_kernel": P(None, mp_axis, None), "proj_bias": P(None, None),
        "ln2_scale": P(None, None), "ln2_bias": P(None, None),
        "fc_kernel": P(None, None, mp_axis), "fc_bias": P(None, mp_axis),
        "fc_out_kernel": P(None, mp_axis, None), "fc_out_bias": P(None, None),
    }


def transformer_block(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
                      cfg: TransformerConfig,
                      mask: Optional[jnp.ndarray] = None,
                      rng: Optional[jax.Array] = None,
                      deterministic: bool = True,
                      attention_fn: Optional[AttentionFn] = None,
                      mesh=None):
    """One (unstacked) block: params here have NO leading layer axis.

    Pre-LN (GPT-2/Megatron) or post-LN (original BERT) per
    cfg.pre_layer_norm — the reference's fused layer supports both
    (transformer.py:458-462 normalize_invertible interplay).

    With ``cfg.moe`` the FFN sublayer routes through the expert-parallel
    MoE FFN whenever this layer's params carry the expert tensors
    (``moe_fc_kernel`` et al. — every ``moe_layer_freq``-th block), and
    the block returns ``(x, moe_stats_or_None)`` instead of ``x``;
    ``mesh`` feeds the ep > 1 all-to-all shard_map.
    """
    B, S, H = x.shape
    nH, dH = cfg.num_heads, cfg.head_dim
    r1 = r2 = r3 = None
    if rng is not None:
        r1, r2, r3 = jax.random.split(rng, 3)
    # cfg-resolved LayerNorms: the fused Pallas kernels when the config
    # enables them, the reference jnp chain otherwise (identical math —
    # the fused residual+LN pass computes s = x + delta then LN(s)
    # exactly like the two separate ops below would).
    ln = layer_norm_fn(cfg)
    res_ln = residual_layer_norm_fn(cfg)
    gelu_up = gelu_dense_fn(cfg)

    # --- attention sublayer ---
    with jax.named_scope("attn"):
        h = ln(x, params["ln1_scale"], params["ln1_bias"]) \
            if cfg.pre_layer_norm else x
        qkv = dense(h, params["qkv_kernel"], params["qkv_bias"])
        if attention_fn is None:
            # The default takes the projection as the GEMM leaves it: on
            # TPU the flash kernels read q, k, v out of ``qkv`` in place.
            from ..ops.flash_attention import auto_attention_qkv
            attn = auto_attention_qkv(
                qkv, nH, mask=mask, causal=cfg.causal,
                attn_dropout=cfg.attn_dropout, rng=r1,
                deterministic=deterministic)
        else:
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, S, nH, dH)
            k = k.reshape(B, S, nH, dH)
            v = v.reshape(B, S, nH, dH)
            attn = attention_fn(q, k, v, mask=mask, causal=cfg.causal,
                                attn_dropout=cfg.attn_dropout, rng=r1,
                                deterministic=deterministic)
            attn = attn.reshape(B, S, H)
        attn = dense(attn, params["proj_kernel"], params["proj_bias"])
        attn = dropout(attn, cfg.hidden_dropout, r2, deterministic)
    with jax.named_scope("mlp"):
        if cfg.pre_layer_norm:
            # Fused residual-add + next sublayer's LN: x continues the
            # residual stream from s, h feeds the FFN.
            x, h = res_ln(x, attn, params["ln2_scale"], params["ln2_bias"])
        else:
            # Post-LN: the normalized value IS the residual stream.
            _, x = res_ln(x, attn, params["ln1_scale"], params["ln1_bias"])
            h = x

    # --- FFN sublayer (dense, or the expert-parallel MoE FFN) ---
    moe_stats = None
    with jax.named_scope("mlp"):
        if "moe_fc_kernel" in params:
            from ..moe.layer import moe_ffn
            h, moe_stats = moe_ffn(params, h, cfg, mesh=mesh)
        else:
            h = gelu_up(h, params["fc_kernel"], params["fc_bias"])
            h = dense(h, params["fc_out_kernel"], params["fc_out_bias"])
        h = dropout(h, cfg.hidden_dropout, r3, deterministic)
        if cfg.pre_layer_norm:
            x = x + h
        else:
            _, x = res_ln(x, h, params["ln2_scale"], params["ln2_bias"])
    if cfg.moe is not None:
        return x, moe_stats
    return x


def _remat_policy(name: str):
    if name == "none":
        return None
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if name == "dots_flash":
        # dots + the flash-attention kernel's (out, lse) residuals (tagged
        # in ops.flash_attention._tag_residuals). Without the names the
        # pallas forward kernel re-runs inside backward (+1/3 attention
        # FLOPs); saving them costs B*S*H bf16 + B*nH*S f32 per layer.
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots,
            jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"))
    if name == "attn":
        # Save only matmul outputs that feed the residual stream; recompute
        # softmax/dropout — the attn_dropout_checkpoint + gelu_checkpoint
        # territory of the reference (transformer.py:120-135).
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(f"unknown remat policy '{name}'")


def apply_blocks(stacked: Dict[str, jnp.ndarray], x: jnp.ndarray,
                 cfg: TransformerConfig,
                 mask: Optional[jnp.ndarray] = None,
                 rng: Optional[jax.Array] = None,
                 deterministic: bool = True,
                 attention_fn: Optional[AttentionFn] = None,
                 pld_theta: Optional[jnp.ndarray] = None,
                 layer_valid: Optional[jnp.ndarray] = None,
                 zero3=None, mesh=None):
    """Run all L layers via lax.scan over the stacked leading axis.

    With ``cfg.moe`` the return value is ``(x, moe_stats)`` — the
    per-MoE-layer stats aggregated over layers (moe/layer.py), ``mesh``
    feeding the ep > 1 all-to-all shard_map. MoE does not compose with
    ``pld_theta``/``layer_valid`` (a skipped layer has no fixed-shape
    stats) or the ``zero3`` layer scan (use the generic stage-3
    leaf-at-use gather instead); ``moe_layer_freq > 1`` requires
    ``scan_layers=False`` (mixed dense/MoE blocks cannot share one scan
    body — the dense and expert FFN stacks cover different layers).

    ``zero3`` (a bound ``runtime.zero.stage3.Zero3Scan``) reroutes the
    layer loop through the ZeRO-3 prefetched scan: the stacked params
    arrive as dp SHARDS, each layer's slice is all-gathered
    ``prefetch_depth`` layers ahead of use inside the scan (the gather
    overlaps the previous layer's compute), dropped right after its
    fwd/bwd consumption, and its grads reduce-scattered back to the
    owning shard inside the backward scan. Does not compose with
    ``pld_theta``/``layer_valid`` (the manual-VJP scan has no per-layer
    skip) or ``scan_layers=False``; ``remat_policy`` is subsumed — the
    backward re-gathers and recomputes each layer by construction.

    ``pld_theta`` (traced scalar in (0, 1]) enables progressive layer drop
    (reference progressive_layer_drop.py:29-37 + the PLD paper's
    depth-scaled schedule): layer l is KEPT with probability
    ``1 - (l+1)/L * (1 - theta)`` — deeper layers drop more often — via
    ``lax.cond``, so a dropped layer's compute is actually skipped at run
    time, not just masked. Requires ``rng``; ignored when deterministic.

    ``layer_valid`` ([L] 0/1): identity-skip for PADDING layers — the
    non-uniform-pipeline-stage mechanism (stages padded to the max layer
    count run their pad slots as ``lax.cond`` no-ops; see
    gpt2_pipe.gpt2_pipe_spec(stage_layers=...)).
    """
    L = stacked["ln1_scale"].shape[0]
    if rng is None:
        keys = jnp.zeros((L, 2), jnp.uint32)
        use_rng = False
    else:
        keys = jax.random.split(rng, L)
        use_rng = True

    has_moe = cfg.moe is not None
    if has_moe:
        from ..moe.layer import (MOE_PARAM_KEYS, aggregate_moe_stats,
                                 moe_layer_indices)
        moe_layers = moe_layer_indices(L, cfg.moe_layer_freq)
        if not moe_layers:
            raise ValueError(
                f"cfg.moe is set but moe_layer_freq={cfg.moe_layer_freq} "
                f"selects no MoE layer out of {L}")
        if pld_theta is not None or layer_valid is not None:
            raise ValueError(
                "moe blocks do not compose with progressive layer drop "
                "or padded layer_valid slots (a skipped layer has no "
                "fixed-shape expert stats)")
        if zero3 is not None and getattr(zero3, "bound", False):
            raise ValueError(
                "moe blocks do not compose with the zero3 layer scan — "
                "use the generic stage-3 leaf-at-use gather (no "
                "zero3_scan)")
        if cfg.scan_layers and len(moe_layers) != L:
            raise ValueError(
                "moe_layer_freq > 1 requires scan_layers=False (mixed "
                "dense/MoE blocks cannot share one scan body)")

    block = partial(transformer_block, cfg=cfg, mask=mask,
                    deterministic=deterministic, attention_fn=attention_fn,
                    mesh=mesh)

    if zero3 is not None and getattr(zero3, "bound", False):
        if pld_theta is not None or layer_valid is not None:
            raise ValueError(
                "zero3 layer scan does not compose with progressive "
                "layer drop or padded layer_valid slots")
        if not cfg.scan_layers:
            raise ValueError("zero3 layer scan requires scan_layers=True")
        from ..runtime.zero.stage3 import zero3_block_scan

        def block_fn(p, h, key):
            return block(p, h, rng=key if use_rng else None)
        return zero3_block_scan(block_fn, stacked, x, keys, zero3)
    policy = _remat_policy(cfg.remat_policy)
    if cfg.remat_policy != "none":
        block = jax.checkpoint(
            block, policy=policy, static_argnums=())

    use_pld = pld_theta is not None and not deterministic and use_rng

    def maybe_dropped(p, h, key, layer_idx, valid):
        # One combined run predicate: padding-slot validity AND the PLD
        # keep draw; run through a single lax.cond so skipped layers cost
        # nothing at run time.
        run = None if valid is None else valid != 0
        if use_pld:
            drop_key, key = jax.random.split(key)
            keep_prob = 1.0 - (layer_idx.astype(jnp.float32) + 1.0) / L * \
                (1.0 - pld_theta)
            keep = jax.random.bernoulli(drop_key, keep_prob)
            run = keep if run is None else jnp.logical_and(run, keep)
        if run is None:
            return block(p, h, rng=key if use_rng else None)
        return lax.cond(run,
                        lambda hh: block(p, hh, rng=key if use_rng else None),
                        lambda hh: hh, h)

    if not cfg.scan_layers:
        stats_list = []
        if has_moe:
            moe_pos = {li: p for p, li in enumerate(moe_layers)}
            dense_pos = {li: p for p, li in enumerate(
                i for i in range(L) if i not in moe_pos)}
            ffn_keys = MOE_PARAM_KEYS | {"fc_kernel", "fc_bias",
                                         "fc_out_kernel", "fc_out_bias"}
        for i in range(L):
            if not has_moe:
                p_i = jax.tree_util.tree_map(lambda t: t[i], stacked)
            else:
                # Dense and expert FFN stacks cover DIFFERENT layer
                # subsets; slice each key group at its own position.
                p_i = {}
                for name, t in stacked.items():
                    if name not in ffn_keys:
                        p_i[name] = t[i]
                    elif name in MOE_PARAM_KEYS:
                        if i in moe_pos:
                            p_i[name] = t[moe_pos[i]]
                    elif i in dense_pos:
                        p_i[name] = t[dense_pos[i]]
            v_i = None if layer_valid is None else layer_valid[i]
            out = maybe_dropped(p_i, x, keys[i], jnp.asarray(i), v_i)
            if has_moe:
                x, st = out
                if st is not None:
                    stats_list.append(st)
            else:
                x = out
        if has_moe:
            stacked_stats = jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *stats_list)
            return x, aggregate_moe_stats(stacked_stats)
        return x

    def body(h, layer):
        if layer_valid is None:
            p, key, idx = layer
            out = maybe_dropped(p, h, key, idx, None)
        else:
            p, key, idx, v = layer
            out = maybe_dropped(p, h, key, idx, v)
        if has_moe:
            return out[0], out[1]
        return out, None

    xs = (stacked, keys, jnp.arange(L)) if layer_valid is None else \
        (stacked, keys, jnp.arange(L), layer_valid)
    x, ys = lax.scan(body, x, xs)
    if has_moe:
        return x, aggregate_moe_stats(ys)
    return x


def count_params(params: Any) -> int:
    return sum(int(math.prod(l.shape)) for l in jax.tree_util.tree_leaves(params)
               if hasattr(l, "shape"))

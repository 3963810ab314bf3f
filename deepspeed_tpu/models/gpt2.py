"""GPT-2 causal language model family.

The reference trains GPT-2 through Megatron-LM examples
(tests/model/Megatron_GPT2/, docs/_tutorials/megatron.md); here it is a
built-in model: token+position embeddings → N pre-LN blocks → final LN →
tied-embedding logits → next-token cross-entropy. Sizes cover the benchmark
ladder in BASELINE.json (small → 1.5B).

Sharding story (Megatron TP via GSPMD): block kernels column/row-sharded on
the "model" axis (transformer.block_param_shardings); the token embedding is
vocab-sharded so the tied logits matmul is column-parallel and the CE loss
reduces over the sharded vocab axis with an XLA-inserted all-reduce.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .transformer import (TransformerConfig, apply_blocks, block_param_shardings,
                          count_params, dense_attention, init_block_params,
                          layer_norm, layer_norm_fn)


@dataclasses.dataclass(frozen=True)
class GPT2Config(TransformerConfig):
    causal: bool = True
    pre_layer_norm: bool = True
    max_seq_length: int = 1024
    vocab_size: int = 50304            # padded to a multiple of 128 for MXU tiling
    serving_module: ClassVar[str] = "deepspeed_tpu.inference.decode"

    @property
    def name(self) -> str:
        return f"gpt2-h{self.hidden_size}-l{self.num_layers}"


GPT2_CONFIGS: Dict[str, GPT2Config] = {
    # Benchmark ladder (BASELINE.json configs).
    "gpt2-small":  GPT2Config(hidden_size=768,  num_heads=12, num_layers=12),
    "gpt2-medium": GPT2Config(hidden_size=1024, num_heads=16, num_layers=24),
    "gpt2-large":  GPT2Config(hidden_size=1280, num_heads=20, num_layers=36),
    "gpt2-xl":     GPT2Config(hidden_size=1600, num_heads=25, num_layers=48),  # 1.5B
    "gpt2-tiny":   GPT2Config(hidden_size=128,  num_heads=4,  num_layers=2,
                              max_seq_length=128, vocab_size=512),  # tests
}


def gpt2_init(rng: jax.Array, cfg: GPT2Config) -> Dict[str, Any]:
    k_emb, k_pos, k_blocks = jax.random.split(rng, 3)
    std = cfg.initializer_range
    return {
        "wte": jax.random.normal(k_emb, (cfg.vocab_size, cfg.hidden_size),
                                 jnp.float32) * std,
        "wpe": jax.random.normal(k_pos, (cfg.max_seq_length, cfg.hidden_size),
                                 jnp.float32) * std,
        "blocks": init_block_params(k_blocks, cfg),
        "ln_f_scale": jnp.ones((cfg.hidden_size,), jnp.float32),
        "ln_f_bias": jnp.zeros((cfg.hidden_size,), jnp.float32),
    }


def gpt2_param_shardings(cfg: GPT2Config, mp_axis: str = "model") -> Dict[str, Any]:
    """PartitionSpec tree matching gpt2_init's structure."""
    return {
        "wte": P(mp_axis, None),          # vocab-sharded (column-parallel logits)
        "wpe": P(None, None),
        "blocks": block_param_shardings(mp_axis),
        "ln_f_scale": P(None),
        "ln_f_bias": P(None),
    }


def gpt2_hidden(params: Dict[str, Any], tokens: jnp.ndarray, cfg: GPT2Config,
                rng: Optional[jax.Array] = None, deterministic: bool = True,
                attention_fn=None, pld_theta=None, zero3=None, mesh=None,
                with_moe_stats: bool = False):
    """tokens [B, S] int32 → final hidden states [B, S, H] (post ln_f).

    ``zero3``: a bound ``Zero3Scan`` — the stacked block params arrive
    as ZeRO-3 dp shards and are gathered per layer inside the scan
    (prefetch-overlapped); see models/transformer.apply_blocks.

    ``with_moe_stats=True`` returns ``(hidden, moe_stats_or_None)`` —
    the training loss path consumes the stats; serving/eval callers
    keep the plain return (the stats are dropped, the routed compute is
    identical). ``mesh`` feeds the MoE ep > 1 shard_map."""
    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = params["wte"].astype(cfg.dtype)[tokens] + \
            params["wpe"].astype(cfg.dtype)[None, :S]
    out = apply_blocks(params["blocks"], x, cfg, mask=None, rng=rng,
                       deterministic=deterministic, attention_fn=attention_fn,
                       pld_theta=pld_theta, zero3=zero3, mesh=mesh)
    x, moe_stats = out if cfg.moe is not None else (out, None)
    h = layer_norm_fn(cfg)(x, params["ln_f_scale"], params["ln_f_bias"])
    if with_moe_stats:
        return h, moe_stats
    return h


def gpt2_apply(params: Dict[str, Any], tokens: jnp.ndarray, cfg: GPT2Config,
               rng: Optional[jax.Array] = None, deterministic: bool = True,
               attention_fn=None) -> jnp.ndarray:
    """tokens [B, S] int32 → logits [B, S, V]."""
    x = gpt2_hidden(params, tokens, cfg, rng=rng, deterministic=deterministic,
                    attention_fn=attention_fn)
    # Tied unembedding (the reference ties via TiedLayerSpec in pipeline
    # models; here it is structural).
    with jax.named_scope("lm_head"):
        logits = x @ params["wte"].astype(cfg.dtype).T
    return logits


def gpt2_logits_at(params: Dict[str, Any], tokens: jnp.ndarray,
                   cfg: GPT2Config, index: Union[int, jnp.ndarray] = -1,
                   rng: Optional[jax.Array] = None,
                   deterministic: bool = True,
                   attention_fn=None) -> jnp.ndarray:
    """Logits at ONE sequence position: tokens [B, S] → [B, V].

    Runs the full hidden stack but projects only position ``index``
    through the tied unembedding, so the [B, S, vocab] logits tensor never
    materializes — the serving-side memory contract (the training-side
    equivalent is ops/cross_entropy's chunked projection). ``index`` may
    be a Python int (negative = from the end) or a traced scalar (the
    inference prefill path indexes the prompt's final token inside a
    jitted program).
    """
    x = gpt2_hidden(params, tokens, cfg, rng=rng, deterministic=deterministic,
                    attention_fn=attention_fn)
    if isinstance(index, int):
        if index < 0:
            index += tokens.shape[1]
    else:
        # Traced scalar: dynamic_index_in_dim would CLAMP a negative
        # index to 0 (silent wrong position) — normalize in-graph.
        index = jnp.where(index < 0, index + tokens.shape[1], index)
    with jax.named_scope("lm_head"):
        h = lax.dynamic_index_in_dim(x, index, axis=1,
                                     keepdims=False)         # [B, H]
        return h @ params["wte"].astype(h.dtype).T


def gpt2_loss_fn(cfg: GPT2Config, attention_fn=None, zero3=None, mesh=None):
    """Returns loss_fn(params, batch, rng) for the engine.

    batch: tokens [B, S+1] (inputs are [:, :-1], targets [:, 1:]) or a
    (tokens, targets) tuple.

    The CE head runs through ops.cross_entropy.chunked_softmax_xent, so the
    [tokens, vocab] fp32 logits tensor is never materialized (chunked
    recompute in backward — see that module's docstring).

    ``zero3``: pass the SAME ``Zero3Scan`` object here and to
    ``deepspeed_tpu.initialize(..., zero3_scan=...)`` — the engine binds
    the stage-3 layout at construction, the loss reads it at trace time
    and gathers the stacked block params per layer inside the scan.

    ``cfg.moe``: the loss gains the weighted load-balance aux loss and
    router z-loss, and the fn returns ``(loss, {"moe": stats})`` — the
    engine rides the stats on the telemetry drain. ``mesh`` is required
    when ``expert_parallel_size > 1`` (the all-to-all shard_map).
    """
    from ..ops.cross_entropy import chunked_softmax_xent

    if cfg.moe is not None and cfg.moe.expert_parallel_size > 1 and \
            mesh is None:
        # Without the mesh the MoE layer would silently take its
        # no-collective fallback inside the jit — GSPMD then all-gathers
        # the full expert-sharded weight tree every step, the exact
        # failure expert parallelism exists to avoid. The TRAINING entry
        # point refuses; eval on fetched params (gpt2_apply) keeps the
        # fallback.
        raise ValueError(
            "cfg.moe.expert_parallel_size > 1 requires "
            "gpt2_loss_fn(cfg, mesh=mesh) — the all-to-all shard_map "
            "cannot infer the mesh")

    def loss_fn(params, batch, rng, pld_theta=None):
        if isinstance(batch, (tuple, list)):
            tokens, targets = batch[0], batch[1]
        else:
            tokens, targets = batch[:, :-1], batch[:, 1:]
        x, moe_stats = gpt2_hidden(params, tokens, cfg, rng=rng,
                                   deterministic=False,
                                   attention_fn=attention_fn,
                                   pld_theta=pld_theta, zero3=zero3,
                                   mesh=mesh, with_moe_stats=True)
        B, S = tokens.shape
        with jax.named_scope("lm_head"):
            loss = chunked_softmax_xent(x.reshape(B * S, -1),
                                        params["wte"].astype(cfg.dtype),
                                        targets.reshape(-1))
        if moe_stats is None:
            return loss
        moe = cfg.moe
        loss = loss + moe.aux_loss_weight * moe_stats["aux_loss"] \
            + moe.z_loss_weight * moe_stats["z_loss"]
        return loss, {"moe": moe_stats}
    return loss_fn


def gpt2_num_params(cfg: GPT2Config) -> int:
    H, L, F, V, S = (cfg.hidden_size, cfg.num_layers, cfg.ffn_size,
                     cfg.vocab_size, cfg.max_seq_length)
    per_block = 4 * H + 3 * H * H + 3 * H + H * H + H + 2 * H * F + F + H
    return V * H + S * H + L * per_block + 2 * H


def gpt2_flops_per_token(cfg: GPT2Config, seq_len: Optional[int] = None) -> float:
    """Training FLOPs/token = 6·N_matmul + attention term (PaLM appendix B
    counting). N_matmul includes the tied unembedding (V·H): its logits
    projection is a real trained-weight matmul executed fwd+bwd every step
    (standard MFU accounting includes the vocab projection). Excluded:
    embedding/position lookups (gathers, ~0 FLOPs) and remat recompute
    (not useful work)."""
    S = seq_len or cfg.max_seq_length
    H, L = cfg.hidden_size, cfg.num_layers
    n = gpt2_num_params(cfg) - cfg.vocab_size * H - cfg.max_seq_length * H
    n += cfg.vocab_size * H    # tied unembedding matmul
    return 6.0 * n + 12.0 * L * H * S

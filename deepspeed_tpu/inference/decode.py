"""Incremental GPT-2 forward paths against the paged block pool:
single-token decode, chunked / whole-prompt prefill, and the
speculative verify step.

Four programs, each with a FIXED abstract signature (the recompile
sentinel wraps all of them):

- ``gpt2_verify_paged``: K tokens per slot, for every slot at once —
  token i of slot s sits at position lengths[s] + i. Writes the K new
  rows, attends each under its own causal row, and returns K-bounded
  logits; with ``spec_accept`` it implements draft-then-verify
  speculative decoding whose greedy output is bit-identical to
  single-token decode.
- ``gpt2_decode_paged``: the K=1 verify — one token per slot, LAST-
  position logits only (the same tied-unembedding contraction
  ``models.gpt2.gpt2_logits_at`` exposes for the batch path).
- ``gpt2_prefill_chunk_paged``: one prompt chunk for ONE slot per group,
  attended against the slot's whole cached row under a global-position
  causal mask — so any chunk length divides any prompt without shape
  polymorphism. Prefill and decode are separate programs on purpose
  (prefill/decode disaggregation): a long admission never changes the
  decode signature.
- ``gpt2_prefill_full_paged``: the whole (padded) prompt in one shot
  through the standard block math with a pluggable ``attention_fn`` —
  this is where ring attention plugs in for long-context prefill when
  the mesh has a sequence axis (``ops/ring_attention.ring_attention_fn``).

Every cache access goes through the block-table primitives in
``inference/kv_cache.py``: group-batched over the mesh data axis, one
compiled shape whatever the tables hold, no full-pool gather. The
stacked pools ride the layer loop as a CARRY beside the layer index —
new rows are written into them in place and the attend reads them where
they lie, so no program slices, relays or rewrites the pool.

All block math mirrors ``models/transformer.transformer_block`` for the
deterministic pre-LN case (fp32 softmax, compute-dtype matmuls, same
mask constant), so decode logits match ``gpt2_apply``'s final position
to float tolerance — asserted per step in tests/test_inference.py.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import kv_cache
from .served import ServedModel, register
from ..models.gpt2 import GPT2Config
from ..ops import paged_attention as paged_attn_ops
from ..models.transformer import (dense, gelu_dense_fn, layer_norm,
                                  layer_norm_fn)

# Same masking constant as dense_attention. A NumPy scalar: a jnp one
# would initialise a JAX backend (and take the chip) at import.
NEG_INF = np.float32(-1e9)


def _check_cfg(cfg: GPT2Config) -> None:
    if not cfg.pre_layer_norm or not cfg.causal:
        raise NotImplementedError(
            "the incremental decode path implements the GPT-2 block "
            "(pre-LN, causal); post-LN/bidirectional models have no "
            "autoregressive serving story")


@jax.named_scope("mlp")
def _ffn(p: Dict[str, jax.Array], x: jax.Array, cfg: GPT2Config
         ) -> jax.Array:
    # layer_norm_fn / gelu_dense_fn resolve to the fused Pallas kernels
    # when cfg enables them — the SAME static dispatch the training
    # block uses, so flipping the knob never adds a compiled-signature
    # variant to the serving paths (sentinel-asserted in
    # tests/test_fused_ln.py).
    h = layer_norm_fn(cfg)(x, p["ln2_scale"], p["ln2_bias"])
    h = gelu_dense_fn(cfg)(h, p["fc_kernel"], p["fc_bias"])
    h = dense(h, p["fc_out_kernel"], p["fc_out_bias"])
    return x + h


@jax.named_scope("embed")
def _embed(params: Dict[str, Any], tokens: jax.Array, pos,
           cfg: GPT2Config) -> jax.Array:
    """Token + position embedding; ``pos`` indexes the position table
    (an int array shaped like ``tokens``, or a slice)."""
    return params["wte"].astype(cfg.dtype)[tokens] + \
        params["wpe"].astype(cfg.dtype)[pos]


@jax.named_scope("lm_head")
def _unembed(params: Dict[str, Any], h: jax.Array, cfg: GPT2Config
             ) -> jax.Array:
    """Tied unembedding: h [..., H] -> fp32 logits [..., V]."""
    return (h @ params["wte"].astype(cfg.dtype).T).astype(jnp.float32)


def _qkv(p: Dict[str, jax.Array], x: jax.Array, cfg: GPT2Config
         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """ln1 + QKV projection; x [..., H] → q,k,v [..., nH, dH]."""
    h = layer_norm_fn(cfg)(x, p["ln1_scale"], p["ln1_bias"])
    qkv = dense(h, p["qkv_kernel"], p["qkv_bias"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    split = x.shape[:-1] + (cfg.num_heads, cfg.head_dim)
    return q.reshape(split), k.reshape(split), v.reshape(split)


def _group_shape(arr: jax.Array, num_groups: int) -> jax.Array:
    """[S, ...] → [G, S/G, ...]: split the slot axis into (group,
    slot-in-group) — a local reshape under the slots-over-dp sharding."""
    return arr.reshape((num_groups, arr.shape[0] // num_groups)
                       + arr.shape[1:])


def _paged_attn_block(p, x, kc, vc, layer, cfg: GPT2Config,
                      num_groups: int, blk: jax.Array, off: jax.Array,
                      sel, pos_mask, plan=None, mesh=None):
    """Shared attention step of the paged decode/verify/prefill paths.

    x: [S, K, H] — K tokens for each of S per-slot query streams, with
    S = G * Sg (Sg = 1 stream per group for prefill); kc/vc: the WHOLE
    stacked pools as held ([L, G, B, nH, bs/f, f*D]) and ``layer`` the
    layer of them this block owns; blk/off: [G, Sg*K] where each new row
    goes (``positions_to_blocks``). ``sel`` [G, Sg, J, B] / ``pos_mask``
    [G, Sg, K, J*bs] drive the one-hot baseline; ``plan`` (the streams'
    tables and row positions as ``ops.paged_attention.attend_plan``
    reads them) routes the attend through the Pallas kernel instead (the
    write is the same in-place one either way). Returns (x', kc', vc').
    """
    S, K, H = x.shape
    G = num_groups
    Sg = S // G
    R = Sg * K
    nH, D = cfg.num_heads, cfg.head_dim
    with jax.named_scope("attn"):
        q, k, v = _qkv(p, x, cfg)                    # [S, K, nH, D]
        with jax.named_scope("kv_write"):
            kc, vc = kv_cache.paged_write_rows(
                kc, vc, k.reshape(G, R, nH, D), v.reshape(G, R, nH, D),
                layer, blk, off, mesh=mesh)
        with jax.named_scope("attend"):
            if plan is not None:
                attn = paged_attn_ops.paged_attention(
                    q.reshape(G, Sg, K, nH, D), kc, vc, layer, plan=plan,
                    scale=1.0 / math.sqrt(D), mesh=mesh)
            else:
                attn = kv_cache.paged_attend(
                    q.reshape(G, Sg, K, nH, D),
                    kv_cache.paged_layer_view(kc, layer, D),
                    kv_cache.paged_layer_view(vc, layer, D), sel,
                    pos_mask, 1.0 / math.sqrt(D), NEG_INF)
        attn = attn.reshape(S, K, H).astype(x.dtype)
        x = x + dense(attn, p["proj_kernel"], p["proj_bias"])
    return _ffn(p, x, cfg), kc, vc


def _paged_layers(params, x, kc, vc, block_fn):
    """Run ``block_fn(p, x, kc, vc, layer)`` over the stacked blocks
    with the pools as a CARRY (scan xs/ys would slice a layer out of the
    pool and stack it back: a pool-sized copy per layer)."""
    num_layers = kc.shape[0]

    def body(carry, layer_in):
        p, layer = layer_in
        return block_fn(p, *carry, layer), None

    (x, kc, vc), _ = lax.scan(
        body, (x, kc, vc),
        (params["blocks"], jnp.arange(num_layers, dtype=jnp.int32)))
    return x, kc, vc


def _write_targets(bt_g: jax.Array, pos_g: jax.Array, block_size: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """(block, offset) of every new row: bt_g [G, Sg, J], pos_g
    [G, Sg, K] -> two [G, Sg*K]."""
    G, Sg, K = pos_g.shape
    bt_rows = jnp.broadcast_to(bt_g[:, :, None, :],
                               (G, Sg, K, bt_g.shape[-1]))
    blk, off = kv_cache.positions_to_blocks(bt_rows, pos_g, block_size)
    return blk.reshape(G, Sg * K), off.reshape(G, Sg * K)


def _paged_forward(params, x, kc, vc, bt_g, pos_g, cfg: GPT2Config,
                   paged_kernel: bool, mesh):
    """All layers of the table-driven paths (decode / verify / chunked
    prefill): x [S, K, H] with its streams' tables bt_g [G, Sg, J] and
    row positions pos_g [G, Sg, K]. Returns (x', kc', vc')."""
    G, _, J = bt_g.shape
    bs = kv_cache.paged_block_size(kc, cfg.head_dim)
    sel = pos_mask = plan = None
    if paged_kernel:
        # What the attend reads of the tables and positions is the same
        # for every layer: built once, outside the layer loop.
        with jax.named_scope("attn"), jax.named_scope("attend"):
            plan = paged_attn_ops.attend_plan(bt_g, pos_g, kc,
                                              cfg.head_dim, mesh=mesh)
    else:
        sel = kv_cache.block_select(bt_g, kc.shape[2])
        grid = lax.broadcasted_iota(jnp.int32, (1, 1, 1, J * bs), 3)
        pos_mask = grid <= pos_g[..., None]          # [G, Sg, K, J*bs]
    blk, off = _write_targets(bt_g, pos_g, bs)

    def block(p, h, kc, vc, layer):
        return _paged_attn_block(p, h, kc, vc, layer, cfg, G, blk, off,
                                 sel, pos_mask, plan, mesh)

    return _paged_layers(params, x, kc, vc, block)


def gpt2_verify_paged(params: Dict[str, Any], kc: jax.Array,
                      vc: jax.Array, tokens: jax.Array,
                      lengths: jax.Array, block_tables: jax.Array,
                      cfg: GPT2Config, num_groups: int,
                      paged_kernel: bool = False, mesh=None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The speculative verify step — and, at K=1, plain paged decode.

    tokens: [S, K] — column 0 is each slot's pending last token,
    columns 1.. are the drafted continuation; token i sits at position
    lengths[s] + i. Writes all K tokens' K/V through the block table,
    attends each under its own causal row, and returns fp32 logits
    [S, K, V] (the K-bounded spec-decode analogue of last-position-only
    logits — never a [max_len, vocab] tensor). kc/vc: the full pool as
    held, [L, G, B, nH, bs/f, f*D]. ``paged_kernel`` swaps the one-hot pool
    contraction for the Pallas table-sliced kernel (ops/
    paged_attention.py) — same logits, O(context) work.
    """
    _check_cfg(cfg)
    K = tokens.shape[1]
    pos = lengths[:, None] + jnp.arange(K, dtype=jnp.int32)[None]  # [S,K]
    x = _embed(params, tokens, pos, cfg)
    x, kc, vc = _paged_forward(
        params, x, kc, vc, _group_shape(block_tables, num_groups),
        _group_shape(pos, num_groups), cfg, paged_kernel, mesh)
    x = layer_norm_fn(cfg)(x, params["ln_f_scale"], params["ln_f_bias"])
    logits = _unembed(params, x, cfg)
    return logits, kc, vc


def gpt2_decode_paged(params: Dict[str, Any], kc: jax.Array,
                      vc: jax.Array, tokens: jax.Array,
                      lengths: jax.Array, block_tables: jax.Array,
                      cfg: GPT2Config, num_groups: int,
                      paged_kernel: bool = False, mesh=None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One paged decode step for every slot: the K=1 verify. Returns
    (logits [S, V] fp32, kc', vc'). The caller advances lengths for the
    slots it considers active; position = lengths[s] by construction."""
    logits, kc, vc = gpt2_verify_paged(params, kc, vc, tokens[:, None],
                                       lengths, block_tables, cfg,
                                       num_groups, paged_kernel, mesh)
    return logits[:, 0], kc, vc


def gpt2_prefill_chunk_paged(params: Dict[str, Any], kc: jax.Array,
                             vc: jax.Array, tokens: jax.Array,
                             bt_rows: jax.Array, start: jax.Array,
                             last_idx: jax.Array, active: jax.Array,
                             cfg: GPT2Config,
                             paged_kernel: bool = False, mesh=None
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Group-batched chunked prefill: one prompt chunk for ONE slot per
    group.

    tokens: [G, C]; bt_rows: [G, J] — each group's target slot's block
    table row (DEAD_BLOCK rows for groups with nothing to prefill);
    start/last_idx/active: [G]. Writes each chunk's K/V through its
    group's table and attends against the slot's whole cached row under
    the global-position causal mask. Returns (logits [G, V] fp32 at
    ``last_idx``, kc', vc'). Inactive groups compute garbage that
    writes nowhere — the uniform-program rule that keeps ONE compiled
    shape for any admission pattern.

    Only ONE position per group projects through the unembedding (the
    gpt2_logits_at memory contract: never a [C, vocab] tensor) — the
    scheduler uses it on the final chunk to sample the first token;
    earlier chunks compute it too (uniform program) and discard it.
    Padding rows beyond the prompt inside the final chunk produce
    garbage that nothing reads: causal masking keeps them out of every
    real row, and the next token's decode write overwrites their cache
    rows before any attend reaches them.
    """
    _check_cfg(cfg)
    G, C = tokens.shape
    pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]  # [G, C]
    x = _embed(params, tokens, pos, cfg)         # [G, C, H]
    bt_g = jnp.where(active[:, None, None] > 0, bt_rows[:, None],
                     kv_cache.DEAD_BLOCK)            # [G, 1, J]
    x, kc, vc = _paged_forward(params, x, kc, vc, bt_g, pos[:, None, :],
                               cfg, paged_kernel, mesh)
    x = layer_norm_fn(cfg)(x, params["ln_f_scale"], params["ln_f_bias"])
    oh = (lax.broadcasted_iota(jnp.int32, (G, C), 1) ==
          last_idx[:, None]).astype(x.dtype)
    h_last = jnp.einsum("gc,gch->gh", oh, x)
    logits = _unembed(params, h_last, cfg)
    return logits, kc, vc


def gpt2_prefill_full_paged(params: Dict[str, Any], kc: jax.Array,
                            vc: jax.Array, tokens: jax.Array,
                            bt_rows: jax.Array, last_idx: jax.Array,
                            cfg: GPT2Config,
                            attention_fn: Optional[Callable] = None,
                            mesh=None
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Whole-prompt single-shot prefill (``prefill_chunk: 0``) into the
    block pool. The self-attention over the prompt runs through the
    pluggable ``attention_fn`` — ring attention when the mesh has a
    sequence axis (exact long-context prefill at 1/sp memory per chip),
    the dense/flash default otherwise — with each layer's K/V rows
    written through the target slot's block table by the same in-place
    write the decode step uses (R = the whole padded prompt). tokens:
    [T] padded to max_len; bt_rows: [G, J] — the slot's row in its own
    group, DEAD_BLOCK rows elsewhere, so the write lands only in the
    owning dp shard."""
    _check_cfg(cfg)
    if attention_fn is None:
        from ..ops.flash_attention import auto_attention
        attention_fn = auto_attention
    T = tokens.shape[0]
    G = bt_rows.shape[0]
    bs = kv_cache.paged_block_size(kc, cfg.head_dim)
    x = _embed(params, tokens, slice(T), cfg)[None]        # [1, T, H]
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (G, T))
    blk, off = _write_targets(bt_rows[:, None], pos[:, None], bs)

    def block(p, h, kc, vc, layer):
        with jax.named_scope("attn"):
            q, k, v = _qkv(p, h, cfg)              # [1, T, nH, D]
            with jax.named_scope("kv_write"):
                kc, vc = kv_cache.paged_write_rows(
                    kc, vc, jnp.broadcast_to(k, (G,) + k.shape[1:]),
                    jnp.broadcast_to(v, (G,) + v.shape[1:]), layer, blk,
                    off, mesh=mesh)
            with jax.named_scope("attend"):
                attn = attention_fn(q, k, v, mask=None, causal=True,
                                    deterministic=True)
            attn = attn.reshape(h.shape).astype(h.dtype)
            h = h + dense(attn, p["proj_kernel"], p["proj_bias"])
        return _ffn(p, h, cfg), kc, vc

    x, kc, vc = _paged_layers(params, x, kc, vc, block)
    x = layer_norm_fn(cfg)(x[0], params["ln_f_scale"],
                           params["ln_f_bias"])
    h_last = lax.dynamic_slice(x, (last_idx.astype(jnp.int32),
                                   jnp.int32(0)), (1, x.shape[1]))[0]
    logits = _unembed(params, h_last, cfg)
    return logits, kc, vc


@jax.named_scope("sample")
def spec_accept(logits: jax.Array, tokens: jax.Array, key: jax.Array,
                temperature: jax.Array) -> jax.Array:
    """In-graph draft acceptance: the longest agreeing prefix rule.

    logits: [S, K, V] from the verify step over [last, d_1..d_{K-1}];
    tokens: the [S, K] verify input. Greedy target g[s,i] =
    argmax(logits[s,i]); draft d_i is accepted iff every d_{i'<=i}
    matched g at its position, and the emitted stream is g[s, :m+1]
    (accepted drafts ARE the greedy tokens, plus the first correction /
    bonus) — which is exactly what non-speculative greedy decode would
    have produced token by token. Returns [S, K+1] int32: column 0 is
    n_new (how many of the following tokens are real), columns 1..K the
    emitted tokens — one array, ONE host fetch per iteration.
    """
    S, K = tokens.shape
    g = sample_tokens(logits, key, temperature)          # [S, K]
    match = (tokens[:, 1:] == g[:, :-1]).astype(jnp.int32)   # [S, K-1]
    acc = jnp.cumprod(match, axis=-1).sum(-1) if K > 1 else \
        jnp.zeros((S,), jnp.int32)
    n_new = (acc + 1).astype(jnp.int32)                  # [S]
    return jnp.concatenate([n_new[:, None], g], axis=-1)


# --------------------------------------------------------------------- #
# Sampling (in-graph; PRNG threaded by the engine per iteration)
# --------------------------------------------------------------------- #
@jax.named_scope("sample")
def sample_tokens(logits: jax.Array, key: jax.Array,
                  temperature: jax.Array) -> jax.Array:
    """Greedy (temperature == 0) or temperature sampling; logits
    [..., V] fp32. Temperature is a TRACED scalar so changing it never
    recompiles; both branches are cheap relative to the step, so a
    select beats a cond."""
    greedy = jnp.argmax(logits, axis=-1)
    t = jnp.maximum(temperature.astype(jnp.float32), 1e-6)
    sampled = jax.random.categorical(key, logits / t, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


# --------------------------------------------------------------------- #
# GPT-2 as a served model (inference/served.py): the first
# implementation of the interface the engine serves through
# --------------------------------------------------------------------- #
class GPT2Served(ServedModel):
    """Per-head K and V rows in two pools; the four programs above."""

    def __init__(self, cfg: GPT2Config):
        _check_cfg(cfg)
        super().__init__(cfg)

    @property
    def max_positions(self) -> int:
        return int(self.cfg.max_seq_length)

    @property
    def init_fn(self) -> Callable:
        from ..models.gpt2 import gpt2_init
        return gpt2_init

    @property
    def cache_layers(self) -> int:
        return int(self.cfg.num_layers)

    @property
    def cache_heads(self) -> int:
        return int(self.cfg.num_heads)

    @property
    def cache_row_width(self) -> int:
        return int(self.cfg.head_dim)

    def cache_pools(self, block_size: int):
        D = self.cache_row_width
        f = kv_cache.kv_fold(D, block_size)
        tile = (self.cache_heads, block_size // f, f * D)
        return (("k", tile), ("v", tile))

    @property
    def attend_dims(self) -> Tuple[int, int, int]:
        return self.cache_heads, self.cache_row_width, self.cache_row_width

    def attend_step_counts(self, live_blocks, *, K, spec, mp, q_itemsize):
        return paged_attn_ops.attend_step_counts(
            live_blocks, K=K, num_heads=max(1, spec.num_heads // mp),
            head_dim=spec.head_dim, block_size=spec.block_size,
            table_width=spec.max_blocks_per_slot,
            kv_itemsize=int(jnp.dtype(spec.dtype).itemsize),
            q_itemsize=q_itemsize)

    def decode(self, params, pools, tokens, lengths, block_tables, *,
               num_groups, paged_kernel, mesh=None):
        logits, kc, vc = gpt2_decode_paged(
            params, *pools, tokens, lengths, block_tables, self.cfg,
            num_groups, paged_kernel=paged_kernel, mesh=mesh)
        return logits, (kc, vc), ()

    def verify(self, params, pools, tokens, lengths, block_tables, *,
               num_groups, paged_kernel, mesh=None):
        logits, kc, vc = gpt2_verify_paged(
            params, *pools, tokens, lengths, block_tables, self.cfg,
            num_groups, paged_kernel=paged_kernel, mesh=mesh)
        return logits, (kc, vc), ()

    def prefill_chunk(self, params, pools, tokens, bt_rows, start,
                      last_idx, active, *, paged_kernel, mesh=None):
        logits, kc, vc = gpt2_prefill_chunk_paged(
            params, *pools, tokens, bt_rows, start, last_idx, active,
            self.cfg, paged_kernel=paged_kernel, mesh=mesh)
        return logits, (kc, vc), ()

    def prefill_full(self, params, pools, tokens, bt_rows, last_idx, *,
                     attention_fn=None, mesh=None):
        logits, kc, vc = gpt2_prefill_full_paged(
            params, *pools, tokens, bt_rows, last_idx, self.cfg,
            attention_fn=attention_fn, mesh=mesh)
        return logits, (kc, vc), ()


register(GPT2Config, GPT2Served)


__all__ = ["gpt2_decode_paged", "gpt2_verify_paged",
           "gpt2_prefill_chunk_paged", "gpt2_prefill_full_paged",
           "spec_accept", "sample_tokens", "GPT2Served"]

"""GPT-2 as a served model (inference/served.py): the incremental forward
against the paged block pool that ``ServedModel``'s three programs —
single-token decode, the speculative verify step, chunked prefill — are
written over.  Each program has a FIXED abstract signature (the recompile
sentinel wraps all of them); prefill and decode are separate programs on
purpose (prefill/decode disaggregation): a long admission never changes the
decode signature.  ``decode`` projects LAST-position logits only (the same
tied-unembedding contraction ``models.gpt2.gpt2_logits_at`` exposes for the
batch path).

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
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import kv_cache
from .served import NEG_INF, Rows, ServedModel, register, write_targets
from ..models.gpt2 import GPT2Config
from ..ops import paged_attention as paged_attn_ops
from ..models.transformer import (dense, gelu_dense_fn, layer_norm,
                                  layer_norm_fn)

def _check_cfg(cfg: GPT2Config) -> None:
    if not cfg.pre_layer_norm or not cfg.causal:
        raise NotImplementedError(
            "the incremental decode path implements the GPT-2 block "
            "(pre-LN, causal); post-LN/bidirectional models have no "
            "autoregressive serving story")


@jax.named_scope("mlp")
def _ffn(p: Dict[str, jax.Array], x: jax.Array, cfg: GPT2Config
         ) -> jax.Array:
    # layer_norm_fn resolves to the fused Pallas kernel when cfg enables
    # it — the SAME static dispatch the training block uses, so flipping
    # the knob never adds a compiled-signature variant to the serving
    # paths (sentinel-asserted in tests/test_fused_ln.py); gelu_dense_fn
    # is the training block's one function.
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
                layer, blk, off, mesh=mesh, stream_rows=K)
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
    blk, off = write_targets(bt_g, pos_g, bs)

    # The pools are a CARRY of the layer loop (scan xs/ys would slice a
    # layer out of the pool and stack it back: a pool-sized copy a layer).
    def block(carry, layer_in):
        p, layer = layer_in
        return _paged_attn_block(p, *carry, layer, cfg, G, blk, off, sel,
                                 pos_mask, plan, mesh), None

    (x, kc, vc), _ = lax.scan(
        block, (x, kc, vc),
        (params["blocks"], jnp.arange(kc.shape[0], dtype=jnp.int32)))
    return x, kc, vc


# --------------------------------------------------------------------- #
# The first implementation of the interface the engine serves through
# --------------------------------------------------------------------- #
class GPT2Served(ServedModel):
    """Per-head K and V rows in two pools."""

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

    def attend_step_counts(self, live_blocks, *, K, spec, mp, q_itemsize,
                           calls=1):
        return paged_attn_ops.attend_step_counts(
            live_blocks, K=K, num_heads=max(1, spec.num_heads // mp),
            head_dim=spec.head_dim, block_size=spec.block_size,
            table_width=spec.max_blocks_per_slot,
            kv_itemsize=int(jnp.dtype(spec.dtype).itemsize),
            q_itemsize=q_itemsize) + (
                paged_attn_ops.attend_cold_steps(live_blocks, calls=calls),)

    write_step_counts = ServedModel._kv_write_step_counts

    def embed(self, params, tokens, pos):
        return _embed(params, tokens, pos, self.cfg)

    def forward(self, params, pools, x, rows: Rows, *, paged_kernel, mesh):
        """The stack, then the final LayerNorm: the rows as ``head``
        takes them.  Every row writes (a dead slot's table is
        ``DEAD_BLOCK``: its rows land nowhere); padding rows past a
        prompt's end inside its last chunk write garbage the next token's
        decode write overwrites before any attend reaches it."""
        cfg = self.cfg
        x, kc, vc = _paged_forward(params, x, *pools, rows.tables,
                                   rows.positions, cfg, paged_kernel, mesh)
        x = layer_norm_fn(cfg)(x, params["ln_f_scale"], params["ln_f_bias"])
        return x, (kc, vc), ()

    def head(self, params, h):
        return _unembed(params, h, self.cfg)


register(GPT2Config, GPT2Served)


__all__ = ["GPT2Served"]

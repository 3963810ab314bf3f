"""The ``deepseek_v3`` family as a served model (inference/served.py):
latent attention over a paged latent cache, a dense SwiGLU prefix, then
expert layers that hold a share of their experts.

What is kept per token and layer is ONE row ``[ckv | k_rope]`` (512 + 64
published), whatever the head count, in one pool ``latent`` held as
``ops.latent_attention`` lays it.  Every table-driven program (decode,
verify, chunked prefill) attends in the ABSORBED form: the query goes
through ``wkv_b``'s key half once (``q' = q_nope Wk^T``), scores are ``q' .
ckv + q_rope . k_rope`` against the cached rows as they lie, the weighted
sum of ``ckv`` goes through ``wkv_b``'s value half.  No per-head K or V is
ever materialised, for a cached token or a new one.

The stack is two scans (dense prefix, expert layers) that carry the pool
beside the layer index, as GPT-2's does; the expert weights ride whole
(``moe.share.routed_share``).  Scopes: ``attn`` > ``latent_proj``,
``kv_write``, ``attend``; ``mlp`` (dense layers); ``moe`` > ``router``,
``dispatch``, ``experts``, ``combine``, ``shared``.  Each program also
returns the expert layers' counters, which ride the token fetch.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import kv_cache
from .decode import NEG_INF, _group_shape, _write_targets
from .served import ServedModel, register
from ..models import deepseek_v3 as dsv3
from ..models.deepseek_v3 import DeepseekV3Config
from ..moe import share
from ..ops import latent_attention as latent_ops

_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def _onehot_attend(q_abs, q_rope, pool, layer, sel, pos_mask, scale, C):
    """The one-hot baseline (off-TPU path and the kernel's reference):
    ``kv_cache.paged_attend`` for a row every head shares."""
    rows = latent_ops.logical_rows(
        lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)[:, :, 0],
        C)                                                  # [G, B, bs, W]
    q = jnp.concatenate([q_abs, q_rope], axis=-1)
    s_all = jnp.einsum("gqknw,gbtw->gqknbt", q, rows,
                       preferred_element_type=jnp.float32) * scale
    scores = jnp.einsum("gqjb,gqknbt->gqknjt", sel, s_all)
    G, Q, K, nH, J, bs = scores.shape
    scores = jnp.where(pos_mask[:, :, :, None, :],
                       scores.reshape(G, Q, K, nH, J * bs), NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).reshape(G, Q, K, nH, J, bs)
    wb = jnp.einsum("gqjb,gqknjt->gqknbt", sel, w)
    return jnp.einsum("gqknbt,gbtc->gqknc", wb.astype(rows.dtype),
                      rows[..., :C], preferred_element_type=jnp.float32
                      ).astype(q_abs.dtype)


def _forward(params, pool, x, bt_g, pos_g, live, cfg: DeepseekV3Config,
             paged_kernel: bool, mesh):
    """All layers: x [S, K, H] with its streams' tables bt_g [G, Sg, J],
    row positions pos_g [G, Sg, K] and ``live`` [S, K]: the rows that are
    traffic (a live stream's, and no padding).  The others write no cache
    row, attend nothing, get no expert row and are not counted; what they
    compute nobody reads.  Returns (x', pool', counters)."""
    G, Sg, J = bt_g.shape
    S, K, H = x.shape
    nH, C = cfg.num_attention_heads, cfg.kv_lora_rank
    bs = 2 * pool.shape[4]
    pos = pos_g.reshape(S, K)
    sel = pos_mask = plan = None
    live_g = live.reshape(G, Sg, K)
    reach = jnp.where(live_g, pos_g, -1)       # a dead row attends nothing
    if paged_kernel:
        with jax.named_scope("attn"), jax.named_scope("attend"):
            plan = latent_ops.latent_plan(bt_g, reach, pool, mesh=mesh)
    else:
        sel = kv_cache.block_select(bt_g, pool.shape[2])
        grid = lax.broadcasted_iota(jnp.int32, (1, 1, 1, J * bs), 3)
        pos_mask = grid <= reach[..., None]
    blk, off = _write_targets(bt_g, pos_g, bs)
    blk = jnp.where(live_g.reshape(G, Sg * K), blk, kv_cache.DEAD_BLOCK)

    def attention(p, x, pool, layer):
        with jax.named_scope("attn"):
            with jax.named_scope("latent_proj"):
                h = dsv3.rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
                q_nope, q_rope, ckv, k_rope = dsv3.latent_projections(
                    p, h, pos, cfg)
                wk, wv = dsv3.wkv_b_split(p, cfg)
                q_abs = jnp.einsum(
                    "sknd,cnd->sknc", q_nope, wk.astype(x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype)
                row = jnp.concatenate([ckv, k_rope], axis=-1)
            with jax.named_scope("kv_write"):
                pool = latent_ops.latent_write(
                    pool, row.reshape(G, Sg * K, -1), layer, blk, off,
                    kv_lora=C, mesh=mesh)
            with jax.named_scope("attend"):
                qa = q_abs.reshape(G, Sg, K, nH, C)
                qr = q_rope.reshape(G, Sg, K, nH, -1)
                if plan is not None:
                    u = latent_ops.latent_attention(
                        qa, qr, pool, layer, plan=plan,
                        scale=cfg.softmax_scale, mesh=mesh)
                else:
                    u = _onehot_attend(qa, qr, pool, layer, sel, pos_mask,
                                       cfg.softmax_scale, C)
            with jax.named_scope("latent_proj"):
                o = jnp.einsum(
                    "sknc,cnv->sknv", u.reshape(S, K, nH, C),
                    wv.astype(x.dtype), preferred_element_type=jnp.float32
                ).astype(x.dtype).reshape(S, K, nH * cfg.v_head_dim)
                x = x + dsv3.matmul(o, p["wo"])
        return x, pool

    def dense_layer(carry, layer_in):
        p, layer = layer_in
        x, pool = attention(p, *carry, layer)
        with jax.named_scope("mlp"):
            h = dsv3.rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
            x = x + dsv3.swiglu(h, p["mlp_gate"], p["mlp_up"],
                                p["mlp_down"])
        return (x, pool), None

    Ld, Le = cfg.num_dense_layers, cfg.num_moe_layers
    (x, pool), _ = lax.scan(
        dense_layer, (x, pool),
        (params["dense"], jnp.arange(Ld, dtype=jnp.int32)))

    experts = {k: params["moe"][k] for k in _EXPERT_KEYS}
    row_live = live.reshape(S * K)

    def moe_layer(carry, layer_in):
        p, l = layer_in
        x, pool, (pairs, most, empty) = carry
        x, pool = attention(p, x, pool, Ld + l)
        h = dsv3.rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
        # ``paged_kernel`` is "this path runs its Pallas kernels": the
        # attend, the row write and the grouped expert product alike.
        y, counts = share.expert_layer(
            dict(p, **experts), h.reshape(S * K, H), cfg.routing,
            kernel=paged_kernel, layer=l, row_live=row_live)
        x = x + y.reshape(S, K, H)
        stats = (pairs + counts.sum(), jnp.maximum(most, counts.max()),
                 empty + (counts == 0).sum())
        return (x, pool, stats), None

    zero = jnp.zeros((), jnp.int32)
    (x, pool, stats), _ = lax.scan(
        moe_layer, (x, pool, (zero, zero, zero)),
        ({k: v for k, v in params["moe"].items() if k not in _EXPERT_KEYS},
         jnp.arange(Le, dtype=jnp.int32)))
    return x, pool, stats + (row_live.sum().astype(jnp.int32),)


@jax.named_scope("lm_head")
def _head(params, h, cfg):
    h = dsv3.rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.dot(h, params["lm_head"].astype(h.dtype).T,
                     preferred_element_type=jnp.float32)
    if cfg.vocab_rows == cfg.vocab_size:
        return logits
    # Padding rows of a sliced vocabulary are no tokens: never sampled.
    ids = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(ids < cfg.vocab_size, logits, NEG_INF)


@jax.named_scope("embed")
def _embed(params, tokens, cfg):
    return params["embed"].astype(cfg.dtype)[tokens]


class LatentServed(ServedModel):
    """See the module docstring."""
    counter_names = ("moe_held_pairs", "moe_held_max", "moe_held_empty",
                     "moe_rows")

    @property
    def max_positions(self) -> int:
        return int(self.cfg.max_position_embeddings)

    @property
    def init_fn(self) -> Callable:
        return dsv3.deepseek_v3_init

    @property
    def cache_layers(self) -> int:
        return int(self.cfg.num_hidden_layers)

    @property
    def cache_heads(self) -> int:
        return 1

    @property
    def cache_row_width(self) -> int:
        return int(self.cfg.latent_width)

    def cache_pools(self, block_size: int):
        return (("latent", (1,) + latent_ops.latent_tile(
            block_size, self.cfg.latent_width)),)

    @property
    def attend_dims(self) -> Tuple[int, int, int]:
        return (self.cfg.num_attention_heads, self.cfg.latent_width,
                self.cfg.kv_lora_rank)

    def attend_step_counts(self, live_blocks, *, K, spec, mp, q_itemsize):
        tiles = -(-K // latent_ops.row_tokens(K))
        groups = -(-np.asarray(live_blocks, np.int64)
                   // latent_ops.slots_a_step(spec.max_blocks_per_slot))
        return (int(np.maximum(groups, 1).sum()) * tiles,
                int(groups.sum()) * tiles)

    def counter_args(self, rows) -> Dict[str, Any]:
        """Of the executions fetched: routed pairs that landed on held
        experts, the largest and the mean rows a held expert got in a
        layer, held experts (x layers) that got no row, and the pairs'
        share of all the live rows routed."""
        cfg = self.cfg
        pairs = int(rows[:, 0].sum())
        cells = len(rows) * cfg.num_moe_layers * cfg.held[1]
        routed = int(rows[:, 3].sum()) * cfg.num_experts_per_tok \
            * cfg.num_moe_layers
        return {"moe_held_pairs": pairs,
                "moe_held_max": int(rows[:, 1].max()),
                "moe_held_mean": pairs / cells,
                "moe_held_empty": int(rows[:, 2].sum()),
                "moe_held_pair_share": pairs / routed if routed else 0.0}

    # -- programs ------------------------------------------------------ #
    def verify(self, params, pools, tokens, lengths, block_tables, *,
               num_groups, paged_kernel, mesh=None):
        cfg = self.cfg
        K = tokens.shape[1]
        pos = lengths[:, None] + jnp.arange(K, dtype=jnp.int32)[None]
        live = jnp.broadcast_to(block_tables[:, :1] >= 0, tokens.shape)
        x, pool, counters = _forward(
            params, pools[0], _embed(params, tokens, cfg),
            _group_shape(block_tables, num_groups),
            _group_shape(pos, num_groups), live, cfg, paged_kernel, mesh)
        return _head(params, x, cfg), (pool,), counters

    def decode(self, params, pools, tokens, lengths, block_tables, *,
               num_groups, paged_kernel, mesh=None):
        logits, pools, counters = self.verify(
            params, pools, tokens[:, None], lengths, block_tables,
            num_groups=num_groups, paged_kernel=paged_kernel, mesh=mesh)
        return logits[:, 0], pools, counters

    def prefill_chunk(self, params, pools, tokens, bt_rows, start,
                      last_idx, active, *, paged_kernel, mesh=None):
        """``decode.gpt2_prefill_chunk_paged``'s contract; rows past
        ``last_idx`` (a last chunk's padding) are dead rows."""
        cfg = self.cfg
        G, Cn = tokens.shape
        pos = start[:, None] + jnp.arange(Cn, dtype=jnp.int32)[None]
        bt_g = jnp.where(active[:, None, None] > 0, bt_rows[:, None],
                         kv_cache.DEAD_BLOCK)
        live = (active[:, None] > 0) & (lax.broadcasted_iota(
            jnp.int32, (G, Cn), 1) <= last_idx[:, None])
        x, pool, counters = _forward(
            params, pools[0], _embed(params, tokens, cfg), bt_g,
            pos[:, None, :], live, cfg, paged_kernel, mesh)
        oh = (lax.broadcasted_iota(jnp.int32, (G, Cn), 1)
              == last_idx[:, None]).astype(x.dtype)
        h_last = jnp.einsum("gc,gch->gh", oh, x)
        return _head(params, h_last, cfg), (pool,), counters


register(DeepseekV3Config, LatentServed)

__all__ = ["LatentServed"]

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

With ``hc_mult`` = n (the ``xing4_0`` keys; ``models/hyper_connections.py``)
both scans carry ``X [S, K, n, H]``: ``embed`` > ``hc_expand`` copies the
embedding to n streams, every sublayer reads ``h = sum_j H_pre[j] X[j]``
and writes ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` under ``hc`` >
``hc_maps`` (norm over nH values, the ``[nH, 2n + n*n]`` product, sigmoids,
Sinkhorn), ``hc_pre``, ``hc_post`` INSIDE the sublayer's own scope
(``attn``, ``mlp``, ``moe``), and ``lm_head`` > ``hc_collapse`` sums the
streams.  The maps are per token: the cache, chunked prefill, prefix hits
and copy-on-write are what they are without them.  One more counter rides
the fetch, ``hc_res_err_max`` (a float's bits): the largest deviation of a
row or column sum of ``H_res`` from 1 over the execution's live rows.
Without the key none of this is traced: the programs are the one-stream
ones.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import kv_cache
from .served import (NEG_INF, Rows, ServedModel, held_counter_args, register,
                     write_targets)
from ..models import deepseek_v3 as dsv3
from ..models import hyper_connections as hyper
from ..models.deepseek_v3 import DeepseekV3Config
from ..moe import share
from ..ops import latent_attention as latent_ops
from ..ops.paged_attention import attend_cold_steps

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


def _scope(name):
    return jax.named_scope(name) if name else contextlib.nullcontext()


class LatentContext(NamedTuple):
    """What every latent layer of one program shares: the attend's plan (the
    kernel path) or the block selection and position mask (the one-hot
    path), and every new row's (block, offset) in the pool."""
    plan: Any
    sel: Any
    pos_mask: Any
    blk: jax.Array
    off: jax.Array


def latent_context(bt_g, pos_g, live, pool, paged_kernel: bool, mesh
                   ) -> LatentContext:
    """For streams' latent tables bt_g [G, Sg, J] (the latent CLASS's
    columns where a model keeps other classes beside it), row positions
    pos_g [G, Sg, K] and ``live`` [S, K] (the rows that are traffic: the
    others write no row and attend nothing), over ``pool`` (any layer's:
    the geometry is read)."""
    G, Sg, J = bt_g.shape
    K = pos_g.shape[-1]
    bs = 2 * pool.shape[4]
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
    blk, off = write_targets(bt_g, pos_g, bs)
    blk = jnp.where(live_g.reshape(G, Sg * K), blk, kv_cache.DEAD_BLOCK)
    return LatentContext(plan, sel, pos_mask, blk, off)


def latent_sublayer(p, h, pos, pool, layer, ctx: LatentContext, cfg, mesh):
    """One latent-attention mixer in the ABSORBED form (module docstring),
    inside the caller's ``attn`` scope: h [S, K, H] un-normed at positions
    pos [S, K]; its rows go into ``pool`` at ``layer`` (an index into THIS
    pool's layers), the attend reads them there.  ``cfg`` names the latent
    keys (``models.deepseek_v3.latent_projections``) and ``softmax_scale``.
    Returns (y [S, K, H], pool')."""
    G = ctx.blk.shape[0]
    S, K = h.shape[:2]
    Sg = S // G
    nH, C = cfg.num_attention_heads, cfg.kv_lora_rank
    with jax.named_scope("latent_proj"):
        h = dsv3.rms_norm(h, p["input_norm"], cfg.rms_norm_eps)
        q_nope, q_rope, ckv, k_rope = dsv3.latent_projections(
            p, h, pos, cfg)
        wk, wv = dsv3.wkv_b_split(p, cfg)
        q_abs = jnp.einsum(
            "sknd,cnd->sknc", q_nope, wk.astype(h.dtype),
            preferred_element_type=jnp.float32).astype(h.dtype)
        row = jnp.concatenate([ckv, k_rope], axis=-1)
    with jax.named_scope("kv_write"):
        pool = latent_ops.latent_write(
            pool, row.reshape(G, Sg * K, -1), layer, ctx.blk, ctx.off,
            kv_lora=C, mesh=mesh)
    with jax.named_scope("attend"):
        qa = q_abs.reshape(G, Sg, K, nH, C)
        qr = q_rope.reshape(G, Sg, K, nH, -1)
        if ctx.plan is not None:
            u = latent_ops.latent_attention(
                qa, qr, pool, layer, plan=ctx.plan,
                scale=cfg.softmax_scale, mesh=mesh)
        else:
            u = _onehot_attend(qa, qr, pool, layer, ctx.sel, ctx.pos_mask,
                               cfg.softmax_scale, C)
    with jax.named_scope("latent_proj"):
        o = jnp.einsum(
            "sknc,cnv->sknv", u.reshape(S, K, nH, C),
            wv.astype(h.dtype), preferred_element_type=jnp.float32
        ).astype(h.dtype).reshape(S, K, nH * cfg.v_head_dim)
        return dsv3.matmul(o, p["wo"]), pool


class LatentServed(ServedModel):
    """See the module docstring."""
    counter_names = ("moe_held_pairs", "moe_held_max", "moe_held_empty",
                     "moe_rows")

    def __init__(self, cfg):
        super().__init__(cfg)
        if cfg.hyper is not None:
            self.counter_names += ("hc_res_err_max",)

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

    def attend_step_counts(self, live_blocks, *, K, spec, mp, q_itemsize,
                           calls=1):
        kt = latent_ops.row_tokens(K)
        tiles = -(-K // kt)
        slots, _ = latent_ops.slots_a_step(
            kt * self.cfg.num_attention_heads, spec.max_blocks_per_slot,
            latent_ops.latent_tile(spec.block_size, self.cfg.latent_width),
            self.cfg.kv_lora_rank, int(jnp.dtype(spec.dtype).itemsize))
        groups = -(-np.asarray(live_blocks, np.int64) // slots)
        return (int(np.maximum(groups, 1).sum()) * tiles,
                int(groups.sum()) * tiles,
                attend_cold_steps(live_blocks, calls=calls))

    def counter_args(self, rows) -> Dict[str, Any]:
        """Of the executions fetched: routed pairs that landed on held
        experts, the largest and the mean rows a held expert got in a
        layer, held experts (x layers) that got no row, and the pairs'
        share of all the live rows routed."""
        cfg = self.cfg
        layers = cfg.num_moe_layers
        args = held_counter_args(
            rows, len(rows) * layers * cfg.held[1],
            int(rows[:, 3].sum()) * cfg.num_experts_per_tok * layers)
        if cfg.hyper is not None:
            # The largest |row or column sum of H_res - 1| over the live
            # rows of every sublayer: what the Sinkhorn iterations left.
            args["hc_res_err_max"] = float(
                rows[:, 4].astype(np.int32).view(np.float32).max())
        return args

    # -- the block ------------------------------------------------------ #
    @jax.named_scope("embed")
    def embed(self, params, tokens, pos):
        x = params["embed"].astype(self.cfg.dtype)[tokens]
        if self.cfg.hyper is None:
            return x
        with jax.named_scope("hc_expand"):
            return hyper.expand(x, self.cfg.hyper.mult)

    def forward(self, params, pools, x, rows: Rows, *, paged_kernel, mesh):
        """x [S, K, H] (``[S, K, n, H]`` with ``hc_mult`` = n residual
        streams); ``pools``: (latent,)."""
        cfg = self.cfg
        pool, = pools
        live = rows.live
        K = rows.positions.shape[-1]
        S, H = x.shape[0], x.shape[-1]
        pos = rows.positions.reshape(S, K)
        ctx = latent_context(rows.tables, rows.positions, live, pool,
                             paged_kernel, mesh)

        # The residual path.  One stream: a sublayer reads x and adds to it.
        # ``hc_mult`` streams: it reads a mixture of them and writes back
        # through two more maps (models/hyper_connections.py), per token, so
        # nothing of it enters the cache.  ``outer`` names the sublayer's scope
        # where the caller is not inside it; ``plain`` is where the one-stream
        # add is filed.
        def read(p, sub, x, outer=None):
            if cfg.hyper is None:
                return x, None
            with _scope(outer), jax.named_scope("hc"):
                with jax.named_scope("hc_maps"):
                    m = dsv3.hc_maps(p, sub, x, cfg)
                with jax.named_scope("hc_pre"):
                    return hyper.mix_in(m, x), m

        def write(m, x, y, outer=None, plain=None):
            if m is None:
                with _scope(plain):
                    return x + y
            with _scope(outer), jax.named_scope("hc"), \
                    jax.named_scope("hc_post"):
                return hyper.mix_out(m, x, y)

        def res_error(m_attn, m_ffn):
            """A layer's scan output: what Sinkhorn left of its two maps (None
            on one stream)."""
            if cfg.hyper is None:
                return None
            return jnp.maximum(hyper.res_error(m_attn, live),
                               hyper.res_error(m_ffn, live))

        def attention(p, x, pool, layer):
            with jax.named_scope("attn"):
                h, m = read(p, "attn", x)
                y, pool = latent_sublayer(p, h, pos, pool, layer, ctx, cfg,
                                          mesh)
                x = write(m, x, y, plain="latent_proj")
            return x, pool, m

        def dense_layer(carry, layer_in):
            p, layer = layer_in
            x, pool, m_attn = attention(p, *carry, layer)
            with jax.named_scope("mlp"):
                h, m = read(p, "ffn", x)
                h = dsv3.rms_norm(h, p["post_norm"], cfg.rms_norm_eps)
                x = write(m, x, dsv3.swiglu(h, p["mlp_gate"], p["mlp_up"],
                                            p["mlp_down"]))
            return (x, pool), res_error(m_attn, m)

        Ld, Le = cfg.num_dense_layers, cfg.num_moe_layers
        (x, pool), err_dense = lax.scan(
            dense_layer, (x, pool),
            (params["dense"], jnp.arange(Ld, dtype=jnp.int32)))

        experts = {k: params["moe"][k] for k in _EXPERT_KEYS}
        row_live = live.reshape(S * K)

        def moe_layer(carry, layer_in):
            p, l = layer_in
            x, pool, (pairs, most, empty) = carry
            x, pool, m_attn = attention(p, x, pool, Ld + l)
            h, m = read(p, "ffn", x, outer="moe")
            h = dsv3.rms_norm(h, p["post_norm"], cfg.rms_norm_eps)
            # ``paged_kernel`` is "this path runs its Pallas kernels": the
            # attend, the row write and the grouped expert product alike.
            y, counts = share.expert_layer(
                dict(p, **experts), h.reshape(S * K, H), cfg.routing,
                kernel=paged_kernel, layer=l, row_live=row_live)
            x = write(m, x, y.reshape(S, K, H), outer="moe")
            stats = (pairs + counts.sum(), jnp.maximum(most, counts.max()),
                     empty + (counts == 0).sum())
            return (x, pool, stats), res_error(m_attn, m)

        zero = jnp.zeros((), jnp.int32)
        (x, pool, stats), err_moe = lax.scan(
            moe_layer, (x, pool, (zero, zero, zero)),
            ({k: v for k, v in params["moe"].items() if k not in _EXPERT_KEYS},
             jnp.arange(Le, dtype=jnp.int32)))
        stats += (row_live.sum().astype(jnp.int32),)
        if cfg.hyper is not None:
            # A float among the int32 counters: its bits ride the token fetch.
            err = jnp.maximum(err_dense.max(), err_moe.max())
            stats += (lax.bitcast_convert_type(err, jnp.int32),)
        return x, (pool,), stats

    @jax.named_scope("lm_head")
    def head(self, params, h):
        """Logits of ``h [..., H]`` (``[..., n, H]``: the streams' sum)."""
        cfg = self.cfg
        if cfg.hyper is not None:
            with jax.named_scope("hc_collapse"):
                h = hyper.collapse(h)
        h = dsv3.rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        logits = jnp.dot(h, params["lm_head"].astype(h.dtype).T,
                         preferred_element_type=jnp.float32)
        if cfg.vocab_rows == cfg.vocab_size:
            return logits
        # Padding rows of a sliced vocabulary are no tokens: never sampled.
        ids = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        return jnp.where(ids < cfg.vocab_size, logits, NEG_INF)


register(DeepseekV3Config, LatentServed)

__all__ = ["LatentServed", "LatentContext", "latent_context",
           "latent_sublayer"]

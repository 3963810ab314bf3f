"""The ``falcon_h1`` family as a served model (inference/served.py): EVERY
layer runs a Mamba-2 state-space mixer, which keeps a fixed-size fp32 STATE
a stream, and grouped-query attention, which keeps K/V rows a token, side by
side on the same normed input — two KINDS of cache, of two dtypes, in one
layer.

The kinds are declared, not coded for: ``cache_classes`` names ``full`` (pools
``k.full`` / ``v.full``, per-head K/V tiles of ``block_size`` tokens,
unbounded reach) and ``state`` (``per_stream``; pools ``ssm.state``, a
stream's ``S [nh, N, P]`` of a layer in FLOAT32 as the decode kernel tiles
it, and ``conv.state``, the last ``mamba_d_conv - 1`` rows of the projected
``xBC`` in the cache's dtype) — EACH of all the layers — and
``class_geometry`` answers for each, the state pool's dtype included (a
pool's own entry: the engine has no word on it).  The engine gives each
class its own pools, block table and allocator behind
``kv_cache.ClassAllocators`` — pages shared by reference, the state by
snapshots, one prefix rule across both — and a program gets the pools class
by class and every table row as the classes' rows side by side
(``table_widths``): the ``full`` columns, then the stream's page.

``decode`` has one row a stream: the state update is ``ops.ssm_scan
.state_update`` on the chip (every live page's layer read once and written
once, in place; a gather, the plain recurrence and a scatter that drops dead
slots off it).  ``prefill_chunk`` has a chunk of one stream a group: the
chunked scan from the page's state (zeros at position 0), rows past
``last_idx`` neither decaying the state nor adding to it.  The scan runs in
sub-chunks of gcd(``mamba_chunk_size``, the cache's block, the chunk) rows,
so that every block boundary of the prompt is one of the scan's own carried
states: the model declares ``freezes_in_chunk``, and the chunk that reaches
a snapshot's boundary writes the state as it stood THERE (and the conv rows
that end there) into the snapshot's page as well — no second accumulation,
no cut of the prompt, no copy.  A state cannot be rolled back over rejected
drafts: ``verify`` raises, and ``inference.spec_k`` must be 0.  The
attention branch runs ``ops.paged_attention`` as ``inference/afmoe.py``'s
unbounded class does (``group`` = 5 query heads a K/V head as query rows).

The layers are walked in a static loop.  Scopes: ``embed``; ``attn`` >
``qkv_proj``, ``kv_write``, ``attend_full``, ``out_proj``; ``ssm`` >
``ssm_in_proj``, ``ssm_conv``, ``ssm_state_update`` (decode) /
``ssm_chunk_scan`` (prefill), ``ssm_gate_norm``, ``ssm_out_proj``; ``mlp``;
``lm_head``.  The filter rows go through ``served.filter_rows``, which
rewrites a decode step's in place where the tile allows: 5,120 channels of
bf16 are 40 sublane rows a held row, two and a half tiles, so the published
width keeps the gather and scatter.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import kv_cache
from .afmoe import GqaPagedServed, _attend_rows, _gather_attend
from .served import (CacheClass, filter_rows, group_shape, register,
                     stream_pages)
from ..models import falcon_h1 as fh1
from ..models.blocks import rms_norm
from ..models.falcon_h1 import FalconH1Config
from ..ops import paged_attention as paged_attn_ops
from ..ops import ssm_scan

FULL_CLASS, STATE_CLASS = "full", "state"


def conv_tile(cfg: FalconH1Config) -> Tuple[int, int, int]:
    """A page's tile of one layer's filter rows as held: ``mamba_d_conv -
    1`` rows of ``conv_dim``, row-major, in rows of 128 lanes where they
    divide (a ``[3, C]`` minor pair would be padded to the sublane tile)."""
    n = (cfg.mamba_d_conv - 1) * cfg.conv_dim
    return (1, n // 128, 128) if n % 128 == 0 \
        else (1, cfg.mamba_d_conv - 1, cfg.conv_dim)


def _forward(params, pools, x, bt_g, pos_g, live, cfg: FalconH1Config,
             widths, paged_kernel: bool, mesh, chunked: bool, freeze=None):
    """All layers: x [S, K, H] with its streams' table rows bt_g [G, Sg, W]
    (the classes' rows side by side, ``widths`` wide), row positions pos_g
    [G, Sg, K] and ``live`` [S, K]: the rows that are traffic (a live
    stream's, and no padding; a stream's live rows come first).  The others
    write no cache row and no page and attend nothing; what they compute
    nobody reads.  ``pools``: (k, v, ssm, conv).  ``chunked``: a prefill
    chunk (the scan over its K rows), else the decode program (K = 1: the
    state update).
    ``freeze``: (row [S], page [S]) — a stream's state as it stands after
    chunk row ``row`` goes into ``page`` too (a snapshot; ``DEAD_BLOCK``:
    none), or None.  Returns (x', pools')."""
    G, Sg, K = pos_g.shape
    S = G * Sg
    nH, D, grp = cfg.num_attention_heads, cfg.head_dim, cfg.group
    taps = cfg.mamba_d_conv
    pos = pos_g.reshape(S, K)
    live_g = live.reshape(G, Sg, K)
    kc, vc, ssm, conv = pools
    w_full, w_state = widths
    assert w_state == 1, widths

    # -- the attention branch's table, writes and plan: one for all layers
    bt = bt_g[:, :, :w_full]
    bs = kv_cache.paged_block_size(kc, D)
    seen = jnp.where(live_g, pos_g, -1)        # a dead row attends nothing
    rows = _attend_rows(K, grp)
    runs = K // rows
    table = jnp.broadcast_to(bt[:, :, None, :], (G, Sg, K, w_full))
    blk, off = kv_cache.positions_to_blocks(table, pos_g, bs)
    blk = jnp.where(live_g, blk, kv_cache.DEAD_BLOCK).reshape(G, Sg * K)
    off = off.reshape(G, Sg * K)
    bt_runs = jnp.broadcast_to(bt[:, :, None, :], (G, Sg, runs, w_full)) \
        .reshape(G, Sg * runs, w_full)
    seen_runs = seen.reshape(G, Sg * runs, rows)
    plan = None
    if paged_kernel:
        with jax.named_scope("attn"), jax.named_scope("attend_full"):
            plan = paged_attn_ops.attend_plan(bt_runs, seen_runs, kc, D,
                                              mesh=mesh, group=grp)

    # -- the state's page, where it goes back and what a snapshot takes.
    # The scan's sub-chunk: every block boundary is one of its carried
    # states (a chunk starts at one: the engine's widths are whole blocks).
    q_rows = math.gcd(cfg.mamba_chunk_size, bs, K)
    page = bt_g[:, :, w_full].reshape(S)
    sp = stream_pages(page, pos, live, ssm.shape[2], Sg, taps - 1, freeze,
                      scan_rows=q_rows)

    def attention(p, u, layer):
        nonlocal kc, vc
        with jax.named_scope("attn"):
            with jax.named_scope("qkv_proj"):
                q, k, v = fh1.qkv(p, u, pos, cfg)
            with jax.named_scope("kv_write"):
                kc, vc = kv_cache.paged_write_rows(
                    kc, vc, k.reshape((G, Sg * K) + k.shape[2:]),
                    v.reshape((G, Sg * K) + v.shape[2:]), layer, blk, off,
                    mesh=mesh)
            with jax.named_scope("attend_full"):
                qr = q.reshape(G, Sg * runs, rows, nH, D)
                if plan is not None:
                    a = paged_attn_ops.paged_attention(
                        qr, kc, vc, layer, plan=plan,
                        scale=cfg.softmax_scale, mesh=mesh)
                else:
                    a = _gather_attend(qr, kc, vc, layer, bt_runs,
                                       seen_runs, None, cfg.softmax_scale)
            with jax.named_scope("out_proj"):
                return fh1.scaled_matmul(a.reshape(S, K, nH * D), p["wo"],
                                         cfg.attention_out_multiplier)

    def decode_states(x_h, B, C, dt, a, layer):
        """One row a stream: every live page's layer rewritten in place."""
        nonlocal ssm
        args = (x_h[:, 0], B[:, 0], C[:, 0], dt[:, 0], jnp.exp(a[:, 0]))
        if paged_kernel:
            y, ssm = ssm_scan.state_update(
                ssm, layer, page.reshape(G, Sg),
                *(group_shape(v, G) for v in args), mesh=mesh)
            return y.reshape((S, 1) + y.shape[2:])
        y, new = ssm_scan.recurrent_update(ssm[layer, sp.group, sp.page],
                                           *args)
        ssm = ssm.at[layer, sp.group, sp.to[0]].set(new, mode="drop")
        return jnp.where(sp.wrote[:, None, None], y, 0.0)[:, None]

    def chunk_states(x_h, B, C, dt, a, layer):
        """A chunk of rows a stream, from the page's state."""
        nonlocal ssm
        dt = jnp.where(live[..., None], dt, 0.0)
        a = jnp.where(live[..., None], a, 0.0)
        ys = []
        for s in range(S):
            S0 = jnp.where(sp.carried[s],
                           ssm[layer, sp.group[s], sp.page[s]], 0.0)
            y, S1, kept = ssm_scan.chunked_scan(
                S0, x_h[s], B[s], C[s], dt[s], a[s], chunk=q_rows,
                keep=None if sp.keep_chunk is None else sp.keep_chunk[s])
            for where, new in zip(sp.to, (S1, kept)):
                ssm = ssm.at[layer, sp.group[s], where[s]].set(
                    new, mode="drop")
            ys.append(y)
        return jnp.stack(ys)

    def mixer(p, u, layer):
        nonlocal conv
        with jax.named_scope("ssm"):
            with jax.named_scope("ssm_in_proj"):
                z, xbc, dt_raw = fh1.ssm_in(p, u, cfg)
            with jax.named_scope("ssm_conv"):
                rows_in, conv = filter_rows(sp, conv, layer, xbc,
                                            paged_kernel=paged_kernel,
                                            mesh=mesh)
                x_h, B, C = fh1.ssm_split(fh1.ssm_conv(p, rows_in, cfg), cfg)
                dt, a = fh1.ssm_steps(p, dt_raw)
            if not chunked:
                with jax.named_scope("ssm_state_update"):
                    y = decode_states(x_h, B, C, dt, a, layer)
            else:
                with jax.named_scope("ssm_chunk_scan"):
                    y = chunk_states(x_h, B, C, dt, a, layer)
            with jax.named_scope("ssm_gate_norm"):
                y = y + p["D"][:, None] * x_h.astype(jnp.float32)
                y = fh1.gated_norm(p, y.reshape(S, K, -1), z, cfg, u.dtype)
            with jax.named_scope("ssm_out_proj"):
                return fh1.scaled_matmul(y, p["ssm_out"],
                                         cfg.ssm_out_multiplier)

    for layer, p in enumerate(params["layers"]):
        u = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
        x = x + attention(p, u, layer) + mixer(p, u, layer)
        with jax.named_scope("mlp"):
            g = rms_norm(x, p["pre_ff_norm"], cfg.rms_norm_eps)
            x = x + fh1.gated_mlp(p, g, cfg)
    return x, (kc, vc, ssm, conv)


@jax.named_scope("lm_head")
def _head(params, h, cfg):
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(h, params["lm_head"].astype(h.dtype).T,
                   preferred_element_type=jnp.float32) \
        * cfg.lm_head_multiplier


@jax.named_scope("embed")
def _embed(params, tokens, cfg):
    x = params["embed"][tokens].astype(jnp.float32) \
        * cfg.embedding_multiplier
    return x.astype(cfg.dtype)


class FalconH1Served(GqaPagedServed):
    """See the module docstring.  What a model of grouped-query K/V pages
    answers is ``GqaPagedServed``'s; this family's own is the second KIND
    of cache in the same layers."""
    # The scan carries the state from sub-chunk to sub-chunk and every
    # block boundary is one: the program that passes a snapshot's leaves it.
    freezes_in_chunk = True

    @property
    def init_fn(self) -> Callable:
        return fh1.falcon_h1_init

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        """Both classes hold ALL the layers."""
        L = int(self.cfg.num_hidden_layers)
        return (CacheClass(FULL_CLASS, L),
                CacheClass(STATE_CLASS, L, per_stream=True))

    def class_geometry(self, cls: CacheClass, block_size: int
                       ) -> Dict[str, Any]:
        """``full``: K and V tiles of the K/V heads, in the engine's cache
        dtype.  ``state``: ``ssm``, the stream's state of a layer in FLOAT32
        whatever the cache's dtype, and ``conv``, its filter's rows in the
        cache's.  Its yardstick (``token_row_bytes``) is what a token keeps
        a layer as K/V rows in the ``full`` class beside it."""
        if not cls.per_stream:
            return super().class_geometry(cls, block_size)
        cfg = self.cfg
        state = ssm_scan.state_tile(cfg.mamba_n_heads, cfg.mamba_d_state,
                                    cfg.mamba_d_head)
        return dict(pools=(("ssm", state, jnp.float32),
                           ("conv", conv_tile(cfg))),
                    num_heads=cfg.mamba_n_heads,
                    head_dim=cfg.mamba_d_state * cfg.mamba_d_head,
                    token_row_bytes=2 * cfg.num_key_value_heads
                    * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize)

    # -- programs ------------------------------------------------------ #
    def verify(self, params, pools, tokens, lengths, block_tables, *,
               num_groups, paged_kernel, mesh=None):
        raise NotImplementedError(
            "a state-space layer's state cannot be rolled back over "
            "rejected drafts: set inference.spec_k to 0")

    def decode(self, params, pools, tokens, lengths, block_tables, *,
               num_groups, paged_kernel, mesh=None):
        cfg = self.cfg
        live = (block_tables >= 0).any(axis=1, keepdims=True)
        x, pools = _forward(
            params, pools, _embed(params, tokens[:, None], cfg),
            group_shape(block_tables, num_groups),
            group_shape(lengths[:, None], num_groups), live, cfg,
            self._widths(block_tables), paged_kernel, mesh, chunked=False)
        return _head(params, x[:, 0], cfg), pools, None

    def prefill_chunk(self, params, pools, tokens, bt_rows, start,
                      last_idx, active, freeze_idx=None, freeze_page=None,
                      *, paged_kernel, mesh=None):
        """``decode.gpt2_prefill_chunk_paged``'s contract; rows past
        ``last_idx`` (a last chunk's padding) are dead rows.  The state a
        chunk starts from is whatever the stream's own page holds — a
        snapshot the engine copied there, or the chunk before — and zeros
        at position 0.  ``freezes_in_chunk``: a group's state as it stands
        after chunk row ``freeze_idx`` (the last row of a block) goes into
        page ``freeze_page`` as well (``DEAD_BLOCK``: the group leaves none
        in this chunk; without the operands the program writes the
        stream's own page only)."""
        cfg = self.cfg
        G, Cn = tokens.shape
        cols = lax.broadcasted_iota(jnp.int32, (G, Cn), 1)
        pos = start[:, None] + cols
        bt_g = jnp.where(active[:, None, None] > 0, bt_rows[:, None],
                         kv_cache.DEAD_BLOCK)
        live = (active[:, None] > 0) & (cols <= last_idx[:, None])
        x, pools = _forward(
            params, pools, _embed(params, tokens, cfg), bt_g,
            pos[:, None, :], live, cfg, self._widths(bt_rows), paged_kernel,
            mesh, chunked=True, freeze=None if freeze_idx is None
            else (freeze_idx, freeze_page))
        oh = (cols == last_idx[:, None]).astype(x.dtype)
        h_last = jnp.einsum("gc,gch->gh", oh, x)
        return h_last, pools, None

    def head(self, params, h):
        return _head(params, h, self.cfg)


register(FalconH1Config, FalconH1Served)

__all__ = ["FalconH1Served", "FULL_CLASS", "STATE_CLASS", "conv_tile"]

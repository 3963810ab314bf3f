"""The ``lfm2_moe`` family as a served model (inference/served.py): gated
short-convolution layers, which keep a fixed-size STATE a stream, between
grouped-query attention layers, which keep K/V rows a token — two KINDS of
cache in one model — then a dense SwiGLU or an expert layer that holds
every expert.

The kinds are declared, not coded for: ``cache_classes`` names ``full``
(the attention layers; pools ``k.full`` / ``v.full``, per-head K/V tiles of
``block_size`` tokens, unbounded reach) and ``conv`` (the conv layers,
``per_stream``; one pool ``conv.conv`` whose PAGE is a stream's last
``conv_L_cache - 1`` rows of ``z = B * X`` — ``(z_{t-1}, z_t)`` — of every
conv layer, held lane-dense in the cache's dtype), and ``class_geometry``
answers for each.  The engine gives each class its own pools, block table
and allocator behind ``kv_cache.ClassAllocators`` — pages shared by
reference, the state by snapshots, one prefix rule across both — and a
program gets the pools class by class and every table row as the classes'
rows side by side (``table_widths``): the ``full`` columns, then the
stream's page.

A conv layer is the same three lines in every program: the page's rows
(zeros for a stream at position 0) ahead of the rows' own ``z``, the filter
over each run of ``conv_L_cache`` of them, and the page rewritten with the
rows that end at the stream's last LIVE row — in place in the donated pool,
for live streams only (a dead slot, an inactive group or padding writes
nothing).  ``decode`` has one row a stream, ``prefill_chunk`` a chunk of
one stream a group.  The state after ANY row of a chunk is a gather of the
chunk's ``z`` rows, so the model declares ``freezes_in_chunk``: the chunk
that reaches a snapshot's boundary is handed the boundary's row and the
snapshot's page and writes the rows that end THERE into that page as well —
the same gather at a second index, a second scatter into the donated pool —
and the engine neither cuts the prompt at the boundary nor copies the
stream's page (a turn of a session is one pass over the experts, not two).
A state cannot be rolled back over rejected drafts:
``verify`` raises, and ``inference.spec_k`` must be 0.  The attention
layers run ``ops.paged_attention`` as ``inference/afmoe.py``'s unbounded
class does (``group`` query heads a K/V head as query rows).

The layers are walked in a static loop (their kinds differ; nothing is
stacked or sliced).  Scopes: ``embed``; ``conv`` > ``conv_in_proj``,
``conv_mix`` (gates' product, filter, the page's rewrite: in place by
``ops.filter_rows.shift_rows`` in the decode program on the chip,
``served.filter_rows``), ``conv_out_proj``;
``attn`` > ``qkv_proj``, ``kv_write``, ``attend_full``, ``out_proj``;
``mlp`` (dense layers); ``moe`` > ``router``, ``dispatch``, ``experts``,
``combine``; ``lm_head``.  Each program also returns the expert layers'
counters, which ride the token fetch.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import kv_cache
from .afmoe import AfmoeServed, _attend_rows, _gather_attend
from .served import (CacheClass, filter_rows, group_shape, register,
                     stream_pages)
from ..models import lfm2
from ..models.blocks import matmul, rms_norm, swiglu
from ..models.lfm2 import CONV, Lfm2Config
from ..moe import share
from ..ops import paged_attention as paged_attn_ops

FULL_CLASS, CONV_CLASS = "full", "conv"


def _classes(cfg: Lfm2Config) -> Tuple[CacheClass, ...]:
    """The classes that have a layer, the pages first."""
    out = []
    if cfg.num_attention_layers:
        out.append(CacheClass(FULL_CLASS, cfg.num_attention_layers))
    if cfg.num_conv_layers:
        out.append(CacheClass(CONV_CLASS, cfg.num_conv_layers,
                              per_stream=True))
    return tuple(out)


def conv_tile(cfg: Lfm2Config) -> Tuple[int, int, int]:
    """A page's tile of one conv layer as held: ``conv_L_cache - 1`` rows
    of ``hidden_size``, row-major, in rows of 128 lanes where they divide
    (a ``[2, H]`` minor pair would be padded to the sublane tile)."""
    n = (cfg.conv_L_cache - 1) * cfg.hidden_size
    return (1, n // 128, 128) if n % 128 == 0 \
        else (1, cfg.conv_L_cache - 1, cfg.hidden_size)


def _forward(params, pools, x, bt_g, pos_g, live, cfg: Lfm2Config,
             widths, paged_kernel: bool, mesh, freeze=None):
    """All layers: x [S, K, H] with its streams' table rows bt_g [G, Sg,
    W] (the classes' rows side by side, ``widths`` wide), row positions
    pos_g [G, Sg, K] and ``live`` [S, K]: the rows that are traffic (a
    live stream's, and no padding; a stream's live rows come first).  The
    others write no cache row and no page, attend nothing, get no expert
    row and are not counted; what they compute nobody reads.  ``pools``:
    every class's in ``cache_classes`` order.  ``freeze``: (row [S], page
    [S]) — a stream's conv state as it stands after chunk row ``row`` goes
    into ``page`` too (a snapshot; ``DEAD_BLOCK``: none), or None.
    Returns (x', pools', counters)."""
    G, Sg, K = pos_g.shape
    S, H = G * Sg, x.shape[-1]
    nH, D, grp = cfg.num_attention_heads, cfg.head_dim, cfg.group
    pos = pos_g.reshape(S, K)
    live_g = live.reshape(G, Sg, K)
    pools = list(pools)
    at_col, at_pool, full, conv = 0, 0, None, None
    for cls, width in zip(_classes(cfg), widths):
        bt = bt_g[:, :, at_col:at_col + width]
        at_col += width
        if cls.per_stream:
            # the conv state's page, where it goes back and what a
            # snapshot takes (a second write: the rows that end at the
            # snapshot's row, to the snapshot's page)
            conv = dict(at=at_pool, layer=0, pages=stream_pages(
                bt[:, :, 0].reshape(S), pos, live, pools[at_pool].shape[2],
                Sg, cfg.conv_L_cache - 1, freeze))
            at_pool += 1
            continue
        kc = pools[at_pool]
        bs = kv_cache.paged_block_size(kc, D)
        seen = jnp.where(live_g, pos_g, -1)    # a dead row attends nothing
        rows = _attend_rows(K, grp)
        runs = K // rows
        table = jnp.broadcast_to(bt[:, :, None, :], (G, Sg, K, width))
        blk, off = kv_cache.positions_to_blocks(table, pos_g, bs)
        blk = jnp.where(live_g, blk, kv_cache.DEAD_BLOCK)
        # A chunk's rows in runs, each a stream of the attend.
        bt_runs = jnp.broadcast_to(
            bt[:, :, None, :], (G, Sg, runs, width)).reshape(
                G, Sg * runs, width)
        seen_runs = seen.reshape(G, Sg * runs, rows)
        plan = None
        if paged_kernel:
            with jax.named_scope("attn"), \
                    jax.named_scope("attend_" + cls.name):
                plan = paged_attn_ops.attend_plan(
                    bt_runs, seen_runs, kc, D, mesh=mesh, group=grp)
        full = dict(at=at_pool, layer=0, plan=plan, bt=bt_runs,
                    seen=seen_runs, blk=blk.reshape(G, Sg * K),
                    off=off.reshape(G, Sg * K), rows=rows, runs=runs)
        at_pool += 2

    def attention(p, x):
        c = full
        kc, vc = pools[c["at"]], pools[c["at"] + 1]
        layer = c["layer"]
        c["layer"] += 1
        with jax.named_scope("attn"):
            with jax.named_scope("qkv_proj"):
                u = rms_norm(x, p["op_norm"], cfg.norm_eps)
                q, k, v = lfm2.qkv(p, u, pos, cfg)
            with jax.named_scope("kv_write"):
                kc, vc = kv_cache.paged_write_rows(
                    kc, vc, k.reshape((G, Sg * K) + k.shape[2:]),
                    v.reshape((G, Sg * K) + v.shape[2:]), layer,
                    c["blk"], c["off"], mesh=mesh)
            with jax.named_scope("attend_" + FULL_CLASS):
                qr = q.reshape(G, Sg * c["runs"], c["rows"], nH, D)
                if c["plan"] is not None:
                    a = paged_attn_ops.paged_attention(
                        qr, kc, vc, layer, plan=c["plan"],
                        scale=cfg.softmax_scale, mesh=mesh)
                else:
                    a = _gather_attend(qr, kc, vc, layer, c["bt"],
                                       c["seen"], None, cfg.softmax_scale)
            with jax.named_scope("out_proj"):
                x = x + matmul(a.reshape(S, K, nH * D), p["wo"])
        pools[c["at"]], pools[c["at"] + 1] = kc, vc
        return x

    def convolution(p, x):
        c = conv
        pool = pools[c["at"]]
        layer = c["layer"]
        c["layer"] += 1
        L = cfg.conv_L_cache
        with jax.named_scope("conv"):
            with jax.named_scope("conv_in_proj"):
                u = rms_norm(x, p["op_norm"], cfg.norm_eps)
                z, gate = lfm2.conv_gates(p, u)                # [S, K, H]
            with jax.named_scope("conv_mix"):
                zc, pool = filter_rows(c["pages"], pool, layer, z,
                                       paged_kernel=paged_kernel,
                                       mesh=mesh)
                taps = p["conv_k"].astype(jnp.float32)
                mixed = sum(zc[:, j:j + K].astype(jnp.float32) * taps[:, j]
                            for j in range(L))
                y = (gate.astype(jnp.float32) * mixed).astype(x.dtype)
            with jax.named_scope("conv_out_proj"):
                x = x + matmul(y, p["w_out"])
        pools[c["at"]] = pool
        return x

    row_live = live.reshape(S * K)
    zero = jnp.zeros((), jnp.int32)
    pairs, most, empty = zero, zero, zero
    for l, p in enumerate(params["layers"]):
        x = convolution(p, x) if cfg.layer_types[l] == CONV \
            else attention(p, x)
        if l < cfg.num_dense_layers:
            with jax.named_scope("mlp"):
                u = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
                x = x + swiglu(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
            continue
        with jax.named_scope("moe"):
            u = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
            # ``paged_kernel`` is "this path runs its Pallas kernels"; the
            # family has no shared expert: the routed share is the layer.
            y, counts = share.routed_share(
                p, u.reshape(S * K, H), cfg.routing, paged_kernel,
                row_live=row_live)
            x = x + y.reshape(S, K, H)
        pairs = pairs + counts.sum()
        most = jnp.maximum(most, counts.max())
        empty = empty + (counts == 0).sum()
    return x, tuple(pools), (pairs, most, empty,
                             row_live.sum().astype(jnp.int32))


@jax.named_scope("lm_head")
def _head(params, h, cfg):
    """The family's ``embedding_norm``, then the head, tied to the
    embedding."""
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return jnp.dot(h, params["embed"].astype(h.dtype).T,
                   preferred_element_type=jnp.float32)


@jax.named_scope("embed")
def _embed(params, tokens, cfg):
    return params["embed"].astype(cfg.dtype)[tokens]


class Lfm2Served(AfmoeServed):
    """See the module docstring.  What a model of grouped-query K/V pages
    and expert layers that hold every expert answers is ``AfmoeServed``'s
    (the K/V tiles, the attend's dimensions and step counts, the expert
    counters); this family's own is the second KIND of cache."""
    # A conv layer's state at ANY row of a chunk is a gather of the chunk's
    # ``z`` rows: the program that passes a snapshot's boundary leaves it.
    freezes_in_chunk = True

    @property
    def init_fn(self) -> Callable:
        return lfm2.lfm2_init

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        return _classes(self.cfg)

    def class_geometry(self, cls: CacheClass, block_size: int
                       ) -> Dict[str, Any]:
        """``full``: K and V tiles of the K/V heads.  ``conv``: one pool
        whose tile is a stream's rows of a conv layer, one "head" of them;
        its yardstick (``token_row_bytes``, a conv layer's share) is what a
        token keeps as K/V rows in this model's attention layers: the
        pages a snapshot saves prefilling."""
        if not cls.per_stream:
            return super().class_geometry(cls, block_size)
        cfg = self.cfg
        tile = conv_tile(cfg)
        kv_token = (2 * cfg.num_key_value_heads * cfg.head_dim
                    * cfg.num_attention_layers
                    * jnp.dtype(cfg.dtype).itemsize)
        return dict(pools=(("conv", tile),), num_heads=1,
                    head_dim=tile[1] * tile[2],
                    token_row_bytes=-(-kv_token // cls.layers))

    # -- programs ------------------------------------------------------ #
    def verify(self, params, pools, tokens, lengths, block_tables, *,
               num_groups, paged_kernel, mesh=None):
        raise NotImplementedError(
            "a conv layer's state cannot be rolled back over rejected "
            "drafts: set inference.spec_k to 0")

    def decode(self, params, pools, tokens, lengths, block_tables, *,
               num_groups, paged_kernel, mesh=None):
        cfg = self.cfg
        live = (block_tables >= 0).any(axis=1, keepdims=True)
        x, pools, counters = _forward(
            params, pools, _embed(params, tokens[:, None], cfg),
            group_shape(block_tables, num_groups),
            group_shape(lengths[:, None], num_groups), live, cfg,
            self._widths(block_tables), paged_kernel, mesh)
        return _head(params, x[:, 0], cfg), pools, counters

    def prefill_chunk(self, params, pools, tokens, bt_rows, start,
                      last_idx, active, freeze_idx=None, freeze_page=None,
                      *, paged_kernel, mesh=None):
        """``decode.gpt2_prefill_chunk_paged``'s contract; rows past
        ``last_idx`` (a last chunk's padding) are dead rows.  The page a
        chunk starts from is whatever the stream's own holds — a snapshot
        the engine copied there, or the chunk before — and zeros at
        position 0.  ``freezes_in_chunk``: a group's state as it stands
        after chunk row ``freeze_idx`` (a live one) goes into page
        ``freeze_page`` as well, a snapshot at that row's boundary
        (``DEAD_BLOCK``: the group leaves none in this chunk; without the
        operands the program writes the stream's own page only)."""
        cfg = self.cfg
        G, Cn = tokens.shape
        cols = lax.broadcasted_iota(jnp.int32, (G, Cn), 1)
        pos = start[:, None] + cols
        bt_g = jnp.where(active[:, None, None] > 0, bt_rows[:, None],
                         kv_cache.DEAD_BLOCK)
        live = (active[:, None] > 0) & (cols <= last_idx[:, None])
        x, pools, counters = _forward(
            params, pools, _embed(params, tokens, cfg), bt_g,
            pos[:, None, :], live, cfg, self._widths(bt_rows), paged_kernel,
            mesh, freeze=None if freeze_idx is None
            else (freeze_idx, freeze_page))
        oh = (cols == last_idx[:, None]).astype(x.dtype)
        h_last = jnp.einsum("gc,gch->gh", oh, x)
        return h_last, pools, counters

    def head(self, params, h):
        return _head(params, h, self.cfg)


register(Lfm2Config, Lfm2Served)

__all__ = ["Lfm2Served", "FULL_CLASS", "CONV_CLASS", "conv_tile"]

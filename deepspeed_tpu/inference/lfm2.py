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
A state cannot be rolled back over rejected drafts (``rolls_back`` is
False: ``verify`` raises, and ``inference.spec_k`` must be 0).  The attention
layers are ``inference/kv_pages.py``'s branch over this model's ``full``
class (``group`` query heads a K/V head as query rows).

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

from .afmoe import AfmoeServed
from .kv_pages import write_and_attend
from .served import (CacheClass, Rows, filter_rows, filter_tile, register,
                     stream_pages)
from ..models import lfm2
from ..models.blocks import matmul, rms_norm, swiglu
from ..models.lfm2 import CONV, Lfm2Config
from ..moe import share

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


class Lfm2Served(AfmoeServed):
    """See the module docstring.  What a model of grouped-query K/V pages
    and expert layers that hold every expert answers is ``AfmoeServed``'s
    (the K/V tiles, the attend's dimensions and step counts, the expert
    counters); this family's own is the second KIND of cache."""
    # A conv layer's state at ANY row of a chunk is a gather of the chunk's
    # ``z`` rows: the program that passes a snapshot's boundary leaves it.
    freezes_in_chunk = True
    rolls_back = False

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
        tile = filter_tile(cfg.conv_L_cache - 1, cfg.hidden_size)
        kv_token = (2 * cfg.num_key_value_heads * cfg.head_dim
                    * cfg.num_attention_layers
                    * jnp.dtype(cfg.dtype).itemsize)
        return dict(pools=(("conv", tile),), num_heads=1,
                    head_dim=tile[1] * tile[2],
                    token_row_bytes=-(-kv_token // cls.layers))

    # -- the block ------------------------------------------------------ #
    @jax.named_scope("embed")
    def embed(self, params, tokens, pos):
        return params["embed"].astype(self.cfg.dtype)[tokens]

    def forward(self, params, pools, x, rows: Rows, *, paged_kernel, mesh):
        """``pools``: every class's in ``cache_classes`` order — (k, v) of
        ``full``, then ``conv``."""
        cfg = self.cfg
        G, Sg, K = rows.positions.shape
        S, H = G * Sg, x.shape[-1]
        pos = rows.positions.reshape(S, K)
        pools = list(pools)
        full = self.paged_classes(rows, pools, paged_kernel=paged_kernel,
                                  mesh=mesh).get(FULL_CLASS)
        conv = None
        if self.cache_classes[-1].per_stream:
            # the conv state's page (the table's last column; its pool the
            # last), where it goes back and what a snapshot takes (a second
            # write: the rows that end at the snapshot's row, to its page)
            conv = dict(layer=0, pages=stream_pages(
                rows.tables[:, :, sum(rows.widths) - 1].reshape(S), pos,
                rows.live, pools[-1].shape[2], Sg, cfg.conv_L_cache - 1,
                rows.freeze))

        def attention(p, x):
            with jax.named_scope("attn"):
                with jax.named_scope("qkv_proj"):
                    u = rms_norm(x, p["op_norm"], cfg.norm_eps)
                    q, k, v = lfm2.qkv(p, u, pos, cfg)
                a = write_and_attend(full, pools, q, k, v,
                                     scale=cfg.softmax_scale, mesh=mesh)
                with jax.named_scope("out_proj"):
                    x = x + matmul(a, p["wo"])
            return x

        def convolution(p, x):
            c = conv
            layer = c["layer"]
            c["layer"] += 1
            L = cfg.conv_L_cache
            with jax.named_scope("conv"):
                with jax.named_scope("conv_in_proj"):
                    u = rms_norm(x, p["op_norm"], cfg.norm_eps)
                    z, gate = lfm2.conv_gates(p, u)            # [S, K, H]
                with jax.named_scope("conv_mix"):
                    zc, pools[-1] = filter_rows(
                        c["pages"], pools[-1], layer, z,
                        paged_kernel=paged_kernel, mesh=mesh)
                    taps = p["conv_k"].astype(jnp.float32)
                    mixed = sum(zc[:, j:j + K].astype(jnp.float32)
                                * taps[:, j] for j in range(L))
                    y = (gate.astype(jnp.float32) * mixed).astype(x.dtype)
                with jax.named_scope("conv_out_proj"):
                    x = x + matmul(y, p["w_out"])
            return x

        row_live = rows.live.reshape(S * K)
        zero = jnp.zeros((), jnp.int32)
        pairs, most, empty = zero, zero, zero
        for l, p in enumerate(params["layers"]):
            x = convolution(p, x) if cfg.layer_types[l] == CONV \
                else attention(p, x)
            if l < cfg.num_dense_layers:
                with jax.named_scope("mlp"):
                    u = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
                    x = x + swiglu(u, p["mlp_gate"], p["mlp_up"],
                                   p["mlp_down"])
                continue
            with jax.named_scope("moe"):
                u = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
                # ``paged_kernel`` is "this path runs its Pallas kernels";
                # the family has no shared expert: the routed share is the
                # layer.
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
    def head(self, params, h):
        """The family's ``embedding_norm``, then the head, tied to the
        embedding."""
        h = rms_norm(h, params["final_norm"], self.cfg.norm_eps)
        return jnp.dot(h, params["embed"].astype(h.dtype).T,
                       preferred_element_type=jnp.float32)


register(Lfm2Config, Lfm2Served)

__all__ = ["Lfm2Served", "FULL_CLASS", "CONV_CLASS"]

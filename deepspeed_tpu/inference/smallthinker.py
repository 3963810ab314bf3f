"""The ``smallthinker`` family as a served model (inference/served.py):
grouped-query attention over per-head K/V pages whose layers are of TWO
CLASSES — full attention without positions and sliding-window attention
with rotary positions, by the config's two per-layer lists — and in EVERY
layer an expert layer that holds every expert, routed from the block's
normed INPUT.

The cache is ``inference/afmoe.py``'s, declared and not coded for:
``cache_classes`` names ``full`` (unbounded) and ``window`` (reach =
``sliding_window_size``) from ``sliding_window_layout``, the engine gives
each its pools, block table and allocator, and the attention branch's
tables, write targets, plans, row writes and attends are
``inference/kv_pages.py``'s ``paged_classes`` / ``write_and_attend``, called
here (7 query heads a K/V head as ``group`` x K query rows of the same
kernels).  ``AfmoeServed``'s K/V tiles, step counts, expert counters and
head serve as they are; this module is the BLOCK:

    x = N_in(h);  plan = route + dispatch FROM x      (moe > router, dispatch)
    h = h + Attn(x)                                   (attn > ...)
    z = N_post(h);  h = h + experts(z) under plan     (moe > experts, combine)

The routing of block ``l`` depends on nothing its attention computes, and
the program says so: ``moe.share.plan_routes`` stands BEFORE the attention,
``apply_routes`` after the post-attention norm.  Whether the compiler
overlaps the two is its business.

The layers are walked in a static loop (their kinds differ; nothing is
stacked or sliced).  Scopes: ``embed``; ``attn`` > ``qkv_proj`` (the input
norm, the projections, the rotary), ``kv_write``, ``attend_window`` /
``attend_full``, ``out_proj``; ``moe`` > ``router``, ``dispatch``,
``experts``, ``combine``; ``lm_head``.  Each program also returns the
expert layers' counters, which ride the token fetch.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from .afmoe import FULL_CLASS, WINDOW_CLASS, AfmoeServed
from .kv_pages import write_and_attend
from .served import CacheClass, Rows, register
from ..models import smallthinker
from ..models.blocks import matmul, rms_norm
from ..models.smallthinker import SmallthinkerConfig
from ..moe import share


def _classes(cfg: SmallthinkerConfig) -> Tuple[CacheClass, ...]:
    """The classes that have a layer, the unbounded one first."""
    n_window = sum(cfg.sliding_window_layout)
    n_full = cfg.num_hidden_layers - n_window
    out = []
    if n_full:
        out.append(CacheClass(FULL_CLASS, n_full))
    if n_window:
        out.append(CacheClass(WINDOW_CLASS, n_window,
                              int(cfg.sliding_window_size)))
    return tuple(out)


class SmallthinkerServed(AfmoeServed):
    """See the module docstring."""
    @property
    def init_fn(self) -> Callable:
        return smallthinker.smallthinker_init

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        return _classes(self.cfg)

    def counter_args(self, rows) -> Dict[str, Any]:
        """``AfmoeServed``'s, and ``rows``: the live rows the fetched
        execution(s) routed (on a ``prefill`` span: of the chunk program
        that ended the prompt)."""
        return dict(super().counter_args(rows), rows=int(rows[:, 3].sum()))

    # -- the block ------------------------------------------------------ #
    @jax.named_scope("embed")
    def embed(self, params, tokens, pos):
        return params["embed"].astype(self.cfg.dtype)[tokens]

    def forward(self, params, pools, h, rows: Rows, *, paged_kernel, mesh):
        cfg = self.cfg
        G, Sg, K = rows.positions.shape
        S, H = G * Sg, h.shape[-1]
        pos = rows.positions.reshape(S, K)
        pools = list(pools)
        classes = self.paged_classes(rows, pools, paged_kernel=paged_kernel,
                                     mesh=mesh)
        row_live = rows.live.reshape(S * K)
        zero = jnp.zeros((), jnp.int32)
        pairs, most, empty = zero, zero, zero
        for l, p in enumerate(params["layers"]):
            with jax.named_scope("attn"), jax.named_scope("qkv_proj"):
                x = rms_norm(h, p["input_norm"], cfg.rms_norm_eps)
            # The block's routing, from its INPUT: nothing below feeds it.
            with jax.named_scope("moe"):
                routes = share.plan_routes(p, x.reshape(S * K, H),
                                           cfg.routing, row_live)
            with jax.named_scope("attn"):
                with jax.named_scope("qkv_proj"):
                    q, k, v = smallthinker.qkv(p, x, pos, cfg,
                                               bool(cfg.rope_layout[l]))
                a = write_and_attend(
                    classes[WINDOW_CLASS if cfg.sliding_window_layout[l]
                            else FULL_CLASS],
                    pools, q, k, v, scale=cfg.softmax_scale, mesh=mesh)
                with jax.named_scope("out_proj"):
                    h = h + matmul(a, p["wo"])
            with jax.named_scope("moe"):
                z = rms_norm(h, p["post_attn_norm"], cfg.rms_norm_eps)
                # ``paged_kernel`` is "this path runs its Pallas kernels":
                # the attend, the row write and the grouped expert product
                # alike.
                y, counts = share.apply_routes(
                    p, z.reshape(S * K, H), routes, cfg.routing,
                    kernel=paged_kernel, act="relu")
                h = h + y.reshape(S, K, H)
            pairs = pairs + counts.sum()
            most = jnp.maximum(most, counts.max())
            empty = empty + (counts == 0).sum()
        return h, tuple(pools), (pairs, most, empty,
                                 row_live.sum().astype(jnp.int32))


register(SmallthinkerConfig, SmallthinkerServed)

__all__ = ["SmallthinkerServed"]

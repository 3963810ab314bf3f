"""The ``solar_open2`` family as a served model (inference/served.py): three
Kimi-Delta-Attention layers, which keep a fixed-size fp32 STATE a stream (and
the last rows of three short filters), to one grouped-query attention layer,
which keeps K/V PAGES — two KINDS of cache in different layers — every layer
an expert layer that holds a share of its experts.

The kinds are declared, not coded for: ``cache_classes`` names ``full`` (the
grouped-query layers; pools ``k.full`` / ``v.full``, per-head K/V tiles of
``block_size`` tokens, unbounded reach) and ``state`` (the KDA layers,
``per_stream``; pools ``state.state`` in FLOAT32 and ``conv.state``:
``inference/kda_state.py``), and ``class_geometry`` answers for each.  The
engine gives each class its own pools, block table and allocator behind
``kv_cache.ClassAllocators`` — pages shared by reference, the state by
snapshots, one prefix rule across both, so a HIT is across kinds: pages up to
a boundary AND a state snapshot AT it — and a program gets the pools class by
class and every table row as the classes' rows side by side
(``table_widths``): the ``full`` columns, then the stream's page.  A
snapshot here is 13 MB at the published widths — what ~3,200 tokens keep as
K/V — so which snapshots stay is the allocator's question, not a detail.

A grouped-query layer is ``inference/kv_pages.py``'s branch over the ``full``
class (``group`` query heads a K/V head as query rows, no rotation), its
output under ``kv_pages.output_gate`` (scope ``attn_gate``) ahead of the
output projection.  A KDA layer is ``kda_state.KdaPages.mixer``: the decode
update in place, the chunked delta rule from the page's state in prefill,
with ``beta`` in (0, 2) (``models.kimi_linear.kda_gates`` reads the config's
``kda_allow_neg_eigval``); the chunk that reaches a snapshot's boundary
leaves it (``freezes_in_chunk``) and nothing rolls back (``rolls_back`` is
False: ``verify`` raises, and ``inference.spec_k`` must be 0).

The layers are walked in a static loop (their kinds differ).  Scopes:
``embed``; ``attn`` > ``kda_proj``, ``kda_conv``, ``kda_gate``,
``kda_update`` / ``kda_chunk``, ``kda_out`` in a KDA layer, ``qkv_proj``,
``kv_write``, ``attend_full``, ``attn_gate``, ``out_proj`` in a grouped-query
one; ``moe`` > ``router``, ``dispatch``, ``experts``, ``combine``,
``shared``; ``lm_head``.  Each program returns the expert layers' counters
(the held share's, as ``LatentServed`` names them), which ride the token
fetch.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from . import kv_cache
from .kda_state import KdaPages, scan_rows, state_geometry
from .kv_pages import GqaPagedServed, output_gate, write_and_attend
from .served import CacheClass, Rows, held_counter_args, register
from ..models import solar_open2 as so
from ..models.blocks import matmul, rms_norm
from ..models.solar_open2 import KDA, SolarOpen2Config
from ..moe import share

FULL_CLASS, STATE_CLASS = "full", "state"


class SolarOpen2Served(GqaPagedServed):
    """See the module docstring.  What a model of grouped-query K/V pages
    answers is ``GqaPagedServed``'s (the K/V tiles, the attend's dimensions
    and step counts); this family's own is the second KIND of cache, in most
    of its layers, and expert layers that hold a share."""
    freezes_in_chunk = True
    rolls_back = False
    counter_names = ("moe_held_pairs", "moe_held_max", "moe_held_empty",
                     "moe_rows")

    @property
    def init_fn(self) -> Callable:
        return so.solar_open2_init

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        cfg = self.cfg
        return (CacheClass(FULL_CLASS, cfg.num_gqa_layers),
                CacheClass(STATE_CLASS, cfg.num_kda_layers,
                           per_stream=True))

    def class_geometry(self, cls: CacheClass, block_size: int
                       ) -> Dict[str, Any]:
        """``full``: K and V tiles of the K/V heads.  ``state``: see
        ``kda_state.state_geometry``; its yardstick is what a token keeps as
        K/V rows in this model's grouped-query layers."""
        if not cls.per_stream:
            return super().class_geometry(cls, block_size)
        cfg = self.cfg
        return state_geometry(
            cfg, cls.layers, 2 * cfg.num_key_value_heads * cfg.head_dim
            * cfg.num_gqa_layers * jnp.dtype(cfg.dtype).itemsize)

    def counter_args(self, rows) -> Dict[str, Any]:
        """Of the executions fetched: routed pairs that landed on held
        experts, the largest and the mean rows a held expert got in a
        layer, held experts (x layers) that got no row, and the pairs'
        share of all the live rows routed."""
        cfg = self.cfg
        layers = cfg.num_moe_layers
        return held_counter_args(
            rows, len(rows) * layers * cfg.held[1],
            int(rows[:, 3].sum()) * cfg.num_experts_per_tok * layers)

    # -- the block ------------------------------------------------------ #
    @jax.named_scope("embed")
    def embed(self, params, tokens, pos):
        return params["embed"].astype(self.cfg.dtype)[tokens]

    def forward(self, params, pools, x, rows: Rows, *, paged_kernel, mesh):
        """``pools``: (k, v) of ``full``, then (state, conv).
        ``rows.chunked``: the chunked delta rule over a prefill chunk's K
        rows, else the decode program's state update (K = 1)."""
        cfg = self.cfg
        G, Sg, K = rows.positions.shape
        S, H = G * Sg, x.shape[-1]
        pools = list(pools)
        w_full, w_state = rows.widths
        assert w_state == 1, rows.widths
        full = self.paged_classes(rows, pools, paged_kernel=paged_kernel,
                                  mesh=mesh)[FULL_CLASS]
        block = kv_cache.paged_block_size(pools[0], cfg.head_dim)
        pages = KdaPages(
            cfg, pools[2], pools[3], rows.tables[:, :, w_full].reshape(S),
            rows, q_rows=scan_rows(block, K), paged_kernel=paged_kernel,
            mesh=mesh)

        def attention(p, x):
            with jax.named_scope("attn"):
                with jax.named_scope("qkv_proj"):
                    u = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
                    q, k, v, gate = so.qkvg(p, u, cfg)
                a = write_and_attend(full, pools, q, k, v,
                                     scale=cfg.softmax_scale, mesh=mesh)
                with jax.named_scope("attn_gate"):
                    a = output_gate(a, gate, x.dtype)
                with jax.named_scope("out_proj"):
                    return x + matmul(a, p["wo"])

        row_live = rows.live.reshape(S * K)
        zero = jnp.zeros((), jnp.int32)
        pairs, most, empty = zero, zero, zero
        at = 0
        for l, p in enumerate(params["layers"]):
            if cfg.layer_kinds[l] == KDA:
                x = pages.mixer(p, x, at)
                at += 1
            else:
                x = attention(p, x)
            h = rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
            # ``paged_kernel`` is "this path runs its Pallas kernels": the
            # attend, the state update and the grouped expert product alike.
            y, counts = share.expert_layer(
                p, h.reshape(S * K, H), cfg.routing, kernel=paged_kernel,
                row_live=row_live)
            x = x + y.reshape(S, K, H)
            pairs = pairs + counts.sum()
            most = jnp.maximum(most, counts.max())
            empty = empty + (counts == 0).sum()
        pools[2], pools[3] = pages.state, pages.conv
        return x, tuple(pools), (pairs, most, empty,
                                 row_live.sum().astype(jnp.int32))

    @jax.named_scope("lm_head")
    def head(self, params, h):
        h = rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.dot(h, params["lm_head"].astype(h.dtype).T,
                       preferred_element_type=jnp.float32)


register(SolarOpen2Config, SolarOpen2Served)

__all__ = ["SolarOpen2Served", "FULL_CLASS", "STATE_CLASS"]

"""The ``afmoe`` family as a served model (inference/served.py): grouped-
query attention over per-head K/V pages whose layers are of TWO CLASSES —
full attention (reads the whole context) and sliding-window attention
(reads ``sliding_window`` tokens back) — then a dense SwiGLU or an expert
layer that holds every expert.

The classes are declared, not coded for: ``cache_classes`` names them
(``full``: unbounded; ``window``: reach = ``sliding_window``), and the
engine gives each its own pools (``k.full`` / ``v.full``, ``k.window`` /
``v.window``: ``[layers of the class, G, B, nKV, bs/f, f*D]``), block
table and allocator (inference/kv_cache.py).  A program gets the pools
class by class and every table row as the classes' rows side by side
(``table_widths``); a window-class row is a ring.  Both classes run the
same kernels (``ops.paged_attention``): the row write as it is (a tile has
the K/V heads), the attend with ``group`` query heads a K/V head and, for
the window class, a plan that walks only the blocks in reach: the branch is
``inference/kv_pages.py``'s (``paged_classes`` once a program,
``write_and_attend`` once a layer), shared with every family of such pages.

The layers are walked in a static loop (their kinds differ; nothing is
stacked or sliced).  Scopes: ``attn`` > ``qkv_proj``, ``kv_write``,
``attend_window`` / ``attend_full``, ``out_proj``; ``mlp`` (dense layers);
``moe`` > ``router``, ``dispatch``, ``experts``, ``combine``, ``shared``.
Each program also returns the expert layers' counters, which ride the
token fetch.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from .kv_pages import GqaPagedServed, output_gate, write_and_attend
from .served import CacheClass, Rows, held_counter_args, register
from ..models import afmoe
from ..models.afmoe import AfmoeConfig, SLIDING
from ..models.blocks import matmul, rms_norm, swiglu
from ..moe import share

FULL_CLASS, WINDOW_CLASS = "full", "window"


def _classes(cfg: AfmoeConfig) -> Tuple[CacheClass, ...]:
    """The classes that have a layer, the unbounded one first."""
    n_window = sum(t == SLIDING for t in cfg.layer_types)
    n_full = cfg.num_hidden_layers - n_window
    out = []
    if n_full:
        out.append(CacheClass(FULL_CLASS, n_full))
    if n_window:
        out.append(CacheClass(WINDOW_CLASS, n_window,
                              int(cfg.sliding_window)))
    return tuple(out)


class AfmoeServed(GqaPagedServed):
    """See the module docstring.  A family of the same two classes and
    counters whose BLOCK differs (``inference/smallthinker.py``) overrides
    ``embed`` / ``forward`` / ``head``."""
    counter_names = ("moe_held_pairs", "moe_held_max", "moe_held_empty",
                     "moe_rows")

    @property
    def init_fn(self) -> Callable:
        return afmoe.afmoe_init

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        return _classes(self.cfg)

    def counter_args(self, rows) -> Dict[str, Any]:
        """Of the executions fetched: routed pairs (every expert is held:
        all of them), the largest and the mean rows an expert got in a
        layer, experts (x layers) that got no row, and the pairs' share of
        all the live rows routed (1.0 here), under the names the latent
        family's share uses."""
        cfg = self.cfg
        layers = cfg.num_moe_layers
        return held_counter_args(
            rows, len(rows) * layers * cfg.num_experts,
            int(rows[:, 3].sum()) * cfg.num_experts_per_tok * layers)

    # -- the block ------------------------------------------------------ #
    @jax.named_scope("embed")
    def embed(self, params, tokens, pos):
        cfg = self.cfg
        x = params["embed"].astype(cfg.dtype)[tokens]
        if cfg.mup_enabled:
            x = (x.astype(jnp.float32)
                 * cfg.hidden_size ** 0.5).astype(x.dtype)
        return x

    def forward(self, params, pools, x, rows: Rows, *, paged_kernel, mesh):
        """``pools``: (k, v) of every class in ``cache_classes`` order.  A
        window-class row of a chunk reaches ``reach + chunk - 1`` rows back
        at most: what the ring is sized for."""
        cfg = self.cfg
        G, Sg, K = rows.positions.shape
        S, H = G * Sg, x.shape[-1]
        pos = rows.positions.reshape(S, K)
        pools = list(pools)
        classes = self.paged_classes(rows, pools, paged_kernel=paged_kernel,
                                     mesh=mesh)

        def attention(p, x, sliding: bool):
            c = classes[WINDOW_CLASS if sliding else FULL_CLASS]
            with jax.named_scope("attn"):
                with jax.named_scope("qkv_proj"):
                    h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
                    q, k, v, gate = afmoe.qkvg(p, h, pos, cfg, sliding)
                a = write_and_attend(c, pools, q, k, v,
                                     scale=cfg.softmax_scale, mesh=mesh)
                with jax.named_scope("out_proj"):
                    a = output_gate(a, gate, x.dtype)
                    x = x + rms_norm(matmul(a, p["wo"]),
                                     p["post_attn_norm"], cfg.rms_norm_eps)
            return x

        row_live = rows.live.reshape(S * K)
        zero = jnp.zeros((), jnp.int32)
        pairs, most, empty = zero, zero, zero
        for i, p in enumerate(params["layers"]):
            x = attention(p, x, cfg.layer_types[i] == SLIDING)
            if i < cfg.num_dense_layers:
                with jax.named_scope("mlp"):
                    h = rms_norm(x, p["pre_mlp_norm"], cfg.rms_norm_eps)
                    y = swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
                    x = x + rms_norm(y, p["post_mlp_norm"],
                                     cfg.rms_norm_eps)
                continue
            with jax.named_scope("moe"):
                h = rms_norm(x, p["pre_mlp_norm"], cfg.rms_norm_eps)
                # ``paged_kernel`` is "this path runs its Pallas kernels":
                # the attend, the row write and the grouped expert product
                # alike.
                y, counts = share.expert_layer(
                    p, h.reshape(S * K, H), cfg.routing,
                    kernel=paged_kernel, row_live=row_live)
                x = x + rms_norm(y.reshape(S, K, H), p["post_mlp_norm"],
                                 cfg.rms_norm_eps)
            pairs = pairs + counts.sum()
            most = jnp.maximum(most, counts.max())
            empty = empty + (counts == 0).sum()
        return x, tuple(pools), (pairs, most, empty,
                                 row_live.sum().astype(jnp.int32))

    @jax.named_scope("lm_head")
    def head(self, params, h):
        h = rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.dot(h, params["lm_head"].astype(h.dtype).T,
                       preferred_element_type=jnp.float32)


register(AfmoeConfig, AfmoeServed)

__all__ = ["AfmoeServed", "FULL_CLASS", "WINDOW_CLASS"]

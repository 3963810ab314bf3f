"""The ``brumby`` family as a served model (inference/served.py): every
layer a power-retention layer, so what is kept is not a row a token but a
fixed-size STATE a stream and layer — the interface's per-stream pool.

Two pools, ``state`` and ``norm`` (``ops.power_retention``: the fp32
``S`` and its normaliser ``z`` of every K/V head, as the decode kernel
tiles them), one PAGE a stream.  ``decode`` rewrites every live stream's
page in place (``power_retention.state_update`` on the chip; a gather, the
plain recurrent form and a scatter that drops dead slots off it);
``prefill_chunk`` carries a stream's state from chunk to chunk in the
chunked form, starting from zeros at position 0 and from whatever the
page holds otherwise — a snapshot the engine copied there, or the chunk
before.  A recurrent state cannot be rolled back over rejected drafts
(``rolls_back`` is False: ``verify`` raises, and ``inference.spec_k`` must
be 0).

The stack is one scan that carries both pools beside the layer index.
Scopes: ``embed``; ``attn`` > ``qkv_proj``, ``state_update`` (decode) /
``retention_chunk`` (prefill), ``out_proj``; ``mlp``; ``lm_head``.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .served import CacheClass, Rows, ServedModel, register
from ..models import brumby
from ..models.brumby import BrumbyConfig
from ..ops import power_retention as pr


def _page_of(pool, layer, g, page):
    """One page's layer of a stacked pool ``[L, G, B, ...]`` (a slice)."""
    tile = pool.shape[3:]
    return lax.dynamic_slice(pool, (layer, g, page) + (0,) * len(tile),
                             (1, 1, 1) + tile)[0, 0, 0]


def _put_page(pool, new, layer, g, page):
    return lax.dynamic_update_slice(
        pool, new[None, None, None].astype(pool.dtype),
        (layer, g, page) + (0,) * new.ndim)


def _decode_states(state, norm, layer, pages, q, k, v, log_g, eps):
    """``power_retention.state_update`` off the chip: gather the live
    pages' layer, the plain recurrent form, scatter them back; a dead
    slot's write is dropped (its page index is out of range)."""
    G, Sg = pages.shape
    B = state.shape[2]
    N = G * Sg
    page = pages.reshape(N)
    live = page >= 0
    g = jnp.arange(N) // Sg
    at = jnp.maximum(page, 0)
    flat = lambda a: a.reshape((N,) + a.shape[2:])          # noqa: E731
    y, S_new, z_new = pr.recurrent_update(
        state[layer, g, at], norm[layer, g, at], flat(q), flat(k), flat(v),
        flat(log_g), eps)
    to = jnp.where(live, page, B)
    state = state.at[layer, g, to].set(S_new, mode="drop")
    norm = norm.at[layer, g, to].set(z_new, mode="drop")
    y = jnp.where(live[:, None, None], y, 0.0)
    return y.reshape(q.shape), state, norm


class RetentionServed(ServedModel):
    """See the module docstring."""
    rolls_back = False

    @property
    def max_positions(self) -> int:
        return int(self.cfg.max_position_embeddings)

    @property
    def init_fn(self) -> Callable:
        return brumby.brumby_init

    @property
    def cache_layers(self) -> int:
        return int(self.cfg.num_hidden_layers)

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        """One class, a fixed-size state a stream (a page, not rows)."""
        return (CacheClass("", self.cache_layers, per_stream=True),)

    @property
    def cache_heads(self) -> int:
        return int(self.cfg.num_key_value_heads)

    @property
    def cache_row_width(self) -> int:
        return int(self.cfg.head_dim)

    def cache_pools(self, block_size: int):
        """The state and its normaliser, float32 whatever
        ``inference.kv_cache_dtype``."""
        return tuple((name, tile, jnp.float32) for name, tile in
                     pr.state_tiles(self.cfg.num_key_value_heads,
                                    self.cfg.head_dim))

    @property
    def token_row_bytes(self) -> int:
        """K and V rows of every K/V head in the compute dtype: what an
        attention model of these widths would keep a token and layer."""
        return (2 * self.cfg.num_key_value_heads * self.cfg.head_dim
                * jnp.dtype(self.cfg.dtype).itemsize)

    def cache_cost(self, keys: int, block_size: int, itemsize: int
                   ) -> Tuple[int, int]:
        """The state's read and write and the products against it: the
        same for every token, whatever ``keys``."""
        cfg = self.cfg
        D, F = cfg.head_dim, pr.feature_width(cfg.head_dim)
        flops = 2 * F * (D + 1) * (cfg.num_attention_heads
                                   + cfg.num_key_value_heads)
        return flops, 2 * cfg.num_key_value_heads * F * (D + 1) * 4

    def attend_step_counts(self, live_blocks, *, K, spec, mp, q_itemsize,
                           calls=1):
        """The state update's steps; none cold: no attend walks a state."""
        live = np.asarray(live_blocks)
        return pr.state_update_steps(int((live > 0).sum()), live.size,
                                     self.cfg.num_key_value_heads,
                                     self.cfg.head_dim) + (0,)

    # -- the block ------------------------------------------------------ #
    @jax.named_scope("embed")
    def embed(self, params, tokens, pos):
        return params["embed"].astype(self.cfg.dtype)[tokens]

    def forward(self, params, pools, x, rows: Rows, *, paged_kernel, mesh):
        """``pools``: (state, norm); ``rows.tables [G, Sg, 1]`` is each
        stream's page.  The decode program (one row a stream) rewrites every
        live stream's page; a chunk (one stream a group) at position 0
        starts from zeros, any other from what the page holds, and rows
        that are not live neither decay the state nor add to it."""
        cfg = self.cfg
        G, Sg, K = rows.positions.shape
        pages = rows.tables[:, :, 0]                              # [G, Sg]
        if rows.chunked:
            positions, live = rows.positions[:, 0], rows.live     # [G, K]

            @jax.named_scope("retention_chunk")
            def retention(state, norm, layer, q, k, v, log_g):
                ys = []
                for g in range(G):
                    page = jnp.maximum(pages[g, 0], 0)
                    S0 = _page_of(state, layer, g, page)
                    z0 = _page_of(norm, layer, g, page)
                    carried = positions[g, 0] > 0
                    y, S1, z1 = pr.chunked_retention(
                        jnp.where(carried, S0, 0.0),
                        jnp.where(carried, z0, 0.0), q[g], k[g], v[g],
                        log_g[g], live[g], cfg.retention_eps)
                    ok = pages[g, 0] >= 0         # (an active group's page)
                    state = _put_page(state, jnp.where(ok, S1, S0), layer,
                                      g, page)
                    norm = _put_page(norm, jnp.where(ok, z1, z0), layer, g,
                                     page)
                    ys.append(y)
                return jnp.stack(ys), state, norm
        else:
            # one row a stream: the layers run without the K = 1 axis
            x, positions = x[:, 0], rows.positions.reshape(G * Sg)
            grouped = lambda a: a.reshape((G, Sg) + a.shape[1:])  # noqa: E731

            @jax.named_scope("state_update")
            def retention(state, norm, layer, q, k, v, log_g):
                args = (state, norm, layer, pages, grouped(q), grouped(k),
                        grouped(v), grouped(log_g))
                if paged_kernel:
                    y, state, norm = pr.state_update(
                        *args, eps=cfg.retention_eps, mesh=mesh)
                else:
                    y, state, norm = _decode_states(*args, cfg.retention_eps)
                return y.reshape(q.shape), state, norm

        state, norm = pools

        def layer_fn(carry, layer_in):
            p, layer = layer_in
            x, state, norm = carry
            with jax.named_scope("attn"):
                with jax.named_scope("qkv_proj"):
                    h = brumby.rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
                    q, k, v, log_g = brumby.retention_projections(
                        p, h, positions, cfg)
                y, state, norm = retention(state, norm, layer, q, k, v,
                                           log_g)
                with jax.named_scope("out_proj"):
                    y = y.astype(x.dtype).reshape(x.shape[:-1] + (-1,))
                    x = x + brumby.matmul(y, p["wo"])
            with jax.named_scope("mlp"):
                h = brumby.rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
                x = x + brumby.swiglu(h, p["mlp_gate"], p["mlp_up"],
                                      p["mlp_down"])
            return (x, state, norm), None

        (x, state, norm), _ = lax.scan(
            layer_fn, (x, state, norm),
            (params["layers"],
             jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)))
        return x if rows.chunked else x[:, None], (state, norm), None

    @jax.named_scope("lm_head")
    def head(self, params, h):
        h = brumby.rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.dot(h, params["lm_head"].astype(h.dtype).T,
                       preferred_element_type=jnp.float32)


register(BrumbyConfig, RetentionServed)

__all__ = ["RetentionServed"]

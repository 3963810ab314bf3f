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
drafts (``rolls_back`` is False: ``verify`` raises, and ``inference.spec_k``
must be 0).  The attention branch is ``inference/kv_pages.py``'s over this
model's ``full`` class (``group`` = 5 query heads a K/V head as query rows).

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

from . import kv_cache
from .kv_pages import GqaPagedServed, write_and_attend
from .served import (CacheClass, Rows, filter_rows, filter_tile,
                     group_shape, register, stream_pages)
from ..models import falcon_h1 as fh1
from ..models.blocks import rms_norm
from ..models.falcon_h1 import FalconH1Config
from ..ops import ssm_scan

FULL_CLASS, STATE_CLASS = "full", "state"


class FalconH1Served(GqaPagedServed):
    """See the module docstring.  What a model of grouped-query K/V pages
    answers is ``GqaPagedServed``'s; this family's own is the second KIND
    of cache in the same layers."""
    # The scan carries the state from sub-chunk to sub-chunk and every
    # block boundary is one: the program that passes a snapshot's leaves it.
    freezes_in_chunk = True
    rolls_back = False

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
                           ("conv", filter_tile(cfg.mamba_d_conv - 1,
                                                cfg.conv_dim))),
                    num_heads=cfg.mamba_n_heads,
                    head_dim=cfg.mamba_d_state * cfg.mamba_d_head,
                    token_row_bytes=2 * cfg.num_key_value_heads
                    * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize)

    # -- the block ------------------------------------------------------ #
    @jax.named_scope("embed")
    def embed(self, params, tokens, pos):
        x = params["embed"][tokens].astype(jnp.float32) \
            * self.cfg.embedding_multiplier
        return x.astype(self.cfg.dtype)

    def forward(self, params, pools, x, rows: Rows, *, paged_kernel, mesh):
        """``pools``: (k, v, ssm, conv).  ``rows.chunked``: the scan over a
        prefill chunk's K rows, else the decode program's state update (K =
        1)."""
        cfg = self.cfg
        G, Sg, K = rows.positions.shape
        S = G * Sg
        taps = cfg.mamba_d_conv
        pos = rows.positions.reshape(S, K)
        live = rows.live
        pools = list(pools)
        SSM, CONV = 2, 3                    # (after the full class's k, v)
        w_full, w_state = rows.widths
        assert w_state == 1, rows.widths
        full = self.paged_classes(rows, pools, paged_kernel=paged_kernel,
                                  mesh=mesh)[FULL_CLASS]

        # -- the state's page, where it goes back and what a snapshot
        # takes.  The scan's sub-chunk: every block boundary is one of its
        # carried states (a chunk starts at one: the engine's widths are
        # whole blocks).
        q_rows = math.gcd(
            cfg.mamba_chunk_size,
            kv_cache.paged_block_size(pools[0], cfg.head_dim), K)
        page = rows.tables[:, :, w_full].reshape(S)
        sp = stream_pages(page, pos, live, pools[SSM].shape[2], Sg, taps - 1,
                          rows.freeze, scan_rows=q_rows)

        def attention(p, u):
            with jax.named_scope("attn"):
                with jax.named_scope("qkv_proj"):
                    q, k, v = fh1.qkv(p, u, pos, cfg)
                a = write_and_attend(full, pools, q, k, v,
                                     scale=cfg.softmax_scale, mesh=mesh)
                with jax.named_scope("out_proj"):
                    return fh1.scaled_matmul(a, p["wo"],
                                             cfg.attention_out_multiplier)

        def decode_states(x_h, B, C, dt, a, layer):
            """One row a stream: every live page's layer rewritten in
            place."""
            ssm = pools[SSM]
            args = (x_h[:, 0], B[:, 0], C[:, 0], dt[:, 0], jnp.exp(a[:, 0]))
            if paged_kernel:
                y, pools[SSM] = ssm_scan.state_update(
                    ssm, layer, page.reshape(G, Sg),
                    *(group_shape(v, G) for v in args), mesh=mesh)
                return y.reshape((S, 1) + y.shape[2:])
            y, new = ssm_scan.recurrent_update(
                ssm[layer, sp.group, sp.page], *args)
            pools[SSM] = ssm.at[layer, sp.group, sp.to[0]].set(
                new, mode="drop")
            return jnp.where(sp.wrote[:, None, None], y, 0.0)[:, None]

        def chunk_states(x_h, B, C, dt, a, layer):
            """A chunk of rows a stream, from the page's state."""
            ssm = pools[SSM]
            dt = jnp.where(live[..., None], dt, 0.0)
            a = jnp.where(live[..., None], a, 0.0)
            ys = []
            for s in range(S):
                S0 = jnp.where(sp.carried[s],
                               ssm[layer, sp.group[s], sp.page[s]], 0.0)
                y, S1, kept = ssm_scan.chunked_scan(
                    S0, x_h[s], B[s], C[s], dt[s], a[s], chunk=q_rows,
                    keep=None if sp.keep_chunk is None
                    else sp.keep_chunk[s])
                for where, new in zip(sp.to, (S1, kept)):
                    ssm = ssm.at[layer, sp.group[s], where[s]].set(
                        new, mode="drop")
                ys.append(y)
            pools[SSM] = ssm
            return jnp.stack(ys)

        def mixer(p, u, layer):
            with jax.named_scope("ssm"):
                with jax.named_scope("ssm_in_proj"):
                    z, xbc, dt_raw = fh1.ssm_in(p, u, cfg)
                with jax.named_scope("ssm_conv"):
                    rows_in, pools[CONV] = filter_rows(
                        sp, pools[CONV], layer, xbc,
                        paged_kernel=paged_kernel, mesh=mesh)
                    x_h, B, C = fh1.ssm_split(
                        fh1.ssm_conv(p, rows_in, cfg), cfg)
                    dt, a = fh1.ssm_steps(p, dt_raw)
                if not rows.chunked:
                    with jax.named_scope("ssm_state_update"):
                        y = decode_states(x_h, B, C, dt, a, layer)
                else:
                    with jax.named_scope("ssm_chunk_scan"):
                        y = chunk_states(x_h, B, C, dt, a, layer)
                with jax.named_scope("ssm_gate_norm"):
                    y = y + p["D"][:, None] * x_h.astype(jnp.float32)
                    y = fh1.gated_norm(p, y.reshape(S, K, -1), z, cfg,
                                       u.dtype)
                with jax.named_scope("ssm_out_proj"):
                    return fh1.scaled_matmul(y, p["ssm_out"],
                                             cfg.ssm_out_multiplier)

        for layer, p in enumerate(params["layers"]):
            u = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
            x = x + attention(p, u) + mixer(p, u, layer)
            with jax.named_scope("mlp"):
                g = rms_norm(x, p["pre_ff_norm"], cfg.rms_norm_eps)
                x = x + fh1.gated_mlp(p, g, cfg)
        return x, tuple(pools), None

    @jax.named_scope("lm_head")
    def head(self, params, h):
        h = rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.dot(h, params["lm_head"].astype(h.dtype).T,
                       preferred_element_type=jnp.float32) \
            * self.cfg.lm_head_multiplier


register(FalconH1Config, FalconH1Served)

__all__ = ["FalconH1Served", "FULL_CLASS", "STATE_CLASS"]

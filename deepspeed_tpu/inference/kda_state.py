"""The Kimi-Delta-Attention layers of ONE program over their per-stream
pages — what every family with such layers shares whatever its other layers
keep (``inference/kimi_linear.py``: latent rows; ``inference/solar_open2.py``:
K/V pages): the state class's geometry, the pages' bookkeeping, the decode
update in place, the chunked rule from a carried state, and the mixer round
them.

``state_geometry`` answers ``ServedModel.class_geometry`` for the class:
pools ``state`` (a stream's ``S [nh, dk, dv]`` of a layer in FLOAT32 as
``ops.kda`` tiles it) and ``conv`` (the last ``short_conv_kernel_size - 1``
rows of the projected ``[q~ | k~ | v~]`` in the cache's dtype).

``KdaPages`` is built once a program from the two pools, the streams' page
column and the program's ``Rows``; ``mixer(p, x, layer)`` is one KDA layer
(scopes ``attn`` > ``kda_proj``, ``kda_conv``, ``kda_gate``, ``kda_update``
(decode) / ``kda_chunk`` (prefill), ``kda_out``) and leaves the pools in
``.state`` / ``.conv``.  ``decode`` has one row a stream: the delta-rule
update is ``ops.kda.state_update`` on the chip (every live page's layer read
once and written once, in place, with the dependent pass in between), else a
gather, ``ops.kda.recurrent_update`` and a scatter that drops dead slots; the
filters' rows go through their pages the same way (``served.filter_rows``).
A prefill chunk runs the CHUNKED delta rule from the page's state (zeros at
position 0) in sub-chunks of ``scan_rows`` rows — gcd(``KDA_CHUNK``, the
cache's block, the chunk), so that every block boundary of the prompt is one
of the scan's carried states and the chunk that reaches a snapshot's boundary
can write the state as it stood THERE into the snapshot's page as well
(``freezes_in_chunk``); rows past ``last_idx`` neither decay the state nor
write to it.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .served import Rows, filter_rows, filter_tile, group_shape, stream_pages
from ..models import kimi_linear as kl
from ..models.blocks import rms_norm
from ..ops import kda

KDA_CHUNK = 64            # rows a step of the chunked delta rule


def state_geometry(cfg, layers: int, token_bytes: int) -> Dict[str, Any]:
    """The per-stream class of ``layers`` KDA layers as its
    ``PagedKVCacheSpec`` takes it.  ``token_bytes``: what a token keeps in
    the model's OTHER class over all its layers — the blocks a snapshot
    saves prefilling; the class's yardstick (``token_row_bytes``) is a KDA
    layer's share of it."""
    tile = kda.state_tile(cfg.kda_num_heads, cfg.kda_head_dim,
                          cfg.kda_head_dim)
    return dict(pools=(("state", tile, jnp.float32),
                       ("conv", filter_tile(cfg.short_conv_kernel_size - 1,
                                            cfg.conv_dim))),
                num_heads=cfg.kda_num_heads,
                head_dim=cfg.kda_head_dim * cfg.kda_head_dim,
                token_row_bytes=-(-token_bytes // layers))


def scan_rows(block_size: int, K: int) -> int:
    """The chunked rule's sub-chunk for a program of K rows a stream over
    blocks of ``block_size`` (a chunk starts at a block boundary: the
    engine's widths are whole blocks)."""
    return math.gcd(KDA_CHUNK, block_size, K)


class KdaPages:
    """See the module docstring.  ``state`` / ``conv``: the class's pools;
    ``page`` [S]: the streams' page column of the table; ``q_rows``:
    ``scan_rows`` of the program."""

    def __init__(self, cfg, state, conv, page, rows: Rows, *, q_rows: int,
                 paged_kernel: bool, mesh):
        G, Sg, K = rows.positions.shape
        self.cfg, self.state, self.conv = cfg, state, conv
        self.G, self.S, self.q_rows = G, G * Sg, q_rows
        self.page, self.live, self.chunked = page, rows.live, rows.chunked
        self.paged_kernel, self.mesh = paged_kernel, mesh
        # the state's page, where it goes back and what a snapshot takes
        self.sp = stream_pages(
            page, rows.positions.reshape(self.S, K), rows.live,
            state.shape[2], Sg, cfg.short_conv_kernel_size - 1, rows.freeze,
            scan_rows=q_rows)

    def decode_states(self, q, k, v, g, beta, layer):
        """One row a stream: every live page's layer rewritten in place."""
        sp, S, G = self.sp, self.S, self.G
        args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        if self.paged_kernel:
            o, self.state = kda.state_update(
                self.state, layer, self.page.reshape(G, S // G),
                *(group_shape(a, G) for a in args), mesh=self.mesh)
            return o.reshape((S, 1) + o.shape[2:])
        o, new = kda.recurrent_update(self.state[layer, sp.group, sp.page],
                                      *args)
        self.state = self.state.at[layer, sp.group, sp.to[0]].set(
            new, mode="drop")
        return jnp.where(sp.wrote[:, None, None], o, 0.0)[:, None]

    def chunk_states(self, q, k, v, g, beta, layer):
        """A chunk of rows a stream, from the page's state."""
        sp, live = self.sp, self.live
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
        os_ = []
        for s in range(self.S):
            S0 = jnp.where(sp.carried[s],
                           self.state[layer, sp.group[s], sp.page[s]], 0.0)
            o, S1, kept = kda.chunked_delta_rule(
                S0, q[s], k[s], v[s], g[s], beta[s], chunk=self.q_rows,
                keep=None if sp.keep_chunk is None else sp.keep_chunk[s])
            for where, new in zip(sp.to, (S1, kept)):
                self.state = self.state.at[
                    layer, sp.group[s], where[s]].set(new, mode="drop")
            os_.append(o)
        return jnp.stack(os_)

    def mixer(self, p, x, layer):
        """``x + KDA_layer(RMSNorm(x))`` for the class's layer ``layer``."""
        cfg = self.cfg
        with jax.named_scope("attn"):
            with jax.named_scope("kda_proj"):
                u = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
                qkv = kl.kda_in(p, u, cfg)              # [S, K, 3 W]
            with jax.named_scope("kda_conv"):
                rows_in, self.conv = filter_rows(
                    self.sp, self.conv, layer, qkv,
                    paged_kernel=self.paged_kernel, mesh=self.mesh)
                q, k, v = kl.kda_qkv(kl.kda_conv(p, rows_in, cfg), cfg)
            with jax.named_scope("kda_gate"):
                g, beta = kl.kda_gates(p, u, cfg)
            if not self.chunked:
                with jax.named_scope("kda_update"):
                    o = self.decode_states(q, k, v, g, beta, layer)
            else:
                with jax.named_scope("kda_chunk"):
                    o = self.chunk_states(q, k, v, g, beta, layer)
            with jax.named_scope("kda_out"):
                return x + kl.kda_out(p, o, u, cfg)


__all__ = ["KDA_CHUNK", "KdaPages", "scan_rows", "state_geometry"]

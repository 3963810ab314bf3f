"""The ``kimi_linear`` family as a served model (inference/served.py): three
Kimi-Delta-Attention layers, which keep a fixed-size fp32 STATE a stream
(and the last rows of three short filters), to one latent-attention layer,
which keeps a ``[ckv | k_pe]`` row a token — two KINDS of cache in different
layers — then a dense SwiGLU (layer 1) or an expert layer that holds a share
of its experts.

The kinds are declared, not coded for: ``cache_classes`` names ``latent``
(the latent layers; one pool ``latent.latent``, the row every head shares as
``ops.latent_attention`` lays it, unbounded reach) and ``state`` (the KDA
layers, ``per_stream``; pools ``state.state``, a stream's ``S [nh, dk, dv]``
of a layer in FLOAT32 as ``ops.kda`` tiles it, and ``conv.state``, the last
``short_conv_kernel_size - 1`` rows of the projected ``[q~ | k~ | v~]`` in the
cache's dtype), and ``class_geometry`` answers for each.  The engine gives
each class its own pools, block table and allocator behind
``kv_cache.ClassAllocators`` — latent blocks shared by reference, the state
by snapshots, one prefix rule across both, so a HIT is across kinds: latent
blocks up to a boundary AND a state snapshot AT it — and a program gets the
pools class by class and every table row as the classes' rows side by side
(``table_widths``): the latent columns, then the stream's page.

A latent layer is ``inference/latent.py``'s sublayer called on this model's
latent class (``latent_context`` once a program, ``latent_sublayer`` a
layer): absorbed decode and chunk prefill through the same kernels, with no
rotation and a full-rank query (``models/deepseek_v3.latent_projections``).

The KDA layers over their pages are ``inference/kda_state.py``'s (``KdaPages``:
shared with every family that has such layers).  ``decode`` has one row a
stream: the delta-rule update is
``ops.kda.state_update`` on the chip (every live page's layer read once and
written once, in place, with the dependent pass in between), else a gather,
``ops.kda.recurrent_update`` and a scatter that drops dead slots; the
filters' rows go through their pages the same way, in place by
``ops.filter_rows.shift_rows`` where ``served.filter_rows`` finds a tile
the kernel takes (12,288 channels: 96 sublane rows a held row).
``prefill_chunk`` has a chunk of one stream a group: the CHUNKED delta rule
from the page's state (zeros at position 0) in sub-chunks of
gcd(``KDA_CHUNK``, the cache's block, the chunk) rows; rows past
``last_idx`` neither decay the state nor write to it.  Every block boundary
of the prompt is one of the scan's carried states: the model declares
``freezes_in_chunk``, and the chunk that reaches a snapshot's boundary
writes the state as it stood THERE (and the filter rows that end there) into
the snapshot's page as well.  A state cannot be rolled back over rejected
drafts (``rolls_back`` is False: ``verify`` raises, and ``inference.spec_k``
must be 0).

The layers are walked in a static loop (their kinds differ).  Scopes:
``embed``; ``attn`` > ``kda_proj``, ``kda_conv``, ``kda_gate``,
``kda_update`` (decode) / ``kda_chunk`` (prefill), ``kda_out`` in a KDA
layer, ``latent_proj``, ``kv_write``, ``attend`` in a latent one; ``mlp``
(layer 1); ``moe`` > ``router``, ``dispatch``, ``experts``, ``combine``,
``shared``; ``lm_head``.  Each program returns the expert layers' counters
(``LatentServed``'s), which ride the token fetch.  The chunked form bounds no
exponent (every one it forms is a difference that cannot be positive:
``ops/kda.py``), so there is no bound whose bites a counter could count.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from .kda_state import KDA_CHUNK, KdaPages, scan_rows, state_geometry
from .latent import LatentServed, latent_context, latent_sublayer
from .served import CacheClass, Rows, register
from ..models import kimi_linear as kl
from ..models.blocks import rms_norm, swiglu
from ..models.kimi_linear import KDA, KimiLinearConfig
from ..moe import share

LATENT_CLASS, STATE_CLASS = "latent", "state"


class KimiLinearServed(LatentServed):
    """See the module docstring.  What a model of a latent row a token and
    expert layers that hold a share answers is ``LatentServed``'s (the
    latent tile, the attend's dimensions and step counts, the expert
    counters); this family's own is the second KIND of cache, in most of its
    layers."""
    # The chunked delta rule carries the state from sub-chunk to sub-chunk
    # and every block boundary is one: the program that passes a snapshot's
    # leaves it.
    freezes_in_chunk = True
    rolls_back = False

    @property
    def init_fn(self) -> Callable:
        return kl.kimi_linear_init

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        cfg = self.cfg
        return (CacheClass(LATENT_CLASS, cfg.num_latent_layers),
                CacheClass(STATE_CLASS, cfg.num_kda_layers,
                           per_stream=True))

    def class_geometry(self, cls: CacheClass, block_size: int
                       ) -> Dict[str, Any]:
        """``latent``: the row every head shares, in the engine's cache
        dtype.  ``state``: ``state``, the stream's ``S`` of a KDA layer in
        FLOAT32 whatever the cache's dtype, and ``conv``, its filters' rows
        in the cache's.  Its yardstick (``token_row_bytes``, a KDA layer's
        share) is what a token keeps as latent rows in this model's latent
        layers: the blocks a snapshot saves prefilling."""
        if not cls.per_stream:
            return super().class_geometry(cls, block_size)
        cfg = self.cfg
        return state_geometry(
            cfg, cls.layers, cfg.latent_width * cfg.num_latent_layers
            * jnp.dtype(cfg.dtype).itemsize)

    # -- the block ------------------------------------------------------ #
    @jax.named_scope("embed")
    def embed(self, params, tokens, pos):
        return params["embed"].astype(self.cfg.dtype)[tokens]

    def forward(self, params, pools, x, rows: Rows, *, paged_kernel, mesh):
        """``pools``: (latent, state, conv).  ``rows.chunked``: the chunked
        delta rule over a prefill chunk's K rows, else the decode program's
        state update (K = 1)."""
        cfg = self.cfg
        G, Sg, K = rows.positions.shape
        S, H = G * Sg, x.shape[-1]
        pos = rows.positions.reshape(S, K)
        live = rows.live
        latent, state, conv = pools
        w_latent, w_state = rows.widths
        assert w_state == 1, rows.widths
        ctx = latent_context(rows.tables[:, :, :w_latent], rows.positions,
                             live, latent, paged_kernel, mesh)

        # -- the KDA layers' pages (``inference/kda_state.py``).  The scan's
        # sub-chunk: every block boundary is one of its carried states (a
        # chunk starts at one: the engine's widths are whole blocks).
        pages = KdaPages(
            cfg, state, conv, rows.tables[:, :, w_latent].reshape(S), rows,
            q_rows=scan_rows(2 * latent.shape[4], K),
            paged_kernel=paged_kernel, mesh=mesh)

        def latent_mixer(p, x, layer):
            nonlocal latent
            with jax.named_scope("attn"):
                y, latent = latent_sublayer(p, x, pos, latent, layer, ctx,
                                            cfg, mesh)
                with jax.named_scope("latent_proj"):
                    return x + y

        row_live = live.reshape(S * K)
        zero = jnp.zeros((), jnp.int32)
        pairs, most, empty = zero, zero, zero
        at = {KDA: 0, kl.LATENT: 0}
        for l, p in enumerate(params["layers"]):
            kind = cfg.layer_kinds[l]
            x = (pages.mixer if kind == KDA else latent_mixer)(p, x, at[kind])
            at[kind] += 1
            if l < cfg.num_dense_layers:
                with jax.named_scope("mlp"):
                    h = rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
                    x = x + swiglu(h, p["mlp_gate"], p["mlp_up"],
                                   p["mlp_down"])
                continue
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
        return x, (latent, pages.state, pages.conv), (
            pairs, most, empty, row_live.sum().astype(jnp.int32))

    @jax.named_scope("lm_head")
    def head(self, params, h):
        h = rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.dot(h, params["lm_head"].astype(h.dtype).T,
                       preferred_element_type=jnp.float32)


register(KimiLinearConfig, KimiLinearServed)

__all__ = ["KimiLinearServed", "LATENT_CLASS", "STATE_CLASS", "KDA_CHUNK"]

"""The ``minicpm_sala`` family as a served model (inference/served.py): one
layer in four a grouped-query attention that past ``sparse_dense_len`` tokens
reads only the blocks a weight-free selection over POOLED keys chooses a
token and K/V head (``minicpm4``: InfLLM-V2), three in four a Lightning
linear attention whose cache is an fp32 state a stream.

Two classes, declared and not coded for (``cache_classes``):

- ``sparse`` — the ``minicpm4`` layers.  THREE pools: ``k`` and ``v`` (per
  K/V head, ``block_size`` tokens' rows: ``GqaPagedServed``'s tiles) and
  ``ck``, the pooled keys, at ANOTHER RATE: ``block / stride`` rows a block
  and K/V head beside the block's ``block`` K and V rows.  A pool at another
  rate is just another entry of ``cache_pools`` with its own tile: the cache
  manager allocates, shares, copies on write and frees a block's tile of
  every pool of the class together.  What makes that SAFE here is where a
  pooled key is put: in the block where its window ENDS (``ops/sparse_select
  .py``), so a block's ``ck`` rows depend on nothing after the block's end.
  The engine's ``block_size`` must be the model's ``sparse_block_size``: it
  is the unit of selection.
- ``state`` — the Lightning layers, ``per_stream``: pool ``state``, ``S [nh,
  d, d]`` float32 a layer (``ops/ssm_scan.py``'s tile: the recurrence is
  its ``S = da S + B (x) dt x``, ``y = S C`` with every head its own group,
  ``dt = 1`` and ``da`` the head's constant).  Snapshots are the prefix
  cache; the chunk that passes a snapshot's boundary leaves it
  (``freezes_in_chunk``); no roll-back (``spec_k`` 0).

A sparse layer, decode rows and a chunk's rows alike: the new K/V rows
written in place (scope ``kv_write``), the pooled keys whose windows end at
these rows (``ck_write``), the selection (``select``: pool block ids a row
and K/V head, ``[rows, nKV, chosen_width]``), and the attend over the chosen
blocks only (``attend_sparse``): ``ops.paged_attention``'s kernel under the
per-K/V-head plan — every (row, K/V head) a stream of the kernel, the
head's 16 query heads its query rows — or the gather below off the chip.

Scopes: ``embed``; ``attn`` > ``qkv_proj``, ``kv_write``, ``ck_write``,
``select``, ``attend_sparse``, ``out_proj`` (a sparse layer) / ``la_proj``,
``la_state_update`` (decode) / ``la_chunk`` (prefill), ``la_gate_norm``,
``la_out`` (a Lightning layer); ``mlp``; ``lm_head``.  Counters (they ride
the token fetch): ``sparse_blocks_read`` and ``sparse_blocks_in_reach`` (per
live row, sparse layer and K/V head: blocks the attend walked / blocks a
dense attend would), ``ck_rows_scored`` (pooled rows the selection scored),
``ck_blocks_read`` (blocks of pooled keys the selection gathered to score
them, a sparse layer and K/V head: a sharing group's table row once a tile
of its streams where a decode step's tables share, every stream's table
width where not).
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import kv_cache
from .kv_pages import GqaPagedServed, paged_classes
from .served import (NEG_INF, CacheClass, Rows, group_shape, register,
                     stream_pages)
from ..models import minicpm_sala as sala
from ..models.blocks import matmul, rms_norm
from ..models.minicpm_sala import MinicpmSalaConfig
from ..ops import paged_attention as paged_attn_ops
from ..ops import sparse_select, ssm_scan

SPARSE_CLASS, STATE_CLASS = "sparse", "state"


def gather_attend_heads(q, pool_k, pool_v, layer, chosen, count, fill,
                        scale):
    """The per-K/V-head attend without the kernel (off-TPU path and the
    kernel's reference).  q [G, Q, 1, nH, D]; chosen [G, Q, nKV, J] the
    blocks each head of a stream walks, ``count`` [G, Q, nKV] of them live,
    the last one filled up to offset ``fill`` [G, Q, 1] (-1: nothing)."""
    G, Q, _, nH, D = q.shape
    nKV, J = chosen.shape[2:]
    kl = kv_cache.paged_layer_view(pool_k, layer, D)     # [G, B, nKV, bs, D]
    vl = kv_cache.paged_layer_view(pool_v, layer, D)
    bs = kl.shape[3]
    g = jnp.arange(G)[:, None, None, None]
    h = jnp.arange(nKV)[None, None, :, None]
    kb = kl[g, jnp.maximum(chosen, 0), h]            # [G, Q, nKV, J, bs, D]
    vb = vl[g, jnp.maximum(chosen, 0), h]
    j = jnp.arange(J)[None, None, None, :, None]
    t = jnp.arange(bs)[None, None, None, None, :]
    last = count[..., None, None] - 1
    ok = (j < last) | ((j == last) & (t <= fill[:, :, :, None, None]))
    qg = q.reshape(G, Q, nKV, nH // nKV, D)
    s = jnp.einsum("gqnmd,gqnjtd->gqnmjt", qg, kb,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, :, :, None], s, NEG_INF)
    w = jax.nn.softmax(s.reshape(s.shape[:4] + (J * bs,)), axis=-1)
    w = jnp.where(ok.any(axis=(-1, -2))[..., None, None], w, 0.0)
    out = jnp.einsum("gqnmjt,gqnjtd->gqnmd",
                     w.reshape(s.shape).astype(vb.dtype), vb,
                     preferred_element_type=jnp.float32)
    return out.reshape(G, Q, 1, nH, D).astype(q.dtype)


class MinicpmSalaServed(GqaPagedServed):
    """See the module docstring.  What a model of grouped-query K/V pages
    answers is ``GqaPagedServed``'s (over the ``sparse`` class); this
    family's own are the pooled keys, the selection, the per-head attend and
    the Lightning state."""
    counter_names = ("sparse_blocks_read", "sparse_blocks_in_reach",
                     "ck_rows_scored", "ck_blocks_read")
    # What the selection chose INSIDE the program, a row: pool block ids
    # ``[sparse layers, nKV, chosen_width]`` and how many of them are live
    # ``[sparse layers, nKV]`` (a check holds them to the reference's sets).
    probe_names = ("sparse_chosen", "sparse_count")
    freezes_in_chunk = True
    rolls_back = False

    @property
    def init_fn(self) -> Callable:
        return sala.minicpm_sala_init

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        cfg = self.cfg
        return (CacheClass(SPARSE_CLASS, len(cfg.sparse_layers)),
                CacheClass(STATE_CLASS, len(cfg.lightning_layers),
                           per_stream=True))

    def class_geometry(self, cls: CacheClass, block_size: int
                       ) -> Dict[str, Any]:
        cfg = self.cfg
        if cls.per_stream:
            d = cfg.lightning_head_dim
            return dict(pools=(("state", ssm_scan.state_tile(
                cfg.lightning_nh, d, d), jnp.float32),),
                num_heads=cfg.lightning_nh, head_dim=d * d,
                token_row_bytes=2 * cfg.num_key_value_heads * cfg.head_dim
                * jnp.dtype(cfg.dtype).itemsize)
        if block_size != cfg.sparse_block_size:
            raise ValueError(
                f"inference.block_size={block_size}: this model selects "
                f"blocks of sparse_block_size={cfg.sparse_block_size} keys, "
                "and a cache block is that unit")
        geometry = super().class_geometry(cls, block_size)
        ck = (cfg.num_key_value_heads, cfg.pooled_a_block, cfg.head_dim)
        return dict(geometry, pools=geometry["pools"] + (("ck", ck),))

    def attend_bytes(self, keys: int, block_size: int, itemsize: int) -> int:
        """K and V rows of the blocks a query reads: ``sparse_topk`` of
        them past ``sparse_dense_len`` keys."""
        cfg = self.cfg
        if keys > cfg.sparse_dense_len:
            keys = cfg.sparse_topk * cfg.sparse_block_size
        return 2 * cfg.num_key_value_heads * cfg.head_dim * int(keys) \
            * int(itemsize)

    def attend_flops(self, keys: int) -> int:
        cfg = self.cfg
        if keys > cfg.sparse_dense_len:
            keys = cfg.sparse_topk * cfg.sparse_block_size
        return super().attend_flops(keys)

    def attend_step_counts(self, live_blocks, *, K, spec, mp, q_itemsize,
                           calls=1):
        """A (row, K/V head) is a step of at most ``chosen_width`` slots."""
        cfg = self.cfg
        walked = np.minimum(np.asarray(live_blocks), cfg.chosen_width)
        heads = np.repeat(walked, cfg.num_key_value_heads * K)
        return paged_attn_ops.attend_step_counts(
            heads, K=cfg.group, num_heads=1, head_dim=spec.head_dim,
            block_size=spec.block_size, table_width=cfg.chosen_width,
            kv_itemsize=int(jnp.dtype(spec.dtype).itemsize),
            q_itemsize=q_itemsize) + (
                paged_attn_ops.attend_cold_steps(heads, calls=calls),)

    def counter_args(self, rows) -> Dict[str, Any]:
        read, reach = int(rows[:, 0].sum()), int(rows[:, 1].sum())
        return {"sparse_blocks_read": read, "sparse_blocks_in_reach": reach,
                "ck_rows_scored": int(rows[:, 2].sum()),
                "ck_blocks_read": int(rows[:, 3].sum()),
                "sparse_read_share": read / reach if reach else 0.0}

    # -- the block ------------------------------------------------------ #
    @jax.named_scope("embed")
    def embed(self, params, tokens, pos):
        x = params["embed"][tokens].astype(jnp.float32) * self.cfg.scale_emb
        return x.astype(self.cfg.dtype)

    def forward(self, params, pools, x, rows: Rows, *, paged_kernel, mesh):
        """``pools``: (k, v, ck, state)."""
        cfg = self.cfg
        sz = sparse_select.Sizes.of(cfg)
        G, Sg, K = rows.positions.shape
        S = G * Sg
        pos = rows.positions.reshape(S, K)
        live = rows.live
        pools = list(pools)
        KP, VP, CK, STATE = 0, 1, 2, 3
        w_sparse, w_state = rows.widths
        assert w_state == 1, rows.widths
        c = cfg.residual_scale
        D, nKV = cfg.head_dim, cfg.num_key_value_heads
        table = rows.tables[:, :, :w_sparse]                 # [G, Sg, W]
        # the K/V rows' write targets (no plan: the attend's is per head)
        targets = paged_classes(
            self.cache_classes, rows, pools, head_dim=D, group=cfg.group,
            paged_kernel=False, mesh=mesh)[SPARSE_CLASS]
        # a row's offset in its own (the newest) block; -1: attends nothing
        fill = jnp.where(live, pos % sz.block, -1).reshape(G, Sg * K, 1)
        newest = jnp.where(live, pos // sz.block + 1, 0)
        seen = jnp.where(live, jnp.maximum((pos + 1) // sz.stride - 1, 0), 0)
        n_sparse = len(cfg.sparse_layers)
        counters = [jnp.zeros((), jnp.int32),
                    (newest.sum() * nKV * n_sparse).astype(jnp.int32),
                    (seen.sum() * nKV * n_sparse).astype(jnp.int32),
                    jnp.zeros((), jnp.int32)]

        q_rows = math.gcd(sz.block, K)
        page = rows.tables[:, :, w_sparse].reshape(S)
        sp = stream_pages(page, pos, live, pools[STATE].shape[2], Sg, 0,
                          rows.freeze, scan_rows=q_rows)
        lam = jnp.asarray(sala.decay(cfg), jnp.float32)          # [nh]
        picked = []             # (chosen, count) a sparse layer: the probes

        def sparse_mixer(p, u, layer):
            with jax.named_scope("qkv_proj"):
                q, k, v = sala.sparse_qkv(p, u, cfg)
            with jax.named_scope("kv_write"):
                pools[KP], pools[VP] = kv_cache.paged_write_rows(
                    pools[KP], pools[VP],
                    k.reshape((G, Sg * K) + k.shape[2:]),
                    v.reshape((G, Sg * K) + v.shape[2:]), layer,
                    targets["blk"], targets["off"], mesh=mesh,
                    stream_rows=targets["stream_rows"],
                    one_block=targets["one_block"])
            with jax.named_scope("ck_write"):
                if rows.chunked:
                    n_live = live.sum(axis=1).astype(jnp.int32)
                    pools[CK] = sparse_select.write_pooled_chunk(
                        pools[CK], pools[KP], layer, k, table[:, 0],
                        pos[:, 0], n_live - 1, (n_live > 0), sz)
                else:
                    for i in range(K):
                        pools[CK] = sparse_select.write_pooled_rows(
                            pools[CK], pools[KP], layer, table,
                            pos[:, i].reshape(G, Sg),
                            live[:, i].reshape(G, Sg), sz)
            with jax.named_scope("select"):
                chosen, count, read = sparse_select.select_blocks_counted(
                    q, pools[CK], layer, table.reshape(S, w_sparse), pos,
                    live, sz, cfg.softmax_scale)
                counters[0] = counters[0] + count.sum().astype(jnp.int32)
                counters[3] = counters[3] + read
                picked.append((chosen, count))
            with jax.named_scope("attend_sparse"):
                width = chosen.shape[-1]
                chosen = chosen.reshape(G, Sg * K, nKV, width)
                count = count.reshape(G, Sg * K, nKV)
                qr = q.reshape(G, Sg * K, 1, cfg.num_attention_heads, D)
                if paged_kernel:
                    plan = paged_attn_ops.attend_plan(
                        chosen, fill, pools[KP], D, mesh=mesh,
                        group=cfg.group, count=count)
                    a = paged_attn_ops.paged_attention(
                        qr, pools[KP], pools[VP], layer, plan=plan,
                        scale=cfg.softmax_scale, mesh=mesh)
                else:
                    a = gather_attend_heads(
                        qr, pools[KP], pools[VP], layer, chosen, count, fill,
                        cfg.softmax_scale)
            with jax.named_scope("out_proj"):
                return sala.gated_out(p, a.reshape(S, K, -1), u)

        def decode_states(q, k, v, layer):
            """One row a stream: every live page's layer rewritten in
            place."""
            state = pools[STATE]
            one = jnp.ones((S, cfg.lightning_nh), jnp.float32)
            args = (v[:, 0], k[:, 0], q[:, 0], one, one * lam[None])
            if paged_kernel:
                y, pools[STATE] = ssm_scan.state_update(
                    state, layer, page.reshape(G, Sg),
                    *(group_shape(a, G) for a in args), mesh=mesh)
                return y.reshape((S, 1) + y.shape[2:])
            y, new = ssm_scan.recurrent_update(
                state[layer, sp.group, sp.page], *args)
            pools[STATE] = state.at[layer, sp.group, sp.to[0]].set(
                new, mode="drop")
            return jnp.where(sp.wrote[:, None, None], y, 0.0)[:, None]

        def chunk_states(q, k, v, layer):
            """A chunk of rows a stream, from the page's state."""
            state = pools[STATE]
            dt = live.astype(jnp.float32)[..., None] \
                * jnp.ones((cfg.lightning_nh,), jnp.float32)
            a = dt * jnp.log(lam)
            ys = []
            for s in range(S):
                S0 = jnp.where(sp.carried[s],
                               state[layer, sp.group[s], sp.page[s]], 0.0)
                y, S1, kept = ssm_scan.chunked_scan(
                    S0, v[s], k[s], q[s], dt[s], a[s], chunk=q_rows,
                    keep=None if sp.keep_chunk is None
                    else sp.keep_chunk[s])
                for where, new in zip(sp.to, (S1, kept)):
                    state = state.at[layer, sp.group[s], where[s]].set(
                        new, mode="drop")
                ys.append(y)
            pools[STATE] = state
            return jnp.stack(ys)

        def lightning_mixer(p, u, layer):
            with jax.named_scope("la_proj"):
                q, k, v = sala.lightning_qkv(p, u, pos, cfg)
            if not rows.chunked:
                with jax.named_scope("la_state_update"):
                    y = decode_states(q, k, v, layer)
            else:
                with jax.named_scope("la_chunk"):
                    y = chunk_states(q, k, v, layer)
            with jax.named_scope("la_gate_norm"):
                o = rms_norm(y * cfg.lightning_scale, p["o_norm"],
                             cfg.rms_norm_eps)
                gate = jax.nn.sigmoid(jnp.dot(
                    u, p["wg"].astype(u.dtype),
                    preferred_element_type=jnp.float32))
                o = (o.reshape(S, K, -1) * gate).astype(u.dtype)
            with jax.named_scope("la_out"):
                return matmul(o, p["wo"])

        at = {SPARSE_CLASS: 0, STATE_CLASS: 0}
        for p, kind in zip(params["layers"], cfg.mixer_types):
            with jax.named_scope("attn"):
                u = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
                cls = SPARSE_CLASS if kind == sala.SPARSE else STATE_CLASS
                mixer = sparse_mixer if kind == sala.SPARSE \
                    else lightning_mixer
                y = mixer(p, u, at[cls])
                at[cls] += 1
                x = (x.astype(jnp.float32)
                     + c * y.astype(jnp.float32)).astype(x.dtype)
            with jax.named_scope("mlp"):
                z = rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
                x = (x.astype(jnp.float32)
                     + c * sala.mlp(p, z).astype(jnp.float32)).astype(x.dtype)
        probes = tuple(jnp.stack(a, axis=2).astype(jnp.int32)
                       for a in zip(*picked))
        return x, tuple(pools), tuple(counters), probes

    @jax.named_scope("lm_head")
    def head(self, params, h):
        cfg = self.cfg
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        logits = jnp.dot(h, params["lm_head"].astype(h.dtype).T,
                         preferred_element_type=jnp.float32) \
            * cfg.logit_scale
        if cfg.vocab_rows == cfg.vocab_size:
            return logits
        # Padding rows of the held vocabulary are no tokens: never sampled.
        ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        return jnp.where(ids < cfg.vocab_size, logits, NEG_INF)


register(MinicpmSalaConfig, MinicpmSalaServed)

__all__ = ["MinicpmSalaServed", "SPARSE_CLASS", "STATE_CLASS",
           "gather_attend_heads"]

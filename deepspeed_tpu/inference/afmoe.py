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
the window class, a plan that walks only the blocks in reach.  A prefill
chunk is split into runs of query rows (``_attend_rows``), each a stream of
the attend with the chunk's table, so that a K/V head's query rows fit the
kernel's VMEM budget and a run walks only ITS reach.

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
from jax import lax

from . import kv_cache
from .served import (NEG_INF, CacheClass, ServedModel, group_shape,
                     register)
from ..models import afmoe
from ..models.afmoe import AfmoeConfig, SLIDING
from ..models.blocks import matmul, rms_norm, swiglu
from ..moe import share
from ..ops import paged_attention as paged_attn_ops

FULL_CLASS, WINDOW_CLASS = "full", "window"
# Query rows a K/V head takes in one step of the attend kernel at most
# (``group`` heads x the rows of a run): what keeps a step's fp32 state
# inside ``ops.paged_attention._VMEM_BUDGET`` at head_dim 128.
_MAX_HEAD_ROWS = 512


def _attend_rows(K: int, group: int) -> int:
    """Query rows a run of a chunk of K rows holds."""
    rows = K
    while group * rows > _MAX_HEAD_ROWS and rows % 2 == 0:
        rows //= 2
    return rows


def _gather_attend(q, pool_k, pool_v, layer, bt, pos, reach, scale):
    """The attend without the kernel (off-TPU path and the kernel's
    reference): the table's blocks gathered, a mask from positions.
    q [G, Q, K, nH, D]; the stacked pools as held; bt [G, Q, J]; pos
    [G, Q, K] (-1: a row that attends nothing); ``reach``: the table is a
    window's ring (slot c holds the newest logical block congruent to c
    that the stream has reached)."""
    G, Q, K, nH, D = q.shape
    J = bt.shape[-1]
    kl = kv_cache.paged_layer_view(pool_k, layer, D)     # [G, B, nKV, bs, D]
    vl = kv_cache.paged_layer_view(pool_v, layer, D)
    nKV, bs = kl.shape[2], kl.shape[3]
    take = jax.vmap(lambda rows, idx: rows[idx])
    kb = take(kl, jnp.maximum(bt, 0))                # [G, Q, J, nKV, bs, D]
    vb = take(vl, jnp.maximum(bt, 0))
    slot = jnp.arange(J, dtype=jnp.int32)
    if reach is None:
        block = jnp.broadcast_to(slot, bt.shape)
    else:
        last = jnp.max(pos, axis=2, keepdims=True) // bs        # [G, Q, 1]
        block = last - (last - slot) % J
    kp = block[..., None] * bs + jnp.arange(bs, dtype=jnp.int32)  # [G,Q,J,bs]
    ok = (bt >= 0)[..., None] & (kp >= 0)
    ok = ok[:, :, None] & (kp[:, :, None] <= pos[..., None, None])
    if reach is not None:
        ok = ok & (kp[:, :, None] > pos[..., None, None] - reach)
    qg = q.reshape(G, Q, K, nKV, nH // nKV, D)
    s = jnp.einsum("gqknmd,gqjntd->gqknmjt", qg, kb,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, :, :, None, None], s, NEG_INF)
    w = jax.nn.softmax(s.reshape(s.shape[:5] + (J * bs,)), axis=-1)
    # A row with nothing to attend emits zeros, as the kernel does.
    w = jnp.where(ok.any(axis=(-1, -2))[:, :, :, None, None, None], w, 0.0)
    out = jnp.einsum("gqknmjt,gqjntd->gqknmd",
                     w.reshape(s.shape).astype(vb.dtype), vb,
                     preferred_element_type=jnp.float32)
    return out.reshape(G, Q, K, nH, D).astype(q.dtype)


def paged_classes(classes, widths, pools, bt_g, pos_g, live_g, *,
                  head_dim: int, group: int, paged_kernel: bool, mesh):
    """The attention branch's tables, write targets and plans, ONCE for
    all layers of each class of K/V pages: ``classes`` (``CacheClass``es
    in ``cache_classes`` order, each with a (k, v) pair in ``pools``) whose
    table rows lie side by side in bt_g [G, Sg, W], ``widths`` wide; row
    positions pos_g [G, Sg, K]; ``live_g`` [G, Sg, K] (a dead row writes
    nothing and attends nothing).  A chunk's rows go in runs
    (``_attend_rows``), each a stream of the attend with the chunk's table.
    Returns {class name: what ``write_and_attend`` takes}."""
    G, Sg, K = pos_g.shape
    seen = jnp.where(live_g, pos_g, -1)        # a dead row attends nothing
    rows = _attend_rows(K, group)
    runs = K // rows
    out, at = {}, 0
    for i, (cls, width) in enumerate(zip(classes, widths)):
        bt = bt_g[:, :, at:at + width]
        at += width
        kc = pools[2 * i]
        bs = kv_cache.paged_block_size(kc, head_dim)
        table = jnp.broadcast_to(bt[:, :, None, :], (G, Sg, K, width))
        blk, off = kv_cache.positions_to_blocks(
            table, pos_g, bs, ring=cls.reach is not None)
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
                    bt_runs, seen_runs, kc, head_dim, mesh=mesh,
                    reach=cls.reach, group=group)
        out[cls.name] = dict(
            name=cls.name, at=2 * i, reach=cls.reach, plan=plan,
            bt=bt_runs, seen=seen_runs, blk=blk.reshape(G, Sg * K),
            off=off.reshape(G, Sg * K), rows=rows, runs=runs, layer=0)
    return out


def write_and_attend(c, pools, q, k, v, *, scale: float, mesh):
    """The next layer of class ``c`` (one of ``paged_classes``'): its new
    K/V rows written in place into ``pools`` (a list; scope ``kv_write``),
    then the attend of q [S, K, nH, D] over the class's pages (scope
    ``attend_<class>``: the kernel under its plan, or the gather).
    Returns the attended rows [S, K, nH * D]."""
    S, K, nH, D = q.shape
    G = c["blk"].shape[0]
    Sg = S // G
    kc, vc = pools[c["at"]], pools[c["at"] + 1]
    layer = c["layer"]
    c["layer"] += 1
    with jax.named_scope("kv_write"):
        kc, vc = kv_cache.paged_write_rows(
            kc, vc, k.reshape((G, Sg * K) + k.shape[2:]),
            v.reshape((G, Sg * K) + v.shape[2:]), layer,
            c["blk"], c["off"], mesh=mesh)
    with jax.named_scope("attend_" + c["name"]):
        qr = q.reshape(G, Sg * c["runs"], c["rows"], nH, D)
        if c["plan"] is not None:
            a = paged_attn_ops.paged_attention(
                qr, kc, vc, layer, plan=c["plan"], scale=scale, mesh=mesh)
        else:
            a = _gather_attend(qr, kc, vc, layer, c["bt"], c["seen"],
                               c["reach"], scale)
    pools[c["at"]], pools[c["at"] + 1] = kc, vc
    return a.reshape(S, K, nH * D)


def _forward(params, pools, x, bt_g, pos_g, live, cfg: AfmoeConfig,
             widths, paged_kernel: bool, mesh):
    """All layers: x [S, K, H] with its streams' table rows bt_g [G, Sg,
    W] (the classes' rows side by side, ``widths`` wide), row positions
    pos_g [G, Sg, K] and ``live`` [S, K]: the rows that are traffic (a
    live stream's, and no padding).  The others write no cache row, attend
    nothing, get no expert row and are not counted; what they compute
    nobody reads.  ``pools``: (k, v) of every class in ``cache_classes``
    order.  Returns (x', pools', counters)."""
    G, Sg, K = pos_g.shape
    S, H = G * Sg, x.shape[-1]
    pos = pos_g.reshape(S, K)
    pools = list(pools)
    classes = paged_classes(
        _classes(cfg), widths, pools, bt_g, pos_g, live.reshape(G, Sg, K),
        head_dim=cfg.head_dim, group=cfg.group, paged_kernel=paged_kernel,
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
                a = (a.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(x.dtype)
                x = x + rms_norm(matmul(a, p["wo"]), p["post_attn_norm"],
                                 cfg.rms_norm_eps)
        return x

    row_live = live.reshape(S * K)
    zero = jnp.zeros((), jnp.int32)
    pairs, most, empty = zero, zero, zero
    for i, p in enumerate(params["layers"]):
        x = attention(p, x, cfg.layer_types[i] == SLIDING)
        if i < cfg.num_dense_layers:
            with jax.named_scope("mlp"):
                h = rms_norm(x, p["pre_mlp_norm"], cfg.rms_norm_eps)
                y = swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
                x = x + rms_norm(y, p["post_mlp_norm"], cfg.rms_norm_eps)
            continue
        with jax.named_scope("moe"):
            h = rms_norm(x, p["pre_mlp_norm"], cfg.rms_norm_eps)
            # ``paged_kernel`` is "this path runs its Pallas kernels": the
            # attend, the row write and the grouped expert product alike.
            y, counts = share.expert_layer(
                p, h.reshape(S * K, H), cfg.routing, kernel=paged_kernel,
                row_live=row_live)
            x = x + rms_norm(y.reshape(S, K, H), p["post_mlp_norm"],
                             cfg.rms_norm_eps)
        pairs = pairs + counts.sum()
        most = jnp.maximum(most, counts.max())
        empty = empty + (counts == 0).sum()
    return x, tuple(pools), (pairs, most, empty,
                             row_live.sum().astype(jnp.int32))


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


@jax.named_scope("lm_head")
def _head(params, h, cfg):
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(h, params["lm_head"].astype(h.dtype).T,
                   preferred_element_type=jnp.float32)


@jax.named_scope("embed")
def _embed(params, tokens, cfg):
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.mup_enabled:
        x = (x.astype(jnp.float32) * cfg.hidden_size ** 0.5).astype(x.dtype)
    return x


class GqaPagedServed(ServedModel):
    """What a model of grouped-query K/V pages answers whatever else its
    layers hold (experts, a conv state, a state-space mixer): the K/V
    tiles, the attend's dimensions and step counts (``group`` query heads a
    K/V head as query rows).  ``cfg`` names ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``group``, ``num_hidden_layers``
    and ``max_position_embeddings``."""
    @property
    def max_positions(self) -> int:
        return int(self.cfg.max_position_embeddings)

    @property
    def cache_layers(self) -> int:
        return int(self.cfg.num_hidden_layers)

    @property
    def cache_heads(self) -> int:
        return int(self.cfg.num_key_value_heads)

    @property
    def cache_row_width(self) -> int:
        return int(self.cfg.head_dim)

    def cache_pools(self, block_size: int):
        D = self.cache_row_width
        f = kv_cache.kv_fold(D, block_size)
        tile = (self.cache_heads, block_size // f, f * D)
        return (("k", tile), ("v", tile))

    @property
    def attend_dims(self) -> Tuple[int, int, int]:
        return (self.cfg.num_attention_heads, self.cfg.head_dim,
                self.cfg.head_dim)

    def attend_step_counts(self, live_blocks, *, K, spec, mp, q_itemsize,
                           calls=1):
        """Of a layer of the FIRST class (``spec``), the K/V head's
        ``group * K`` query rows as the kernel takes them."""
        return paged_attn_ops.attend_step_counts(
            live_blocks, K=self.cfg.group * _attend_rows(K, self.cfg.group),
            num_heads=max(1, spec.num_heads // mp), head_dim=spec.head_dim,
            block_size=spec.block_size,
            table_width=spec.max_blocks_per_slot,
            kv_itemsize=int(jnp.dtype(spec.dtype).itemsize),
            q_itemsize=q_itemsize) + (
                paged_attn_ops.attend_cold_steps(live_blocks, calls=calls),)


class AfmoeServed(GqaPagedServed):
    """See the module docstring.  The three programs are written over
    ``_embed`` / ``_forward`` / ``_head``: a family of the same two classes
    and counters whose BLOCK differs (``inference/smallthinker.py``) names
    its own."""
    counter_names = ("moe_held_pairs", "moe_held_max", "moe_held_empty",
                     "moe_rows")
    _embed = staticmethod(_embed)
    _forward = staticmethod(_forward)
    _head = staticmethod(_head)

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
        pairs = int(rows[:, 0].sum())
        cells = len(rows) * cfg.num_moe_layers * cfg.num_experts
        routed = int(rows[:, 3].sum()) * cfg.num_experts_per_tok \
            * cfg.num_moe_layers
        return {"moe_held_pairs": pairs,
                "moe_held_max": int(rows[:, 1].max()),
                "moe_held_mean": pairs / cells if cells else 0.0,
                "moe_held_empty": int(rows[:, 2].sum()),
                "moe_held_pair_share": pairs / routed if routed else 0.0}

    # -- programs ------------------------------------------------------ #
    def verify(self, params, pools, tokens, lengths, block_tables, *,
               num_groups, paged_kernel, mesh=None):
        cfg = self.cfg
        K = tokens.shape[1]
        pos = lengths[:, None] + jnp.arange(K, dtype=jnp.int32)[None]
        live = jnp.broadcast_to(
            (block_tables >= 0).any(axis=1, keepdims=True), tokens.shape)
        x, pools, counters = self._forward(
            params, pools, self._embed(params, tokens, cfg),
            group_shape(block_tables, num_groups),
            group_shape(pos, num_groups), live, cfg,
            self._widths(block_tables), paged_kernel, mesh)
        return self._head(params, x, cfg), pools, counters

    def decode(self, params, pools, tokens, lengths, block_tables, *,
               num_groups, paged_kernel, mesh=None):
        logits, pools, counters = self.verify(
            params, pools, tokens[:, None], lengths, block_tables,
            num_groups=num_groups, paged_kernel=paged_kernel, mesh=mesh)
        return logits[:, 0], pools, counters

    def prefill_chunk(self, params, pools, tokens, bt_rows, start,
                      last_idx, active, *, paged_kernel, mesh=None):
        """``decode.gpt2_prefill_chunk_paged``'s contract; rows past
        ``last_idx`` (a last chunk's padding) are dead rows.  A window-class
        row of the chunk reaches ``reach + chunk - 1`` rows back at most:
        what the ring is sized for."""
        cfg = self.cfg
        G, Cn = tokens.shape
        pos = start[:, None] + jnp.arange(Cn, dtype=jnp.int32)[None]
        bt_g = jnp.where(active[:, None, None] > 0, bt_rows[:, None],
                         kv_cache.DEAD_BLOCK)
        live = (active[:, None] > 0) & (lax.broadcasted_iota(
            jnp.int32, (G, Cn), 1) <= last_idx[:, None])
        x, pools, counters = self._forward(
            params, pools, self._embed(params, tokens, cfg), bt_g,
            pos[:, None, :], live, cfg, self._widths(bt_rows), paged_kernel,
            mesh)
        oh = (lax.broadcasted_iota(jnp.int32, (G, Cn), 1)
              == last_idx[:, None]).astype(x.dtype)
        h_last = jnp.einsum("gc,gch->gh", oh, x)
        return h_last, pools, counters

    def head(self, params, h):
        return self._head(params, h, self.cfg)


register(AfmoeConfig, AfmoeServed)

__all__ = ["GqaPagedServed", "AfmoeServed", "FULL_CLASS", "WINDOW_CLASS",
           "paged_classes", "write_and_attend"]

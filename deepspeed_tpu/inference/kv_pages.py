"""The attention branch over per-head K/V pages of grouped query heads —
ONE place for every family that keeps such pages, whatever else its layers
hold (experts, a conv state, a state-space mixer): the afmoe, smallthinker,
lfm2 and falcon-h1 families.  (GPT-2's block keeps ``kv_cache.paged_attend``,
the one-hot reference ``tests/test_paged_kernel.py`` holds the kernel to;
the latent family's sublayer is ``inference/latent.py``'s.)

``GqaPagedServed`` answers what such a model is asked about its K/V pages
(the tiles, the attend's dimensions and step counts).  Its ``forward`` calls
``paged_classes`` once a program — every K/V class's table slice, write
targets, runs of query rows and the kernel's plan, for all layers of the
class — and ``write_and_attend`` once a layer: the new rows written in place
(scope ``kv_write``), then the attend over the class's pages (scope
``attend_<class>``), ``ops.paged_attention``'s kernel under its plan or the
gather below; a family with an output gate puts ``output_gate`` on what
comes back.  A class may be a window's RING (``CacheClass.reach``); a
``per_stream`` class among a model's classes is its owner's business and is
passed over.  A prefill chunk is split into runs of query rows
(``attend_rows``), each a stream of the attend with the chunk's table, so
that a K/V head's query rows and their fp32 state fit the kernel's VMEM
beside four heads' tiles (``ops.paged_attention._tile_rule``); a run walks
only ITS reach.  A run of ``_DENSE_ROWS`` query rows a K/V head or more
takes the attend's chunk-shaped body (``_pattn_chunk_kernel``: bound by its
products, so that the runs re-read the reach costs nothing that shows —
cell 14 reads it seven times, 3 ms of bandwidth under 7 ms of products);
decode and verify keep ``_pattn_kernel``.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import kv_cache
from .served import NEG_INF, CacheClass, Rows, ServedModel
from ..ops import paged_attention as paged_attn_ops

# Query rows a K/V head takes in one step of the attend kernel at most
# (``group`` heads x the rows of a run): what keeps the fp32 state of four
# heads a step inside ``ops.paged_attention._CHUNK_VMEM_BUDGET`` at head_dim
# 128.  On the v5e at cell 14's shape a chunk's attend reads 7.35 ms in
# runs of 512 rows a K/V head (four heads a step) and 7.35 in runs of 1,024
# (two): the longer run buys nothing (PERF.md section 6, PR 65).
MAX_HEAD_ROWS = 512


def attend_rows(K: int, group: int) -> int:
    """Query rows a run of a chunk of K rows holds."""
    rows = K
    while group * rows > MAX_HEAD_ROWS and rows % 2 == 0:
        rows //= 2
    return rows


def gather_attend(q, pool_k, pool_v, layer, bt, pos, reach, scale):
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


def paged_classes(classes: Sequence[CacheClass], rows: Rows, pools, *,
                  head_dim: int, group: int, paged_kernel: bool, mesh
                  ) -> Dict[str, dict]:
    """The attention branch's tables, write targets and plans, ONCE for
    all layers of each class of K/V pages: ``classes`` (a model's
    ``cache_classes``, whose table rows lie side by side in ``rows.tables``,
    ``rows.widths`` wide), the K/V classes first, each with a (k, v) pair in
    ``pools`` in that order; a ``per_stream`` class is passed over.  A dead
    row (``rows.live``) writes nothing and attends nothing.  A chunk's rows
    go in runs (``attend_rows``), each a stream of the attend with the
    chunk's table.  Returns {class name: what ``write_and_attend`` takes}."""
    G, Sg, K = rows.positions.shape
    pos_g, live_g = rows.positions, rows.live.reshape(G, Sg, K)
    seen = jnp.where(live_g, rows.sees, -1)    # a dead row attends nothing
    n_rows = attend_rows(K, group)
    runs = K // n_rows
    out, at = {}, 0
    for i, (cls, width) in enumerate(zip(classes, rows.widths)):
        bt = rows.tables[:, :, at:at + width]
        at += width
        if cls.per_stream:
            continue
        assert len(out) == i, "K/V-page classes come before a state's"
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
        seen_runs = seen.reshape(G, Sg * runs, n_rows)
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
            off=off.reshape(G, Sg * K), rows=n_rows, runs=runs, layer=0,
            # the write's: K consecutive rows a stream, in one page where a
            # model of blocks' block divides it
            stream_rows=K, one_block=rows.one_block and bs % K == 0)
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
            c["blk"], c["off"], mesh=mesh, stream_rows=c["stream_rows"],
            one_block=c["one_block"])
    with jax.named_scope("attend_" + c["name"]):
        qr = q.reshape(G, Sg * c["runs"], c["rows"], nH, D)
        if c["plan"] is not None:
            a = paged_attn_ops.paged_attention(
                qr, kc, vc, layer, plan=c["plan"], scale=scale, mesh=mesh)
        else:
            a = gather_attend(qr, kc, vc, layer, c["bt"], c["seen"],
                              c["reach"], scale)
    pools[c["at"]], pools[c["at"] + 1] = kc, vc
    return a.reshape(S, K, nH * D)


def output_gate(a: jax.Array, gate: jax.Array, dtype) -> jax.Array:
    """The attended rows under a sigmoid OUTPUT GATE, elementwise, ahead of
    the output projection: ``a`` and the gate's logits ``[..., nH * D]``,
    fp32 inside, ``dtype`` out.  The families that publish one (``afmoe``,
    ``solar_open2``) call it on what ``write_and_attend`` returns."""
    return (a.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)


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
            live_blocks, K=self.cfg.group * attend_rows(K, self.cfg.group),
            num_heads=max(1, spec.num_heads // mp), head_dim=spec.head_dim,
            block_size=spec.block_size,
            table_width=spec.max_blocks_per_slot,
            kv_itemsize=int(jnp.dtype(spec.dtype).itemsize),
            q_itemsize=q_itemsize) + (
                paged_attn_ops.attend_cold_steps(live_blocks, calls=calls),)

    def attend_run_rows(self, K: int) -> int:
        return attend_rows(K, self.cfg.group)

    write_step_counts = ServedModel._kv_write_step_counts

    def paged_classes(self, rows: Rows, pools, *, paged_kernel: bool, mesh):
        """``paged_classes`` of this model's classes and head geometry."""
        return paged_classes(
            self.cache_classes, rows, pools, head_dim=self.cfg.head_dim,
            group=self.cfg.group, paged_kernel=paged_kernel, mesh=mesh)


__all__ = ["GqaPagedServed", "MAX_HEAD_ROWS", "attend_rows",
           "gather_attend", "output_gate", "paged_classes",
           "write_and_attend"]

"""KV cache — the static-shape memory plane of the serving tier.

The cache is a pool of fixed-size blocks (the PagedAttention/vLLM
design), logically

    k, v : [layers, groups, blocks_per_group, heads, block_size, head_dim]

and HELD lane-dense: when ``head_dim`` is under the TPU's 128 lanes,
``f = 128 // head_dim`` consecutive positions of a block lie side by
side in the minor dimension (``[..., heads, block_size/f, f*head_dim]``,
see ``kv_fold``). The bytes are the logical array's own, row-major — the
logical view is a reshape — but in this shape the compiler's default
device layout is row-major and unpadded too, so the kernels read the
pool as it lies and nothing ever relays it. The pool is born in that
shape, donated to every step, and stays whole for the engine's life. A
request owns a list of BLOCK IDS (its block table row), not a
``max_seq_len`` reservation: short and long requests share HBM, blocks
allocate lazily as a context grows, and common prompt prefixes are
shared copy-on-write across requests — full-block granularity, keyed by
a position-dependent chain hash, reference-counted by the host-side
``BlockAllocator``. The ``groups`` axis is the mesh data axis: a slot's
blocks always live in the slot's own dp shard (the allocator enforces
it), so every access through the block table is GROUP-LOCAL — the
Pallas kernels run under ``shard_map`` with group-local block ids, the
one-hot baseline is a group-batched contraction GSPMD partitions with
zero communication — and no per-device transient ever exceeds the pool
shard (the ``materialization`` lint gate proves it: no full-pool
gather).

Nothing about admission, progress, or eviction changes a compiled
signature — that is the property the recompile sentinel gates in the
serving tests. Sharding is born on the training mesh's axes: groups
over the data axis, ``heads`` over the model axis (Megatron TP head
sharding, matching ``models/transformer.block_param_shardings``).

APPENDS are written into the donated pool where it lies
(``paged_write_rows`` -> ``ops.paged_attention.paged_write``): an aliased
Pallas call whose scalar-prefetched (layer, block, offset) pick the block
tile of each RUN of new rows (a stream's consecutive rows in one block; a
row, where a stream brings one), rewrite that tile in VMEM and put it
back. A step touches a tile a run — its cost does not depend on
``num_blocks`` — and the stacked pool goes through the layer loop as a
carry beside the layer index, never sliced. Block GATHERS are the Pallas
paged-attention kernel on TPU; the one-hot ``paged_attend`` contraction
(which slices its layer out and reads it whole) stays as the CPU-mesh
path and the reference the kernel is tested against.  ONE copy serves
every pool (``copy_pages``: block to block of a group, every layer, a
slice read and an update in place in the donated pool, whatever the
pool's size): a copy-on-write fork of a block, a snapshot into or out of
a stream's page.

Three POLICIES manage a pool, one class each behind one interface
(``allocator_for`` picks; the engine asks none of them what it is):
``BlockAllocator`` (pages of tokens' rows, shared by reference count),
``BoundedBlockAllocator`` (the same in a ring: window layers) and
``StateAllocator`` (a page a stream, snapshots for a prefix cache);
``ClassAllocators`` is several of them side by side.

A served model may instead keep a fixed-size STATE per stream (a
retention layer's recurrent state: ``StateAllocator``).  The pool, the
hash chain and the LRU are the same; a block is then a PAGE — one stream's
whole state, every layer — and a block table is one page wide.  A stream
owns one page and rewrites it in place, so
nothing can be shared by reference count: the prefix cache keeps SNAPSHOTS
(a page frozen at a block boundary of a prompt, keyed by the chain hash
there, retained LRU like a cached block), ``match_snapshot`` finds the
longest boundary that has one, and admission COPIES it into the stream's
own page (``copy_pages``).  Which boundaries are worth a page is
``StateAllocator.snapshot_boundary``.  HOW a snapshot is frozen is the
model's: a state that only the end of a chunk program can freeze (a
retention layer's) has its prompt cut at the boundary and its page copied
there; one that is a gather of the chunk's rows (a conv layer's) is written
into the snapshot's page by the chunk program that reaches the boundary
(``ServedModel.freezes_in_chunk``; ``AdmitPlan.snapshot_in_program``), and
``span_args`` counts a snapshot's copy only where one was dispatched.

A served model may also keep its layers in several CLASSES, each with its
own pools, block table, free list, reference counts and prefix index (one
``PagedKVCacheSpec`` and one allocator a class, ``ClassAllocators`` over
them; a stream's table row is the classes' rows side by side).  A class has
a ``reach``: unbounded (every layer of GPT-2: the above), or a number of
tokens a query reads back, itself included (sliding-window layers:
``BoundedBlockAllocator``).  A bounded class keeps only what is in reach:
its table is a RING ``table_blocks`` wide, logical block j of a stream at
slot ``j % width``; before every program the stream RETURNS the blocks that
lie wholly behind the program's first query less the reach (``extend``) and
draws the ones its new rows need; the rows a block holds never move.
Admission counts ``min(tokens, reach + chunk) / block_size`` blocks for it,
and charges the stream all of them until its release, whatever it shares: a
shared block is given up by each stream as ITS window slides, so it is no
free ride.  A prefix hit at token P needs the unbounded classes' blocks over
``[0, P)`` and a bounded class's over ``[P - reach, P)`` only
(``match_limit``), and a bounded class enters into its index only the blocks
a hit at the prompt's own end would need: a cached document keeps its whole
length in the one and a ``reach``-long tail in the other.

The classes of one model may differ in KIND: K/V pages of its attention
layers BESIDE a state a stream of its recurrent or convolution layers.  THE
PREFIX RULE is one across kinds: a hit of ``n`` blocks needs every page
class to hold blocks ``0 .. n-1`` (as far as it reaches) AND every state
class to hold a snapshot AT boundary ``n`` — a state is valid at one
position — so ``match_limit`` of a state class answers the longest boundary
``<= n`` that has a snapshot and ``ClassAllocators._agreed`` iterates to the
boundary every class can serve.  What the page classes had cached beyond it
is counted (``AdmitPlan.lost_to_kind``), not hidden.  The composite's plan
carries the state class's copy (snapshot -> the stream's own page), the
snapshot it is to leave and the class that owns both (``copy_class``): the
device copy runs on that class's pools alone (``copy_pools``).
"""
from __future__ import annotations

import dataclasses
import math
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import paged_attention as paged_attn_ops
from ..parallel.topology import DP_AXIS, MP_AXIS


DEAD_BLOCK = -1     # block-table entry for "unallocated" — writes through
                    # it land nowhere and gathers through it read zeros


@dataclasses.dataclass(frozen=True)
class PagedKVCacheSpec:
    """Static geometry of the block pool: fixed at engine construction.

    ``num_blocks`` is the GLOBAL pool size; it is laid out as
    ``[num_groups, blocks_per_group]`` with the group axis sharded over
    dp, and the allocator only hands a slot blocks from the slot's own
    group — that locality is what keeps every block-table gather a
    zero-communication batched contraction under GSPMD.
    """
    num_layers: int
    num_slots: int
    num_blocks: int
    block_size: int
    max_len: int
    num_heads: int
    head_dim: int
    num_groups: int = 1
    dtype: Any = jnp.bfloat16
    # What the served model keeps, where it is not GPT-2's per-head K and
    # V: ((pool name, ONE block's tile as held [heads, rows, lanes]), ...)
    # (``inference.served.ServedModel.cache_pools``).  ``num_heads`` /
    # ``head_dim`` then describe a cache row's heads and logical width.  A
    # pool that is not of the spec's ``dtype`` says so in a third entry
    # ((name, tile, dtype): a float32 state beside bfloat16 rows in one
    # page).
    pools: Optional[Tuple[Tuple[Any, ...], ...]] = None
    # A tile is one STREAM's state of a layer, of fixed size (a page; see
    # the module docstring), not ``block_size`` tokens' rows; what a
    # token of this model would keep a layer as K/V rows, in bytes — the
    # yardstick of ``StateAllocator.snapshot_boundary`` — and, where the
    # state sits BESIDE classes of pages, the rows of one prefill program
    # (``class_specs``; 0: the state is the model's only cache).
    per_stream: bool = False
    token_row_bytes: int = 0
    program_rows: int = 0
    # The CLASS of cache layers this pool serves (module docstring): its
    # name ("" for a model's only class), how many tokens back a query of
    # these layers reads, itself included (None: all of them), and the
    # width of the ring a bounded class's table is.
    name: str = ""
    reach: Optional[int] = None
    table_blocks: int = 0

    def __post_init__(self):
        if not self.num_blocks:
            # Full provisioning: every slot's table full, so admission
            # never blocks on HBM; a smaller pool oversubscribes and the
            # admission gate accounts free blocks.
            object.__setattr__(self, "num_blocks", self.num_slots
                               * self.max_blocks_per_slot)

    @property
    def pool_tiles(self) -> Tuple[Tuple[str, Tuple[int, int, int]], ...]:
        """((pool name, one block's tile as held), ...): ``pools``, or
        the K and V pools ``num_heads`` / ``head_dim`` give."""
        if self.pools is not None:
            tiles = tuple((entry[0], entry[1]) for entry in self.pools)
        else:
            f = self.fold
            tile = (self.num_heads, self.block_size // f, f * self.head_dim)
            tiles = (("k", tile), ("v", tile))
        if not self.name:
            return tiles
        # A named class's pools carry its name: one cache dict holds all.
        return tuple((f"{pool}.{self.name}", tile) for pool, tile in tiles)

    @property
    def pool_names(self) -> Tuple[str, ...]:
        return tuple(name for name, _ in self.pool_tiles)

    @property
    def pool_dtypes(self) -> Dict[str, Any]:
        """Every pool's dtype, by the name it is held under: the spec's
        ``dtype`` unless the pool's declaration names its own."""
        if self.pools is None:
            return dict.fromkeys(self.pool_names, self.dtype)
        return {name: entry[2] if len(entry) > 2 else self.dtype
                for name, entry in zip(self.pool_names, self.pools)}

    @property
    def pool_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """Every pool as it is held: ``[L, G, B, heads, rows, lanes]``."""
        lead = (self.num_layers, self.num_groups, self.blocks_per_group)
        return {name: lead + tuple(tile) for name, tile in self.pool_tiles}

    @property
    def blocks_per_group(self) -> int:
        return self.num_blocks // self.num_groups

    @property
    def slots_per_group(self) -> int:
        return self.num_slots // self.num_groups

    @property
    def max_blocks_per_slot(self) -> int:
        """Block-table width J: logical blocks a full slot spans (one
        page for a per-stream pool; the ring of a bounded class)."""
        if self.per_stream:
            return 1
        if self.reach is not None:
            return self.table_blocks
        return self.max_len // self.block_size

    def first_block(self, pos: int) -> int:
        """The first logical block a query at position ``pos`` reads."""
        if self.reach is None:
            return 0
        return max(0, int(pos) - self.reach + 1) // self.block_size

    @property
    def page_tokens(self) -> int:
        """Tokens a prompt has to add for a page of a per-stream pool to
        be worth keeping as its snapshot: those whose K/V rows would fill
        the page (a snapshot of fewer is dearer than the rows it stands
        for) — and, beside classes of pages, one prefill program's rows
        at most: there the rows ARE cached as well, only a snapshot at
        their end lets a later prompt use them, and what it then saves is
        a whole program, whatever the page's bytes."""
        tokens = -(-self.block_nbytes()
                   // (self.num_layers * max(1, self.token_row_bytes)))
        return min(tokens, self.program_rows) if self.program_rows \
            else tokens

    @property
    def fold(self) -> int:
        """Positions of a block side by side in the lanes (``kv_fold``)."""
        return kv_fold(self.head_dim, self.block_size)

    @property
    def shape(self) -> Tuple[int, int, int, int, int, int]:
        """The (first) pool as it is held: for K and V ``logical_shape``
        with ``fold`` positions folded into the minor dimension (a
        reshape)."""
        return self.pool_shapes[self.pool_names[0]]

    @property
    def logical_shape(self) -> Tuple[int, int, int, int, int, int]:
        return (self.num_layers, self.num_groups, self.blocks_per_group,
                self.num_heads, self.block_size, self.head_dim)

    def nbytes(self) -> int:
        """Total pool bytes, every pool (global, unsharded)."""
        return self.block_nbytes() * self.num_blocks

    def block_nbytes(self) -> int:
        """Bytes one block holds across all layers and pools — the unit
        of the hbm_bytes_per_token accounting."""
        dtypes = self.pool_dtypes
        return self.num_layers * sum(
            math.prod(tile) * jnp.dtype(dtypes[name]).itemsize
            for name, tile in self.pool_tiles)

    def validate(self, mesh: Optional[Mesh] = None) -> None:
        for name in ("num_layers", "num_slots", "num_blocks", "block_size",
                     "max_len", "num_heads", "head_dim", "num_groups"):
            if int(getattr(self, name)) <= 0:
                raise ValueError(f"PagedKVCacheSpec.{name} must be "
                                 f"positive, got {getattr(self, name)}")
        if self.max_len % self.block_size:
            raise ValueError(
                f"inference.block_size={self.block_size} must divide the "
                f"cache capacity ({self.max_len}) — a slot's last logical "
                "block would otherwise overhang the position table")
        if self.reach is not None and not (
                self.reach > 0 and not self.per_stream
                and -(-self.reach // self.block_size) < self.table_blocks
                <= self.max_len // self.block_size):
            raise ValueError(
                f"a class of reach {self.reach} needs a ring of more than "
                f"reach / block_size and at most max_len / block_size "
                f"blocks, got table_blocks={self.table_blocks}")
        if self.num_blocks % self.num_groups:
            raise ValueError(
                f"inference.num_blocks={self.num_blocks} must be divisible "
                f"by the mesh data axis ({self.num_groups}) — blocks are "
                "born sharded over dp alongside the slots they serve")
        if self.num_slots % self.num_groups:
            raise ValueError(
                f"inference.max_slots={self.num_slots} must be divisible "
                f"by the mesh data axis ({self.num_groups})")
        if mesh is not None:
            mp = int(mesh.shape.get(MP_AXIS, 1))
            if self.num_heads % mp != 0:
                raise ValueError(
                    f"model heads ({self.num_heads}) not divisible by the "
                    f"mesh model axis ({mp}) for TP head sharding")


def kv_fold(head_dim: int, block_size: int) -> int:
    """How many positions of a block share the 128 lanes of the pool's
    minor dimension: ``128 // head_dim`` where ``head_dim`` divides 128
    (as far as ``block_size`` divides by it), 1 where ``head_dim``
    already fills the lanes. Computed from the shapes; there is no
    option."""
    if head_dim >= 128 or 128 % head_dim:
        return 1
    return math.gcd(128 // head_dim, block_size)


def paged_logical_view(pool: jax.Array, head_dim: int) -> jax.Array:
    """``[..., nH, bs/f, f*D]`` (as held) -> ``[..., nH, bs, D]``: the
    same bytes, for tests, the one-hot baseline and anything else that
    wants to index positions."""
    f = pool.shape[-1] // head_dim
    return pool.reshape(pool.shape[:-2] + (pool.shape[-2] * f, head_dim))


def paged_folded_view(logical: jax.Array) -> jax.Array:
    """Inverse of ``paged_logical_view``: ``[..., nH, bs, D]`` -> the
    lane-dense shape the pool is held in."""
    bs, D = logical.shape[-2:]
    f = kv_fold(D, bs)
    return logical.reshape(logical.shape[:-2] + (bs // f, f * D))


def paged_block_size(pool: jax.Array, head_dim: int) -> int:
    """Positions a block of the pool (as held) spans."""
    return pool.shape[-2] * pool.shape[-1] // head_dim


def paged_layer_view(pool: jax.Array, layer, head_dim: int) -> jax.Array:
    """One layer of the stacked pool, logical: ``[G, B, nH, bs, D]``.
    A slice — what the one-hot baseline reads; the kernels never do."""
    return paged_logical_view(
        lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False), head_dim)


def paged_partition_spec() -> P:
    """[layers, groups, blocks, heads, block_size/f, f*head_dim]: groups
    over dp, heads over mp."""
    return P(None, DP_AXIS, None, MP_AXIS, None, None)


def paged_shardings(mesh: Mesh, names: Sequence[str] = ("k", "v")
                    ) -> Dict[str, NamedSharding]:
    spec = paged_partition_spec()
    return {name: NamedSharding(mesh, spec) for name in names}


def copy_pages(pool: jax.Array, src: jax.Array, dst: jax.Array,
               mesh: Optional[Mesh] = None) -> jax.Array:
    """Copy block (page) ``src[g]`` to block ``dst[g]`` of every group
    ``g`` (all layers), block to block: a slice read and a
    ``dynamic_update_slice`` into the donated pool, whatever the pool's
    size and whatever a block's tile holds.  pool ``[L, G, B, ...]``;
    src / dst ``[G]`` int32, a group with ``dst < 0`` copies nothing (its
    block ``src`` onto itself).  Over a ``mesh`` every shard copies within
    its own groups (``shard_map``, as the write and the attend run)."""
    def local(pool, src, dst):
        L, G = pool.shape[:2]
        tile = pool.shape[3:]
        zeros = (0,) * len(tile)
        for g in range(G):
            s_ = jnp.maximum(src[g], 0)
            d_ = jnp.where(dst[g] >= 0, dst[g], s_)  # nothing: onto itself
            page = lax.dynamic_slice(pool, (0, g, s_) + zeros,
                                     (L, 1, 1) + tile)
            pool = lax.dynamic_update_slice(pool, page, (0, g, d_) + zeros)
        return pool

    pool_spec = paged_attn_ops._pool_spec
    return paged_attn_ops._on_mesh(
        local, mesh, lambda dpn, mpn: (pool_spec(dpn, mpn), P(dpn), P(dpn)),
        pool_spec)(pool, src, dst)


def init_paged_cache(spec: PagedKVCacheSpec,
                     mesh: Optional[Mesh] = None) -> Dict[str, jax.Array]:
    """Zero-initialized pool, born sharded when a mesh is given."""
    spec.validate(mesh)

    def make():
        dtypes = spec.pool_dtypes
        return {name: jnp.zeros(shape, dtypes[name])
                for name, shape in spec.pool_shapes.items()}

    if mesh is None:
        return make()
    return jax.jit(make, out_shardings=paged_shardings(
        mesh, spec.pool_names))()


# --------------------------------------------------------------------- #
# In-graph paged primitives. All of them are group-batched: every array
# carries the [G, ...] group axis so the work partitions over dp with
# zero communication.
# --------------------------------------------------------------------- #
def positions_to_blocks(bt: jax.Array, pos: jax.Array, block_size: int,
                        ring: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """Resolve token positions through a block table (``ring``: a bounded
    class's, logical block j at slot ``j % J``).

    bt: [..., J] physical block ids (DEAD_BLOCK where unallocated);
    pos: [...] int32 token positions, same leading shape. Returns
    (block [...], offset [...]) with block == DEAD_BLOCK for positions
    past the table (pos >= J * block_size) or through a dead entry — a
    write through those lands nowhere by construction.
    """
    J = bt.shape[-1]
    j = pos // block_size
    if ring:
        j = jnp.where(pos >= 0, j % J, -1)
    off = pos % block_size
    jm = j[..., None] == lax.broadcasted_iota(
        jnp.int32, j.shape + (J,), j.ndim)                   # [..., J]
    blk = jnp.where(jm.any(-1), (jm * bt).sum(-1), DEAD_BLOCK)
    return blk.astype(jnp.int32), off.astype(jnp.int32)


def block_select(bt: jax.Array, blocks_per_group: int) -> jax.Array:
    """One-hot block-table selector: bt [G, Q, J] → [G, Q, J, B] f32.
    Dead entries (DEAD_BLOCK) select nothing."""
    iota = lax.broadcasted_iota(jnp.int32, bt.shape + (blocks_per_group,),
                                bt.ndim)
    return (bt[..., None] == iota).astype(jnp.float32)


def paged_write_rows(pool_k: jax.Array, pool_v: jax.Array,
                     k_new: jax.Array, v_new: jax.Array, layer,
                     blk: jax.Array, off: jax.Array, mesh=None, *,
                     stream_rows: int = 1, one_block: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """Write R rows per group into layer ``layer`` of both pools at
    (block, offset), in place.

    pool_k/pool_v: the stacked pools as held, [L, G, B, nH, bs/f, f*D];
    k_new/v_new: [G, R, nH, D]; blk/off: [G, R]. Rows with blk ==
    DEAD_BLOCK write nowhere. Distinct live rows always target distinct
    (block, offset) cells — slots never share a writable block (the
    allocator's copy-on-write invariant) — and the rows of one block are
    consecutive (a stream's positions ascend). ``stream_rows``: the rows
    are streams of this many at consecutive positions each (a program's K
    rows a stream), ``one_block``: each stream's in ONE block — the write
    then takes a block tile each way per RUN of a stream's rows in a block
    and pool, not per row, whatever the pool's size: see
    ``ops.paged_attention.paged_write``."""
    return paged_attn_ops.paged_write(pool_k, pool_v, k_new, v_new, layer,
                                      blk, off, mesh=mesh,
                                      stream_rows=stream_rows,
                                      one_block=one_block)


def paged_attend(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                 sel: jax.Array, pos_mask: jax.Array, scale: float,
                 neg_inf) -> jax.Array:
    """Attention through the block table, group-batched — the one-hot
    CPU-mesh / parity baseline of the Pallas kernel.

    q: [G, Q, K, nH, D] (Q query streams per group, K tokens each);
    pool_k/pool_v: ONE layer, logical (``paged_layer_view``):
    [G, B, nH, bs, D]; sel: [G, Q, J, B] one-hot block
    selector; pos_mask: [G, Q, K, J*bs] bool (True = attendable).
    Returns [G, Q, K, nH, D].

    Scores contract q against the WHOLE group-local pool first
    ([G,Q,K,nH,B,bs] fp32 — no head_dim factor, so it is the small
    transient), then the one-hot selector picks each stream's J blocks;
    the value combine routes the weights back through the selector. No
    gathered K/V copy ever materializes and nothing crosses a group
    boundary.
    """
    J = sel.shape[2]
    bs = pool_k.shape[3]
    s_all = jnp.einsum("gqknd,gbntd->gqknbt", q, pool_k
                       ).astype(jnp.float32) * scale
    scores = jnp.einsum("gqjb,gqknbt->gqknjt", sel, s_all)
    G, Q, K, nH = scores.shape[:4]
    scores = scores.reshape(G, Q, K, nH, J * bs)
    scores = jnp.where(pos_mask[:, :, :, None, :], scores, neg_inf)
    w = jax.nn.softmax(scores, axis=-1).reshape(G, Q, K, nH, J, bs)
    wb = jnp.einsum("gqjb,gqknjt->gqknbt", sel, w)
    return jnp.einsum("gqknbt,gbntd->gqknd", wb.astype(pool_v.dtype),
                      pool_v)


# --------------------------------------------------------------------- #
# Host-side block allocator: free lists, refcounts, prefix cache, CoW
# --------------------------------------------------------------------- #
def chain_hash(prev: int, block: bytes) -> int:
    """Position-dependent hash of one full block's tokens (``block``: their
    int64 bytes) given the hash of the preceding chain — two different
    prefixes never collide on position, only on (astronomically unlikely)
    hash collision."""
    return hash((prev, block))


def chain_hashes(prompt: np.ndarray, block_size: int) -> List[int]:
    """The chain hash at every full block of ``prompt``: THE walk (the
    prompt converted once, a block a slice of its bytes)."""
    data = np.asarray(prompt).astype(np.int64).tobytes()
    step = 8 * block_size
    out, h = [], 0
    for at in range(0, len(data) - step + 1, step):
        h = chain_hash(h, data[at:at + step])
        out.append(h)
    return out


class PromptChain:
    """A prompt with its chain of block hashes, walked ONCE: what every
    question of an admission is answered from (can it be admitted, how
    much is cached, what is the plan — each allocator method takes a bare
    prompt or one of these).  The chain is a function of the tokens and the
    block size alone, no allocator's state, so it lives as long as the
    request does (``scheduler.Request.chained``); what an INDEX says of it
    is asked again every time.  ``walks``: the walks made for this prompt
    (1 once any allocator of one block size has asked)."""

    __slots__ = ("tokens", "walks", "_hashes")

    def __init__(self, prompt):
        self.tokens = np.asarray(prompt, np.int32).reshape(-1)
        self.walks = 0
        self._hashes: Dict[int, List[int]] = {}     # by block size

    @classmethod
    def of(cls, prompt) -> "PromptChain":
        return prompt if isinstance(prompt, cls) else cls(prompt)

    def __len__(self) -> int:
        return len(self.tokens)

    def __array__(self, dtype=None, copy=None):
        return self.tokens if dtype is None \
            else self.tokens.astype(dtype, copy=False)

    def hashes(self, block_size: int, by=()) -> List[int]:
        """The chain at every full block; the call that has to walk counts
        it here and on each of ``by`` (``chain_walks``: the allocator's,
        the engine's aggregator's)."""
        hashes = self._hashes.get(block_size)
        if hashes is None:
            hashes = self._hashes[block_size] = chain_hashes(
                self.tokens, block_size)
            self.walks += 1
            for counter in by:
                counter.chain_walks += 1
        return hashes


class PoolExhausted(RuntimeError):
    """No free or reclaimable block in the group — admission must be
    rejected (the scheduler keeps the request queued; a live slot is
    never touched)."""


class BlockAllocator:
    """Host-authoritative state of a pool of blocks of tokens' rows — and
    the interface of every policy (module docstring).

    Per group (dp shard): a free list, per-block refcounts, and the
    prefix cache — a chain-hash index over full PROMPT blocks plus an
    LRU of retained blocks whose refcount dropped to zero (they keep
    their bytes until pool pressure reclaims them, so a popular system
    prompt stays resident across request lifetimes).

    Admission is RESERVATION-based: ``can_admit`` checks that the
    group can cover the request's worst-case block need (prompt +
    max_new + spec lookahead, minus the prefix blocks it free-rides
    on), and ``admit_prompt`` books that reservation so later lazy
    allocations (decode appends) can never strand a live slot
    mid-flight. Conservative next to vLLM's optimistic
    preempt-and-recompute, and it never corrupts a running request —
    the tradeoff docs/tutorials/inference.md spells out.
    """

    # (program name, profiler scope) of the device copy a plan's
    # ``cow_src -> cow_dst`` asks for: here a copy-on-write fork.
    copy_program = ("copy_block", "cow_copy")

    def __init__(self, spec: PagedKVCacheSpec):
        self.spec = spec
        # the pools that copy runs on: block ids mean nothing elsewhere
        self.copy_pools = spec.pool_names
        G, B = spec.num_groups, spec.blocks_per_group
        self._free: List[List[int]] = [list(range(B)) for _ in range(G)]
        self._ref = np.zeros((G, B), np.int64)
        # chain-hash -> local block id, per group; and its inverse for
        # eviction bookkeeping.
        self._hash_index: List[Dict[int, int]] = [{} for _ in range(G)]
        self._block_hash: List[Dict[int, int]] = [{} for _ in range(G)]
        # Retained zero-ref blocks, LRU order (oldest first).
        self._lru: List["OrderedDict[int, None]"] = \
            [OrderedDict() for _ in range(G)]
        self._reserved: List[int] = [0] * G      # outstanding, per group
        self._slot_reserved: Dict[int, int] = {}  # slot -> remaining
        self._slot_group: Dict[int, int] = {}
        # Cumulative telemetry the aggregator snapshots.
        self.cow_copies = 0
        self.reclaimed = 0
        # Whole-prompt chain computations this allocator had to make
        # (``PromptChain.hashes``): one a request, not one a question.
        self.chain_walks = 0
        # Blocks live streams gave back before their release: a bounded
        # class's (``BoundedBlockAllocator``); nothing else returns any.
        self.returned = 0

    # ---- accounting ---- #
    def blocks_in_use(self) -> int:
        """Live (ref > 0) blocks across all groups — shared blocks count
        once; LRU-retained blocks are reclaimable, not in use."""
        return int((self._ref > 0).sum())

    def bytes_in_use(self) -> int:
        return self.blocks_in_use() * self.spec.block_nbytes()

    def available(self, group: int) -> int:
        """Blocks this group can still hand out: free + reclaimable
        minus outstanding reservations."""
        return (len(self._free[group]) + len(self._lru[group])
                - self._reserved[group])

    def need_blocks(self, prompt_len: int, max_new: int,
                    spec_k: int = 0) -> int:
        """Worst-case logical blocks a request spans (capped at the
        table width)."""
        tokens = prompt_len + max_new + spec_k
        need = -(-tokens // self.spec.block_size)
        return min(need, self.spec.max_blocks_per_slot)

    # ---- prefix cache ---- #
    @property
    def table_width(self) -> int:
        return self.spec.max_blocks_per_slot

    def _walked(self, prompt) -> Tuple[PromptChain, List[int]]:
        """``prompt`` (bare, or a ``PromptChain``) as a chain and its
        hashes at this pool's block size: where every public method
        starts, and hands the CHAIN on."""
        chain = PromptChain.of(prompt)
        return chain, chain.hashes(self.spec.block_size, by=(self,))

    def match_prefix(self, group: int, prompt,
                     limit: Optional[int] = None
                     ) -> Tuple[List[int], List[int]]:
        """Longest cached full-block chain matching ``prompt`` in this
        group (its first ``limit`` blocks at most) → (block ids, chain
        hashes). Stops at the first miss."""
        hashes = self._walked(prompt)[1]
        idx = self._hash_index[group]
        blocks: List[int] = []
        for h in hashes if limit is None else hashes[:limit]:
            b = idx.get(h)
            if b is None:
                break
            blocks.append(b)
        return blocks, hashes[:len(blocks)]

    def matched_blocks(self, group: int, prompt) -> int:
        """Full blocks of ``prompt`` the prefix cache of this group
        covers: the cached chain's length."""
        return len(self.match_prefix(group, prompt)[0])

    def match_limit(self, group: int, hashes: Sequence[int], n: int) -> int:
        """The most blocks ``m <= n`` of a prompt (its chain ``hashes``)
        this class can serve a prefix hit at: all of ``[0, m)`` cached."""
        idx = self._hash_index[group]
        n = min(n, len(hashes))
        m = 0
        while m < n and hashes[m] in idx:
            m += 1
        return m

    def can_admit(self, group: int, prompt, max_new: int,
                  spec_k: int = 0, limit: Optional[int] = None) -> bool:
        """``limit``: the prefix match is cut to that many blocks (what a
        model's classes agreed on, ``ClassAllocators``)."""
        return self._covers(group, len(prompt), max_new, spec_k,
                            self.match_prefix(group, prompt, limit)[0])

    def _covers(self, group: int, plen: int, max_new: int, spec_k: int,
                matched: List[int]) -> bool:
        """``can_admit`` of a prompt whose cached chain is ``matched``."""
        need = self.need_blocks(plen, max_new, spec_k)
        # Only LIVE shared blocks are a free ride; reviving an
        # LRU-retained block consumes reclaimable capacity like any
        # fresh allocation does.
        free_ride = int((self._ref[group, matched] > 0).sum())
        return self.available(group) >= need - free_ride

    # ---- allocation primitives ---- #
    def _pop_block(self, group: int) -> int:
        if self._free[group]:
            return self._free[group].pop()
        if self._lru[group]:
            b, _ = self._lru[group].popitem(last=False)   # oldest
            h = self._block_hash[group].pop(b, None)
            if h is not None:
                self._hash_index[group].pop(h, None)
            self.reclaimed += 1
            return b
        raise PoolExhausted(
            f"group {group}: no free or reclaimable block "
            f"({self.spec.blocks_per_group} blocks, "
            f"{self._reserved[group]} reserved)")

    def _draw(self, group: int, slot: int) -> int:
        """Allocate one block for ``slot``, drawing down its
        reservation when one is booked."""
        b = self._pop_block(group)
        self._ref[group, b] = 1
        if self._slot_reserved.get(slot, 0) > 0:
            self._slot_reserved[slot] -= 1
            self._reserved[group] -= 1
        return b

    def _incref(self, group: int, b: int) -> None:
        if self._ref[group, b] == 0:
            self._lru[group].pop(b, None)       # revive from retention
        self._ref[group, b] += 1

    def _decref(self, group: int, b: int) -> None:
        self._ref[group, b] -= 1
        assert self._ref[group, b] >= 0, "block refcount underflow"
        if self._ref[group, b] == 0:
            if b in self._block_hash[group]:
                # Prefix block: retain (LRU) so the next request with
                # this prompt still hits; reclaimed under pressure.
                self._lru[group][b] = None
            else:
                self._free[group].append(b)

    # ---- request lifecycle ---- #
    def _refuse(self, group: int, plen: int, max_new: int) -> None:
        raise PoolExhausted(
            f"group {group}: {self.available(group)} block(s) "
            f"available < worst-case need for a "
            f"{plen}+{max_new}-token request")

    def admit_prompt(self, slot: int, group: int, prompt,
                     max_new: int, spec_k: int = 0,
                     limit: Optional[int] = None) -> "AdmitPlan":
        """Allocate/share the prompt's blocks and book the request's
        worst-case reservation. Returns the plan the engine prefills
        from. Raises PoolExhausted when ``can_admit`` would be False.
        ``limit``: see ``can_admit``."""
        chain, hashes = self._walked(prompt)
        bs = self.spec.block_size
        plen = len(chain)
        matched_blocks = self.match_prefix(group, chain, limit)[0]
        if not self._covers(group, plen, max_new, spec_k, matched_blocks):
            self._refuse(group, plen, max_new)
        # Always re-prefill at least the prompt's last token: its
        # logits seed the first sampled token, and the block holding it
        # must be privately writable for the decode appends that follow.
        matched = min(len(matched_blocks) * bs, plen - 1)
        n_keep = matched // bs                   # fully shared blocks
        cow_src = cow_dst = None
        for b in matched_blocks[:n_keep]:
            self._incref(group, b)
        table: List[int] = list(matched_blocks[:n_keep])
        if n_keep < len(matched_blocks):
            # The chain covered the whole prompt; the final shared block
            # must be written (re-prefilled last token + decode appends)
            # → fork it copy-on-write into a private block.
            cow_src, cow_dst = matched_blocks[n_keep], self._draw(group, slot)
            table.append(cow_dst)
            self.cow_copies += 1
        # Private blocks for the unshared prompt tail.
        while len(table) * bs < plen:
            table.append(self._draw(group, slot))
        # Book the rest of the worst-case need.
        need = self.need_blocks(plen, max_new, spec_k)
        remaining = max(0, need - len(table))
        self._slot_reserved[slot] = remaining
        self._slot_group[slot] = group
        self._reserved[group] += remaining
        # Register the prompt's full PRIVATE blocks in the prefix cache
        # (shared ones are already registered; the CoW fork is NOT — its
        # content diverges the moment the slot decodes into it... except
        # it holds exactly the cached chain's tokens until then; keep it
        # out of the index so the cached original stays authoritative).
        for j in range(n_keep, plen // bs):
            h, b = hashes[j], table[j]
            if b != cow_dst and h not in self._hash_index[group]:
                self._hash_index[group][h] = b
                self._block_hash[group][b] = h
        return AdmitPlan(slot=slot, group=group, table=table,
                         matched=matched, cow_src=cow_src, cow_dst=cow_dst)

    def extend(self, slot: int, row: np.ndarray, first_pos: int,
               upto_pos: int) -> None:
        """Make ``row`` (the slot's table row, edited in place) ready for a
        program whose queries span positions ``[first_pos, upto_pos]``:
        draw the blocks its new rows need — the per-iteration HBM growth
        the hbm_bytes_per_token metric tracks."""
        j = int((row != DEAD_BLOCK).sum())
        while j <= min(upto_pos // self.spec.block_size,
                       self.table_width - 1):
            row[j] = self.alloc_block(slot)
            j += 1

    def class_stats(self) -> Dict[str, Dict[str, int]]:
        """{class name: reach, blocks, in use, returned by sliding,
        reclaimed} for a NAMED class (a model's only class has no name
        and adds nothing to the spans)."""
        if not self.spec.name:
            return {}
        return {self.spec.name: {
            "reach": self.spec.reach, "blocks": self.spec.num_blocks,
            "live": self.blocks_in_use(), "returned": self.returned,
            "reclaimed": self.reclaimed}}

    def span_args(self, plans: Optional[Sequence["AdmitPlan"]] = None,
                  live: int = 0) -> Dict[str, int]:
        """What this KIND of cache adds to the engine's spans (as
        ``class_stats`` does for named classes): to ``prefill`` for the
        admissions ``plans``, else to ``decode`` over ``live`` streams.
        Pages of rows add nothing."""
        return {}

    def snapshot_totals(self) -> Dict[str, int]:
        """Running totals for ``snapshot()["state"]``; none here."""
        return {}

    def commit_snapshot(self, plan: "AdmitPlan") -> None:
        """The engine has frozen ``plan``'s state at its boundary: only a
        policy that keeps snapshots has anything to do."""

    def abandon_snapshot(self, plan: "AdmitPlan") -> None:
        """``plan``'s prefill failed (the engine tells every policy)."""

    def alloc_block(self, slot: int) -> int:
        """Lazily allocate one more block for a live slot (a decode or
        verify append crossing a block boundary), drawing down the
        slot's reservation. Raises PoolExhausted only for slots
        admitted WITHOUT a reservation (direct engine use) on a drained
        pool — scheduler admissions are always covered."""
        if slot not in self._slot_group:
            raise RuntimeError(
                f"slot {slot} has no admitted prompt — prefill() admits "
                "through the allocator before any decode can append")
        return self._draw(self._slot_group[slot], slot)

    def release(self, slot: int, table: Sequence[int]) -> None:
        """Evict: drop every table reference and the unused
        reservation. Prefix blocks whose refcount hits zero are
        RETAINED (LRU) for future hits; private ones return to the
        free list."""
        group = self._slot_group.pop(slot, None)
        if group is None:
            return
        rem = self._slot_reserved.pop(slot, 0)
        self._reserved[group] -= rem
        for b in table:
            if b != DEAD_BLOCK:
                self._decref(group, int(b))


@dataclasses.dataclass
class AdmitPlan:
    """What ``BlockAllocator.admit_prompt`` decided: the slot's initial
    block-table row, how many prompt tokens ride cached blocks, and the
    copy-on-write fork to perform (device copy src → dst) if any."""
    slot: int
    group: int
    table: List[int]
    matched: int
    cow_src: Optional[int] = None
    cow_dst: Optional[int] = None
    # Per-stream pools: the stream's own ``page``; freeze its state into
    # ``snapshot_page`` when prefill has consumed ``snapshot_at`` tokens
    # (0: no snapshot), then ``commit_snapshot`` it under
    # ``snapshot_hash``.
    page: Optional[int] = None
    snapshot_at: int = 0
    snapshot_page: Optional[int] = None
    snapshot_hash: int = 0
    # Set by the engine where the chunk program that reached the boundary
    # froze the state into ``snapshot_page`` itself: no copy was dispatched.
    snapshot_in_program: bool = False
    # A bounded class: how many cached blocks the stream shares (those in
    # reach of ``matched``); several classes: {class name: tokens it took
    # from its cache}.
    shared_blocks: int = 0
    cached_by_class: Optional[Dict[str, int]] = None
    # Classes of several KINDS: the class whose pools ``cow_src ->
    # cow_dst`` and the snapshot's copy run on (its name), and the tokens
    # the page classes had cached beyond the boundary the state class
    # could resume at.
    copy_class: Optional[str] = None
    lost_to_kind: int = 0


class StateAllocator(BlockAllocator):
    """The allocator of a ``per_stream`` pool (module docstring): a block
    is a PAGE, a stream owns one and rewrites it in place, and the prefix
    cache keeps SNAPSHOTS — a page frozen at a block boundary of a prompt,
    keyed by the chain hash there, retained LRU like a cached block."""

    # a snapshot into a stream's own page at admission, a stream's page
    # into a snapshot when prefill reaches its boundary
    copy_program = ("state_copy", "state_copy")

    def __init__(self, spec: PagedKVCacheSpec):
        super().__init__(spec)
        self.snapshots_taken = 0
        self.snapshots_in_program = 0
        self.snapshot_hits = 0

    def need_blocks(self, prompt_len: int, max_new: int,
                    spec_k: int = 0) -> int:
        """One page, whatever the lengths."""
        return 1

    def extend(self, slot: int, row: np.ndarray, first_pos: int,
               upto_pos: int) -> None:
        """A stream's page is all it ever holds."""

    # ---- the prefix cache: snapshots ---- #
    def matched_blocks(self, group: int, prompt) -> int:
        """The longest boundary of ``prompt`` that has a snapshot."""
        return self.match_snapshot(group, prompt)[0]

    def match_limit(self, group: int, hashes: Sequence[int], n: int) -> int:
        """The longest boundary ``m <= n`` (in blocks) of a prompt (its
        chain ``hashes``) that has a snapshot, 0 if none: a state is valid
        at ONE position, so the blocks before it count for nothing."""
        idx = self._hash_index[group]
        for m in range(min(n, len(hashes)), 0, -1):
            if hashes[m - 1] in idx:
                return m
        return 0

    def match_snapshot(self, group: int, prompt,
                       limit: Optional[int] = None
                       ) -> Tuple[int, Optional[int], int]:
        """The LONGEST block boundary of ``prompt`` (``limit`` blocks at
        most: what a model's classes agreed on) that has a snapshot and
        leaves at least the last token to prefill -> (blocks it covers,
        its page or None, the chain hash at the prompt's last full
        block)."""
        chain, hashes = self._walked(prompt)
        n = (len(chain) - 1) // self.spec.block_size
        best = self.match_limit(group, hashes,
                                n if limit is None else min(n, limit))
        page = self._hash_index[group][hashes[best - 1]] if best else None
        return best, page, hashes[-1] if hashes else 0

    def snapshot_boundary(self, prompt_len: int, resumed: int) -> int:
        """THE RULE of which boundaries get a page: the prompt's last
        full block, when the tokens it adds beyond the snapshot it
        resumed from are worth one (``PagedKVCacheSpec.page_tokens``: they
        would fill a page as K/V rows of this model or, beside classes of
        pages, a whole prefill program) — below that the page is
        dearer than what it saves.  So a document served once leaves a
        snapshot; a short question over it does not, and cannot push a
        document out.  Returns the boundary in tokens, or 0."""
        boundary = prompt_len // self.spec.block_size * self.spec.block_size
        return boundary if boundary - resumed >= self.spec.page_tokens else 0

    # ---- request lifecycle ---- #
    def can_admit(self, group: int, prompt, max_new: int,
                  spec_k: int = 0, limit: Optional[int] = None) -> bool:
        """The stream's own page, drawn while the snapshot it resumes
        from (if retained) is held out of reach."""
        return self._page_left(
            group, self.match_snapshot(group, prompt, limit)[1])

    def _page_left(self, group: int, src: Optional[int]) -> bool:
        return self.available(group) - int(
            src is not None and src in self._lru[group]) >= 1

    def admit_prompt(self, slot: int, group: int, prompt,
                     max_new: int, spec_k: int = 0,
                     limit: Optional[int] = None) -> "AdmitPlan":
        """The stream's own page; the snapshot to copy into it first
        (``cow_src`` -> ``cow_dst``: the one AT the longest boundary within
        ``limit``) and the position prefill resumes at;
        and, where ``snapshot_boundary`` says so and a page can be had,
        the page that will hold this prompt's own snapshot
        (``snapshot_at``, ``snapshot_page``: the engine freezes the state
        there when prefill reaches it — a copy of the stream's page behind
        a cut, or the chunk program's own second write — and THEN enters
        it into the prefix cache, ``commit_snapshot``; until then the page
        is out of every list and nothing can match it)."""
        bs = self.spec.block_size
        n, src, h_last = self.match_snapshot(group, prompt, limit)
        if not self._page_left(group, src):
            self._refuse(group, len(prompt), max_new)
        if src is not None:
            self._incref(group, src)            # out of the LRU's reach
        own = self._draw(group, slot)
        at = self.snapshot_boundary(len(prompt), n * bs)
        snap = None
        if at and h_last not in self._hash_index[group] \
                and self.available(group) > 0:
            snap = self._pop_block(group)
        if src is not None:
            self._decref(group, src)            # back, most recently used
            self.snapshot_hits += 1
        self._slot_reserved[slot] = 0
        self._slot_group[slot] = group
        return AdmitPlan(slot=slot, group=group, table=[own], page=own,
                         matched=n * bs, cow_src=src,
                         cow_dst=own if src is not None else None,
                         snapshot_at=at if snap is not None else 0,
                         snapshot_page=snap, snapshot_hash=h_last)

    def commit_snapshot(self, plan: "AdmitPlan") -> None:
        """The engine has frozen ``plan``'s state into its snapshot page
        (the copy, or the chunk program that writes it, is dispatched):
        key the page by the chain hash of its boundary and retain it, most
        recently used."""
        g, page, h = plan.group, plan.snapshot_page, plan.snapshot_hash
        if h in self._hash_index[g]:            # another admission's is in
            self._free[g].append(page)
            return
        self._hash_index[g][h] = page
        self._block_hash[g][page] = h
        self._lru[g][page] = None
        self.snapshots_taken += 1
        self.snapshots_in_program += bool(plan.snapshot_in_program)

    def abandon_snapshot(self, plan: "AdmitPlan") -> None:
        """Prefill failed: a snapshot page that was never committed goes
        back to the free list (a committed one holds a whole state and
        stays)."""
        g, page = plan.group, plan.snapshot_page
        if page is not None \
                and self._block_hash[g].get(page) != plan.snapshot_hash:
            self._free[g].append(page)

    # ---- what the spans say of a state ---- #
    def span_args(self, plans: Optional[Sequence["AdmitPlan"]] = None,
                  live: int = 0) -> Dict[str, int]:
        """``prefill``: tokens resumed from a snapshot (what
        ``cached_tokens`` means here), snapshots the admissions took,
        those of them a chunk program froze itself, and the bytes the
        page copies that WERE dispatched moved (read + written: a
        snapshot into the stream's page, the page into a snapshot no
        program froze); ``decode``: the pages its streams rewrite."""
        if plans is None:
            return {"state_pages_live": int(live)}
        return {
            "resumed_tokens": sum(int(p.matched) for p in plans),
            "snapshot_taken": sum(p.snapshot_page is not None
                                  for p in plans),
            "snapshot_in_program": sum(p.snapshot_in_program
                                       for p in plans),
            "state_copy_bytes": 2 * self.spec.block_nbytes() * sum(
                (p.cow_src is not None) + (p.snapshot_page is not None
                                           and not p.snapshot_in_program)
                for p in plans)}

    def snapshot_totals(self) -> Dict[str, int]:
        return {"snapshots_taken": self.snapshots_taken,
                "snapshots_in_program": self.snapshots_in_program,
                "snapshot_hits": self.snapshot_hits,
                "snapshots_evicted": self.reclaimed}


class BoundedBlockAllocator(BlockAllocator):
    """The allocator of a BOUNDED class (window layers: ``spec.reach``
    tokens; module docstring).  A stream's table is a ring, logical block j
    at slot ``j % width``; ``extend`` returns what lies behind a program's
    reach before it draws what the program writes; the prefix cache holds a
    prompt's tail, and a hit shares the cached blocks in reach of the
    resume point — never the block of the prompt's last token, so nothing
    shared is ever written (no copy-on-write here).

    Admission is by COMMITMENT, not by reservation less a free ride: a
    stream is charged ``need_blocks`` — the most it holds at once — from
    admission to release, whatever it shares.  A block two streams share
    is given up by each as ITS window slides on, and the one that lets go
    first then draws a block of its own while the other still holds the
    shared one: a free ride would have to be paid back mid-flight, with
    nothing to pay it from.  The streams' blocks in use never exceed the
    sum of their needs, that sum never exceeds the pool, so a draw always
    finds a free or a retained block; what sharing saves is the prefill,
    and room for more retained tails."""

    def __init__(self, spec: PagedKVCacheSpec):
        super().__init__(spec)
        self._committed: List[int] = [0] * spec.num_groups
        self._slot_need: Dict[int, int] = {}
        # per slot [lowest logical block held, next to draw], and the
        # prompt blocks to enter into the prefix cache once drawn
        self._slot_span: Dict[int, List[int]] = {}
        self._slot_hashes: Dict[int, Dict[int, int]] = {}

    def available(self, group: int) -> int:
        """Blocks of this group no admitted stream is charged for."""
        return self.spec.blocks_per_group - self._committed[group]

    # ---- prefix cache ---- #
    def matched_blocks(self, group: int, prompt) -> int:
        """The longest boundary whose blocks in reach are all cached."""
        chain, hashes = self._walked(prompt)
        return self.match_limit(group, hashes,
                                (len(chain) - 1) // self.spec.block_size)

    def match_limit(self, group: int, hashes: Sequence[int], n: int) -> int:
        """The most blocks ``m <= n`` a hit can be served at: only what a
        query at the boundary reads has to be cached,
        ``[first_block(m * block_size), m)``."""
        idx = self._hash_index[group]
        n = min(n, len(hashes))
        runs, run = [], 0           # cached blocks in a row, ending at j
        for j in range(n):
            run = run + 1 if hashes[j] in idx else 0
            runs.append(run)
        bs = self.spec.block_size
        for m in range(n, 0, -1):
            if runs[m - 1] >= m - self.spec.first_block(m * bs):
                return m
        return 0

    def _match(self, group: int, prompt, limit: Optional[int]):
        """(blocks matched n, the cached blocks a stream resuming at
        ``n * block_size`` shares: logical ``[first block in reach, n)``,
        the prompt's chain hashes)."""
        bs = self.spec.block_size
        chain, hashes = self._walked(prompt)
        n = self.match_limit(group, hashes, min(
            (len(chain) - 1) // bs, len(hashes) if limit is None else limit))
        idx = self._hash_index[group]
        return n, [idx[hashes[j]] for j in
                   range(self.spec.first_block(n * bs), n)], hashes

    # ---- request lifecycle ---- #
    def can_admit(self, group: int, prompt, max_new: int,
                  spec_k: int = 0, limit: Optional[int] = None) -> bool:
        return self.available(group) >= self.need_blocks(
            len(prompt), max_new, spec_k)

    def admit_prompt(self, slot: int, group: int, prompt,
                     max_new: int, spec_k: int = 0,
                     limit: Optional[int] = None) -> "AdmitPlan":
        """The ring holds the cached blocks in reach of the resume point
        and nothing else yet (``extend`` draws a program's blocks when it
        is dispatched); of the prompt's own blocks only those a hit at the
        PROMPT's end would read are entered into the prefix cache, as they
        are drawn."""
        # (a hit anywhere earlier in the prompt finds the full classes'
        # blocks and not this one's: it is no hit)
        need = self.need_blocks(len(prompt), max_new, spec_k)
        if self.available(group) < need:
            raise PoolExhausted(
                f"group {group}: {self.available(group)} block(s) of class "
                f"{self.spec.name!r} uncommitted < the {need} a "
                f"{len(prompt)}+{max_new}-token request may hold at once")
        bs, J = self.spec.block_size, self.table_width
        n, shared, hashes = self._match(group, prompt, limit)
        lo = n - len(shared)
        row = [DEAD_BLOCK] * J
        for j, b in zip(range(lo, n), shared):
            self._incref(group, b)
            row[j % J] = b
        self._slot_group[slot] = group
        self._slot_need[slot] = need
        self._committed[group] += need
        self._slot_span[slot] = [lo, n]
        # ... from the first block a hit at the longest boundary an
        # IDENTICAL prompt may resume at would read (its last token is
        # always prefilled again), which is no later than what a longer
        # prompt's hit at this one's last full block reads.
        full = len(prompt) // bs
        tail = self.spec.first_block((len(prompt) - 1) // bs * bs)
        self._slot_hashes[slot] = {
            j: hashes[j] for j in range(max(n, tail), full)}
        return AdmitPlan(slot=slot, group=group, table=row,
                         matched=n * bs, shared_blocks=len(shared))

    def extend(self, slot: int, row: np.ndarray, first_pos: int,
               upto_pos: int) -> None:
        """``BlockAllocator.extend`` for a ring: first RETURN the blocks
        that lie wholly behind ``first_pos``'s reach (shared ones lose a
        reference, the stream's own go back to the free list or, entered
        into the prefix cache, are retained), then draw."""
        group = self._slot_group[slot]
        J = self.table_width
        span = self._slot_span[slot]
        keep = self.spec.first_block(first_pos)
        while span[0] < min(keep, span[1]):
            c = span[0] % J
            self._decref(group, int(row[c]))
            row[c] = DEAD_BLOCK
            self.returned += 1
            span[0] += 1
        pending = self._slot_hashes[slot]
        while span[1] <= upto_pos // self.spec.block_size:
            j = span[1]
            assert row[j % J] == DEAD_BLOCK, "a window's ring overran"
            b = row[j % J] = self._draw(group, slot)
            h = pending.pop(j, None)
            if h is not None and h not in self._hash_index[group]:
                self._hash_index[group][h] = b
                self._block_hash[group][b] = h
            span[1] += 1

    def release(self, slot: int, table: Sequence[int]) -> None:
        if slot in self._slot_need:
            self._committed[self._slot_group[slot]] -= \
                self._slot_need.pop(slot)
            del self._slot_span[slot], self._slot_hashes[slot]
        super().release(slot, table)


class ClassAllocators:
    """The allocators of a model's classes of cache layers behind the one
    interface the engine uses (module docstring).  A stream's table row is
    the classes' rows side by side (``columns``); a prefix hit is the
    longest one EVERY class can serve — all of ``[0, n)`` (as far as it
    reaches) cached in a class of pages, a snapshot AT ``n`` in a class of
    states; admission needs every class to cover its own worst case.  No
    class of pages forks a block copy-on-write: a match stops short of the
    block that holds the prompt's last token.  The one class that copies is
    a class of states (``_copier``: a snapshot into the stream's page, the
    page into a snapshot): the plan carries its copy and its snapshot, and
    ``copy_pools`` names the pools they run on."""

    def __init__(self, specs: Sequence[PagedKVCacheSpec]):
        self.classes = [_policy(s)(s) for s in specs]
        self.spec = specs[0]
        states = [a for a in self.classes if a.spec.per_stream]
        if len(states) > 1:
            raise NotImplementedError(
                "two per-stream classes in one model: a plan carries one "
                "page copy")
        self._copier = states[0] if states else None
        self.copy_program = (self._copier or self.classes[0]).copy_program
        self.copy_pools = self._copier.copy_pools if states else tuple(
            name for a in self.classes for name in a.copy_pools)
        self.columns, at = [], 0
        for a in self.classes:
            self.columns.append(slice(at, at + a.table_width))
            at += a.table_width
        self.table_width = at
        self.chain_walks = 0        # as ``BlockAllocator``'s: its own

    # ---- accounting ---- #
    def blocks_in_use(self) -> int:
        return sum(a.blocks_in_use() for a in self.classes)

    def bytes_in_use(self) -> int:
        return sum(a.bytes_in_use() for a in self.classes)

    def available(self, group: int) -> int:
        """The scarcest class's."""
        return min(a.available(group) for a in self.classes)

    @property
    def reclaimed(self) -> int:
        return sum(a.reclaimed for a in self.classes)

    def _merged(self, method: str, *args, **kw) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for a in self.classes:
            out.update(getattr(a, method)(*args, **kw))
        return out

    def class_stats(self) -> Dict[str, Dict[str, int]]:
        return self._merged("class_stats")

    def span_args(self, plans=None, live: int = 0) -> Dict[str, int]:
        """The classes' own, and for admissions into a model of several
        kinds ``prefix_lost_to_kind_tokens``: what the classes of pages
        had cached beyond the boundary the class of states could resume
        at."""
        args = self._merged("span_args", plans, live)
        if plans is not None and self._copier is not None:
            args["prefix_lost_to_kind_tokens"] = sum(
                int(p.lost_to_kind) for p in plans)
        return args

    def snapshot_totals(self) -> Dict[str, int]:
        return self._merged("snapshot_totals")

    # ---- the prefix cache ---- #
    def _agreed(self, group: int, chain: PromptChain, classes=None) -> int:
        """The longest boundary (in blocks) every class of ``classes``
        (all of them by default) can serve a hit at."""
        bs = self.spec.block_size
        hashes = chain.hashes(bs, by=(self,))
        n, before = (len(chain) - 1) // bs, None
        while n != before:
            before = n
            for a in self.classes if classes is None else classes:
                n = a.match_limit(group, hashes, n)
        return n

    def matched_blocks(self, group: int, prompt) -> int:
        return self._agreed(group, PromptChain.of(prompt))

    def can_admit(self, group: int, prompt, max_new: int,
                  spec_k: int = 0) -> bool:
        chain = PromptChain.of(prompt)
        n = self._agreed(group, chain)
        return all(a.can_admit(group, chain, max_new, spec_k, limit=n)
                   for a in self.classes)

    # ---- request lifecycle ---- #
    def admit_prompt(self, slot: int, group: int, prompt,
                     max_new: int, spec_k: int = 0) -> AdmitPlan:
        bs = self.spec.block_size
        chain = PromptChain.of(prompt)
        n = self._agreed(group, chain)
        # (before any class enters this prompt's own blocks in its index)
        pages = [a for a in self.classes if a is not self._copier]
        lost = bs * (self._agreed(group, chain, pages) - n) \
            if pages and self._copier is not None else 0
        row = np.full(self.table_width, DEAD_BLOCK, np.int32)
        cached: Dict[str, int] = {}
        done = []
        try:
            for a, cols in zip(self.classes, self.columns):
                plan = a.admit_prompt(slot, group, chain, max_new, spec_k,
                                      limit=n)
                done.append((a, cols, plan))
                row[cols][:len(plan.table)] = plan.table
                assert plan.matched == n * bs \
                    and (plan.cow_src is None or a is self._copier)
                cached[a.spec.name] = bs * (
                    plan.shared_blocks if a.spec.reach is not None else n)
        except PoolExhausted:
            # what the classes before drew goes back, a state class's
            # uncommitted snapshot page with it
            for a, cols, plan in done:
                a.abandon_snapshot(plan)
                a.release(slot, row[cols])
            raise
        out = AdmitPlan(slot=slot, group=group, table=list(row),
                        matched=n * bs, cached_by_class=cached)
        if self._copier is not None:
            own = next(p for a, _, p in done if a is self._copier)
            out = dataclasses.replace(
                own, table=out.table, cached_by_class=cached,
                copy_class=self._copier.spec.name, lost_to_kind=lost)
        return out

    def extend(self, slot: int, row: np.ndarray, first_pos: int,
               upto_pos: int) -> None:
        for a, cols in zip(self.classes, self.columns):
            a.extend(slot, row[cols], first_pos, upto_pos)

    def release(self, slot: int, table: Sequence[int]) -> None:
        table = np.asarray(table, np.int32)
        for a, cols in zip(self.classes, self.columns):
            a.release(slot, table[cols])

    def commit_snapshot(self, plan: AdmitPlan) -> None:
        """To the class that owns the plan's snapshot page."""
        if self._copier is not None:
            self._copier.commit_snapshot(plan)

    def abandon_snapshot(self, plan: AdmitPlan) -> None:
        if self._copier is not None:
            self._copier.abandon_snapshot(plan)


def class_specs(classes, asked, rows: int, of_class=None, **geometry
                ) -> Tuple[PagedKVCacheSpec, ...]:
    """One spec a CLASS of a model's cache layers (``served.CacheClass``:
    name, layers, reach, per_stream) over the engine's ``geometry`` (the
    spec's other fields) and what ``of_class(cls)`` adds for that class
    (its pools' tiles — and dtypes, where a pool names its own — heads, row
    width: ``ServedModel.class_geometry``).
    ``asked``: ``inference.num_blocks``, an int or {class name: blocks} (0
    or a class left out: full provisioning); ``rows``: the most query rows
    of a stream one program holds — a bounded class's table is a ring as
    wide as they reach, and a state beside classes of pages is worth a
    snapshot once a prompt adds that many (``page_tokens``)."""
    table = geometry["max_len"] // geometry["block_size"]
    beside_pages = not all(cls.per_stream for cls in classes)
    return tuple(PagedKVCacheSpec(
        num_layers=cls.layers, name=cls.name, reach=cls.reach,
        per_stream=cls.per_stream,
        program_rows=rows if cls.per_stream and beside_pages else 0,
        num_blocks=int(asked.get(cls.name, 0) if isinstance(asked, dict)
                       else asked),
        table_blocks=0 if cls.reach is None else min(
            table, (cls.reach + rows - 2) // geometry["block_size"] + 2),
        **{**geometry, **(of_class(cls) if of_class else {})})
        for cls in classes)


def attended_specs(specs: Sequence[PagedKVCacheSpec]
                   ) -> Tuple[PagedKVCacheSpec, ...]:
    """Of a model's classes those whose cost grows with the context (an
    attend walks their rows): a state a stream BESIDE classes of pages
    holds no key rows; a model's only class is priced whatever it is (a
    state's constant)."""
    specs = tuple(specs)
    return tuple(sp for sp in specs if not sp.per_stream) or specs


def _policy(spec: PagedKVCacheSpec) -> type:
    """The allocator class of a pool, from what its spec declares."""
    if spec.per_stream:
        return StateAllocator
    return BlockAllocator if spec.reach is None else BoundedBlockAllocator


def allocator_for(specs: Sequence[PagedKVCacheSpec], spec_k: int = 0):
    """The cache manager of a model's classes of cache layers: the bare
    policy of a model's only class (a ring is always behind the
    composite: its admission is the composite's), else
    ``ClassAllocators`` over one each, of whatever kinds.  ``spec_k``: the engine's
    speculation depth, refused where a class cannot drop rejected rows."""
    per_stream = [s for s in specs if s.per_stream]
    if per_stream and spec_k > 0:
        raise ValueError(
            "inference.spec_k > 0 needs a cache that can drop rejected "
            "rows; this model keeps a state per stream")
    if len(specs) == 1 and specs[0].reach is None:
        return _policy(specs[0])(specs[0])
    return ClassAllocators(specs)


__all__ = ["DEAD_BLOCK", "PagedKVCacheSpec", "paged_partition_spec",
           "paged_shardings", "init_paged_cache", "kv_fold",
           "paged_logical_view", "paged_folded_view", "paged_block_size",
           "paged_layer_view", "copy_pages",
           "positions_to_blocks",
           "block_select", "paged_write_rows", "paged_attend",
           "chain_hash", "chain_hashes", "PromptChain", "PoolExhausted",
           "BlockAllocator",
           "AdmitPlan", "StateAllocator", "BoundedBlockAllocator",
           "ClassAllocators", "class_specs", "attended_specs",
           "allocator_for"]

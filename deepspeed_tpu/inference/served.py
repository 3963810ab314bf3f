"""What ``InferenceEngine`` needs of a model: the served-model interface.

The engine, the scheduler, the block allocator, the prefix cache,
admission, sampling and the spans know nothing of a model's block.  They
ask a ``ServedModel`` for:

- **what it keeps in the paged pool** — ``cache_layers`` (or, where its
  layers differ in how far back they read or in KIND, ``cache_classes``:
  the layers by class, each class's reach and whether it is per stream;
  ``class_geometry`` then answers class by class) and
  ``cache_pools(block_size)``: the pools by name, each with the shape of
  ONE block's tile as held (``[heads, rows, lanes]``, lane-dense) — from
  which ``PagedKVCacheSpec``, the pool arrays, ``block_nbytes``,
  admission's block count and the copy follow.  A pool is declared **per
  token** (the default: a block is ``block_size`` tokens' rows of a
  layer; GPT-2 keeps two, ``k`` and ``v``, per-head rows; the
  latent-attention family one, ``latent``, a ``[ckv | k_rope]`` row
  shared by every head) or **per stream** (``CacheClass.per_stream``: a
  block is a PAGE, one stream's fixed-size state of a layer — the retention
  family's ``state`` and ``norm`` — a block table is one page wide, and
  the prefix cache keeps snapshots: ``inference/kv_cache.py``);
- **its block**, as three hooks the engine's three programs are written
  over, ONCE, in this class: ``embed(params, tokens, pos)``,
  ``forward(params, pools, x, rows, *, paged_kernel, mesh) -> (x, pools,
  counters or None)`` — every layer, writing the new rows into the pools in
  place — and ``head(params, h)``, the final norm and unembedding.  The
  programs are ``decode`` (one token a slot), ``verify`` (K tokens a slot,
  speculative; ``decode`` is its K = 1) and ``prefill_chunk`` (one chunk of
  one slot a group).  What rows a program hands the layers is decided here
  and nowhere else, in ``Rows``' two constructors: the streams' tables
  (dead for an inactive group), every row's position, which rows are
  traffic (``live``), whether they are a prefill chunk, and the snapshot a
  chunk freezes (a model whose per-stream state is a gather of a chunk's
  rows may freeze it at a row INSIDE the chunk into a second page, and
  says so: ``freezes_in_chunk``; one whose cache cannot be rolled back over
  rejected drafts says ``rolls_back = False`` and ``verify`` refuses).
  ``prefill_chunk`` alone stops short of the logits: it returns the hidden
  row at ``last_idx``, because only the chunk program that ENDS a prompt
  has a reader for them.  The engine's ``prefill_step`` applies ``head``
  and samples under a branch on an operand the host sets
  (``head_and_sample``): a program that ends no prompt returns ZEROS for
  its tokens ``[G]`` and its logits ``[G, V]``, and nothing fetches either.
  What several families' ``forward`` share is beside the class:
  ``stream_pages`` / ``filter_rows`` / ``filter_tile`` (a ``per_stream``
  class's page bookkeeping and a short filter's rows through it) here, the
  attention branch over K/V pages of grouped heads in
  ``inference/kv_pages.py``, the latent sublayer in ``inference/latent.py``;
- **the cache's cost a token** for the engine's analytic counters:
  ``cache_cost(keys, ...)`` = (FLOPs, cache bytes) a layer spends on one
  query token, which MAY depend on the ``keys`` rows in reach (an attend:
  ``attend_dims`` = (query heads, score width, value width), linear in
  ``keys``; a state: a constant), and ``attend_step_counts``;
- **counters** a program returns beside its logits (``counter_names``):
  int32 scalars that ride the token fetch — the expert layer's held-row
  counts for the latent family, none for GPT-2;
- **probes** (``probe_names``; most models: none): arrays of what a layer
  DECIDED for a row — the blocks a selection chose — that ``decode`` and
  ``prefill_chunk`` return behind the counters, one row a slot (a chunk:
  the row at ``last_idx``, as its logits are), and the engine's programs
  behind their logits: like the logits they stay on the device unless a
  check asks (``return_logits`` fetches both: ``engine.last_probes``).  A
  model with none returns what it returned and its programs are text for
  text what they were.

``served_model(x)`` resolves what a caller hands the engine: a
``ServedModel`` as it is; a model config through the registry
(``register``), which an implementation fills at import; a config that
names its implementation's module (``serving_module``) has that module
imported first — so a model nobody serves costs no import.
"""
from __future__ import annotations

import importlib
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import kv_cache
from ..ops import paged_attention as paged_attn_ops

_IMPLEMENTATIONS: Dict[type, Callable[[Any], "ServedModel"]] = {}


class CacheClass(NamedTuple):
    """A class of a model's cache layers: its name ("" for a model's only
    class), how many layers it holds, how many tokens back a query of
    these layers reads, itself included (None: all of them), and whether
    ``cache_pools`` declares a stream's whole state of a layer, of fixed
    size (a page), not a block of tokens' rows."""
    name: str
    layers: int
    reach: Optional[int] = None
    per_stream: bool = False


class ServedModel:
    """Base of the implementations; see the module docstring."""
    cfg: Any
    counter_names: Tuple[str, ...] = ()
    # The arrays ``forward`` returns behind its counters (module docstring:
    # probes), each ``[S, K, ...]``.
    probe_names: Tuple[str, ...] = ()
    # Each class's table width, in ``cache_classes`` order: the ENGINE sets
    # it when it has sized the tables (a window's ring depends on its
    # prefill chunk); a model of several classes splits a table row by it.
    table_widths: Optional[Tuple[int, ...]] = None
    # Whether ``prefill_chunk`` can FREEZE a ``per_stream`` state at a row
    # inside its chunk into a second page (it then takes two more ``[G]``
    # int32 operands after ``active``: the chunk row the frozen state ends
    # at, and the page that receives it, ``DEAD_BLOCK`` for none).  The
    # engine then leaves a snapshot from the chunk program that passes its
    # boundary; where a model says no (the default) it cuts the prompt
    # there and copies the stream's page.
    freezes_in_chunk: bool = False
    # Whether the cache can be rolled back over rejected drafts (rows past
    # the accepted ones are overwritten before anything reads them).  A
    # fixed-size state a stream cannot: ``verify`` refuses, and
    # ``inference.spec_k`` must be 0.
    rolls_back: bool = True
    # A model generated by diffusion over blocks says how long its blocks
    # are: a row then attends through the END of its block
    # (``Rows.sees``), and a stream's step is a PASS over a block of this
    # many rows (``block_step``), which the engine's ``decode_step`` is
    # then built over.  None: a causal model, one token a stream a step.
    block_length: Optional[int] = None

    def __init__(self, cfg):
        self.cfg = cfg

    # -- identity and limits ------------------------------------------ #
    @property
    def name(self) -> str:
        return self.cfg.name

    @property
    def dtype(self):
        return self.cfg.dtype

    @property
    def max_positions(self) -> int:
        raise NotImplementedError

    @property
    def init_fn(self) -> Callable:
        """``init_fn(rng, cfg) -> params``: the tree's structure, for
        ``from_train_checkpoint``."""
        raise NotImplementedError

    # -- the cache ----------------------------------------------------- #
    @property
    def cache_layers(self) -> int:
        raise NotImplementedError

    @property
    def cache_heads(self) -> int:
        """Heads of a cache row (sharded over the model axis)."""
        raise NotImplementedError

    @property
    def cache_row_width(self) -> int:
        """Values a head keeps per token, layer and pool."""
        raise NotImplementedError

    def cache_pools(self, block_size: int
                    ) -> Tuple[Tuple[str, Tuple[int, int, int]], ...]:
        """((pool name, one block's tile as held [heads, rows, lanes]),
        ...).  A pool whose dtype is not ``inference.kv_cache_dtype``'s to
        choose (a recurrent state is fp32 whatever the rows' dtype) names
        it: (name, tile, dtype)."""
        raise NotImplementedError

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        """The model's cache layers by CLASS (``inference/kv_cache.py``):
        each class gets ``cache_pools`` of its own (named ``<pool>.<class>``),
        its own block table, free list and prefix index; the programs get
        the pools class by class and each table row as the classes' rows
        side by side.  One unnamed, unbounded class of ``cache_layers``
        layers unless a model says otherwise."""
        return (CacheClass("", self.cache_layers),)

    @property
    def token_row_bytes(self) -> int:
        """Per-stream pools: bytes a token of this model would keep a
        layer as K/V rows (what a snapshot page is weighed against)."""
        return 0

    def class_geometry(self, cls: CacheClass, block_size: int
                       ) -> Dict[str, Any]:
        """What the pools of class ``cls`` hold, as its
        ``PagedKVCacheSpec`` takes it: ``pools`` (``cache_pools``),
        ``num_heads`` / ``head_dim`` (a cache row's heads and logical
        width) and ``token_row_bytes``.  One answer for every class unless
        a model's classes differ in KIND (K/V pages beside a state a
        stream: a page's tile is not a block's)."""
        return dict(pools=self.cache_pools(block_size),
                    num_heads=self.cache_heads,
                    head_dim=self.cache_row_width,
                    token_row_bytes=self.token_row_bytes)

    # -- the cache's analytic cost a token ------------------------------ #
    def cache_cost(self, keys: int, block_size: int, itemsize: int
                   ) -> Tuple[int, int]:
        """(FLOPs, cache bytes) a layer spends on ONE query token with
        ``keys`` key rows in reach."""
        return (self.attend_flops(keys),
                self.attend_bytes(keys, block_size, itemsize))

    @property
    def attend_dims(self) -> Tuple[int, int, int]:
        """(query heads, width a score contracts, width a value row
        has)."""
        raise NotImplementedError

    def attend_flops(self, keys: int) -> int:
        """FLOPs a layer's attend spends on ONE query token over
        ``keys`` key rows: scores + weighted values."""
        nq, ws, wv = self.attend_dims
        return 2 * nq * (ws + wv) * int(keys)

    def attend_bytes(self, keys: int, block_size: int, itemsize: int
                     ) -> int:
        """Cache bytes a layer's attend streams for ``keys`` key rows."""
        per_block = sum(math.prod(pool[1])
                        for pool in self.cache_pools(block_size))
        return per_block * int(keys) * int(itemsize) // block_size

    def attend_step_counts(self, live_blocks, *, K: int, spec, mp: int,
                           q_itemsize: int, calls: int = 1
                           ) -> Tuple[int, int, int]:
        """(steps, live steps, live steps nothing started: cold) the
        attend kernel sequences for one layer, in ``calls`` calls (the
        shards of a dp mesh), host integers."""
        raise NotImplementedError

    def write_step_counts(self, first_pos, rows, *, K: int, spec, mp: int
                          ) -> Optional[Tuple[int, int, int]]:
        """(rows, runs, grid steps) of one layer's K/V write into the pages
        of class ``spec`` (``ops.paged_attention.write_step_counts``) for
        streams of K rows from ``first_pos`` [streams], ``rows`` [streams]
        of them live — host integers; None: the model keeps no K/V pages
        that write lands."""
        return None

    def _kv_write_step_counts(self, first_pos, rows, *, K, spec, mp):
        """``write_step_counts`` of a model whose ``forward`` lands its K/V
        rows through ``kv_cache.paged_write_rows``, a program's K rows a
        stream (a block of a model of blocks in one page where it divides
        the page: ``Rows.one_block``)."""
        return paged_attn_ops.write_step_counts(
            first_pos, rows, K=K, block_size=spec.block_size,
            one_block=K == self.block_length and spec.block_size % K == 0,
            num_heads=max(1, spec.num_heads // mp), head_dim=spec.head_dim)

    def attend_run_rows(self, K: int) -> int:
        """Query rows of a prefill chunk of K rows that share ONE walk of
        the stream's key rows: all of them unless a model's attend takes a
        chunk in runs (``inference/kv_pages.attend_rows``), each of which
        walks its own reach."""
        return K

    def counter_args(self, rows) -> Dict[str, Any]:
        """Span args / running-mean samples from fetched counters ``rows
        [executions, len(counter_names)]`` (int64)."""
        return {}

    def _widths(self, table) -> Tuple[int, ...]:
        """``table_widths`` as a program splits a table row by them (a
        model of one class: the row's own width)."""
        widths = self.table_widths
        if widths is None and len(self.cache_classes) == 1:
            widths = (table.shape[-1],)
        if widths is None or sum(widths) != table.shape[-1]:
            raise ValueError(
                f"{type(self).__name__}: a table row {table.shape[-1]} wide "
                f"against class widths {widths}: the engine sets "
                "table_widths when it sizes the tables")
        return widths

    # -- the model's own: what the programs below are written over ------ #
    def embed(self, params, tokens, pos):
        """Hidden rows ``[..., H]`` (or more trailing axes: several residual
        streams) of ``tokens`` at positions ``pos``, both ``[S, K]`` (a
        chunk: ``[G, C]``), under the scope ``embed``."""
        raise NotImplementedError

    def forward(self, params, pools: Sequence, x, rows: "Rows", *,
                paged_kernel: bool, mesh):
        """All layers over ``x [S, K, ...]`` and its ``rows``: the new cache
        rows and pages written into ``pools`` in place, for live rows only
        — a dead row writes nothing, attends nothing and is not counted;
        what it computes nobody reads.  Returns (``x'`` as ``head`` takes
        it, ``pools'``, the model's counters or None) and, from a model with
        ``probe_names``, a tuple of those arrays ``[S, K, ...]`` behind
        them."""
        raise NotImplementedError

    def head(self, params, h):
        """fp32 logits ``[..., V]`` of hidden rows as ``forward`` leaves
        them: the model's final norm and unembedding, under the scope
        ``lm_head``."""
        raise NotImplementedError

    # -- the programs -------------------------------------------------- #
    def decode(self, params, pools: Sequence, tokens, lengths,
               block_tables, *, num_groups: int, paged_kernel: bool,
               mesh=None):
        """One token a slot, the K = 1 ``verify``: ``tokens`` / ``lengths``
        ``[S]`` -> (logits ``[S, V]`` fp32, pools, counters[, probes, a
        row ``[S, ...]`` each]).  The caller advances ``lengths`` for the
        slots it considers active."""
        logits, pools, counters, *probes = self._rows_a_slot(
            params, pools, tokens[:, None], lengths, block_tables,
            num_groups, paged_kernel, mesh)
        return (logits[:, 0], pools, counters,
                *(tuple(p[:, 0] for p in ps) for ps in probes))

    def verify(self, params, pools: Sequence, tokens, lengths,
               block_tables, *, num_groups: int, paged_kernel: bool,
               mesh=None):
        """The speculative verify step: ``tokens [S, K]`` — column 0 each
        slot's pending last token, columns 1.. the drafted continuation;
        token i sits at position ``lengths[s] + i``.  Writes all K tokens'
        rows through the block table, attends each under its own causal
        row, and returns fp32 logits ``[S, K, V]`` (never a ``[max_len,
        vocab]`` tensor); with ``spec_accept`` its greedy output is
        bit-identical to single-token decode."""
        if not self.rolls_back:
            raise NotImplementedError(
                f"{type(self).__name__}: a state a stream cannot be rolled "
                "back over rejected drafts: set inference.spec_k to 0")
        return self._rows_a_slot(params, pools, tokens, lengths,
                                 block_tables, num_groups, paged_kernel,
                                 mesh)

    def _rows_a_slot(self, params, pools, tokens, lengths, block_tables,
                     num_groups, paged_kernel, mesh):
        rows = Rows.of_slots(lengths, block_tables, tokens.shape[1],
                             num_groups, self._widths(block_tables),
                             self.block_length)
        x, pools, counters, *probes = self._layers(
            params, pools, tokens, rows, paged_kernel, mesh)
        return (self.head(params, x), pools, counters, *probes)

    def _layers(self, params, pools, tokens, rows, paged_kernel, mesh):
        x = self.embed(params, tokens, rows.positions.reshape(tokens.shape))
        return self.forward(params, pools, x, rows,
                            paged_kernel=paged_kernel, mesh=mesh)

    def prefill_chunk(self, params, pools: Sequence, tokens, bt_rows,
                      start, last_idx, active, freeze_idx=None,
                      freeze_page=None, *, paged_kernel: bool, mesh=None):
        """Group-batched chunked prefill: one prompt chunk for ONE slot a
        group.  ``tokens [G, C]``; ``bt_rows [G, W]``, each group's target
        slot's table row; ``start`` / ``last_idx`` / ``active`` ``[G]``.
        Writes the chunk's rows through its group's table and attends
        against the slot's whole cached row under the global-position
        causal mask, so any chunk length divides any prompt without shape
        polymorphism.  An inactive group computes garbage that writes
        nowhere — the uniform-program rule that keeps ONE compiled shape for
        any admission pattern — and rows past ``last_idx`` (a last chunk's
        padding) are dead: whatever cache rows a model lets them write, the
        next token's decode write overwrites before any attend reaches them.
        A state a stream starts from what the stream's own page holds (a
        snapshot the engine copied there, or the chunk before) and from
        zeros at position 0.  ``freezes_in_chunk``: a group's state as it
        stands after chunk row ``freeze_idx`` (the last row of a block)
        goes into page ``freeze_page`` as well (``DEAD_BLOCK``: none in
        this chunk; without the operands: the stream's own page only).

        Returns (the hidden row at ``last_idx`` as ``head`` takes it, ``[G,
        ...]``; pools; counters[; the probes' rows at ``last_idx``]) — NOT
        logits: only ONE position a group
        ever projects through the unembedding (never a ``[C, vocab]``
        tensor), and only in the chunk program that ends a prompt: the
        caller applies ``head`` under a branch (``head_and_sample``)."""
        rows = Rows.of_chunk(
            bt_rows, start, last_idx, active, tokens.shape[1],
            self._widths(bt_rows),
            None if freeze_idx is None else (freeze_idx, freeze_page),
            self.block_length)
        x, pools, counters, *probes = self._layers(
            params, pools, tokens, rows, paged_kernel, mesh)

        def row_at_last(p):
            """``p [G, C, ...]`` (integers: a gather) -> ``[G, ...]``."""
            at = last_idx.reshape((-1,) + (1,) * (p.ndim - 1))
            return jnp.take_along_axis(p, at, axis=1)[:, 0]
        return (Rows.last(x, last_idx), pools, counters,
                *(tuple(map(row_at_last, ps)) for ps in probes))


def register(config_type: type,
             factory: Callable[[Any], ServedModel]) -> None:
    """An implementation's module calls this at import."""
    _IMPLEMENTATIONS[config_type] = factory


def served_model(model: Any) -> ServedModel:
    """The served model of what the engine was handed (module
    docstring)."""
    if isinstance(model, ServedModel):
        return model
    module = getattr(model, "serving_module", None)
    if module and type(model) not in _IMPLEMENTATIONS:
        importlib.import_module(module)
    for config_type, factory in _IMPLEMENTATIONS.items():
        if isinstance(model, config_type):
            return factory(model)
    raise TypeError(
        f"no served-model implementation for {type(model).__name__}: pass "
        "a ServedModel, or a config whose implementation is registered "
        "(inference.served.register)")


def held_counter_args(rows, cells: int, routed: int) -> Dict[str, Any]:
    """What an expert layer's four counters say of the executions fetched
    (``rows [executions, 4+]``: routed pairs that landed on held experts, the
    largest rows a held expert got in a layer, held experts x layers that got
    no row, live rows routed): the pairs, the largest and the MEAN rows an
    expert got over ``cells`` (executions x expert layers x experts held),
    the empty ones, and the pairs' share of the ``routed`` pairs (live rows x
    experts a token x expert layers).  One set of names for every family
    whose layers hold experts, whatever share."""
    pairs = int(rows[:, 0].sum())
    return {"moe_held_pairs": pairs,
            "moe_held_max": int(rows[:, 1].max()),
            "moe_held_mean": pairs / cells if cells else 0.0,
            "moe_held_empty": int(rows[:, 2].sum()),
            "moe_held_pair_share": pairs / routed if routed else 0.0}


def split_counters(fetched, n: int):
    """A program's int32 fetch ``[tokens..., counters...]`` -> (tokens,
    counters or None)."""
    return (fetched[:-n], fetched[-n:]) if n else (fetched, None)


def with_counters(sampled, counters):
    """The one array a program's fetch rides: the sampled tokens with the
    model's counters appended (nothing for a model without)."""
    if counters is None or not len(counters):
        return sampled
    return jnp.concatenate([sampled.reshape(-1).astype(jnp.int32),
                            jnp.stack(list(counters)).astype(jnp.int32)])


# --------------------------------------------------------------------- #
# What every implementation's programs share
# --------------------------------------------------------------------- #
# Same masking constant as dense_attention. A NumPy scalar: a jnp one
# would initialise a JAX backend (and take the chip) at import.
NEG_INF = np.float32(-1e9)


def group_shape(arr: jax.Array, num_groups: int) -> jax.Array:
    """[S, ...] → [G, S/G, ...]: split the slot axis into (group,
    slot-in-group) — a local reshape under the slots-over-dp sharding."""
    return arr.reshape((num_groups, arr.shape[0] // num_groups)
                       + arr.shape[1:])


def write_targets(bt_g: jax.Array, pos_g: jax.Array, block_size: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """(block, offset) of every new row: bt_g [G, Sg, J], pos_g
    [G, Sg, K] -> two [G, Sg*K]."""
    G, Sg, K = pos_g.shape
    bt_rows = jnp.broadcast_to(bt_g[:, :, None, :],
                               (G, Sg, K, bt_g.shape[-1]))
    blk, off = kv_cache.positions_to_blocks(bt_rows, pos_g, block_size)
    return blk.reshape(G, Sg * K), off.reshape(G, Sg * K)


def _block_end(pos: jax.Array, block_length: int) -> jax.Array:
    """The last position of the block of ``block_length`` each of ``pos``
    lies in."""
    return pos // block_length * block_length + (block_length - 1)


class Rows(NamedTuple):
    """What a program hands every layer, for S streams (``Sg`` a group) of
    K rows each: ``tables`` [G, Sg, W], the streams' table rows
    (``DEAD_BLOCK`` throughout for a dead slot or an inactive group: its
    writes land nowhere), the model's classes' columns side by side,
    ``widths`` wide (``cache_classes`` order); ``positions`` [G, Sg, K];
    ``live`` [S, K]: the rows that are traffic — a live stream's, and no
    padding; a stream's live rows come first; ``sees`` [G, Sg, K]: the last
    position each row may ATTEND, inclusive — its own (the same array as
    ``positions``) under a causal mask; the end of its block of
    ``block_length`` positions for a model whose rows see each other inside
    a block (``ServedModel.block_length``); ``chunked``: the rows are a
    prefill chunk of one stream a group (a state advances by a scan over
    them) and not one row — or K drafted ones — of every slot; ``freeze``:
    (row [S], page [S]) — a stream's state as it stands after chunk row
    ``row`` goes into ``page`` too (a snapshot; ``DEAD_BLOCK``: none), or
    None; ``one_block``: a stream's K rows ARE one block of a model of
    blocks — its first position is a multiple of K (the engine's word: a
    prompt's prefill ends at a block boundary and a commit advances a
    length by a block), so where K divides a page the K/V write lands them
    in one step.  Built by the two constructors below and by nothing else:
    what rows a program computes is decided here."""
    tables: jax.Array
    widths: Tuple[int, ...]
    positions: jax.Array
    live: jax.Array
    sees: jax.Array
    chunked: bool = False
    freeze: Optional[Tuple[jax.Array, jax.Array]] = None
    one_block: bool = False

    @classmethod
    def of_slots(cls, lengths, block_tables, K: int, num_groups: int,
                 widths, block_length: Optional[int] = None) -> "Rows":
        """K rows of every slot: row i of slot s sits at ``lengths[s] + i``;
        a slot whose table ``[S, W]`` holds no block is dead."""
        pos = lengths[:, None] + jnp.arange(K, dtype=jnp.int32)[None]
        live = jnp.broadcast_to(
            (block_tables >= 0).any(axis=1, keepdims=True), pos.shape)
        tables = group_shape(block_tables, num_groups)
        pos = group_shape(pos, num_groups)
        return cls(tables, widths, pos, live, pos if block_length is None
                   else _block_end(pos, block_length),
                   one_block=K == block_length)

    @classmethod
    def of_chunk(cls, bt_rows, start, last_idx, active, width: int, widths,
                 freeze=None, block_length: Optional[int] = None) -> "Rows":
        """A chunk of ``width`` rows of ONE slot a group, from position
        ``start``: a group that is not ``active`` is dead, rows past
        ``last_idx`` are padding (and nothing sees them: a row of a block
        the chunk's live rows end in sees no further than the last of
        them)."""
        G = bt_rows.shape[0]
        pos = start[:, None] + jnp.arange(width, dtype=jnp.int32)[None]
        tables = jnp.where(active[:, None, None] > 0, bt_rows[:, None],
                           kv_cache.DEAD_BLOCK)
        live = (active[:, None] > 0) & (lax.broadcasted_iota(
            jnp.int32, (G, width), 1) <= last_idx[:, None])
        pos = pos[:, None, :]
        sees = pos if block_length is None else jnp.minimum(
            _block_end(pos, block_length),
            (start + last_idx)[:, None, None])
        return cls(tables, widths, pos, live, sees, True, freeze)

    @staticmethod
    def last(x, last_idx) -> jax.Array:
        """``x [G, C, ...]`` -> the row at ``last_idx [G]`` of each group's
        chunk, ``[G, ...]``, as a one-hot contraction (no gather; any
        trailing axes: the latent family's several residual streams)."""
        G, C = x.shape[:2]
        oh = (lax.broadcasted_iota(jnp.int32, (G, C), 1)
              == last_idx[:, None]).astype(x.dtype)
        picked = jnp.einsum("gc,gch->gh", oh, x.reshape(G, C, -1))
        return picked.reshape((G,) + x.shape[2:])


class StreamPages(NamedTuple):
    """Where one program reads and writes the pages of a ``per_stream``
    class, a stream a row (S streams, ``Sg`` a group): ``group`` and
    ``page`` [S] index a pool's ``[layer, group, page]`` (page 0 for a
    stream that has none: what is read there nobody uses); ``wrote`` [S]:
    the stream has a page and a live row; ``carried`` [S]: its rows
    continue it (position > 0) — else it starts from zeros whatever the
    page holds; ``to``: the page each copy of the new state goes to — the
    stream's own and, where the program freezes a snapshot, the
    snapshot's — with the pool's page COUNT (out of range: a dropped
    write) for a stream that wrote none; ``keep`` [S, held]: for each of
    ``to``, the rows of ``[held | new rows]`` that are the filter's state
    there; ``keep_chunk`` [S]: the sub-chunk of a scan after which the
    snapshot's state stands (None without one).

    Two kinds of lines read it.  Plain ``jax.numpy`` (every prefill chunk,
    the CPU, a tile no kernel takes) gathers ``page`` and scatters to ``to``:
    a stream that wrote nothing costs a read of page 0 and a write DROPPED
    by the out-of-range index, because a scatter has one shape for all S
    streams.  The decode programs' in-place kernels (``ops.kda`` /
    ``ops.ssm_scan.state_update``, ``ops.filter_rows.shift_rows``) take
    ``wrote`` itself and SKIP such a stream: a copy that is never started
    costs nothing, and page 0 may be another stream's — a kernel that
    rewrote it with what it read would race that stream's own write."""
    group: jax.Array
    page: jax.Array
    wrote: jax.Array
    carried: jax.Array
    to: Tuple[jax.Array, ...]
    keep: Tuple[jax.Array, ...]
    keep_chunk: Optional[jax.Array] = None


def stream_pages(page: jax.Array, pos: jax.Array, live: jax.Array,
                 num_pages: int, streams_a_group: int, held: int,
                 freeze=None, scan_rows: Optional[int] = None
                 ) -> StreamPages:
    """``page`` [S]: the streams' table column (``DEAD_BLOCK``: none);
    ``pos`` / ``live`` [S, K]: the rows' positions and which are traffic (a
    stream's live rows come first); ``held``: the rows a short filter keeps
    (its taps - 1).  ``freeze``: (row [S], page [S]) — the state as it
    stands after chunk row ``row`` goes into ``page`` too (``DEAD_BLOCK``:
    none); ``scan_rows``: the sub-chunk of the model's scan, whose carried
    states are the only ones a snapshot can take."""
    S, K = pos.shape
    n_live = live.sum(axis=1).astype(jnp.int32)                  # [S]
    wrote = (page >= 0) & (n_live > 0)

    def ending_after(n):
        """The filter's state once ``n`` of a stream's rows are consumed:
        the ``held`` rows up to there, in [held | rows]."""
        return n[:, None] + jnp.arange(held, dtype=jnp.int32)[None]
    to = [jnp.where(wrote, page, num_pages)]
    keep = [ending_after(jnp.maximum(n_live, 1))]
    keep_chunk = None
    if freeze is not None:
        row, snap = freeze
        to.append(jnp.where(wrote & (snap >= 0), snap, num_pages))
        keep.append(ending_after(jnp.clip(row + 1, 1, K)))
        if scan_rows is not None:
            keep_chunk = jnp.clip((row + 1) // scan_rows - 1, 0,
                                  K // scan_rows - 1)
    return StreamPages(
        jnp.arange(S, dtype=jnp.int32) // streams_a_group,
        jnp.maximum(page, 0), wrote, pos[:, 0] > 0, tuple(to), tuple(keep),
        keep_chunk)


def filter_tile(held: int, width: int) -> Tuple[int, int, int]:
    """A page's tile of one layer's short-filter rows as held: ``held``
    rows (the filter's taps - 1) of ``width`` channels, row-major, in rows
    of 128 lanes where they divide (a ``[held, width]`` minor pair would be
    padded to the sublane tile) — the form ``ops.filter_rows.takes``."""
    n = held * width
    return (1, n // 128, 128) if n % 128 == 0 else (1, held, width)


# ``filter_rows`` calls that lowered to the in-place kernel.  The choice is
# made while a program is traced, so this counts traces, not executions: the
# engine reads it around its decode program's trace for the ``decode``
# span's ``filter_rows_in_place``.
filter_rows_lowered = {"in_place": 0}


def filter_rows(sp: StreamPages, pool: jax.Array, layer, new: jax.Array, *,
                paged_kernel: bool = False, mesh=None
                ) -> Tuple[jax.Array, jax.Array]:
    """A short filter's rows through its pages: ``new`` [S, K, C] behind
    the ``held`` rows the stream's page of ``pool [layers, groups, pages,
    *tile]`` holds (zeros for a stream that starts here) -> (``[S, held +
    K, C]`` in ``new``'s dtype, the pool with the rows that end at the
    last live row — and at a snapshot's row — written to ``sp.to``).

    Which lines a program takes follows from what it hands over.  A DECODE
    program on the chip (``paged_kernel``, K = 1, one page to write: no
    snapshot, and a pool whose tile ``ops.filter_rows.takes``) rewrites the
    pages in place by one kernel a layer, ``ops.filter_rows.shift_rows``:
    on the chip the gather, select, concatenate, ``take_along_axis`` and
    scatter below are a dozen passes over the rows (each a relayout between
    the page's tile and ``[S, held + K, C]``, or a gather), and a decode
    step does little else per page.  Every other program keeps the lines
    below: a prefill chunk (K in the hundreds for one stream a group, where
    the page is a small share of the rows, and a snapshot's second page), a
    tile the kernel cannot take, the CPU — and the tests, which hold the
    kernel to
    these lines bit for bit.  The two differ only where nobody looks: the
    kernel hands a stream that ``wrote`` nothing zeros for its old rows and
    skips its write, where these lines read page 0 for it and drop the
    write by the out-of-range index in ``sp.to``."""
    S, K, C = new.shape
    held = sp.keep[0].shape[1]
    if paged_kernel and K == 1 and len(sp.to) == 1:
        from ..ops import filter_rows as in_place
        if in_place.takes(pool.shape, pool.dtype, held, new.dtype):
            G = pool.shape[1]
            filter_rows_lowered["in_place"] += 1
            rows, pool = in_place.shift_rows(
                pool, layer,
                group_shape(jnp.where(sp.wrote, sp.page, -1), G),
                group_shape(sp.carried, G), group_shape(new[:, 0], G),
                held=held, mesh=mesh)
            return jnp.moveaxis(rows.reshape(held + 1, S, C), 0, 1), pool
    old = pool[layer, sp.group, sp.page].reshape(S, held, -1)
    old = jnp.where(sp.carried[:, None, None], old, 0)
    rows = jnp.concatenate([old.astype(new.dtype), new], axis=1)
    for kept, where in zip(sp.keep, sp.to):
        tail = jnp.take_along_axis(rows, kept[:, :, None], axis=1)
        pool = pool.at[layer, sp.group, where].set(
            tail.reshape((S,) + pool.shape[3:]).astype(pool.dtype),
            mode="drop")
    return rows, pool


# Sampling (in-graph; PRNG threaded by the engine per iteration)
@jax.named_scope("sample")
def sample_tokens(logits: jax.Array, key: jax.Array,
                  temperature: jax.Array) -> jax.Array:
    """Greedy (temperature == 0) or temperature sampling; logits
    [..., V] fp32. Temperature is a TRACED scalar, so changing it never
    recompiles, and the program BRANCHES on it: a greedy step draws no
    noise over the vocabulary (at the published vocabularies the draw was
    most of what ``sample`` cost: PERF.md section 6, PR 55), a sampling
    step computes no argmax."""
    def draw(logits):
        t = jnp.maximum(temperature.astype(jnp.float32), 1e-6)
        return jax.random.categorical(key, logits / t, axis=-1)

    def greedy(logits):
        return jnp.argmax(logits, axis=-1)

    return lax.cond(temperature > 0, draw, greedy, logits).astype(jnp.int32)


def head_and_sample(read: jax.Array, head: Callable, h: jax.Array,
                    key: jax.Array, temperature: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """(tokens ``[G]`` int32, logits ``[G, V]`` fp32) of a chunk program's
    last hidden rows ``h [G, ...]`` — where somebody reads them.  ``read``
    is a scalar operand of the program: nonzero in a dispatch that ENDS
    some group's prompt, which then runs ``head(h)`` and ``sample_tokens``
    as every chunk program used to; any other dispatch takes the other
    branch and returns ZEROS of the same shapes (nobody fetches them: the
    host knows which program ended a prompt).  One compiled program either
    way; on the chip only the taken branch runs."""
    def live(h):
        logits = head(h)
        return sample_tokens(logits, key, temperature), logits

    def unread(h):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            jax.eval_shape(live, h))

    return lax.cond(read > 0, live, unread, h)


@jax.named_scope("sample")
def spec_accept(logits: jax.Array, tokens: jax.Array, key: jax.Array,
                temperature: jax.Array) -> jax.Array:
    """In-graph draft acceptance: the longest agreeing prefix rule.

    logits: [S, K, V] from the verify step over [last, d_1..d_{K-1}];
    tokens: the [S, K] verify input. Greedy target g[s,i] =
    argmax(logits[s,i]); draft d_i is accepted iff every d_{i'<=i}
    matched g at its position, and the emitted stream is g[s, :m+1]
    (accepted drafts ARE the greedy tokens, plus the first correction /
    bonus) — which is exactly what non-speculative greedy decode would
    have produced token by token. Returns [S, K+1] int32: column 0 is
    n_new (how many of the following tokens are real), columns 1..K the
    emitted tokens — one array, ONE host fetch per iteration.
    """
    S, K = tokens.shape
    g = sample_tokens(logits, key, temperature)          # [S, K]
    match = (tokens[:, 1:] == g[:, :-1]).astype(jnp.int32)   # [S, K-1]
    acc = jnp.cumprod(match, axis=-1).sum(-1) if K > 1 else \
        jnp.zeros((S,), jnp.int32)
    n_new = (acc + 1).astype(jnp.int32)                  # [S]
    return jnp.concatenate([n_new[:, None], g], axis=-1)


__all__ = ["CacheClass", "ServedModel", "register", "served_model",
           "split_counters", "with_counters", "held_counter_args", "NEG_INF",
           "group_shape",
           "write_targets", "Rows", "StreamPages", "stream_pages",
           "filter_tile", "filter_rows", "filter_rows_lowered",
           "sample_tokens", "head_and_sample", "spec_accept"]

"""The ``sdar_moe`` family as a served model (inference/served.py): a
Qwen3-MoE layer over per-head K/V pages of grouped query heads
(``inference/kv_pages.py``, as the afmoe, lfm2 and falcon-h1 families keep
them) with every expert held (``moe/share.py``) — generated by DIFFUSION
OVER BLOCKS: a stream's step is not one token but a PASS over a block of
``block_length`` positions that see each other.

What that asks of the served-model interface, and where it is answered:

- ``block_length``: ``served.Rows`` gives every row the END of its block as
  the last position it attends (``Rows.sees``), in the slots' program and in
  a prefill chunk alike; the attend takes each row's own limit already, and
  a program's rows are written before it attends, so no kernel changes;
- ``block_step``: the engine's ``decode_step`` for this model.  ``B`` rows a
  slot through ``_rows_a_slot`` (the base class's, the K-row ``verify``
  form), then under scope ``unmask`` the per-slot update of the block: at
  the still-undecided positions the proposal (``sample_tokens``: the
  argmax when greedy) and its confidence ``softmax(logits)[x0]`` in fp32,
  and the rule's choice of which to keep (``models.sdar.unmask``).  A slot
  that comes in with nothing undecided COMMITS: the rows this pass wrote —
  computed from the block's final tokens — are the block's K/V, the caller
  advances its length by ``B``, and its next block starts all undecided.
  The rows of a denoise pass are written too (through the same in-place
  write, past the stream's length) and are overwritten by the commit's.
  Slots in different passes of their blocks ride one program of one shape.
  The head is applied to all ``S x B`` rows of every pass, a committing
  slot's included (its logits decide nothing): which slots commit is known
  only as data, and a program of one shape cannot leave their rows out.
- a block in progress is an int32 row ``[B]`` of token ids with ``-1`` at
  an undecided position (never the mask token's id: a proposal may BE that
  id, and must then count as decided); the mask token's embedding goes in
  where the program embeds it.

A prefill chunk is the base class's under ``Rows.sees``; the engine ends a
prompt's prefill at the last block boundary and samples nothing.

Scopes: ``embed``; ``attn`` > ``qkv_proj``, ``kv_write``, ``attend_full``,
``out_proj``; ``moe`` > ``router``, ``dispatch``, ``experts``, ``combine``;
``lm_head``; ``unmask`` (> ``sample``).  Counters (they ride the fetch):
the expert layers' held-row counts under the names the afmoe family's have,
and of a block step the live rows it computed, the slots that committed
and the positions it unmasked.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from .kv_pages import GqaPagedServed, write_and_attend
from .served import CacheClass, Rows, register, sample_tokens
from ..models import sdar
from ..models.blocks import matmul, rms_norm
from ..models.sdar import SdarConfig
from ..moe import share

UNDECIDED = -1
FULL_CLASS = "full"       # the one class of cache layers, under the name
#                           the families of several classes give its kind


class SdarServed(GqaPagedServed):
    """See the module docstring."""
    counter_names = ("moe_held_pairs", "moe_held_max", "moe_held_empty",
                     "moe_rows", "block_rows", "commits", "unmasked")
    # Nothing is drafted and nothing accepted: ``verify`` has no meaning
    # here, and the engine refuses ``spec_k`` for a model of blocks.
    rolls_back = False

    def __init__(self, cfg: SdarConfig):
        super().__init__(cfg)
        self.block_length = int(cfg.block_length)

    @property
    def init_fn(self) -> Callable:
        return sdar.sdar_init

    @property
    def cache_classes(self):
        return (CacheClass(FULL_CLASS, self.cache_layers),)

    @property
    def runs_ahead(self) -> bool:
        """Whether the host knows from the schedule alone which pass of a
        block commits (the static rule): the engine then dispatches a pass
        before it fetches the one before.  Under the dynamic rule the
        commit is the device's news."""
        return self.cfg.remasking == "low_confidence_static"

    def denoise_passes(self, undecided: int) -> int:
        """Denoise passes the static schedule gives a block that starts
        with ``undecided`` positions undecided (its commit pass comes
        after them)."""
        return min(int(self.cfg.denoising_steps), int(undecided))

    def counter_args(self, rows) -> Dict[str, Any]:
        """The afmoe family's expert-load args, and of block steps
        ``block_rows``, ``commits``, ``unmasked`` (left out of a fetch
        that held none: a prefill's)."""
        cfg = self.cfg
        pairs = int(rows[:, 0].sum())
        cells = len(rows) * cfg.num_moe_layers * cfg.num_experts
        routed = int(rows[:, 3].sum()) * cfg.num_experts_per_tok \
            * cfg.num_moe_layers
        args = {"moe_held_pairs": pairs,
                "moe_held_max": int(rows[:, 1].max()),
                "moe_held_mean": pairs / cells if cells else 0.0,
                "moe_held_empty": int(rows[:, 2].sum()),
                "moe_held_pair_share": pairs / routed if routed else 0.0}
        if rows[:, 4].sum():
            args.update(block_rows=int(rows[:, 4].sum()),
                        commits=int(rows[:, 5].sum()),
                        unmasked=int(rows[:, 6].sum()))
        return args

    # -- the block ------------------------------------------------------ #
    @jax.named_scope("embed")
    def embed(self, params, tokens, pos):
        return params["embed"].astype(self.cfg.dtype)[tokens]

    def forward(self, params, pools, x, rows: Rows, *, paged_kernel, mesh):
        cfg = self.cfg
        G, Sg, K = rows.positions.shape
        S, H = G * Sg, x.shape[-1]
        pos = rows.positions.reshape(S, K)
        pools = list(pools)
        c = self.paged_classes(rows, pools, paged_kernel=paged_kernel,
                               mesh=mesh)[FULL_CLASS]
        row_live = rows.live.reshape(S * K)
        zero = jnp.zeros((), jnp.int32)
        pairs, most, empty = zero, zero, zero
        for p in params["layers"]:
            with jax.named_scope("attn"):
                with jax.named_scope("qkv_proj"):
                    h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
                    q, k, v = sdar.qkv(p, h, pos, cfg)
                a = write_and_attend(c, pools, q, k, v,
                                     scale=cfg.softmax_scale, mesh=mesh)
                with jax.named_scope("out_proj"):
                    x = x + matmul(a, p["wo"])
            with jax.named_scope("moe"):
                h = rms_norm(x, p["post_attn_norm"], cfg.rms_norm_eps)
                y, counts = share.routed_share(
                    p, h.reshape(S * K, H), cfg.routing,
                    kernel=paged_kernel, row_live=row_live)
                x = x + y.reshape(S, K, H)
            pairs = pairs + counts.sum()
            most = jnp.maximum(most, counts.max())
            empty = empty + (counts == 0).sum()
        return x, tuple(pools), (pairs, most, empty,
                                 row_live.sum().astype(jnp.int32),
                                 zero, zero, zero)

    @jax.named_scope("lm_head")
    def head(self, params, h):
        h = rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.dot(h, params["lm_head"].astype(h.dtype).T,
                       preferred_element_type=jnp.float32)

    # -- the step ------------------------------------------------------- #
    def block_step(self, params, pools, blocks, passes, lengths,
                   block_tables, key, temperature, *, num_groups: int,
                   paged_kernel: bool, mesh=None):
        """One pass over every slot's block: ``blocks [S, B]`` (token ids,
        ``UNDECIDED`` where undecided), ``passes [S]`` (denoise passes of
        the block behind it), ``lengths [S]`` (the block's first position)
        -> (logits ``[S, B, V]`` fp32, pools, counters, the blocks after
        the pass — a committed slot's next block, all undecided —, their
        passes, which slots committed ``[S]``)."""
        cfg = self.cfg
        undecided = blocks < 0
        tokens = jnp.where(undecided, cfg.mask_token_id, blocks)
        logits, pools, counters = self._rows_a_slot(
            params, pools, tokens, lengths, block_tables, num_groups,
            paged_kernel, mesh)
        with jax.named_scope("unmask"):
            live = (block_tables >= 0).any(axis=1)
            commit = live & ~undecided.any(axis=1)
            x0 = sample_tokens(logits, key, temperature)
            top = jnp.max(logits, axis=-1, keepdims=True)
            lse = top[..., 0] + jnp.log(jnp.exp(logits - top).sum(-1))
            conf = jnp.exp(jnp.take_along_axis(
                logits, x0[..., None], axis=-1)[..., 0] - lse)
            take = sdar.unmask(undecided, conf, passes, cfg) \
                & live[:, None]
            after = jnp.where(take, x0, blocks)
            after = jnp.where(commit[:, None], UNDECIDED, after)
            passes = jnp.where(commit, 0, passes + 1)
            counters = counters[:4] + (
                counters[3], commit.sum().astype(jnp.int32),
                take.sum().astype(jnp.int32))
        return logits, pools, counters, after, passes, commit


register(SdarConfig, SdarServed)

__all__ = ["SdarServed", "UNDECIDED", "FULL_CLASS"]

"""An admission walks its prompt's hash chain once (``kv_cache.PromptChain``).

Every question an admission asks its cache manager — can it go in, how much
is cached, what is the plan — starts from the chain hash at each full block
of the prompt.  The chain is walked ONCE a request and kept with it
(``scheduler.Request.chained``); handed a bare prompt, every public method
makes the chain at its boundary and goes down the same path.

For each of the four managers (``BlockAllocator``; ``BoundedBlockAllocator``
behind ``ClassAllocators``; ``StateAllocator``; the composite of a page class
and a state class):
(a) admitted from kept chains and from bare prompts, the plans, tables,
    reference counts, LRU order and counters are equal, over a sequence with a
    cold document, hits, a copy-on-write fork, a snapshot taken and resumed,
    and a ``PoolExhausted``;
(b) through ``select_slot`` + ``prefill_many`` (``engine.serve``) the
    allocator's ``chain_walks`` rises by exactly one a REQUEST, a head of the
    queue that was refused three passes running included;
and (c) the chain's values are the per-block loop's that every allocator
method used to run for itself.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine, kv_cache
from deepspeed_tpu.inference.kv_cache import (
    DEAD_BLOCK, PagedKVCacheSpec, PoolExhausted, PromptChain, allocator_for)
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.parallel.topology import build_mesh

BS, ROWS = 4, 8     # block size; the most rows a program of the script holds


# ------------------------------------------------------------------ #
# The four managers, from the one factory
# ------------------------------------------------------------------ #
def _specs(kind):
    geo = dict(num_slots=8, block_size=BS, max_len=256, num_groups=1,
               num_heads=2, head_dim=16, dtype=jnp.float32)
    full = PagedKVCacheSpec(num_layers=2, name="full", num_blocks=40, **geo)
    lone = PagedKVCacheSpec(num_layers=2, per_stream=True, num_blocks=5,
                            **geo)
    # (a page is worth ROWS tokens' rows: ``page_tokens``)
    lone = dataclasses.replace(
        lone, token_row_bytes=lone.block_nbytes() // (2 * ROWS))
    return {
        "block": [PagedKVCacheSpec(num_layers=2, num_blocks=40, **geo)],
        "bounded": [full, PagedKVCacheSpec(
            num_layers=2, name="window", reach=8, num_blocks=20,
            table_blocks=(8 + ROWS - 2) // BS + 2, **geo)],
        "state": [lone],
        "both": [full, PagedKVCacheSpec(
            num_layers=2, name="state", per_stream=True, num_blocks=6,
            program_rows=ROWS, **geo)]}[kind]


MANAGERS = {"block": kv_cache.BlockAllocator,
            "bounded": kv_cache.ClassAllocators,
            "state": kv_cache.StateAllocator,
            "both": kv_cache.ClassAllocators}


def _books(alloc):
    """Everything an allocator keeps, class by class."""
    return [(a._ref.tolist(), [list(f) for f in a._free],
             [list(lru) for lru in a._lru], [dict(i) for i in a._hash_index],
             list(a._reserved), dict(a._slot_reserved), a.cow_copies,
             a.reclaimed, a.returned, a.snapshot_totals(),
             getattr(a, "_committed", None))
            for a in getattr(alloc, "classes", [alloc])]


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 50, size=n).astype(np.int32)


def _script(alloc, chained):
    """The sequence of (a); returns (what every step answered, what
    happened, the prompts admitted or refused)."""
    log, seen, asked = [], set(), 0
    rows = {}

    def admit(slot, prompt, max_new=4):
        nonlocal asked
        asked += 1
        arg = PromptChain(prompt) if chained else prompt
        log.append((alloc.can_admit(0, arg, max_new),
                    alloc.matched_blocks(0, arg)))
        try:
            plan = alloc.admit_prompt(slot, 0, arg, max_new)
        except PoolExhausted as e:
            seen.add("exhausted")
            log.append(str(e))
            return None
        row = np.full(alloc.table_width, DEAD_BLOCK, np.int32)
        row[:len(plan.table)] = plan.table
        for first in range(plan.matched, len(prompt), ROWS):
            alloc.extend(slot, row, first,
                         min(first + ROWS, len(prompt)) - 1)
        if plan.snapshot_page is not None:
            alloc.commit_snapshot(plan)
            seen.add("snapshot_taken")
        seen.add("hit" if plan.matched else "cold")
        if plan.cow_src is not None:
            seen.add("snapshot_resumed" if plan.page is not None else "cow")
        rows[slot] = row
        log.append((dataclasses.asdict(plan), row.tolist()))
        return plan

    def release(slot):
        alloc.release(slot, rows.pop(slot))

    doc = _tokens(0, 24)                                # six whole blocks
    admit(0, doc)                                       # cold
    admit(1, np.concatenate([doc, _tokens(1, 5)]))      # a hit on all six
    admit(2, doc)                                       # its last block forks
    release(0), release(1)
    admit(3, np.concatenate([doc[:12], _tokens(2, 13)]))    # a hit on three
    log.append(_books(alloc))
    slot = 4
    while "exhausted" not in seen and slot < 8:         # fresh, until refused
        admit(slot, _tokens(10 + slot, 20), max_new=20)
        slot += 1
    release(2), release(3)
    admit(0, np.concatenate([doc, _tokens(3, 7)]))      # retained, revived
    log.append(_books(alloc))
    return log, seen, asked


@pytest.mark.parametrize("kind", sorted(MANAGERS))
def test_a_kept_chain_and_a_bare_prompt_decide_alike(kind):
    bare, kept = (allocator_for(_specs(kind)) for _ in range(2))
    assert type(bare) is MANAGERS[kind]
    log_bare, seen, asked = _script(bare, chained=False)
    log_kept, seen_kept, _ = _script(kept, chained=True)
    assert seen == seen_kept and {"cold", "hit", "exhausted"} <= seen
    assert ("cow" in seen) == (kind == "block")
    assert ({"snapshot_taken", "snapshot_resumed"} <= seen) \
        == (kind in ("state", "both"))
    for step, (b, k) in enumerate(zip(log_bare, log_kept)):
        assert b == k, step
    assert _books(bare) == _books(kept)
    # one walk a prompt where the chain is kept; a bare prompt is walked
    # by each of the three questions (can it, how much, the plan)
    assert kept.chain_walks == asked and bare.chain_walks == 3 * asked


# ------------------------------------------------------------------ #
# (b) one walk a request through the engine
# ------------------------------------------------------------------ #
def _model(kind):
    """(config, parameters) of a toy model whose cache is of ``kind``."""
    if kind == "both":
        from deepspeed_tpu.models.lfm2 import lfm2_init
        from test_lfm2_serving import tiny
        return tiny(), lfm2_init(jax.random.PRNGKey(0), tiny())
    from test_decode_lookahead import FAMILIES
    return FAMILIES[{"block": "gpt2", "bounded": "two_class",
                     "state": "retention"}[kind]]()[:2]


# ``inference.num_blocks`` that two streams of ~30 tokens fill
ENGINES = {"block": 16, "bounded": {"full": 16, "window": 20}, "state": 2,
           "both": {"full": 64, "conv": 2}}


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_a_request_is_walked_once_however_often_it_is_refused(kind):
    """Two streams fill the scarce pool (blocks, or pages of a state), so
    the third request heads the queue through their whole replies: every
    pass asks ``select_slot`` again, and none walks its prompt again."""
    cfg, params = _model(kind)
    eng = InferenceEngine(cfg, params, config={"inference": {
        "max_slots": 4, "max_seq_len": 64, "prefill_chunk": 8,
        "block_size": 4, "num_blocks": ENGINES[kind]}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    assert type(eng.allocator) is MANAGERS[kind]
    first = _tokens(20, 21)
    prompts = [first, _tokens(21, 22), _tokens(22, 23),
               np.concatenate([first[:20], _tokens(23, 3)])]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=8)
            for i, p in enumerate(prompts)]
    before = eng.allocator.chain_walks
    report = eng.serve(reqs)
    assert report["completed"] == len(reqs)
    assert max(r.admission_attempts for r in reqs) >= 3
    assert eng.allocator.chain_walks - before == len(reqs)
    assert report["prefix"]["admissions"] == len(reqs) \
        == report["prefix"]["chain_walks"]
    assert all(r.chain.walks == 1 for r in reqs)
    # handed bare prompts, the same two calls make a chain each, and so
    # does the router's question
    eng.reset_serving_stats()
    slot = eng.select_slot(prompts[1], 2)
    eng.prefill(prompts[1], slot, max_new_tokens=2)
    assert eng.last_admit_info(slot)["chain_walks"] == 1
    eng.release_slot(slot)
    eng.prefix_match_tokens(prompts[2])
    assert eng.allocator.chain_walks - before == len(reqs) + 3
    assert eng.serving.snapshot()["prefix"]["chain_walks"] == 3


# ------------------------------------------------------------------ #
# (c) the values
# ------------------------------------------------------------------ #
def _legacy(prompt, bs):
    """The loop ``match_prefix`` and ``chain_hashes`` ran before."""
    out, h = [], 0
    for j in range(len(prompt) // bs):
        h = hash((h, prompt[j * bs:(j + 1) * bs].astype(np.int64).tobytes()))
        out.append(h)
    return out


@pytest.mark.parametrize("n", [0, 1, 63, 64, 65, 71_000])
def test_the_chain_is_the_per_block_loops(n):
    prompt = np.random.default_rng(n).integers(
        0, 70_000, size=n).astype(np.int32)
    chain = PromptChain(prompt)
    for bs in (64, 16):
        want = _legacy(prompt, bs)
        assert len(want) == n // bs
        assert chain.hashes(bs) == want == kv_cache.chain_hashes(prompt, bs)
        assert chain.hashes(bs) is chain.hashes(bs)
    assert chain.walks == 2 and len(chain) == n
    assert PromptChain.of(chain) is chain
    assert (np.asarray(chain) == prompt).all()
    # whatever integer type the prompt came in
    assert PromptChain(prompt.astype(np.int64)).hashes(64) == _legacy(
        prompt, 64) == PromptChain(prompt.tolist()).hashes(64)

"""The ``afmoe`` family (Trinity Mini) through the normal serving path (PR
38): sliding-window and full-attention layers in TWO CLASSES of one cache
manager, fewer K/V heads than query heads in the paged kernels, every
expert held.

What is held to what:
1. Served logits — one chunk that covers the prompt (the model's forward),
   then prefill chunks and decode through the two-class cache at contexts
   several windows long, across returned blocks, and a second request
   through the prefix-hit path — against the plain float32 reference the
   benchmark keeps (``perfbench/lib/afmoe_reference.py``), kernels on and
   off.
2. The allocator alone: a window class returns blocks as it slides, counts
   admission by its ring, registers only a prompt's tail, serves a hit from
   ``[P - reach, P)``, keeps reference counts under a shared tail, refuses
   at exhaustion without touching a live stream, and ``release`` returns
   every block of both classes.
3. ``_pattn_kernel`` / ``_kv_write_kernel`` (interpret mode) with 8 query
   heads a K/V head and a reach against plain ``jax.numpy``; with one and
   none, bit for bit the output the kernels gave before this PR.
4. ``moe/share.py`` under this family's routing: the whole layer equals
   the reference's, and two half shares with the shared expert counted once
   add up to it.
5. The controls the benchmark's ``correct`` relies on: the reference with
   the window off, with rotary on the full layers, without the gate or in
   8 bits fails the runner's limits where the served path passes.
"""
import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference import (                           # noqa: E402
    InferenceEngine, kv_cache, kv_pages)
from deepspeed_tpu.inference import afmoe as afmoe_serving      # noqa: E402
from deepspeed_tpu.inference.kv_cache import (                  # noqa: E402
    DEAD_BLOCK, BlockAllocator, ClassAllocators, PagedKVCacheSpec,
    allocator_for,
    PoolExhausted)
from deepspeed_tpu.models.afmoe import (                        # noqa: E402
    FULL, SLIDING, AfmoeConfig, afmoe_init)
from deepspeed_tpu.models import blocks                        # noqa: E402
from deepspeed_tpu.moe import share                             # noqa: E402
from deepspeed_tpu.ops import paged_attention as pa             # noqa: E402
from deepspeed_tpu.parallel.topology import build_mesh          # noqa: E402
from perfbench.lib import afmoe_reference as reference          # noqa: E402


def one_device():
    return build_mesh(devices=jax.devices()[:1])


def tiny(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, sliding_window=8,
        max_position_embeddings=256, dtype=jnp.float32,
        initializer_range=0.08)
    base.update(kw)
    return AfmoeConfig(**base)


def sizes_of(cfg):
    """The configuration file's keys for the reference."""
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d["layer_types"] = list(cfg.layer_types)
    return d


def seeded(cfg, seed=0):
    """The seeded init with the norms' weights moved off 1, so that a norm
    left out or applied on the wrong side shows."""
    params = afmoe_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 1)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(tree, [
        a * jnp.asarray(rng.uniform(0.6, 1.4, a.shape), a.dtype)
        if "norm" in str(path[-1]) else a for path, a in leaves])


def engine_of(cfg, params, kernel, **inference):
    conf = dict(max_slots=4, max_seq_len=128, block_size=4, prefill_chunk=8,
                paged_kernel=kernel,
                num_blocks={"full": 64, "window": 40})
    conf.update(inference)
    return InferenceEngine(cfg, params, config={"inference": conf},
                           mesh=one_device())


def ref_logits(params, cfg, tokens, positions, **kw):
    lg, margin = reference.forward(
        params, jnp.asarray(np.asarray(tokens, np.int32)), sizes_of(cfg),
        out_positions=list(positions), q_block=16, **kw)
    return np.asarray(lg), np.asarray(margin)


# --------------------------------------------------------------------- #
# 0. The config
# --------------------------------------------------------------------- #
def test_layer_types_follow_the_published_rule():
    assert AfmoeConfig().layer_types == (SLIDING, SLIDING, SLIDING, FULL) * 8
    cfg = AfmoeConfig.from_hf({"num_hidden_layers": 5, "num_dense_layers": 1,
                               "layer_types": [SLIDING] * 3 + [FULL]
                               + [SLIDING] * 28})
    assert cfg.layer_types == (SLIDING, SLIDING, SLIDING, FULL, SLIDING)
    assert cfg.group == 8 and cfg.routing.held == (0, 128)
    assert cfg.routing.n_group == cfg.routing.topk_group == 1
    served = afmoe_serving.AfmoeServed(cfg)
    assert [tuple(c) for c in served.cache_classes] == [
        ("full", 1, None, False), ("window", 4, 2048, False)]
    assert served.cache_pools(64) == (("k", (4, 64, 128)),
                                      ("v", (4, 64, 128)))
    with pytest.raises(ValueError):
        AfmoeConfig(num_hidden_layers=3, layer_types=(FULL, FULL))
    only_window = AfmoeConfig(num_hidden_layers=3,
                              global_attn_every_n_layers=9)
    assert [c.name for c in
            afmoe_serving.AfmoeServed(only_window).cache_classes] \
        == ["window"]


def test_published_file_differs_from_the_source_in_depth_only():
    import json
    sizes = json.load(open(os.path.join(ROOT, "perfbench", "configs",
                                        "trinity-mini.json")))
    cfg = AfmoeConfig.from_hf(sizes)
    pub = AfmoeConfig()
    changed = {f.name for f in dataclasses.fields(cfg)
               if getattr(cfg, f.name) != getattr(pub, f.name)}
    assert changed == {"num_hidden_layers", "num_dense_layers",
                       "layer_types"} and sizes["reduced"] == [
        "num_hidden_layers", "num_dense_layers"]
    assert cfg.layer_types == pub.layer_types[:5]
    assert len(sizes["layer_types"]) == 32           # kept whole
    shapes = jax.eval_shape(lambda k: afmoe_init(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n - 4.2415e9) < 1e6                   # 8.48 GB in bf16


# --------------------------------------------------------------------- #
# 1. Served logits against the reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernels"])
def test_one_chunk_is_the_models_forward(kernel):
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_of(cfg, params, kernel, prefill_chunk=64)
    assert list(eng.cache) == ["k.full", "v.full", "k.window", "v.window"]
    assert eng.cache["k.full"].shape == (1, 1, 64, 2, 1, 64)
    assert eng.cache["k.window"].shape == (4, 1, 40, 2, 1, 64)
    prompt = np.random.default_rng(0).integers(0, 128, 37, dtype=np.int32)
    slot = eng.select_slot(prompt, 2)
    _, got = eng.prefill(prompt, slot, return_logits=True, max_new_tokens=2)
    want, _ = ref_logits(params, cfg, prompt, [36])
    assert np.abs(got - want[0]).max() < 2e-5
    eng.close()


def _decode_against_reference(eng, params, cfg, prompt, steps):
    slot = eng.select_slot(prompt, steps + 1)
    tok, pre = eng.prefill(prompt, slot, return_logits=True,
                           max_new_tokens=steps + 1)
    info = dict(eng.last_admit_info(slot))
    eng.activate_slot(slot, len(prompt), tok)
    toks = list(prompt) + [tok]
    errs = [np.abs(pre - ref_logits(params, cfg, toks,
                                    [len(prompt) - 1])[0][0]).max()]
    for _ in range(steps):
        sampled, lg = eng.decode_once(return_logits=True)
        errs.append(np.abs(lg[slot] - ref_logits(
            params, cfg, toks, [len(toks) - 1])[0][0]).max())
        toks.append(int(sampled[slot]))
    return slot, info, max(errs)


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernels"])
def test_chunks_then_decode_across_returned_blocks_and_a_prefix_hit(kernel):
    """45 tokens = 5.6 windows of 8: the ring (5 blocks of 4) has turned
    over twice by the end of the prompt and keeps turning in decode."""
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_of(cfg, params, kernel)
    full, window = eng.allocator.classes
    assert (full.table_width, window.table_width) == (32, 5)
    assert eng.block_tables.shape == (4, 37)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, 45, dtype=np.int32)
    slot, info, err = _decode_against_reference(eng, params, cfg, prompt, 12)
    assert err < 2e-5
    assert info["cached_by_class"] == {"full": 0, "window": 0}
    stats = eng.allocator.class_stats()
    assert stats["full"]["live"] == 15 and stats["full"]["returned"] == 0
    # positions 0..57 written; a query at 57 reads from 50: blocks 12..14
    assert stats["window"]["live"] == 3 and stats["window"]["returned"] == 12
    eng.release_slot(slot)
    assert eng.allocator.blocks_in_use() == 0
    assert [a.available(0) for a in eng.allocator.classes] == [64, 40]
    # The second prompt shares 44 tokens = 11 blocks: the full class serves
    # all of them, the window class the two blocks a query at 44 reads.
    again = np.concatenate([prompt[:44], rng.integers(0, 128, 7,
                                                      dtype=np.int32)])
    slot, info, err = _decode_against_reference(eng, params, cfg, again, 6)
    assert err < 2e-5
    assert info["cached_tokens"] == 44 and not info["cow_fork"]
    assert info["cached_by_class"] == {"full": 44, "window": 8}
    eng.release_slot(slot)
    eng.close()


def test_served_through_the_scheduler_with_the_class_counters(tmp_path):
    from deepspeed_tpu.inference.scheduler import Request
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_of(cfg, params, False)
    rng = np.random.default_rng(3)
    doc = rng.integers(0, 128, 40, dtype=np.int32)
    eng.serve([Request(rid=-1, prompt=doc, max_new_tokens=1, arrival_s=0.0)])
    eng.reset_serving_stats()
    reqs = [Request(rid=i, prompt=np.concatenate(
        [doc, rng.integers(0, 128, 5 + i, dtype=np.int32)]) if i % 2 else
        rng.integers(0, 128, 9 + i, dtype=np.int32),
        max_new_tokens=14, arrival_s=0.0) for i in range(6)]
    report = eng.serve(reqs)
    assert report["completed"] == 6 and report["recompiles"] == 0
    for r in reqs:
        toks = list(r.prompt) + list(r.out_tokens)
        want, _ = ref_logits(params, cfg, toks[:-1],
                             range(len(r.prompt) - 1, len(toks) - 1))
        assert list(np.argmax(want, -1)) == list(r.out_tokens)
    classes = report["cache_classes"]
    assert classes["window"]["returned"] > 0 == classes["full"]["returned"]
    assert classes["window"]["reach"] == 8 and classes["full"]["reach"] is None
    assert report["model_counters"]["moe_held_pair_share"] == 1.0
    assert report["prefix"]["hit_rate"] > 0.3
    assert eng.allocator.blocks_in_use() == 0
    # the document: whole in the full class, its tail in the window class
    assert eng.prefix_match_tokens(np.concatenate([doc, doc[:1]])) == 40
    full, window = eng.allocator.classes
    assert len(full._hash_index[0]) >= 10
    hashes = kv_cache.chain_hashes(doc, 4)
    # 40 = ten whole blocks: an identical prompt resumes at 36 and reads
    # from 29, a longer one at 40 and reads from 33: blocks 7, 8, 9
    assert [h in window._hash_index[0] for h in hashes] \
        == [False] * 7 + [True] * 3
    eng.close()


def test_speculation_is_refused_only_where_the_cache_cannot_drop_rows():
    """``verify`` falls out of ``decode`` (K rows a stream): the engine
    builds with spec_k > 0 and the ring is sized for its rows."""
    cfg = tiny()
    eng = engine_of(cfg, seeded(cfg), False, spec_k=2, prefill_chunk=8)
    assert eng.allocator.classes[1].table_width == 5
    eng.close()


@pytest.mark.parametrize("asked,blocks", [
    ({"full": 64, "window": 40}, (64, 40)),
    ({"full": 48}, (48, 4 * 5)),        # a class left out: every ring whole
    (0, (4 * 32, 4 * 5)),               # full provisioning, both
    (64, None),                         # which class would an int size?
])
def test_num_blocks_sizes_a_models_classes_by_name(asked, blocks):
    cfg = tiny()
    if blocks is None:
        with pytest.raises(ValueError, match="class name: blocks"):
            engine_of(cfg, seeded(cfg), False, num_blocks=asked)
        return
    eng = engine_of(cfg, seeded(cfg), False, num_blocks=asked)
    assert tuple(sp.num_blocks for sp in eng.cache_specs) == blocks
    assert eng.num_blocks == sum(blocks)
    eng.close()


def test_a_model_of_one_class_takes_no_dict():
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.runtime.config import DeepSpeedConfigError
    cfg = gpt2.GPT2_CONFIGS["gpt2-tiny"]
    params = gpt2.gpt2_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="takes an int"):
        InferenceEngine(cfg, params, mesh=one_device(), config={
            "inference": {"max_slots": 2, "block_size": 4,
                          "num_blocks": {"full": 8}}})
    with pytest.raises(DeepSpeedConfigError, match="num_blocks"):
        InferenceEngine(cfg, params, mesh=one_device(), config={
            "inference": {"max_slots": 2, "block_size": 4,
                          "num_blocks": {"full": -1}}})


# --------------------------------------------------------------------- #
# 2. The allocator alone
# --------------------------------------------------------------------- #
def _spec(name, reach, blocks, table=0, layers=1):
    return PagedKVCacheSpec(
        num_layers=layers, num_slots=4, num_blocks=blocks, block_size=4,
        max_len=64, num_heads=2, head_dim=16, name=name, reach=reach,
        table_blocks=table)


def _pair(full_blocks=32, window_blocks=16):
    return ClassAllocators([_spec("full", None, full_blocks),
                            _spec("window", 8, window_blocks, table=5,
                                  layers=4)])


def tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 1000, n, dtype=np.int32)


def test_a_bounded_class_is_validated():
    with pytest.raises(ValueError):
        _spec("w", 8, 16, table=2).validate()       # ring <= reach / bs
    with pytest.raises(ValueError):
        _spec("w", 8, 16, table=17).validate()      # ring > max_len / bs
    _spec("w", 8, 16, table=3).validate()
    assert _spec("w", 8, 16, table=5).max_blocks_per_slot == 5
    assert _spec("w", 8, 16, table=5).first_block(20) == 3
    assert _spec("", None, 16).first_block(20) == 0
    assert _spec("w", 8, 16, table=5).pool_names == ("k.w", "v.w")
    assert _spec("", None, 16).pool_names == ("k", "v")


def test_admission_counts_each_class_by_its_own_need():
    alloc = _pair()
    full, window = alloc.classes
    assert alloc.table_width == 16 + 5
    assert full.need_blocks(30, 10) == 10 and window.need_blocks(30, 10) == 5
    assert window.need_blocks(6, 2) == 2
    plan = alloc.admit_prompt(0, 0, tokens(0, 30), 10)
    # the full class holds the prompt's 8 blocks and books 2 more; the
    # window class books its ring and draws nothing until a program runs
    assert full.blocks_in_use() == 8 and full.available(0) == 32 - 10
    assert window.blocks_in_use() == 0 and window.available(0) == 16 - 5
    assert plan.matched == 0 and plan.cow_src is None
    assert len(plan.table) == 21 and plan.table[16:] == [DEAD_BLOCK] * 5
    assert alloc.available(0) == 11                 # the scarcest class's


def test_the_window_class_returns_blocks_as_it_slides():
    alloc = _pair()
    full, window = alloc.classes
    plan = alloc.admit_prompt(0, 0, tokens(0, 30), 10)
    row = np.asarray(plan.table, np.int32)
    alloc.extend(0, row, 0, 7)                      # chunk [0, 8)
    assert (row[16:] != DEAD_BLOCK).sum() == 2 and window.returned == 0
    alloc.extend(0, row, 8, 15)
    # a query at 8 reads from 1: block 0 stays; blocks 0..3 held
    assert (row[16:] != DEAD_BLOCK).sum() == 4 and window.returned == 0
    held = row[16:].copy()
    alloc.extend(0, row, 16, 23)
    # a query at 16 reads from 9: blocks 0, 1 go; 2..5 held, 4 and 5 new
    assert window.returned == 2 and window.blocks_in_use() == 4
    assert row[16 + 2] == held[2] and row[16 + 3] == held[3]   # never moved
    assert row[16 + 4 % 5] != DEAD_BLOCK and row[16 + 5 % 5] != DEAD_BLOCK
    # the stream stays charged the ring's worth: what it gave back it may
    # draw again
    assert window.available(0) == 16 - 5
    for pos in range(24, 40):                       # decode, a token a step
        alloc.extend(0, row, pos, pos)
    # a query at 39 reads from 32: blocks 8 and 9
    assert window.blocks_in_use() == 2 and window.returned == 8
    assert full.blocks_in_use() == 10 and full.returned == 0
    stats = alloc.class_stats()
    assert stats["window"]["returned"] == 8 and stats["full"]["live"] == 10
    alloc.release(0, row)
    assert alloc.blocks_in_use() == 0
    assert full.available(0) == 32 and window.available(0) == 16


def test_only_a_prompts_tail_enters_the_window_classes_prefix_cache():
    alloc = _pair()
    full, window = alloc.classes
    doc = tokens(1, 26)                             # 6 full blocks
    plan = alloc.admit_prompt(0, 0, doc, 1)
    row = np.asarray(plan.table, np.int32)
    for a in range(0, 26, 8):
        alloc.extend(0, row, a, min(a + 7, 25))
    hashes = kv_cache.chain_hashes(doc, 4)
    assert all(h in full._hash_index[0] for h in hashes)
    # a hit at the prompt's end (24) reads from 17: blocks 4 and 5
    assert [h in window._hash_index[0] for h in hashes] \
        == [False] * 4 + [True] * 2
    alloc.release(0, row)
    # the tail is retained, the rest went back to the free list
    assert len(window._lru[0]) == 2 and len(full._lru[0]) == 6
    assert window.available(0) == 16


@pytest.mark.parametrize("shared,want_full,want_window", [
    (24, 24, 8),        # the whole document: the tail serves the hit
    (20, 0, 0),         # a hit at 20 would need blocks 3, 4: 3 is not kept
    (8, 0, 0),          # nor is anything at the document's start
])
def test_a_hit_needs_the_full_classes_prefix_and_the_windows_reach(
        shared, want_full, want_window):
    alloc = _pair()
    doc = tokens(1, 26)
    plan = alloc.admit_prompt(0, 0, doc, 1)
    row = np.asarray(plan.table, np.int32)
    for a in range(0, 26, 8):
        alloc.extend(0, row, a, min(a + 7, 25))
    alloc.release(0, row)
    prompt = np.concatenate([doc[:shared], tokens(9, 9)])
    assert alloc.matched_blocks(0, prompt) == want_full // 4
    plan = alloc.admit_prompt(1, 0, prompt, 4)
    assert plan.matched == want_full and plan.cow_src is None
    assert plan.cached_by_class == {"full": want_full, "window": want_window}
    row = np.asarray(plan.table, np.int32)
    ring = row[16:]
    if want_window:
        # blocks 4 and 5 of the document at ring slots 4 and 0
        assert (ring != DEAD_BLOCK).tolist() == [True, False, False, False,
                                                 True]
    else:
        assert (ring == DEAD_BLOCK).all()
    alloc.release(1, row)
    assert alloc.blocks_in_use() == 0


def test_a_match_never_takes_the_block_of_the_last_token():
    """No class forks copy-on-write: an identical prompt of whole blocks
    re-prefills its last block."""
    alloc = _pair()
    doc = tokens(2, 24)
    for slot in (0, 1):
        plan = alloc.admit_prompt(slot, 0, doc, 1)
        row = np.asarray(plan.table, np.int32)
        for a in range(plan.matched, 24, 8):
            alloc.extend(slot, row, a, min(a + 7, 23))
        assert plan.matched == (0, 20)[slot] and plan.cow_src is None
        alloc.release(slot, row)
    assert all(a.cow_copies == 0 for a in alloc.classes)


def test_reference_counts_under_a_shared_tail():
    alloc = _pair()
    full, window = alloc.classes
    doc = tokens(1, 24)
    plan = alloc.admit_prompt(0, 0, np.concatenate([doc, [1]]), 1)
    row = np.asarray(plan.table, np.int32)
    for a in range(0, 25, 8):
        alloc.extend(0, row, a, min(a + 7, 24))
    alloc.release(0, row)
    rows = {}
    for slot in (1, 2):
        prompt = np.concatenate([doc, tokens(10 + slot, 6)])
        plan = alloc.admit_prompt(slot, 0, prompt, 8)
        assert plan.cached_by_class == {"full": 24, "window": 8}
        rows[slot] = np.asarray(plan.table, np.int32)
        alloc.extend(slot, rows[slot], 24, 29)
    tail = rows[1][16:][[4, 0]]                      # blocks 4, 5
    assert (rows[2][16:][[4, 0]] == tail).all()
    assert [int(window._ref[0, b]) for b in tail] == [2, 2]
    assert window.blocks_in_use() == 2 + 2 * 2       # the tail once + own
    # stream 1 decodes past the tail: it gives up ITS references only
    for pos in range(30, 38):
        alloc.extend(1, rows[1], pos, pos)
    assert [int(window._ref[0, b]) for b in tail] == [1, 1]
    alloc.release(2, rows[2])
    # nobody holds the tail now: retained for the next hit, not freed
    assert [int(window._ref[0, b]) for b in tail] == [0, 0]
    assert all(b in window._lru[0] for b in tail)
    assert alloc.matched_blocks(0, np.concatenate([doc, [5]])) == 6
    alloc.release(1, rows[1])
    assert alloc.blocks_in_use() == 0
    assert full.available(0) == 32 and window.available(0) == 16


@pytest.mark.parametrize("streams", [2, 3])
def test_streams_on_one_tail_in_a_pool_of_exactly_their_needs(streams):
    """A shared tail is no free ride in a bounded class: every stream lets
    go of it as ITS window slides and then draws blocks of its own, while
    another may still hold it.  A pool of exactly the admitted streams'
    needs carries all of them past the tail, and admits no one more."""
    alloc = _pair(full_blocks=64, window_blocks=5 * streams)
    full, window = alloc.classes
    doc = tokens(1, 24)
    plan = alloc.admit_prompt(0, 0, np.concatenate([doc, [1]]), 1)
    row = np.asarray(plan.table, np.int32)
    for a in range(0, 25, 8):
        alloc.extend(0, row, a, min(a + 7, 24))
    alloc.release(0, row)                   # the tail (2 blocks) is retained
    assert window.available(0) == 5 * streams
    rows = {}
    for slot in range(1, streams + 1):
        prompt = np.concatenate([doc, tokens(10 + slot, 6)])
        assert alloc.can_admit(0, prompt, 34)
        plan = alloc.admit_prompt(slot, 0, prompt, 34)   # 64 tokens: a ring
        assert plan.cached_by_class == {"full": 24, "window": 8}
        rows[slot] = np.asarray(plan.table, np.int32)
    assert window.available(0) == 0
    assert not alloc.can_admit(0, tokens(5, 6), 2)       # not one block more
    with pytest.raises(PoolExhausted):
        alloc.admit_prompt(9, 0, np.concatenate([doc, tokens(9, 6)]), 34)
    for slot in rows:
        alloc.extend(slot, rows[slot], 24, 29)
    # in turn, so that each lets go of the tail while the others hold it
    for slot in rows:
        for pos in range(30, 63):
            alloc.extend(slot, rows[slot], pos, pos)
        assert (rows[slot][16:] != DEAD_BLOCK).sum() <= 5
    assert window.blocks_in_use() <= 5 * streams
    assert window.returned > 0 and window.available(0) == 0
    for slot in rows:
        alloc.release(slot, rows[slot])
    assert alloc.blocks_in_use() == 0
    assert window.available(0) == 5 * streams and full.available(0) == 64


def test_a_shared_tail_buys_no_admission():
    """2 shared + 3 x 3 own = 11 blocks is what a free ride on the tail
    would count for three streams; each may come to hold 5 of its own."""
    alloc = _pair(full_blocks=64, window_blocks=11)
    doc = tokens(1, 24)
    plan = alloc.admit_prompt(0, 0, np.concatenate([doc, [1]]), 1)
    row = np.asarray(plan.table, np.int32)
    for a in range(0, 25, 8):
        alloc.extend(0, row, a, min(a + 7, 24))
    alloc.release(0, row)
    for slot in (1, 2):
        alloc.admit_prompt(slot, 0, np.concatenate([doc, tokens(slot, 6)]),
                           34)
    assert not alloc.can_admit(0, np.concatenate([doc, tokens(3, 6)]), 34)
    assert alloc.classes[1].available(0) == 1


def test_exhaustion_in_either_class_admits_nothing():
    alloc = _pair(full_blocks=32, window_blocks=7)
    full, window = alloc.classes
    a = alloc.admit_prompt(0, 0, tokens(0, 30), 10)   # books 5 of 7
    assert not alloc.can_admit(0, tokens(1, 30), 10)  # window: 2 < 5
    before = (full.available(0), window.available(0), full.blocks_in_use())
    with pytest.raises(PoolExhausted):
        alloc.admit_prompt(1, 0, tokens(1, 30), 10)
    # the full class's admission was rolled back
    assert (full.available(0), window.available(0),
            full.blocks_in_use()) == before
    assert alloc.can_admit(0, tokens(2, 6), 2)        # 2 blocks fit
    alloc.release(0, np.asarray(a.table, np.int32))
    assert alloc.can_admit(0, tokens(1, 30), 10)
    small = _pair(full_blocks=8, window_blocks=16)
    assert not small.can_admit(0, tokens(1, 30), 10)  # full: 8 < 10


def test_one_class_is_the_allocator_it_was():
    """A model with one unbounded class gets the plain allocator: the same
    tables, copy-on-write and ``alloc_block`` as before."""
    spec = _spec("", None, 16)
    alloc = allocator_for([spec])
    assert type(alloc) is BlockAllocator
    assert alloc.table_width == 16 and alloc.class_stats() == {}
    doc = tokens(3, 8)
    a = alloc.admit_prompt(0, 0, doc, 2)
    b = alloc.admit_prompt(1, 0, doc, 2)
    assert b.matched == 7 and b.cow_src == a.table[1] and alloc.cow_copies == 1
    row = np.full(16, DEAD_BLOCK, np.int32)
    row[:len(a.table)] = a.table
    alloc.extend(0, row, 8, 9)
    assert (row != DEAD_BLOCK).sum() == 3 and alloc.returned == 0
    alloc.release(0, row)
    alloc.release(1, b.table)
    assert alloc.blocks_in_use() == 0 and alloc.available(0) == 16


# --------------------------------------------------------------------- #
# 3. The kernels: grouped heads and a reach against jnp; GPT-2's form bit
#    for bit
# --------------------------------------------------------------------- #
def _plain_attend(q, kl, vl, bt, pos, reach, scale):
    """q [Q, K, nH, D]; kl / vl one layer, logical [B, nKV, bs, D]; bt
    [Q, J] (a ring where ``reach``); pos [Q, K]: a loop over rows."""
    Q, K, nH, D = q.shape
    nKV, bs = kl.shape[1], kl.shape[2]
    J = bt.shape[1]
    out = np.zeros((Q, K, nH, D), np.float32)
    for s in range(Q):
        for k in range(K):
            p = int(pos[s, k])
            if p < 0:
                continue
            lo = 0 if reach is None else max(0, p - reach + 1)
            keys = np.arange(lo, p + 1)
            blocks = bt[s, (keys // bs) % J]
            if (blocks < 0).any():
                continue
            kk = kl[blocks, :, keys % bs]                # [n, nKV, D]
            vv = vl[blocks, :, keys % bs]
            for h in range(nH):
                sc = kk[:, h // (nH // nKV)] @ q[s, k, h] * scale
                w = np.exp(sc - sc.max())
                out[s, k, h] = (w / w.sum()) @ vv[:, h // (nH // nKV)]
    return out


@pytest.mark.parametrize("group,reach,D,bs,K", [
    (8, 24, 128, 8, 1),      # the cell's form: decode
    (8, 24, 128, 8, 4),      # a chunk's run of rows
    (2, 10, 16, 4, 3),       # folded lanes (f = 4), a reach off the blocks
    (4, None, 32, 8, 2),     # grouped heads, no window
    (1, 12, 64, 8, 2),       # a window, one K/V head a query head
])
def test_paged_attention_with_grouped_heads_and_a_reach(group, reach, D, bs,
                                                        K):
    rng = np.random.default_rng(group * 100 + (reach or 0))
    nKV, L, B = 2, 2, 24
    nH = nKV * group
    span = 0 if reach is None else (reach + K - 2) // bs + 2
    J = span or 6
    f = kv_cache.kv_fold(D, bs)
    logical = rng.normal(size=(2, L, 1, B, nKV, bs, D)).astype(np.float32)
    pk, pv = (jnp.asarray(x.reshape(L, 1, B, nKV, bs // f, f * D))
              for x in logical)
    # streams at different depths; the last one dead
    starts = [0, bs * J - K - 3 if reach is None else 5 * bs + 3, 2 * bs - 1,
              0]
    Q = len(starts)
    pos = np.stack([np.arange(s, s + K) for s in starts]).astype(np.int32)
    pos[1, -1] = -1 if K > 1 else pos[1, -1]         # a dead row in a run
    pos[3] = -1
    bt = np.full((Q, J), DEAD_BLOCK, np.int32)
    free = list(rng.permutation(B))
    for s in range(Q - 1):
        top = int(pos[s].max())
        lo = 0 if reach is None else max(
            0, int(pos[s][pos[s] >= 0].min()) - reach + 1) // bs
        for j in range(lo, top // bs + 1):
            bt[s, j % J] = free.pop()
    q = rng.normal(size=(1, Q, K, nH, D)).astype(np.float32)
    got = pa.paged_attention(
        jnp.asarray(q), pk, pv, 1, plan=pa.attend_plan(
            jnp.asarray(bt[None]), jnp.asarray(pos[None]), pk, D,
            reach=reach, group=group), scale=D ** -0.5)
    want = _plain_attend(q[0], logical[0, 1, 0], logical[1, 1, 0], bt, pos,
                         reach, D ** -0.5)
    np.testing.assert_allclose(np.asarray(got)[0], want, atol=2e-5)
    assert not np.asarray(got)[0, 3].any()           # the dead stream
    if reach is not None:
        # ... and the served model's own attend without the kernel
        base = kv_pages.gather_attend(
            jnp.asarray(q), pk, pv, 1, jnp.asarray(bt[None]),
            jnp.asarray(pos[None]), reach, D ** -0.5)
        np.testing.assert_allclose(np.asarray(base)[0], want, atol=2e-5)


def test_the_window_plan_walks_only_the_blocks_in_reach():
    bt = jnp.asarray(np.arange(10, 15, dtype=np.int32)[None, None])  # ring 5
    pool = jnp.zeros((1, 1, 32, 2, 4, 16))
    plan = pa.attend_plan(bt, jnp.asarray([[[21]]], jnp.int32), pool, 16,
                          reach=8)
    # a query at 21 reads 14..21: blocks 3, 4, 5 at ring slots 3, 4, 0
    assert int(plan.nlive[0, 0]) == 3
    assert np.asarray(plan.rows)[0, 0, :3].tolist() == [13, 14, 10]
    hi, lo = np.asarray(plan.lim)[0, 0, 0]
    assert (hi, lo) == (21 - 12, 14 - 12)            # counted from block 3
    dead = pa.attend_plan(bt, jnp.asarray([[[-1]]], jnp.int32), pool, 16,
                          reach=8)
    assert int(dead.nlive[0, 0]) == 0
    full = pa.attend_plan(bt, jnp.asarray([[[18]]], jnp.int32), pool, 16)
    assert int(full.nlive[0, 0]) == 5 and full.lim.shape[-1] == 1


def test_one_head_a_head_and_no_reach_is_bit_for_bit_what_it_was():
    """The input and the output of ``/root/scratch/golden.py`` run on the
    tree before this PR (``tests/data/paged_kernels_pr37.npz``)."""
    rng = np.random.default_rng(7)
    L, G, B, nH, bs, D, f = 2, 1, 12, 4, 8, 16, 8
    pk = jnp.asarray(rng.normal(size=(L, G, B, nH, bs // f, f * D)),
                     jnp.float32)
    pv = jnp.asarray(rng.normal(size=(L, G, B, nH, bs // f, f * D)),
                     jnp.float32)
    q = jnp.asarray(rng.normal(size=(G, 3, 2, nH, D)), jnp.float32)
    bt = np.full((G, 3, 5), -1, np.int32)
    bt[0, 0, :3] = [4, 9, 1]
    bt[0, 1, :5] = [0, 2, 3, 5, 6]
    pos = np.array([[[17, 18], [33, 34], [0, 1]]], np.int32)
    knew = jnp.asarray(rng.normal(size=(G, 4, nH, D)), jnp.float32)
    vnew = jnp.asarray(rng.normal(size=(G, 4, nH, D)), jnp.float32)
    blk = jnp.asarray([[9, 9, -1, 6]], jnp.int32)
    off = jnp.asarray([[1, 2, 0, 2]], jnp.int32)
    pk2, pv2 = pa.paged_write(pk, pv, knew, vnew, 1, blk, off)
    out = pa.paged_attention(q, pk2, pv2, 1, jnp.asarray(bt),
                             jnp.asarray(pos), scale=0.25)
    gold = np.load(os.path.join(ROOT, "tests", "data",
                                "paged_kernels_pr37.npz"))
    assert np.array_equal(np.asarray(out), gold["out"])
    digest = hashlib.sha256(np.asarray(pk2).tobytes()
                            + np.asarray(pv2).tobytes()).digest()
    assert np.array_equal(np.frombuffer(digest, np.uint8),
                          gold["pools_sha256"])


@pytest.mark.parametrize("ring", [False, True])
def test_the_row_write_takes_the_kv_heads_and_a_rings_slots(ring):
    """4 K/V heads' rows (of 32 query heads' model) land where the logical
    layout says, through a plain table and through a ring."""
    rng = np.random.default_rng(1)
    L, B, nKV, bs, D, J = 2, 10, 4, 8, 128, 4
    pk = jnp.zeros((L, 1, B, nKV, bs, D), jnp.float32)
    pv = jnp.zeros((L, 1, B, nKV, bs, D), jnp.float32)
    bt = np.asarray([[3, 7, 5, 1]], np.int32)
    pos = np.asarray([[37, 38, 39, 40]], np.int32) if ring \
        else np.asarray([[14, 15, 16, 17]], np.int32)
    table = jnp.broadcast_to(jnp.asarray(bt)[:, None], (1, 4, J))
    blk, off = kv_cache.positions_to_blocks(table, jnp.asarray(pos), bs,
                                            ring=ring)
    want = [bt[0, (p // bs) % J] for p in pos[0]]
    assert np.asarray(blk)[0].tolist() == want
    knew = rng.normal(size=(1, 4, nKV, D)).astype(np.float32)
    vnew = rng.normal(size=(1, 4, nKV, D)).astype(np.float32)
    pk2, pv2 = kv_cache.paged_write_rows(pk, pv, jnp.asarray(knew),
                                         jnp.asarray(vnew), 1, blk, off)
    for r, p in enumerate(pos[0]):
        np.testing.assert_array_equal(
            np.asarray(pk2)[1, 0, want[r], :, p % bs], knew[0, r])
        np.testing.assert_array_equal(
            np.asarray(pv2)[1, 0, want[r], :, p % bs], vnew[0, r])
    assert float(jnp.abs(pk2).sum()) == pytest.approx(
        float(np.abs(knew).sum()), rel=1e-5)
    if not ring:    # past the table: nowhere
        blk, _ = kv_cache.positions_to_blocks(
            table, jnp.asarray([[32, 33, 34, 35]], jnp.int32), bs)
        assert (np.asarray(blk) == DEAD_BLOCK).all()


# --------------------------------------------------------------------- #
# 4. The expert layer under this routing
# --------------------------------------------------------------------- #
def _layer_and_rows(seed=0):
    cfg = tiny()
    p = seeded(cfg, seed)["layers"][2]               # an expert layer
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(24, 64)),
                    jnp.float32)
    return cfg, p, x


def test_routing_is_the_reference_rule_with_one_group():
    cfg, p, x = _layer_and_rows()
    ids, w = share.route(x, p["router"], p["router_bias"], cfg.routing)
    rid, rw, margin = reference.route(x, p["router"], p["router_bias"],
                                      sizes_of(cfg))
    assert np.array_equal(np.sort(ids, -1), np.sort(rid, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(rw, -1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), cfg.route_scale,
                               rtol=1e-5)
    assert (np.asarray(margin) > 0).all()
    # the bias moves the choice, not the weights
    s = jax.nn.sigmoid(x @ p["router"])
    np.testing.assert_allclose(
        w, np.take_along_axis(np.asarray(s), np.asarray(ids), 1)
        / np.take_along_axis(np.asarray(s), np.asarray(ids), 1).sum(
            -1, keepdims=True) * cfg.route_scale, rtol=1e-5)
    by_s = np.argsort(-np.asarray(s), -1)[:, :2]
    assert not np.array_equal(np.sort(ids, -1), np.sort(by_s, -1))


def _reference_layer(cfg, p, x, held=None):
    """What the reference's expert layer adds for normed rows x."""
    sizes = sizes_of(cfg)
    ids, w, _ = reference.route(x, p["router"], p["router_bias"], sizes)
    y = np.zeros(x.shape, np.float32)
    first, count = held or (0, cfg.num_experts)
    for e in range(first, first + count):
        we = np.asarray(jnp.sum(jnp.where(ids == e, w, 0.0), -1))
        g = x @ p["w_gate"][e].T
        u = x @ p["w_up"][e].T
        y += we[:, None] * np.asarray((jax.nn.silu(g) * u) @ p["w_down"][e])
    shared = (jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])) \
        @ p["shared_down"]
    return y, np.asarray(shared)


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
def test_the_whole_layer_equals_the_references(kernel):
    cfg, p, x = _layer_and_rows()
    got, counts = share.expert_layer(p, x, cfg.routing, kernel=kernel)
    routed, shared = _reference_layer(cfg, p, x)
    np.testing.assert_allclose(got, routed + shared, atol=2e-5)
    assert int(counts.sum()) == 24 * 2 and counts.shape == (8,)


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
def test_two_half_shares_add_up_with_the_shared_expert_counted_once(kernel):
    cfg, p, x = _layer_and_rows(1)
    whole, _ = share.expert_layer(p, x, cfg.routing, kernel=kernel)
    parts = []
    for first in (0, 4):
        r = cfg.routing._replace(held=(first, 4))
        half = {k: (v[first:first + 4] if k in ("w_gate", "w_up", "w_down")
                    else v) for k, v in p.items()}
        y, counts = share.routed_share(half, x, r, kernel=kernel)
        want, _ = _reference_layer(cfg, p, x, held=(first, 4))
        np.testing.assert_allclose(y, want, atol=2e-5)
        parts.append(np.asarray(y))
    _, shared = _reference_layer(cfg, p, x)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole, atol=3e-5)


def test_the_latent_family_routes_through_the_same_description():
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3Config
    r = DeepseekV3Config(held=(16, 16)).routing
    assert r == blocks.Routing(experts=256, per_tok=8, n_group=8, topk_group=4,
                              norm=True, scale=2.5, held=(16, 16))
    assert share._row_tile(128, r) == 16 and share._row_tile(
        128, tiny().routing) == 64


# --------------------------------------------------------------------- #
# 5. The controls fail the limits the served path passes
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def served_and_controls():
    """(c)'s shape at the toy: a question over a cached document through
    the prefix-hit path, then a decode, against the reference and against
    each control."""
    cfg = tiny(sliding_window=16, vocab_size=256)
    params = seeded(cfg)
    eng = engine_of(cfg, params, False, prefill_chunk=16)
    rng = np.random.default_rng(5)
    doc = rng.integers(0, 256, 70, dtype=np.int32)
    slot = eng.select_slot(doc, 1)
    eng.prefill(doc, slot, max_new_tokens=1)
    eng.release_slot(slot)
    prompt = np.concatenate([doc, rng.integers(0, 256, 9, dtype=np.int32)])
    slot = eng.select_slot(prompt, 4)
    tok, pre = eng.prefill(prompt, slot, return_logits=True, max_new_tokens=4)
    assert eng.last_admit_info(slot)["cached_by_class"] == {"full": 68,
                                                            "window": 16}
    eng.activate_slot(slot, len(prompt), tok)
    _, dec = eng.decode_once(return_logits=True)
    got = np.stack([pre, dec[slot]])
    toks = list(prompt) + [tok]
    at = [len(prompt) - 1, len(prompt)]
    want, margin = ref_logits(params, cfg, toks, at)
    eng.close()

    def rows(logits):
        return [(f"doc0.{j}", 68, float(np.abs(logits[j] - want[j]).max()),
                 float(margin[j])) for j in range(2)]
    controls = {
        "window_off": ref_logits(params, cfg, toks, at, window=False)[0],
        "rotary_on_full": ref_logits(params, cfg, toks, at,
                                     rotary_all=True)[0],
        "no_gate": ref_logits(params, cfg, toks, at, gate=False)[0],
        "e4m3": ref_logits(params, cfg, toks, at,
                           cast=jnp.float8_e4m3fn)[0]}
    return rows(got), {k: rows(v) for k, v in controls.items()}


def test_the_served_path_passes_the_runners_limits(served_and_controls):
    from perfbench.runners import mixed_docqa
    served, _ = served_and_controls
    assert mixed_docqa.logits_agree(served), served
    assert max(r[2] for r in served) < 2e-5


@pytest.mark.parametrize("control", ["window_off", "rotary_on_full",
                                     "no_gate", "e4m3"])
def test_a_control_fails_the_runners_limits(served_and_controls, control):
    from perfbench.runners import mixed_docqa
    _, controls = served_and_controls
    assert not mixed_docqa.logits_agree(controls[control]), controls[control]
    assert min(r[2] for r in controls[control]) > mixed_docqa.LOGIT_ATOL

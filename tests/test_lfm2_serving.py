"""The ``lfm2_moe`` family (LFM2-24B-A2B) through the normal serving path (PR
45): gated short-convolution layers keep a fixed state a stream BESIDE the
K/V pages of the grouped-query attention layers, two KINDS of cache in one
manager with one prefix rule.

What is held to what:
1. Served logits and conv pages — prefill chunks and decode through both
   kinds of cache, a second request through the prefix-hit path (pages by
   reference + a snapshot copied at the same boundary) — against the plain
   float32 reference the benchmark keeps (``perfbench/lib/lfm2_reference.py``),
   kernels on and off; however a prompt is cut into chunks.
2. THE RULE, on the allocators alone: a hit needs the pages AND a snapshot
   at its boundary; what the pages had beyond it is counted; a reclaimed
   snapshot falls back; exhaustion in either class leaves the other as it
   was; ``commit_snapshot`` / ``abandon_snapshot`` reach the owner.
3. What the engine builds for the four families that were there is what it
   built before (``class_geometry`` is one answer for them).
4. The controls the benchmark's ``correct`` relies on: the reference with
   the conv state zeroed at the resume boundary, or in 8 bits, is far from
   the served path.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference import InferenceEngine, kv_cache   # noqa: E402
from deepspeed_tpu.inference import lfm2 as lfm2_serving        # noqa: E402
from deepspeed_tpu.inference.kv_cache import (                  # noqa: E402
    BlockAllocator, ClassAllocators, PoolExhausted, StateAllocator,
    allocator_for, class_specs)
from deepspeed_tpu.inference.served import (                    # noqa: E402
    filter_tile, served_model)
from deepspeed_tpu.models.lfm2 import (                         # noqa: E402
    CONV, FULL, Lfm2Config, lfm2_init)
from deepspeed_tpu.parallel.topology import build_mesh          # noqa: E402
from perfbench.lib import lfm2_reference as reference           # noqa: E402

BS, WIDTH, N_OUT = 4, 64, 6


def tiny(**kw):
    """1 dense conv layer + one period A C C C: 4 conv layers, 1 attention
    layer of 4 / 2 heads of 16, 8 experts top-2."""
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, num_experts=8,
        num_experts_per_tok=2, layer_types=(CONV, FULL, CONV, CONV, CONV),
        max_position_embeddings=256, dtype=jnp.float32,
        initializer_range=0.08)
    base.update(kw)
    return Lfm2Config(**base)


def sizes_of(cfg):
    """The configuration file's keys for the reference."""
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d["layer_types"] = list(cfg.layer_types)
    d["rope_parameters"] = {"rope_theta": cfg.rope_theta}
    return d


def seeded(cfg, seed=0):
    """The seeded init with the norms' weights moved off 1, so that a norm
    left out or applied on the wrong side shows."""
    params = lfm2_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 1)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(tree, [
        a * jnp.asarray(rng.uniform(0.6, 1.4, a.shape), a.dtype)
        if "norm" in str(path[-1]) else a for path, a in leaves])


CFG = tiny()
_MADE = {}


def params():
    if "params" not in _MADE:
        _MADE["params"] = seeded(CFG)
    return _MADE["params"]


def engine(name):
    """The file's engines, built once: ``chunked`` (chunks of 8 rows, the
    kernels off), ``kernels`` (the same with the Pallas kernels in interpret
    mode), ``whole`` (a chunk of 32 covers a prompt; a conv pool with a page
    a slot and none to spare, so that no snapshot cuts a prompt)."""
    if name not in _MADE:
        conf = dict(max_slots=4, max_seq_len=128, block_size=BS,
                    prefill_chunk=8, paged_kernel=name == "kernels",
                    num_blocks={"full": 96, "conv": 16})
        if name == "whole":
            conf.update(prefill_chunk=32, num_blocks={"full": 96, "conv": 4})
        _MADE[name] = InferenceEngine(
            CFG, params(), config={"inference": conf},
            mesh=build_mesh(devices=jax.devices()[:1]))
    return _MADE[name]


def ref(tokens, positions, state_at=0, **kw):
    """(logits, margin, conv states at ``state_at``) of the reference, one
    compiled function a variant for rows padded to WIDTH."""
    key = tuple(sorted((k, str(v)) for k, v in kw.items()
                       if k != "zero_state_at"))
    if ("ref", key) not in _MADE:
        static = {k: v for k, v in kw.items() if k != "zero_state_at"}
        _MADE["ref", key] = jax.jit(
            lambda p, t, out, at, cut: reference.forward(
                p, t, sizes_of(CFG), out_positions=out, q_block=16,
                state_at=at, zero_state_at=cut, **static))
    row = np.zeros(WIDTH, np.int32)
    row[:len(tokens)] = tokens
    out = np.zeros(N_OUT, np.int32)
    out[:len(positions)] = positions
    lg, margin, states = _MADE["ref", key](
        params(), jnp.asarray(row), jnp.asarray(out), jnp.int32(state_at),
        jnp.int32(kw.get("zero_state_at", 0)))
    n = len(positions)
    return np.asarray(lg)[:n], np.asarray(margin)[:n], np.asarray(states)


def page_of(eng, slot):
    """The stream's conv page, every layer: [conv layers, L - 1, H]."""
    page = int(eng.block_tables[slot][-1])
    pool = np.asarray(eng.cache["conv.conv"])
    return pool[:, 0, page].reshape(pool.shape[0], CFG.conv_L_cache - 1,
                                    CFG.hidden_size)


def through(eng, prompt, steps=2, keep=False):
    """(tokens, logits of the prefill and of ``steps`` decode iterations,
    admission info, the conv page after prefill and after the last
    iteration) of ``prompt`` served alone."""
    slot = eng.select_slot(prompt, steps + 1)
    tok, pre = eng.prefill(prompt, slot, return_logits=True,
                           max_new_tokens=steps + 1)
    info = dict(eng.last_admit_info(slot))
    page0 = page_of(eng, slot)
    eng.activate_slot(slot, len(prompt), tok)
    toks, got = [tok], [np.asarray(pre)]
    for _ in range(steps):
        sampled, lg = eng.decode_once(return_logits=True)
        toks.append(int(sampled[slot]))
        got.append(np.asarray(lg[slot]))
    page1 = page_of(eng, slot)
    if not keep:
        eng.release_slot(slot)
    return toks, np.stack(got), info, page0, page1


def prompt_of(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n,
                                                dtype=np.int32)


# --------------------------------------------------------------------- #
# 0. The config and what the model declares
# --------------------------------------------------------------------- #
def test_a_cut_in_depth_takes_the_dense_layer_and_whole_periods():
    types = [CONV, CONV] + [FULL, CONV, CONV, CONV] * 9 + [FULL, CONV]
    cfg = Lfm2Config.from_hf({
        "num_hidden_layers": 9, "num_dense_layers": 1, "layer_types": types,
        "published": {"num_hidden_layers": 40, "num_dense_layers": 2},
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "norm_eps": 1e-5})
    assert cfg.layer_types == (CONV,) + (FULL, CONV, CONV, CONV) * 2
    assert reference.layer_types({
        "num_hidden_layers": 9, "num_dense_layers": 1, "layer_types": types,
        "published": {"num_dense_layers": 2}}) == list(cfg.layer_types)
    assert (cfg.num_conv_layers, cfg.num_attention_layers) == (7, 2)
    assert cfg.head_dim == 64 and cfg.group == 4
    assert cfg.routing.held == (0, 64) and cfg.routing.norm_eps == 1e-6
    uncut = Lfm2Config.from_hf({"layer_types": types})
    assert uncut.layer_types == tuple(types)


def test_the_model_declares_two_kinds_of_cache():
    cfg = Lfm2Config(layer_types=tuple(
        [CONV, CONV] + [FULL, CONV, CONV, CONV] * 9 + [FULL, CONV]))
    served = lfm2_serving.Lfm2Served(cfg)
    full, conv = served.cache_classes
    assert tuple(full) == ("full", 10, None, False)
    assert tuple(conv) == ("conv", 30, None, True)
    assert served.class_geometry(full, 64) == dict(
        pools=(("k", (8, 32, 128)), ("v", (8, 32, 128))), num_heads=8,
        head_dim=64, token_row_bytes=0)
    geometry = served.class_geometry(conv, 64)
    assert geometry["pools"] == (("conv", (1, 32, 128)),)
    # the yardstick: a token's K/V rows in the ATTENTION layers, a conv
    # layer's share
    assert geometry["token_row_bytes"] == -(-10 * 2048 // 30)
    with pytest.raises(NotImplementedError):
        served.verify(None, (), None, None, None, num_groups=1,
                      paged_kernel=False)


def test_the_engine_builds_a_spec_a_class_from_the_models_own_answer():
    eng = engine("chunked")
    full, conv = eng.cache_specs
    assert (full.name, full.per_stream, full.num_layers) == ("full", False, 1)
    assert (conv.name, conv.per_stream, conv.num_layers) == ("conv", True, 4)
    assert full.pool_shapes == {"k.full": (1, 1, 96, 2, 1, 64),
                                "v.full": (1, 1, 96, 2, 1, 64)}
    assert conv.pool_shapes == {"conv.conv": (4, 1, 16, 1, 1, 128)}
    assert conv.max_blocks_per_slot == 1 and conv.page_tokens == 8
    assert eng.allocator.table_width == 128 // BS + 1
    assert isinstance(eng.allocator, ClassAllocators)
    assert [type(a) for a in eng.allocator.classes] == [BlockAllocator,
                                                        StateAllocator]
    assert eng.allocator.copy_pools == ("conv.conv",)
    assert eng.allocator.copy_program == ("state_copy", "state_copy")
    with pytest.raises(ValueError, match="spec_k"):
        allocator_for(eng.cache_specs, spec_k=2)


# --------------------------------------------------------------------- #
# 1. The program against the reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("name", ["chunked", "kernels"])
def test_served_logits_and_pages_match_the_reference(name):
    """Four chunks (the third ends at the snapshot's boundary, the last
    holds 3 live rows of 8), then decode, through both kinds of cache."""
    eng = engine(name)
    prompt = prompt_of(1, 27)
    toks, got, info, page0, page1 = through(eng, prompt)
    assert info["cached_tokens"] == 0 and info["snapshot_at"] == 24
    seq = np.concatenate([prompt, toks[:-1]])
    want, _, state = ref(seq, [26, 27, 28], state_at=26)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(page0, state, atol=1e-5)
    np.testing.assert_allclose(page1, ref(seq, [28], state_at=28)[2],
                               atol=1e-5)


@pytest.mark.parametrize("name", ["chunked", "kernels"])
def test_a_stream_resumed_from_pages_and_a_snapshot_equals_a_cold_one(name):
    """The second request shares the first's blocks by reference and copies
    its snapshot at the same boundary; its logits and pages are those of an
    engine that never saw the first."""
    eng, cold = engine(name), engine("whole")
    seed = 200 + 2 * (name == "kernels")       # (the cold engine is shared)
    first = prompt_of(seed, 18)
    through(eng, first, steps=1)
    turn = np.concatenate([first, prompt_of(seed + 1, 11)])
    assert eng.prefix_match_tokens(turn) == 16
    toks, got, info, page0, page1 = through(eng, turn)
    assert info["cached_tokens"] == 16 and info["cow_fork"]
    assert info["cached_by_class"] == {"full": 16, "conv": 16}
    assert info["lost_to_kind_tokens"] == 0 and info["snapshot_at"] == 28
    ctoks, cgot, cinfo, cpage0, cpage1 = through(cold, turn)
    assert cinfo["cached_tokens"] == 0 and ctoks == toks
    np.testing.assert_allclose(got, cgot, atol=2e-5)
    np.testing.assert_allclose(page0, cpage0, atol=1e-5)
    np.testing.assert_allclose(page1, cpage1, atol=1e-5)
    seq = np.concatenate([turn, toks[:-1]])
    want, _, state = ref(seq, [28, 29, 30], state_at=30)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(page1, state, atol=1e-5)


@pytest.mark.parametrize("n", [25, 27, 31, 32, 5, 1])
def test_chunks_and_cuts_change_nothing(n):
    """A prompt in chunks of 8 whose snapshot a chunk program leaves on its
    way (n = 25: a last chunk of ONE live row, the snapshot at the last row
    of the chunk before, as for 27; 31: inside the last chunk; 32: at the
    prompt's last row; 5, 1: no snapshot, a padded chunk) against the same
    prompt in one padded chunk with no snapshot."""
    chunked, whole = engine("chunked"), engine("whole")
    held = [through(whole, prompt_of(40 + i, 3), steps=0, keep=True)
            for i in range(3)]            # the pages a snapshot would take
    try:
        prompt = prompt_of(10 + n, n)
        toks, got, info, page0, page1 = through(chunked, prompt)
        wtoks, wgot, winfo, wpage0, wpage1 = through(whole, prompt)
    finally:
        for s in np.flatnonzero(whole.active):
            whole.release_slot(int(s))
    at = n // BS * BS if n >= 8 else 0        # (a page is worth 8 tokens)
    assert info["snapshot_at"] == at
    assert info["chunks"] == -(-n // 8)         # (no cut at the boundary)
    assert winfo["chunks"] == 1 and winfo["snapshot_at"] == 0
    assert toks == wtoks
    np.testing.assert_allclose(got, wgot, atol=2e-5)
    np.testing.assert_allclose(page0, wpage0, atol=1e-5)
    np.testing.assert_allclose(page1, wpage1, atol=1e-5)
    del held


def test_dead_slots_and_inactive_groups_write_no_page():
    """A decode iteration rewrites the pages of LIVE slots only, and a
    warm-up chunk (every group inactive) none at all."""
    eng = engine("chunked")
    before = np.asarray(eng.cache["conv.conv"]).copy()
    eng._warm_prefill_widths()
    np.testing.assert_array_equal(np.asarray(eng.cache["conv.conv"]), before)
    slot = eng.select_slot(prompt_of(5, 6), 3)
    tok, _ = eng.prefill(prompt_of(5, 6), slot, max_new_tokens=3)
    eng.activate_slot(slot, 6, tok)
    before = np.asarray(eng.cache["conv.conv"]).copy()
    eng.decode_once()
    after = np.asarray(eng.cache["conv.conv"])
    own = int(eng.block_tables[slot][-1])
    others = [b for b in range(after.shape[2]) if b != own]
    np.testing.assert_array_equal(after[:, :, others], before[:, :, others])
    assert np.abs(after[:, 0, own] - before[:, 0, own]).max() > 0
    eng.release_slot(slot)


def test_the_controls_are_far_from_the_served_path():
    """What the benchmark's ``correct`` leans on: the reference with the
    conv state zeroed at the resume boundary, and in 8-bit operands, is far
    from the served path where the true reference is near."""
    eng = engine("chunked")
    first = prompt_of(6, 20)
    through(eng, first, steps=0)
    turn = np.concatenate([first, prompt_of(7, 9)])
    toks, got, info, _, page1 = through(eng, turn)
    assert info["cached_tokens"] == 20
    seq = np.concatenate([turn, toks[:-1]])
    at = [28, 29, 30]
    want, _, state = ref(seq, at, state_at=30)
    assert np.abs(got - want).max() < 2e-5
    zeroed = ref(seq, at, state_at=30, zero_state_at=20)[0]
    assert np.abs(got - zeroed).max() > 100 * 2e-5
    low = ref(seq, at, cast=jnp.float8_e4m3fn)[0]
    assert np.abs(got - low).max() > 100 * 2e-5
    # a zeroed state at ANOTHER boundary than the one resumed at differs too
    assert np.abs(zeroed - ref(seq, at, zero_state_at=16)[0]).max() > 1e-3


def test_the_look_ahead_loop_serves_sessions_turn_by_turn():
    """``engine.serve`` over turns that extend each other: every later turn
    is a hit across kinds, a snapshot a turn, tokens those of a synchronous
    loop on a cold engine."""
    from deepspeed_tpu.inference.scheduler import Request
    eng, cold = engine("chunked"), engine("whole")
    history = prompt_of(8, 21)
    turns = [np.concatenate([history] + [prompt_of(80 + j, 9)
                                         for j in range(k + 1)])
             for k in range(3)]
    taken0 = eng.allocator.snapshot_totals()["snapshots_taken"]
    eng.reset_serving_stats()
    for k, prompt in enumerate(turns):      # a round apart, as the cell's
        report = eng.serve([Request(rid=k, prompt=prompt, max_new_tokens=4,
                                    arrival_s=0.0)])
        assert report["completed"] == k + 1
    assert eng._inflight is None and not eng.active.any()
    state = report["state"]
    assert state["snapshots_taken"] - taken0 == 3
    assert state["resumed_tokens"] == 28 + 36       # turn 1 at 28, 2 at 36
    assert state["prefix_lost_to_kind_tokens"] == 0
    assert report["prefix"]["cached_tokens"] == 28 + 36
    toks = through(cold, turns[-1], steps=3)[0]
    slot = eng.select_slot(turns[-1], 4)
    tok, _ = eng.prefill(turns[-1], slot, max_new_tokens=4)
    assert tok == toks[0] and eng.last_admit_info(slot)["cached_tokens"] == 36
    eng.release_slot(slot)


# --------------------------------------------------------------------- #
# 2. The rule, on the allocators alone
# --------------------------------------------------------------------- #
def manager(full=40, conv=8, order=("full", "conv")):
    served = lfm2_serving.Lfm2Served(CFG)
    classes = sorted(served.cache_classes, key=lambda c: order.index(c.name))
    specs = class_specs(
        classes, {"full": full, "conv": conv}, rows=8,
        of_class=lambda cls: served.class_geometry(cls, BS), num_slots=4,
        block_size=BS, max_len=128, num_groups=1, dtype=jnp.float32)
    return allocator_for(specs)


def admitted(alloc, slot, prompt, commit=True):
    plan = alloc.admit_prompt(slot, 0, prompt, 4)
    if commit and plan.snapshot_page is not None:
        alloc.commit_snapshot(plan)
    return plan


def test_a_hit_needs_the_pages_and_a_snapshot_at_its_boundary():
    alloc = manager()
    full, conv = alloc.classes
    base = prompt_of(20, 27)
    plan = admitted(alloc, 0, base)
    assert (plan.matched, plan.snapshot_at, plan.copy_class) == (0, 24, "conv")
    assert plan.page == plan.table[-1] and plan.cow_src is None
    longer = np.concatenate([base, prompt_of(21, 13)])         # 40 tokens
    plan = admitted(alloc, 1, longer)
    assert (plan.matched, plan.snapshot_at) == (24, 40)
    assert plan.cow_src is not None and plan.cow_dst == plan.page
    assert plan.cached_by_class == {"full": 24, "conv": 24}
    assert plan.lost_to_kind == 0
    # The pages hold 8 blocks of this prompt, the snapshots lie at 6 and
    # 10: the hit is at 6, and 2 blocks of pages were there for nothing.
    forked = np.concatenate([longer[:32], prompt_of(22, 9)])
    assert full.matched_blocks(0, forked) == 8
    assert conv.match_limit(0, kv_cache.chain_hashes(forked, BS), 8) == 6
    assert alloc.matched_blocks(0, forked) == 6
    plan = admitted(alloc, 2, forked)
    assert (plan.matched, plan.lost_to_kind) == (24, 8)
    assert alloc.span_args(plans=[plan])["prefix_lost_to_kind_tokens"] == 8
    assert alloc.span_args(plans=[plan])["resumed_tokens"] == 24
    # Pages of 5 blocks and no snapshot on this chain at or before them:
    # nothing to resume from, all 5 blocks' worth lost to the kind.
    other = np.concatenate([base[:20], prompt_of(23, 10)])
    assert alloc.matched_blocks(0, other) == 0
    plan = admitted(alloc, 3, other)
    assert (plan.matched, plan.lost_to_kind, plan.cow_src) == (0, 20, None)


def test_a_reclaimed_snapshot_falls_back_and_the_pages_stay():
    alloc = manager(conv=3)
    full, conv = alloc.classes
    base = prompt_of(24, 27)
    plan = admitted(alloc, 0, base)
    alloc.release(0, plan.table)
    assert alloc.matched_blocks(0, np.concatenate([base, [1]])) == 6
    # two strangers' pages and snapshots push the retained snapshot out
    for slot, seed in ((1, 25), (2, 26)):
        p = admitted(alloc, slot, prompt_of(seed, 9))
        alloc.release(slot, p.table)
    assert conv.reclaimed >= 1
    again = np.concatenate([base, prompt_of(27, 6)])
    assert full.matched_blocks(0, again) == 6          # the pages stayed
    assert alloc.matched_blocks(0, again) == 0         # the hit did not
    plan = admitted(alloc, 0, again)
    assert (plan.matched, plan.lost_to_kind, plan.cow_src) == (0, 24, None)
    assert alloc.snapshot_totals()["snapshots_evicted"] == conv.reclaimed


@pytest.mark.parametrize("order", [("full", "conv"), ("conv", "full")])
@pytest.mark.parametrize("short", ["full", "conv"])
def test_exhaustion_in_one_class_leaves_the_other_as_it_was(order, short):
    """The composite's gate says no, and an admission tried all the same
    gives back what the classes before the dry one drew — a state class's
    uncommitted snapshot page too."""
    alloc = manager(full=4 if short == "full" else 40,
                    conv=1 if short == "conv" else 8, order=order)
    prompt = prompt_of(28, 27)
    if short == "conv":
        holder = admitted(alloc, 3, prompt_of(29, 3))
    by_name = {a.spec.name: a for a in alloc.classes}
    before = {n: (a.available(0), a.blocks_in_use())
              for n, a in by_name.items()}
    assert not alloc.can_admit(0, prompt, 20)
    with pytest.raises(PoolExhausted):
        alloc.admit_prompt(0, 0, prompt, 20)
    other = by_name["conv" if short == "full" else "full"]
    assert (other.available(0), other.blocks_in_use()) \
        == before[other.spec.name]
    assert len(by_name["conv"]._free[0]) + len(by_name["conv"]._lru[0]) \
        + by_name["conv"].blocks_in_use() == by_name["conv"].spec.num_blocks
    if short == "conv":
        alloc.release(3, holder.table)


def test_commit_and_abandon_reach_the_class_that_owns_the_page():
    alloc = manager()
    full, conv = alloc.classes
    prompt = prompt_of(30, 27)
    free0 = len(conv._free[0])
    plan = admitted(alloc, 0, prompt, commit=False)
    assert plan.snapshot_page is not None
    assert len(conv._free[0]) == free0 - 2             # own page + snapshot
    alloc.abandon_snapshot(plan)                       # prefill failed
    alloc.release(0, plan.table)
    assert len(conv._free[0]) == free0 and conv.snapshots_taken == 0
    probe = np.concatenate([prompt, [0]])
    assert alloc.matched_blocks(0, probe) == 0
    plan = admitted(alloc, 0, prompt, commit=False)
    alloc.commit_snapshot(plan)
    assert conv.snapshots_taken == 1 and full.snapshot_totals() == {}
    assert alloc.matched_blocks(0, probe) == 6
    alloc.abandon_snapshot(plan)               # committed: it holds a state
    assert alloc.matched_blocks(0, probe) == 6
    alloc.release(0, plan.table)


def test_a_state_class_alone_resumes_from_its_longest_boundary_as_before():
    """``StateAllocator`` outside a composite (the retention family): no
    limit, the longest boundary that has a snapshot."""
    served = lfm2_serving.Lfm2Served(CFG)
    conv = [c for c in served.cache_classes if c.per_stream]
    spec, = class_specs(conv, 6, rows=8,
                        of_class=lambda c: served.class_geometry(c, BS),
                        num_slots=2, block_size=BS, max_len=128,
                        num_groups=1, dtype=jnp.float32)
    alloc = allocator_for([spec])
    assert type(alloc) is StateAllocator
    base = prompt_of(31, 27)
    plan = alloc.admit_prompt(0, 0, base, 4)
    alloc.commit_snapshot(plan)
    alloc.release(0, plan.table)
    assert alloc.match_snapshot(0, np.concatenate([base, [3, 4]]))[0] == 6
    assert alloc.match_snapshot(0, np.concatenate([base, [3, 4]]),
                                limit=5)[0] == 0
    assert alloc.match_limit(0, kv_cache.chain_hashes(base, BS), 6) == 6


# --------------------------------------------------------------------- #
# 2b. The snapshot a chunk program leaves on its way (PR 46)
# --------------------------------------------------------------------- #
class CutServed(lfm2_serving.Lfm2Served):
    """The family as it was served before: the model says its state cannot
    be frozen inside a chunk, so the engine cuts the prompt at the
    snapshot's boundary and copies the stream's page."""
    freezes_in_chunk = False


def pair(chunk, dp=1):
    """Two engines alike but for the model's word — the chunk program
    freezes the snapshot, or a cut + ``state_copy`` does — built once a
    chunk size (3: a boundary can be the FIRST row of a chunk, which a
    chunk that shares a factor with the block size never sees)."""
    key = ("pair", chunk, dp)
    if key not in _MADE:
        conf = dict(max_slots=2 * dp, max_seq_len=132 if chunk == 3 else 128,
                    block_size=BS, prefill_chunk=chunk, paged_kernel=False,
                    num_blocks={"full": 96 * dp, "conv": 16 * dp})
        mesh = build_mesh(dp=dp, devices=jax.devices()[:dp])
        _MADE[key] = tuple(
            InferenceEngine(model, params(), config={"inference": conf},
                            mesh=mesh)
            for model in (CFG, CutServed(CFG)))
    return _MADE[key]


def snapshot_page_of(eng, prompt, group=0):
    """The conv page (every layer) that holds the snapshot at ``prompt``'s
    last full block, and its index."""
    conv = eng.allocator.classes[-1]
    page = conv._hash_index[group][kv_cache.chain_hashes(prompt, BS)[-1]]
    return np.asarray(eng.cache["conv.conv"])[:, group, page], page


@pytest.mark.parametrize("chunk,history,n,row", [
    (8, 0, 12, 3),       # the middle of the second of two chunks
    (8, 0, 8, 7),        # a chunk's last row, the prompt's last too
    (8, 0, 11, 7),       # a chunk's last row, three rows behind it
    (8, 0, 31, 3),       # inside the fourth chunk
    (8, 18, 13, 3),      # a turn resumed at 16: inside its second chunk
    (8, 18, 7, 7),       # a turn resumed at 16: its one chunk's last row
    (3, 0, 17, 0),       # the FIRST row of a chunk (chunks of 3)
    (3, 0, 10, 1),       # the middle row
    (3, 0, 13, 2),       # the last row, one more chunk behind it
])
def test_a_snapshot_frozen_in_the_program_is_the_cut_and_copys(
        chunk, history, n, row):
    """The page a chunk program freezes at the snapshot's row, bit for bit
    the page the cut + ``state_copy`` path leaves; first token, logits and
    every pool row of the two engines agree."""
    frozen, cut = pair(chunk)
    assert frozen._freeze_in_chunk and not cut._freeze_in_chunk
    seed = 1000 * chunk + 10 * n + history
    prompt = prompt_of(seed, n)
    if history:
        first = prompt_of(seed + 1, history)
        for eng in (frozen, cut):
            through(eng, first, steps=0)
        prompt = np.concatenate([first, prompt])
    resumed = history // BS * BS
    at = len(prompt) // BS * BS
    assert (at - resumed - 1) % chunk == row
    got = through(frozen, prompt)
    want = through(cut, prompt)
    assert got[2]["snapshot_at"] == want[2]["snapshot_at"] == at
    assert got[2]["cached_tokens"] == want[2]["cached_tokens"] == resumed
    # one program fewer wherever the boundary is not a chunk's last row
    assert got[2]["chunks"] == -(-(len(prompt) - resumed) // chunk)
    assert want[2]["chunks"] == -(-(at - resumed) // chunk) \
        + -(-(len(prompt) - at) // chunk)
    page, index = snapshot_page_of(frozen, prompt)
    cpage, cindex = snapshot_page_of(cut, prompt)
    assert index == cindex
    np.testing.assert_array_equal(page, cpage)
    assert got[0] == want[0]
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)
    for name in frozen.cache:
        np.testing.assert_allclose(np.asarray(frozen.cache[name]),
                                   np.asarray(cut.cache[name]), atol=1e-5,
                                   err_msg=name)
    # and it IS the state at the boundary
    state = ref(prompt, [at - 1], state_at=at - 1)[2]
    np.testing.assert_allclose(
        page.reshape(state.shape), state, atol=1e-5)
    taken = frozen.allocator.snapshot_totals()
    assert taken["snapshots_in_program"] == taken["snapshots_taken"]
    assert cut.allocator.snapshot_totals()["snapshots_in_program"] == 0


@pytest.mark.parametrize("name", ["chunked", "kernels"])
def test_a_turn_resumed_from_a_snapshot_frozen_in_a_program_equals_a_cold_one(
        name):
    """The first turn's snapshot (at 20) lies INSIDE its third chunk; the
    next turn copies it and resumes there: logits and pages of an engine
    that never saw the first, and the reference's."""
    eng, cold = engine(name), engine("whole")
    seed = 300 + 2 * (name == "kernels")
    first = prompt_of(seed, 21)
    info = through(eng, first, steps=1)[2]
    assert info["snapshot_at"] == 20 and info["chunks"] == 3
    turn = np.concatenate([first, prompt_of(seed + 1, 10)])
    assert eng.prefix_match_tokens(turn) == 20
    toks, got, info, page0, page1 = through(eng, turn)
    assert info["cached_tokens"] == 20 and info["cow_fork"]
    assert info["cached_by_class"] == {"full": 20, "conv": 20}
    assert info["chunks"] == 2 and info["snapshot_at"] == 28
    ctoks, cgot, cinfo, cpage0, cpage1 = through(cold, turn)
    assert cinfo["cached_tokens"] == 0 and ctoks == toks
    np.testing.assert_allclose(got, cgot, atol=2e-5)
    np.testing.assert_allclose(page0, cpage0, atol=1e-5)
    np.testing.assert_allclose(page1, cpage1, atol=1e-5)
    seq = np.concatenate([turn, toks[:-1]])
    want, _, state = ref(seq, [30, 31, 32], state_at=32)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(page1, state, atol=1e-5)
    # the reference resumed WITHOUT the state at 20 is far from it
    zeroed = ref(seq, [30, 31, 32], zero_state_at=20)[0]
    assert np.abs(got - zeroed).max() > 100 * 2e-5


def _chunk_call(eng, prompt, slot_page, **operands):
    """One hand-made dispatch of ``prefill_step`` over ``prompt`` at
    position 0 into conv page ``slot_page`` (and no K/V block: the rows'
    writes land nowhere); returns the conv pool before and after."""
    G, J = eng.dp, eng.allocator.table_width
    toks = np.zeros((G, eng.prefill_chunk), np.int32)
    toks[0, :len(prompt)] = prompt
    bt = np.full((G, J), kv_cache.DEAD_BLOCK, np.int32)
    bt[0, -1] = slot_page
    args = dict(start=np.zeros(G, np.int32),
                last_idx=np.full(G, len(prompt) - 1, np.int32),
                active=np.ones(G, np.int32),
                freeze_idx=np.zeros(G, np.int32),
                freeze_page=np.full(G, kv_cache.DEAD_BLOCK, np.int32))
    args.update({k: np.asarray(v, np.int32) for k, v in operands.items()})
    before = np.asarray(eng.cache["conv.conv"]).copy()
    *pools, _, _ = eng._prefill_fn(
        eng._params, *eng._pools(), toks, bt, *args.values(),
        np.int32(1), eng._base_rng, np.float32(0.0))
    eng._store_pools(pools)
    return before, np.asarray(eng.cache["conv.conv"])


def test_an_inactive_group_or_one_without_a_snapshot_due_writes_no_second_page():
    """The program's second write lands only where an ACTIVE group names a
    page: a group that leaves no snapshot in the chunk rewrites its own page
    alone, an inactive one nothing, whatever the operands hold."""
    eng = pair(8)[0]
    prompt = prompt_of(70, 8)
    own, snap = 12, 14               # (free pages: nothing resumes from them)
    others = [b for b in range(16) if b not in (own, snap)]
    before, after = _chunk_call(eng, prompt, own)          # no page named
    np.testing.assert_array_equal(after[:, :, others + [snap]],
                                  before[:, :, others + [snap]])
    assert np.abs(after[:, 0, own] - before[:, 0, own]).max() > 0
    before, after = _chunk_call(eng, prompt, own, active=[0],
                                freeze_idx=[3], freeze_page=[snap])
    np.testing.assert_array_equal(after, before)            # inactive
    before, after = _chunk_call(eng, prompt, own, freeze_idx=[3],
                                freeze_page=[snap])
    np.testing.assert_array_equal(after[:, :, others], before[:, :, others])
    # the snapshot's page: what a chunk of rows 0-3 alone leaves in its own
    # (no K/V block here, so only the layer ahead of the attention layer is
    # the reference's); the own page: the state after row 7
    np.testing.assert_allclose(
        after[0, 0, snap].reshape(2, 64),
        ref(prompt, [3], state_at=3)[2][0], atol=1e-5)
    np.testing.assert_allclose(
        after[0, 0, own].reshape(2, 64),
        ref(prompt, [7], state_at=7)[2][0], atol=1e-5)
    frozen = after[:, 0, snap].copy()
    _, after = _chunk_call(eng, prompt[:4], own)
    np.testing.assert_array_equal(after[:, 0, own], frozen)
    # a boundary that IS the last live row: the same rows, to two pages
    before, after = _chunk_call(eng, prompt, own, freeze_idx=[7],
                                freeze_page=[snap])
    np.testing.assert_array_equal(after[:, 0, snap], after[:, 0, own])


def test_a_failed_prefill_returns_the_snapshot_page_no_program_froze():
    """The chunk that would leave the snapshot raises: its page goes back
    (``abandon_snapshot``), nothing can resume there, and the prompt served
    again leaves it."""
    eng = pair(8)[0]
    prompt = prompt_of(71, 21)
    conv = eng.allocator.classes[-1]
    free0, taken0 = conv.available(0), conv.snapshots_taken
    calls, real = [], eng._prefill_fn

    def failing(*args):
        calls.append(1)
        if len(calls) == 3:                  # the chunk that holds row 19
            raise RuntimeError("a bad chunk")
        return real(*args)
    eng._prefill_fn = failing
    slot = eng.select_slot(prompt, 2)
    try:
        with pytest.raises(RuntimeError, match="a bad chunk"):
            eng.prefill(prompt, slot, max_new_tokens=2)
    finally:
        eng._prefill_fn = real
    eng.release_slot(slot)
    probe = np.concatenate([prompt, [0]])
    assert eng.prefix_match_tokens(probe) == 0
    assert conv.available(0) == free0 and conv.snapshots_taken == taken0
    info = through(eng, prompt)[2]
    assert info["cached_tokens"] == 0 and info["snapshot_at"] == 20
    assert eng.prefix_match_tokens(probe) == 20
    assert conv.snapshots_taken == taken0 + 1


def test_one_group_freezes_while_the_other_does_not():
    """``dp`` = 2, one admission a group in one pass of chunk programs: the
    first group's prompt leaves a snapshot inside its third chunk, the
    second's is too short for one; each is what a one-device engine gives,
    and the second group's pool holds its stream's own page and no other."""
    if len(jax.devices()) < 2:
        pytest.skip("needs two host devices")
    eng = pair(8, dp=2)[0]
    one, cut = pair(8)
    assert eng.dp == 2 and eng._freeze_in_chunk
    prompts = [prompt_of(72, 21), prompt_of(73, 6)]
    slots = []
    for p in prompts:
        slots.append(eng.select_slot(p, 2, exclude_groups={
            eng.group_of(s) for s in slots}))
    assert [eng.group_of(s) for s in slots] == [0, 1]
    before = np.asarray(eng.cache["conv.conv"]).copy()
    out = eng.prefill_many([(s, p, 2) for s, p in zip(slots, prompts)],
                           return_logits=True)
    after = np.asarray(eng.cache["conv.conv"])
    infos = [eng.last_admit_info(s) for s in slots]
    assert [i["snapshot_at"] for i in infos] == [20, 0]
    assert [i["chunks"] for i in infos] == [3, 1]
    for (tok, logits), p in zip(out, prompts):
        wtoks, wgot, *_ = through(one, p, steps=0)
        assert tok == wtoks[0]
        np.testing.assert_allclose(logits, wgot[0], atol=2e-5)
    through(cut, prompts[0], steps=0)
    np.testing.assert_allclose(snapshot_page_of(eng, prompts[0])[0],
                               snapshot_page_of(cut, prompts[0])[0],
                               atol=1e-6)
    own = int(eng.block_tables[slots[1]][-1])
    others = [b for b in range(after.shape[2]) if b != own]
    np.testing.assert_array_equal(after[:, 1, others], before[:, 1, others])
    assert np.abs(after[:, 1, own] - before[:, 1, own]).max() > 0
    # group 0: its own page and the snapshot's, no third
    changed = [b for b in range(after.shape[2])
               if np.abs(after[:, 0, b] - before[:, 0, b]).max() > 0]
    assert sorted(changed) == sorted(
        [int(eng.block_tables[slots[0]][-1]),
         snapshot_page_of(eng, prompts[0])[1]])
    for s, p, (tok, _) in zip(slots, prompts, out):
        eng.activate_slot(s, len(p), tok)
        eng.release_slot(s)


def test_nothing_compiles_in_a_window_that_mixes_turns_with_and_without_a_snapshot():
    """Whether a group leaves a snapshot in a chunk is an OPERAND: turns
    that leave one inside a chunk, at a chunk's end and none at all run the
    programs the first serve built."""
    import jax.monitoring
    from jax._src import monitoring
    from deepspeed_tpu.inference.scheduler import Request
    eng = InferenceEngine(
        CFG, params(), config={
            "inference": dict(max_slots=4, max_seq_len=128, block_size=BS,
                              prefill_chunk=8, paged_kernel=False,
                              num_blocks={"full": 96, "conv": 16}),
            "telemetry": {"enabled": True, "fail_on_recompile": True,
                          "report_steps": 10 ** 6}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    history = prompt_of(90, 21)
    # (a turn over it as well: the first copy of a snapshot into a stream's
    # page builds ``state_copy``, which no cold prompt dispatches any more)
    for rid, prompt in enumerate(
            [history, np.concatenate([history, prompt_of(89, 2)])]):
        report = eng.serve([Request(rid=-1 - rid, prompt=prompt,
                                    max_new_tokens=3, arrival_s=0.0)])
    assert report["completed"] == 2
    counts = eng.telemetry.sentinel.compile_counts()
    assert counts["prefill_step"] == 1 and counts["state_copy"] == 1
    compiles = []

    def listener(name, *_, **__):
        if "backend_compile" in name:
            compiles.append(name)
    # 13 more: a snapshot inside the turn's second chunk; 3: none (under a
    # page's worth); 11 behind 32: at a chunk's last row; a cold 5: none
    lengths = [13, 3, 11]
    reqs = [Request(rid=1 + i, prompt=np.concatenate(
        [history, prompt_of(91 + i, n)]), max_new_tokens=3, arrival_s=0.0)
        for i, n in enumerate(lengths)]
    reqs.append(Request(rid=9, prompt=prompt_of(99, 5), max_new_tokens=3,
                        arrival_s=0.0))
    taken0 = eng.allocator.snapshot_totals()
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        report = eng.serve(reqs)
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert report["completed"] == 2 + len(reqs)
    assert report["recompiles"] == 0 and compiles == []
    assert eng.telemetry.sentinel.compile_counts() == counts
    state = report["state"]
    assert state["snapshots_taken"] - taken0["snapshots_taken"] == 2
    assert state["snapshots_in_program"] == state["snapshots_taken"]
    eng.close()


# --------------------------------------------------------------------- #
# 3. The four families that were there
# --------------------------------------------------------------------- #
def _family(name):
    if name == "gpt2":
        from deepspeed_tpu.models import GPT2Config
        return GPT2Config(hidden_size=64, num_heads=4, num_layers=2,
                          max_seq_length=128, vocab_size=128)
    if name == "latent":
        from test_latent_serving import tiny as latent_tiny
        return latent_tiny()
    if name == "retention":
        from test_retention_serving import tiny as retention_tiny
        return retention_tiny()
    from test_afmoe_serving import tiny as afmoe_tiny
    return afmoe_tiny()


@pytest.mark.parametrize("family", ["gpt2", "latent", "retention", "afmoe"])
def test_the_four_families_specs_are_what_the_engine_built_before(family):
    """One answer for every class: the specs built class by class equal the
    specs built from the model's one set of pools (the engine's call before
    this PR)."""
    served = served_model(_family(family))
    geometry = dict(num_slots=4, block_size=4, max_len=128, num_groups=1,
                    dtype=jnp.float32)
    asked = {c.name: 24 for c in served.cache_classes} \
        if len(served.cache_classes) > 1 else 24
    new = class_specs(served.cache_classes, asked, rows=8,
                      of_class=lambda cls: served.class_geometry(cls, 4),
                      **geometry)
    old = class_specs(served.cache_classes, asked, rows=8,
                      num_heads=served.cache_heads,
                      head_dim=served.cache_row_width,
                      pools=served.cache_pools(4),
                      token_row_bytes=served.token_row_bytes, **geometry)
    assert new == old and len(new) == len(served.cache_classes)
    alloc = allocator_for(new)
    names = tuple(n for sp in new for n in sp.pool_names)
    assert alloc.copy_pools == names          # a copy runs on every pool


# --------------------------------------------------------------------- #
# A decode step rewrites the conv rows in place (PR 53)
# --------------------------------------------------------------------- #
def test_decode_rewrites_the_conv_rows_in_place_and_serves_the_same(
        monkeypatch):
    """A hidden size of 1,024 makes a held row 8 sublane rows of fp32, a
    whole tile, so ``served.filter_rows`` hands the decode program's rows to
    ``ops.filter_rows.shift_rows`` (interpret mode here).  The same engine
    traced with the shape rule answering no keeps the plain lines: the
    tokens, the logits and the stream's conv page are equal bit for bit,
    and the ``decode`` span's arg says which was which."""
    from test_filter_rows import assert_the_same_stream, served_both_ways
    cfg = tiny(hidden_size=1024, num_attention_heads=4, num_hidden_layers=3,
               layer_types=(CONV, FULL, CONV), intermediate_size=64,
               moe_intermediate_size=16)
    assert filter_tile(cfg.conv_L_cache - 1, cfg.hidden_size) == (1, 16, 128)
    assert_the_same_stream(*served_both_ways(
        monkeypatch, cfg, seeded(cfg), {"full": 96, "conv": 16},
        prompt_of(3, 11), ("conv.conv",)))
    # the file's own size (64 channels: a [1, 128] tile) keeps the plain lines
    assert engine("kernels").filter_rows_in_place == 0

"""The ``sdar_moe`` family (SDAR-30B-A3B) through the normal serving path (PR
62): generation by diffusion over blocks — a stream's step is a PASS over a
block of ``block_length`` positions that see each other.

What is held to what:
1. Served logits at EVERY pass (denoise and commit) of a stream's blocks —
   chunked prefill under the block mask, then block passes through the paged
   cache — against the plain float32 reference the benchmark keeps
   (``perfbench/lib/sdar_reference.py``: a full forward over the stream's
   tokens, no cache), kernels on and off, for prompts that end at a block
   boundary and inside a block; any chunking of the prefill, and a
   prefix-cache hit, give the cold run's logits.
2. The two unmasking rules (``models.sdar.unmask``) against a plain NumPy
   statement of them, the static count's two statements against each other,
   and the published router against ``moe/share.py``'s ``softmax_topk``.
3. The scheduler: exactly ``gen_length`` tokens a request, the reference's
   own (``generate``), with slots in different passes of their blocks in one
   batch and one compiled program; tokens counted, not rows or passes; the
   loop runs ahead under the static rule and waits under the dynamic one; a
   cut run holds the committed blocks.
4. The allocator: no page that holds an uncommitted row is hashed; what the
   engine refuses (``spec_k``, a page size a block does not divide).
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

from deepspeed_tpu.inference import InferenceEngine             # noqa: E402
from deepspeed_tpu.inference.scheduler import Request           # noqa: E402
from deepspeed_tpu.inference.served import Rows, served_model   # noqa: E402
from deepspeed_tpu.models import sdar                           # noqa: E402
from deepspeed_tpu.models.sdar import SdarConfig, sdar_init     # noqa: E402
from deepspeed_tpu.moe import share                             # noqa: E402
from deepspeed_tpu.parallel.topology import build_mesh          # noqa: E402
from perfbench.lib import sdar_reference as reference           # noqa: E402

B, MASK = 4, 127


def tiny(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, max_position_embeddings=256,
        block_length=B, mask_token_id=MASK, denoising_steps=2,
        remasking="low_confidence_static", dtype=jnp.float32,
        initializer_range=0.3)
    base.update(kw)
    return SdarConfig(**base)


def sizes_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def seeded(cfg, seed=0):
    """The seeded init with the norms' weights moved off 1, so that a norm
    left out or applied on the wrong side shows."""
    params = sdar_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 1)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(tree, [
        a * jnp.asarray(rng.uniform(0.6, 1.4, a.shape), a.dtype)
        if "norm" in str(path[-1]) else a for path, a in leaves])


def engine_of(cfg, params, kernel=False, **inference):
    conf = dict(max_slots=4, max_seq_len=128, block_size=8, prefill_chunk=16,
                paged_kernel=kernel, num_blocks=64)
    conf.update(inference)
    return InferenceEngine(cfg, params, config={"inference": conf},
                           mesh=build_mesh(devices=jax.devices()[:1]))


CFG = tiny()
PARAMS = seeded(CFG)
_REF = jax.jit(lambda p, t, out: reference.forward(
    p, t, sizes_of(CFG), out_positions=out, q_block=16)[0])


def ref_logits(tokens, positions, params=PARAMS):
    row = np.zeros(128, np.int32)
    row[:len(tokens)] = tokens
    return np.asarray(_REF(params, jnp.asarray(row),
                           jnp.asarray(list(positions), jnp.int32)))


@pytest.fixture(scope="module", params=[False, True], ids=["gather", "kernel"])
def engine(request):
    eng = engine_of(CFG, PARAMS, kernel=request.param)
    yield eng
    eng.close()


def passes_of(eng, prompt, blocks: int, max_new=32):
    """``prompt`` served alone: admission, prefill, then block passes until
    ``blocks`` blocks are committed.  Returns [(block start, input ids, the
    pass's logits [B, V], the block after it, tokens handed out)] a pass and
    the admission's info; the slot is released."""
    slot = eng.select_slot(prompt, max_new)
    tok, _ = eng.prefill(prompt, slot, max_new_tokens=max_new)
    assert tok is None                       # a prefill samples nothing
    info = dict(eng.last_admit_info(slot))
    eng.activate_block(slot, prompt)
    out, committed = [], 0
    while committed < blocks:
        start = int(eng.lengths[slot])
        tokens, logits = eng.decode_once(return_logits=True)
        handed = int(eng.last_yield[slot])
        out.append((start, tokens[slot].copy(), logits[slot],
                    eng.block_tokens[slot].copy(), handed))
        committed += int(not (tokens[slot] < 0).any())
    eng.release_slot(slot)
    return out, info


# --------------------------------------------------------------------- #
# 1. Logits at every pass
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("plen", [16, 21, 35, 3],
                         ids=["tail0", "tail1", "tail3", "short"])
def test_every_pass_matches_the_reference(engine, plen):
    """Prefill (two chunks and a short one for the longer prompts) and two
    blocks' passes against the reference's full forward over the stream's
    tokens with the mask token where the block is undecided; the block's
    tokens after a pass are the rule applied to the pass's own logits; a
    commit hands out the block's positions past the prompt's tail."""
    rng = np.random.default_rng(plen)
    prompt = rng.integers(0, 120, size=plen).astype(np.int32)
    passes, _ = passes_of(engine, prompt, blocks=2)
    tail = plen % B
    seq = list(prompt[:plen - tail])
    first = True
    steps = CFG.denoising_steps
    done = 0
    for start, block, logits, after, handed in passes:
        assert start == len(seq)
        undecided = block < 0
        row = np.asarray(seq + [MASK if t < 0 else int(t) for t in block])
        want = ref_logits(row, range(start, start + B))
        np.testing.assert_allclose(logits, want, atol=2e-4, rtol=0)
        if not undecided.any():              # the commit pass
            assert handed == B - (tail if first else 0)
            assert (after < 0).all()
            seq += [int(t) for t in block]
            first, done = False, 0
            continue
        assert handed == 0
        x0, conf = reference.confidences(logits)
        take = reference.unmask_rule(
            undecided, conf, reference.pass_count(undecided.sum(), done,
                                                  steps),
            CFG.remasking, CFG.confidence_threshold)
        np.testing.assert_array_equal(after, np.where(take, x0, block))
        done += 1
    # a block of 4 undecided positions costs 2 denoise passes + a commit; a
    # first block with fewer undecided positions than steps costs fewer
    n_first = min(steps, B - tail) + 1
    assert len(passes) == n_first + steps + 1


def test_any_chunking_and_a_prefix_hit_give_the_cold_runs_logits():
    """The prompt prefilled in one chunk, in chunks of 8 (a block never
    straddles a chunk's end), and a second request admitted on a prefix-cache
    hit behind the first's full pages: the first block's passes read the
    same logits."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 120, size=27).astype(np.int32)
    runs = []
    for chunk in (32, 8):
        eng = engine_of(CFG, PARAMS, prefill_chunk=chunk)
        cold, info = passes_of(eng, prompt, blocks=1)
        assert info["cached_tokens"] == 0
        assert info["chunks"] == -(-(27 // B * B) // chunk)
        hit, info = passes_of(eng, prompt, blocks=1)
        assert info["cached_tokens"] == 24       # three full pages of 8
        runs += [cold, hit]
        eng.close()
    for other in runs[1:]:
        assert len(other) == len(runs[0])
        for a, b in zip(runs[0], other):
            np.testing.assert_array_equal(a[1], b[1])
            np.testing.assert_allclose(a[2], b[2], atol=2e-5, rtol=0)


def test_a_wholly_cached_prompt_prefills_nothing_or_its_last_row():
    """A prompt of whole pages served twice: the second admission forks the
    last page copy-on-write and re-prefills one row; a prompt whose full
    pages are cached and whose rest opens the first block prefills
    nothing."""
    rng = np.random.default_rng(8)
    eng = engine_of(CFG, PARAMS)
    whole = rng.integers(0, 120, size=16).astype(np.int32)
    cold, _ = passes_of(eng, whole, blocks=1)
    again, info = passes_of(eng, whole, blocks=1)
    assert info["cached_tokens"] == 15 and info["cow_fork"]
    np.testing.assert_allclose(again[0][2], cold[0][2], atol=2e-5, rtol=0)
    longer = np.concatenate([whole, rng.integers(0, 120, size=3)]).astype(
        np.int32)
    got, info = passes_of(eng, longer, blocks=1)
    assert info["cached_tokens"] == 16 and info["chunks"] == 0
    row = np.asarray(list(whole) + [int(t) if t >= 0 else MASK
                                    for t in got[0][1]])
    np.testing.assert_allclose(got[0][2], ref_logits(row, range(16, 20)),
                               atol=2e-4, rtol=0)
    eng.close()


# --------------------------------------------------------------------- #
# 2. The rules and the router
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("rule", sdar.RULES)
@pytest.mark.parametrize("steps,block", [(2, 4), (4, 4), (3, 8), (5, 4)])
def test_unmask_is_the_plain_statement_of_the_rule(rule, steps, block):
    """``models.sdar.unmask`` over a batch of blocks in every state against
    ``sdar_reference.unmask_rule`` a block: confidences with ties and, for
    the dynamic rule, some above the threshold."""
    cfg = tiny(block_length=block, denoising_steps=steps, remasking=rule,
               confidence_threshold=0.65)
    rng = np.random.default_rng(steps * 10 + block)
    S = 64
    undecided = rng.random((S, block)) < 0.6
    conf = np.round(rng.random((S, block)), 1).astype(np.float32)
    done = rng.integers(0, steps, size=S).astype(np.int32)
    got = np.asarray(sdar.unmask(jnp.asarray(undecided), jnp.asarray(conf),
                                 jnp.asarray(done), cfg))
    for s in range(S):
        want = reference.unmask_rule(
            undecided[s], conf[s], reference.pass_count(
                undecided[s].sum(), done[s], steps), rule, 0.65)
        np.testing.assert_array_equal(got[s], want, err_msg=str(s))


@pytest.mark.parametrize("steps", [1, 2, 3, 4, 5, 8])
@pytest.mark.parametrize("masked", [1, 2, 3, 4, 7, 8])
def test_the_static_counts_two_statements_agree(steps, masked):
    """An even split of the block's masked positions with the remainder to
    the first passes IS ``ceil(masked now / passes left)`` along the static
    rule's own trajectory; the block is clear after ``min(steps, masked)``
    passes, which is what the engine's schedule counts on."""
    left, counts = masked, []
    for i in range(steps):
        counts.append(reference.pass_count(left, i, steps))
        left -= counts[-1]
    assert counts == reference.static_counts(masked, steps) and left == 0
    assert sum(c > 0 for c in counts) == min(steps, masked)
    served = served_model(tiny(denoising_steps=steps))
    assert served.denoise_passes(masked) == min(steps, masked)


def test_the_published_router_is_softmax_topk():
    """A softmax over all experts, the k largest, divided by their sum
    (``sdar_reference.route``) = a softmax over the k largest logits
    (``moe/share.py`` under this family's ``Routing``)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(33, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    ids, w = share.route(x, router, None, CFG.routing)
    with jax.default_matmul_precision("highest"):
        want_ids, want_w, _ = reference.route(x, router, sizes_of(CFG))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)


def test_rows_see_to_the_end_of_their_block():
    """``Rows.sees``: the same array as ``positions`` for a causal model;
    with a ``block_length`` the end of each row's block, in a chunk no
    further than the chunk's last live row."""
    lengths = jnp.asarray([8, 12], jnp.int32)
    tables = jnp.asarray([[0, 1], [2, -1]], jnp.int32)
    rows = Rows.of_slots(lengths, tables, 4, 1, (2,))
    assert rows.sees is rows.positions
    rows = Rows.of_slots(lengths, tables, 4, 1, (2,), 4)
    np.testing.assert_array_equal(rows.sees, [[[11] * 4, [15] * 4]])
    chunk = Rows.of_chunk(tables[:1], jnp.asarray([4]), jnp.asarray([5]),
                          jnp.asarray([1]), 8, (2,), None, 4)
    np.testing.assert_array_equal(
        chunk.sees[0, 0], [7, 7, 7, 7, 9, 9, 9, 9])
    assert Rows.of_chunk(tables[:1], jnp.asarray([4]), jnp.asarray([5]),
                         jnp.asarray([1]), 8, (2,)).sees is not None


# --------------------------------------------------------------------- #
# 3. The scheduler
# --------------------------------------------------------------------- #
def requests_of(shapes, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, 120, size=n).astype(
        np.int32), max_new_tokens=g, arrival_s=0.0)
        for i, (n, g) in enumerate(shapes)]


def generated(req, cfg=CFG, params=PARAMS):
    return reference.generate(
        lambda toks, pos: ref_logits(toks, pos, params), req.prompt,
        req.max_new_tokens, block_length=cfg.block_length,
        mask_token_id=cfg.mask_token_id,
        denoising_steps=cfg.denoising_steps, rule=cfg.remasking,
        threshold=cfg.confidence_threshold)


SHAPES = [(17, 8), (20, 12), (3, 7), (33, 16), (9, 4), (16, 8), (6, 5)]


def test_the_scheduler_emits_the_references_generation(engine):
    """Seven requests through four slots: tails of every length, budgets
    that end inside a block, slots in different passes of their blocks in
    one batch; every request gets exactly its budget, the reference's own
    tokens; one compiled program; the loop ran ahead of its fetch; tokens
    are counted, not rows or passes."""
    engine.reset_serving_stats()
    reqs = requests_of(SHAPES)
    report = engine.serve(reqs)
    assert report["recompiles"] == 0 and report["unfinished"] == 0
    for r in reqs:
        assert len(r.out_tokens) == r.max_new_tokens
        assert r.out_tokens == generated(r), r.rid
        assert r.t_first is not None and r.ttft_s > 0
        times = r.token_times()
        assert len(times) == r.max_new_tokens
        assert len(set(times)) == len(r.block_times)   # a block, one time
    emitted = sum(r.max_new_tokens for r in reqs)
    counters = report["model_counters"]
    iters = report["iterations"]
    rows, commits = counters["block_rows"] * iters, counters["commits"] * iters
    # the engine counts a committed block's positions past the prompt's
    # tail (a reply's last block may run past its budget)
    assert emitted <= report["decode_tokens"] < emitted + B * len(reqs)
    assert report["decode_tokens"] < rows
    assert rows / B / commits == pytest.approx(3.0, abs=0.35)
    assert report["lookahead_share"] > 0.8
    assert report["block_gap_ms"]["n"] == sum(
        len(r.block_times) - 1 for r in reqs)
    assert report["requests"][0]["new_tokens"] == 8


def test_the_dynamic_rule_waits_for_every_fetch():
    """Under the dynamic rule a commit is the device's news: the scheduler
    fetches every pass before the next, blocks take 2 to ``steps + 1``
    passes, and the tokens are the reference's under the same rule."""
    cfg = tiny(denoising_steps=3, remasking="low_confidence_dynamic",
               confidence_threshold=0.03)
    eng = engine_of(cfg, PARAMS)
    reqs = requests_of(SHAPES[:5], seed=1)
    report = eng.serve(reqs)
    assert report["recompiles"] == 0 and report["lookahead_share"] == 0.0
    for r in reqs:
        assert r.out_tokens == generated(r, cfg), r.rid
    counters, iters = report["model_counters"], report["iterations"]
    per_block = counters["block_rows"] / B / counters["commits"]
    assert 2.0 <= per_block < 4.0
    with pytest.raises(RuntimeError, match="dynamic"):
        slot = eng.select_slot(reqs[0].prompt, 8)
        eng.prefill(reqs[0].prompt, slot, max_new_tokens=8)
        eng.activate_block(slot, reqs[0].prompt)
        eng.decode_once(continuing=[slot])
        eng.decode_once(continuing=[slot])
    eng.close()


def test_a_cut_run_holds_its_committed_blocks():
    """A serve cut by ``max_wall_s``: every request's tokens are whole
    committed blocks (less its prompt's tail), none over its budget; the
    token count is their sum."""
    from perfbench.runners import serve as serve_runner
    eng = engine_of(CFG, PARAMS)
    eng.serve(requests_of(SHAPES[:2]))           # compiled
    reqs = requests_of([(9 + i, 64) for i in range(8)], seed=2)
    t = [0.0]

    def clock():                                 # 5 ms a reading
        t[0] += 0.005
        return t[0]
    eng.serving.clock = clock
    eng.reset_serving_stats()
    eng.serve(reqs, max_wall_s=1.0)
    cut = [r for r in reqs if len(r.out_tokens) < r.max_new_tokens]
    assert cut and any(r.out_tokens for r in cut)
    for r in reqs:
        assert not r.out_tokens \
            or (len(r.out_tokens) + len(r.prompt) % B) % B == 0 \
            or len(r.out_tokens) == r.max_new_tokens
    s = serve_runner.summarize(reqs, 2.0)
    assert s["output_tokens"] == sum(len(r.out_tokens) for r in reqs)
    assert s["failed"] == 0 and not eng.active.any()
    eng.close()


# --------------------------------------------------------------------- #
# 4. The allocator and what the engine refuses
# --------------------------------------------------------------------- #
def test_no_page_that_holds_an_uncommitted_row_is_hashed():
    """Only a prompt's FULL pages enter the prefix cache; the page the
    prompt's tail and the block in progress lie in does not, at admission
    or at any pass, and ``context_tokens`` counts committed rows only."""
    eng = engine_of(CFG, PARAMS)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 120, size=21).astype(np.int32)
    slot = eng.select_slot(prompt, 16)
    eng.prefill(prompt, slot, max_new_tokens=16)
    eng.activate_block(slot, prompt)
    alloc = eng.allocator
    for _ in range(7):
        eng.decode_once()
        table = eng.block_tables[slot]
        hashed = set(alloc._block_hash[0])
        assert hashed == {int(b) for b in table[:21 // 8]}
        # the block in progress lies past the stream's length
        assert eng.lengths[slot] % B == 0
        assert eng._cache_accounting()[2] == eng.lengths[slot]
    assert eng.lengths[slot] == 20 + 2 * B       # two commits in 7 passes
    assert not alloc.span_args()                 # nothing is snapshotted
    eng.release_slot(slot)
    eng.close()


def test_what_a_model_of_blocks_refuses():
    with pytest.raises(ValueError, match="spec_k"):
        engine_of(CFG, PARAMS, spec_k=2)
    with pytest.raises(ValueError, match="block_length"):
        engine_of(CFG, PARAMS, block_size=6, prefill_chunk=12,
                  max_seq_len=120)
    served = served_model(CFG)
    assert served.block_length == B and not served.rolls_back
    with pytest.raises(NotImplementedError):
        served.verify(None, (), None, None, None, num_groups=1,
                      paged_kernel=False)

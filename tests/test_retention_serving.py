"""Power retention served on the normal path (ISSUE 34): the feature map
and the three forms of the retention, the decode kernel against the plain
update, the per-stream state pool and its snapshots in the one cache
manager, and served logits against the benchmark's quadratic reference
(``perfbench/lib/brumby_reference.py``) — with the cases that show the
comparison can fail.  CPU, tiny widths, seeded weights."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine, kv_cache
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.brumby import BrumbyConfig, brumby_init
from deepspeed_tpu.ops import power_retention as pr
from deepspeed_tpu.parallel.topology import build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from perfbench.lib import brumby_reference as reference  # noqa: E402

SIZES = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
             max_position_embeddings=512, rope_theta=1e6,
             assumed=dict(retention_power=2, retention_eps=1e-6))
ATOL = 1e-4         # fp32 everywhere: the served path reads 1e-6


def tiny(**kw):
    return BrumbyConfig.from_hf(SIZES, dtype=jnp.float32,
                                gate_half_life_min=4.0,
                                gate_half_life_max=256.0, **kw)


@pytest.fixture(scope="module")
def params():
    return brumby_init(jax.random.PRNGKey(0), tiny())


def engine(params, kernel=False, **inference):
    conf = dict(max_slots=4, max_seq_len=512, block_size=8,
                prefill_chunk=16, num_blocks=8, paged_kernel=kernel)
    conf.update(inference)
    return InferenceEngine(tiny(), params, config={"inference": conf},
                           mesh=build_mesh(devices=jax.devices()[:1]))


def through(eng, prompt):
    """(first token, [prefill logits, first-decode logits], admission)."""
    slot = eng.select_slot(prompt, 2)
    tok, pre = eng.prefill(prompt, slot, return_logits=True,
                           max_new_tokens=2)
    info = dict(eng.last_admit_info(slot))
    eng.activate_slot(slot, len(prompt), tok)
    _, dec = eng.decode_once(return_logits=True)
    eng.release_slot(slot)
    return tok, np.stack([pre, dec[slot]]), info


def want(params, prompt, tok, sizes=SIZES):
    toks = np.zeros(-(-(len(prompt) + 1) // 32) * 32, np.int32)
    toks[:len(prompt)] = prompt
    toks[len(prompt)] = tok
    return np.asarray(reference.forward(
        params, jnp.asarray(toks), sizes, q_block=32,
        out_positions=jnp.asarray([len(prompt) - 1, len(prompt)])))


def tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 128, size=n,
                                                dtype=np.int32)


# --------------------------------------------------------------------- #
# 1. The feature map and the three forms
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("D", [8, 16, 128])
def test_feature_map_squares_the_dot_product(D):
    rng = np.random.default_rng(D)
    a, b = rng.normal(size=(2, 7, D)).astype(np.float32)
    got = (pr.phi(a) * pr.phi(b)).sum((-1, -2))
    np.testing.assert_allclose(got, (a * b).sum(-1) ** 2, rtol=1e-4, atol=1e-3)
    assert pr.phi(a).shape[-2:] == (D // 2 + 1, D)
    assert pr.feature_width(D) == D * (D + 1) // 2
    # the last diagonal holds its D/2 pairs twice: what is held beyond
    # the distinct pairs
    assert pr.diagonals(D) * D - pr.feature_width(D) == D // 2


def _rows(N, nH=4, nKV=2, D=16, gates=(0.5, 0.9999), seed=0):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(N, nH, D)).astype(np.float32)
    k = rng.normal(size=(N, nKV, D)).astype(np.float32)
    v = rng.normal(size=(N, nKV, D)).astype(np.float32)
    lg = np.log(rng.uniform(*gates, size=(N, nKV))).astype(np.float32)
    return q, k, v, lg


@pytest.mark.parametrize("gates", [(0.5, 0.7), (0.9, 0.9999),
                                   (0.5, 0.9999)])
@pytest.mark.parametrize("N,chunk", [(23, 4), (23, 8), (23, 23), (40, 16),
                                     (5, 8)])
def test_recurrent_chunked_and_quadratic_forms_agree(N, chunk, gates):
    q, k, v, lg = _rows(N, gates=gates, seed=N + chunk)
    quad = pr.retention_quadratic(q, k, v, lg, 1e-6)
    tiles = dict(pr.state_tiles(2, 16))
    S, z = jnp.zeros(tiles["state"]), jnp.zeros(tiles["norm"])
    rec = []
    for i in range(N):
        y, S1, z1 = pr.recurrent_update(S[None], z[None], q[i:i + 1],
                                        k[i:i + 1], v[i:i + 1],
                                        lg[i:i + 1], 1e-6)
        S, z = S1[0], z1[0]
        rec.append(y[0])
    np.testing.assert_allclose(jnp.stack(rec), quad, atol=2e-3, rtol=2e-3)
    # ragged: the last chunk is padded and its padding is not live
    S2, z2 = jnp.zeros(tiles["state"]), jnp.zeros(tiles["norm"])
    out = []
    for s0 in range(0, N, chunk):
        n = min(chunk, N - s0)

        def pad(x):
            return jnp.pad(x[s0:s0 + n], [(0, chunk - n)]
                           + [(0, 0)] * (x.ndim - 1))
        y, S2, z2 = pr.chunked_retention(
            S2, z2, pad(q), pad(k), pad(v), pad(lg), jnp.arange(chunk) < n,
            1e-6)
        out.append(y[:n])
    # (a first row's normaliser can be tiny: fp32 noise over it)
    np.testing.assert_allclose(jnp.concatenate(out), quad, atol=2e-3,
                               rtol=2e-3)
    # and both leave the same state behind
    np.testing.assert_allclose(S2, S, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(z2, z, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("D", [8, 16])
def test_pair_tensor_is_the_sum_the_definition_gives(D):
    """The held state, read free of the feature map, is the plain weighted
    sum of the definition; the reference's recurrence carries it on."""
    N, nKV = 37, 2
    q, k, v, lg = _rows(N, nH=4, nKV=nKV, D=D, gates=(0.7, 0.999), seed=D)
    tiles = dict(pr.state_tiles(nKV, D))
    S, z = jnp.zeros(tiles["state"]), jnp.zeros(tiles["norm"])
    for i in range(N):
        _, S1, z1 = pr.recurrent_update(S[None], z[None], q[i:i + 1],
                                        k[i:i + 1], v[i:i + 1],
                                        lg[i:i + 1], 1e-6)
        S, z = S1[0], z1[0]
    M = pr.pair_tensor(S, z)
    assert M.shape == (nKV, D + 1, D, D)
    G = np.cumsum(lg, axis=0)
    v1 = np.concatenate([v, np.ones((N, nKV, 1), np.float32)], -1)

    def summed(n):
        return np.einsum("tc,tcd,tci,tcj->cdij", np.exp(G[n - 1] - G[:n]),
                         v1[:n], k[:n], k[:n]) / D
    want = summed(N)
    np.testing.assert_allclose(M, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(M, M.swapaxes(2, 3), atol=0)
    np.testing.assert_allclose(
        reference.carry_state(jnp.asarray(summed(20)), k[20:], v[20:],
                              lg[20:]), want, atol=1e-5, rtol=1e-5)
    low = reference.carry_state(jnp.asarray(summed(20)), k[20:], v[20:],
                                lg[20:], cast=jnp.bfloat16)
    assert (np.asarray(low) == np.asarray(low.astype(jnp.bfloat16)
                                          .astype(jnp.float32))).all()
    assert 1e-3 < np.abs(low - want).max() / np.abs(want).max() < 3e-2
    # q^T M q: a query's numerator and denominator at the last position
    quad = pr.retention_quadratic(q, k, v, lg, 0.0)[-1]         # [nH, D]
    qg = q[-1].reshape(nKV, 2, D)
    read = jnp.einsum("chi,cdij,chj->chd", qg, want, qg)
    np.testing.assert_allclose((read[..., :D] / read[..., D:]).reshape(4, D),
                               quad, atol=1e-4, rtol=1e-3)


# --------------------------------------------------------------------- #
# 2. The decode kernel (interpret mode) against the plain update
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("pages", [[3, -1, 0, 5], [-1, -1, -1, -1],
                                   [1, 2, 4, 0], [-1, -1, -1, 2]])
def test_state_update_kernel_matches_and_leaves_dead_pages_alone(pages):
    rng = np.random.default_rng(1)
    L, B, Sg, nKV, D = 2, 6, 4, 2, 16
    tiles = dict(pr.state_tiles(nKV, D))
    st = jnp.asarray(rng.normal(size=(L, 1, B) + tiles["state"]),
                     jnp.float32)
    nm = pr.norm_held(pr.norm_logical(jnp.asarray(
        np.abs(rng.normal(size=(L, 1, B) + tiles["norm"])) + 1,
        jnp.float32)))
    q, k, v, lg = (jnp.asarray(a)[None] for a in _rows(Sg, seed=2))
    y, s2, n2 = pr.state_update(st, nm, 1, jnp.asarray([pages], jnp.int32),
                                q, k, v, lg, eps=1e-6)
    untouched = np.ones((L, B), bool)
    for s, p in enumerate(pages):
        if p < 0:
            assert not np.asarray(y[0, s]).any()
            continue
        untouched[1, p] = False
        yy, ss, zz = pr.recurrent_update(
            st[1, 0, p][None], nm[1, 0, p][None], q[0, s][None],
            k[0, s][None], v[0, s][None], lg[0, s][None], 1e-6)
        np.testing.assert_allclose(y[0, s], yy[0], rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(s2[1, 0, p], ss[0], atol=1e-5)
        np.testing.assert_allclose(n2[1, 0, p], zz[0], atol=1e-5)
    # every page no live stream owns, in every layer: bit for bit
    assert (np.asarray(s2)[:, 0][untouched]
            == np.asarray(st)[:, 0][untouched]).all()
    assert (np.asarray(n2)[:, 0][untouched]
            == np.asarray(nm)[:, 0][untouched]).all()


def test_state_update_steps_count_live_streams_only():
    assert pr.state_update_steps(3, 32, 8, 128) == (32 * 8 * 5, 3 * 8 * 5)
    assert pr.tile_diagonals(128) == 13 and pr.diagonals(128) == 65
    assert dict(pr.state_tiles(8, 128)) == {"state": (8, 8320, 128),
                                            "norm": (8, 80, 128)}


# --------------------------------------------------------------------- #
# 4. Served logits against the reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kernel", [False, True])
def test_served_logits_match_the_reference(params, kernel):
    eng = engine(params, kernel)
    doc = tokens(0, 300)
    # prefill over 19 chunks, then decode through the state pool
    tok, got, info = through(eng, doc)
    assert info["cached_tokens"] == 0
    np.testing.assert_allclose(got, want(params, doc, tok), atol=ATOL)
    # a question over it: the snapshot-hit path
    prompt = np.concatenate([doc, tokens(1, 13)])
    tok, got, info = through(eng, prompt)
    assert info["cached_tokens"] == 296 and info["cow_fork"]
    np.testing.assert_allclose(got, want(params, prompt, tok), atol=ATOL)
    # a longer one: a chunked prefill RESUMED from the snapshot
    prompt = np.concatenate([doc, tokens(2, 75)])
    tok, got, info = through(eng, prompt)
    assert info["cached_tokens"] == 296 and info["chunks"] == 5
    np.testing.assert_allclose(got, want(params, prompt, tok), atol=ATOL)
    state = eng.serving.snapshot()["state"]
    assert state["snapshots_taken"] == 1 and state["snapshot_hits"] == 2
    assert state["resumed_tokens"] == 592
    assert state["state_copy_bytes"] == 2 * 3 * eng.cache_spec.block_nbytes()
    eng.close()


def test_streams_in_one_batch_keep_their_own_states(params):
    eng = engine(params)
    doc = tokens(4, 200)
    reqs = [Request(rid=0, prompt=doc, max_new_tokens=1, arrival_s=0.0)]
    eng.serve(reqs)
    reqs = [Request(rid=i, prompt=np.concatenate([doc, tokens(10 + i, 9 + i)]),
                    max_new_tokens=6, arrival_s=0.0) for i in range(1, 4)]
    eng.serve(reqs)
    alone = engine(params, prefill_chunk=32)
    for r in reqs:
        twin = [Request(rid=r.rid, prompt=r.prompt, max_new_tokens=6,
                        arrival_s=0.0)]
        alone.serve(twin)
        assert twin[0].out_tokens == r.out_tokens
    eng.close()
    alone.close()


# --------------------------------------------------------------------- #
# 5. The comparison can fail
# --------------------------------------------------------------------- #
def _question_error(eng, params, doc, ref_params=None):
    prompt = np.concatenate([doc, tokens(1, 13)])
    tok, got, info = through(eng, prompt)
    assert info["cached_tokens"] == len(doc) // 8 * 8
    return float(np.abs(got - want(ref_params or params, prompt, tok)).max())


def _hold_the_state_in(monkeypatch, dtype):
    """The family's pools declared in ``dtype`` (they name float32)."""
    from deepspeed_tpu.inference import retention
    declared = retention.RetentionServed.cache_pools
    monkeypatch.setattr(
        retention.RetentionServed, "cache_pools",
        lambda self, block_size: tuple(
            (name, tile, dtype) for name, tile, _ in declared(self,
                                                              block_size)))


def _snapshot_page(eng, doc):
    n, page, _ = eng.allocator.match_snapshot(0, np.concatenate([doc, [0]]))
    assert n == len(doc) // 8
    return page


@pytest.mark.parametrize("fault", ["none", "zeroed_snapshot",
                                   "swapped_snapshot", "gate_bias_zero",
                                   "bf16_state"])
def test_the_check_fails_on_each_fault(params, fault, monkeypatch):
    from deepspeed_tpu.inference import retention
    if fault == "bf16_state":
        _hold_the_state_in(monkeypatch, jnp.bfloat16)
    served = params
    if fault == "gate_bias_zero":
        served = dict(params, layers=dict(
            params["layers"], bg=jnp.zeros_like(params["layers"]["bg"])))
    eng = engine(served)
    doc, other = tokens(0, 300), tokens(7, 300)
    for d in (doc, other):
        through(eng, d)
    a, b = _snapshot_page(eng, doc), _snapshot_page(eng, other)
    if fault == "zeroed_snapshot":
        for name in eng.cache:
            eng.cache[name] = eng.cache[name].at[:, 0, a].set(0)
    if fault == "swapped_snapshot":
        for name in eng.cache:
            pool = eng.cache[name]
            eng.cache[name] = pool.at[:, 0, a].set(pool[:, 0, b]) \
                .at[:, 0, b].set(pool[:, 0, a])
    err = _question_error(eng, served, doc, ref_params=params)
    assert (err <= ATOL) == (fault == "none"), (fault, err)
    eng.close()


@pytest.mark.parametrize("fault", ["none", "bf16_state"])
def test_the_page_after_a_reply_is_held_to_the_reference_state(
        fault, monkeypatch):
    """The benchmark's own comparison of a page (``runners/docqa_state``:
    layer 0 after a reply against layer 0 before it carried by the
    reference's recurrence over the program's own keys, values and
    gates): a float32 state passes it and reads fp32
    noise; a bfloat16 state fails it, as the yardstick's bf16 control does
    — where the logits of the same reply pass the benchmark's limit."""
    from deepspeed_tpu.inference import retention
    from perfbench.runners import docqa_state as runner
    if fault == "bf16_state":
        _hold_the_state_in(monkeypatch, jnp.bfloat16)
    cfg = BrumbyConfig.from_hf(SIZES, dtype=jnp.float32,
                               gate_half_life_min=64.0,
                               gate_half_life_max=4096.0)
    params = brumby_init(jax.random.PRNGKey(1), cfg)
    eng = InferenceEngine(
        cfg, params, mesh=build_mesh(devices=jax.devices()[:1]),
        config={"inference": dict(max_slots=4, max_seq_len=512, block_size=8,
                                  prefill_chunk=16, num_blocks=8,
                                  paged_kernel=False)})
    doc, steps = tokens(0, 200), 160
    through(eng, doc)
    prompt = np.concatenate([doc, tokens(1, 13)])
    slot, held, got, info, before = runner._reply_through_the_cache(
        eng, prompt, steps)
    assert info["cached_tokens"] == 200 and len(held) == 213 + steps
    assert eng.context_len(slot) == len(held)
    want, _ = runner._reference(eng, SIZES, 384, 4)(
        held, [212, 213, len(held) - 1])
    rows = runner.state_errors(eng, slot, before, held, steps)
    eng.release_slot(slot)
    served = [r[1:3] for r in rows]
    assert len(rows) == 2 and not runner.state_agrees(r[3:5] for r in rows)
    if fault == "none":
        np.testing.assert_allclose(got, want, atol=ATOL)
        assert runner.state_agrees(served) and np.max(served) < 1e-5
    else:
        assert not runner.state_agrees(served)
        assert np.abs(got - want).max() < runner.LOGIT_ATOL
    eng.close()


def test_without_the_seeded_gate_bias_a_zeroed_snapshot_would_pass(params):
    """Why ``brumby_init`` draws ``bg``: with ``bg = 0`` every head forgets
    in two tokens, and a question's logits no longer depend on the
    snapshot it resumed from."""
    flat = dict(params, layers=dict(
        params["layers"], bg=jnp.zeros_like(params["layers"]["bg"])))
    eng = engine(flat)
    doc = tokens(0, 300)
    through(eng, doc)
    page = _snapshot_page(eng, doc)
    for name in eng.cache:
        eng.cache[name] = eng.cache[name].at[:, 0, page].set(0)
    assert _question_error(eng, flat, doc) <= ATOL
    eng.close()


@pytest.mark.parametrize("variant", ["power1", "no_gate", "no_normaliser",
                                     "no_rope"])
def test_the_reference_without_a_piece_of_the_mathematics_differs(
        params, variant):
    doc = tokens(0, 127)
    toks = jnp.asarray(np.concatenate([doc, [5]]))
    out = jnp.asarray([126, 127])
    full = reference.forward(params, toks, SIZES, out_positions=out,
                             q_block=32)
    less = reference.forward(params, toks, SIZES, out_positions=out,
                             q_block=32, variant=variant)
    assert float(jnp.abs(full - less).max()) > 100 * ATOL


# --------------------------------------------------------------------- #
# 6. Pages and snapshots in the one cache manager
# --------------------------------------------------------------------- #
def _spec(num_blocks=6, page_tokens=80):
    tiles = pr.state_tiles(2, 16)
    return kv_cache.PagedKVCacheSpec(
        num_layers=2, num_slots=4, num_blocks=num_blocks, block_size=8,
        max_len=512, num_heads=2, head_dim=16, dtype=jnp.float32,
        pools=tiles, per_stream=True,
        token_row_bytes=2 * (144 * 16 + 16 * 16) * 4 * 2 // (2 * page_tokens))


def test_a_per_stream_pool_is_pages_one_a_stream():
    spec = _spec()
    assert spec.max_blocks_per_slot == 1 and spec.page_tokens == 80
    assert spec.pool_shapes == {"state": (2, 1, 6, 2, 144, 16),
                                "norm": (2, 1, 6, 2, 16, 16)}
    alloc = kv_cache.allocator_for([spec])
    assert type(alloc) is kv_cache.StateAllocator
    assert alloc.need_blocks(400, 100) == 1
    plan = alloc.admit_prompt(0, 0, tokens(0, 40), 100)
    assert len(plan.table) == 1 and plan.matched == 0
    assert plan.snapshot_page is None and alloc.blocks_in_use() == 1
    alloc.release(0, plan.table)
    assert alloc.blocks_in_use() == 0 and alloc.available(0) == 6


@pytest.mark.parametrize("plen,resumed,at", [
    (300, 0, 296), (80, 0, 80), (79, 0, 0), (87, 0, 80), (313, 296, 0),
    (400, 296, 400), (375, 296, 0)])
def test_the_snapshot_rule(plen, resumed, at):
    alloc = kv_cache.allocator_for([_spec()])
    assert alloc.snapshot_boundary(plen, resumed) == at


def test_a_document_leaves_a_snapshot_and_a_question_does_not():
    alloc = kv_cache.allocator_for([_spec()])
    doc = tokens(0, 300)
    plan = alloc.admit_prompt(0, 0, doc, 1)
    assert plan.snapshot_at == 296 and plan.snapshot_page is not None
    # not in the prefix cache until the engine has frozen the state there
    assert plan.cow_src is None and alloc.snapshots_taken == 0
    assert alloc.match_snapshot(0, np.concatenate([doc, [0]]))[1] is None
    assert alloc.available(0) == 4
    alloc.commit_snapshot(plan)
    assert alloc.snapshots_taken == 1 and alloc.available(0) == 5
    alloc.release(0, plan.table)
    assert alloc.blocks_in_use() == 0 and alloc.available(0) == 6
    q = np.concatenate([doc, tokens(1, 20)])
    assert alloc.match_snapshot(0, q)[:2] == (37, plan.snapshot_page)
    hit = alloc.admit_prompt(1, 0, q, 50)
    assert hit.matched == 296 and hit.cow_src == plan.snapshot_page
    assert hit.cow_dst == hit.table[0] != plan.snapshot_page
    assert hit.snapshot_page is None and alloc.snapshots_taken == 1
    assert alloc.snapshot_hits == 1
    # the document itself again: its snapshot is not its own prefix (the
    # last token has to be prefilled), a shorter boundary has none
    again = alloc.admit_prompt(2, 0, doc[:296], 1)
    assert again.matched == 0 and again.snapshot_page is None


def test_questions_never_evict_a_document_and_lru_evicts_the_oldest():
    alloc = kv_cache.allocator_for([_spec(num_blocks=4)])
    docs = [tokens(i, 120) for i in range(3)]
    pages = []
    for i, d in enumerate(docs[:2]):
        plan = alloc.admit_prompt(0, 0, d, 1)
        alloc.commit_snapshot(plan)
        pages.append(plan.snapshot_page)
        alloc.release(0, plan.table)
    assert alloc.available(0) == 4 and alloc.reclaimed == 0
    # two live questions take the two free pages; a third stream has to
    # reclaim the LEAST recently used snapshot; the gate never lets a
    # stream take the snapshot it resumes from
    live = [alloc.admit_prompt(s, 0, np.concatenate([docs[1], tokens(9, 5)]),
                               20) for s in (0, 1)]
    assert alloc.reclaimed == 0
    assert alloc.match_snapshot(0, np.concatenate([docs[0], [0]]))[1] \
        == pages[0]
    third = alloc.admit_prompt(2, 0, np.concatenate([docs[1], tokens(8, 5)]),
                               20)
    assert alloc.reclaimed == 1 and third.table[0] == pages[0]
    assert alloc.match_snapshot(0, np.concatenate([docs[0], [0]]))[1] is None
    assert third.cow_src == pages[1]
    # the pool is full of live streams and the one snapshot they came
    # from: nothing can be admitted, and nothing was harmed
    assert not alloc.can_admit(0, np.concatenate([docs[1], [3]]), 5)
    with pytest.raises(kv_cache.PoolExhausted):
        alloc.admit_prompt(3, 0, np.concatenate([docs[1], [3]]), 5)
    for s, plan in zip((0, 1, 2), live + [third]):
        alloc.release(s, plan.table)
    assert alloc.blocks_in_use() == 0 and alloc.available(0) == 4
    # a new document with no free page for its snapshot: with three free
    # pages it takes one, and the old snapshot stays
    plan = alloc.admit_prompt(0, 0, docs[2], 1)
    assert plan.snapshot_page is not None and alloc.reclaimed == 1
    assert alloc.match_snapshot(0, np.concatenate([docs[1], [0]]))[1] \
        == pages[1]


def test_a_snapshot_whose_prefill_failed_is_never_matched(params):
    alloc = kv_cache.allocator_for([_spec()])
    doc = tokens(0, 300)
    plan = alloc.admit_prompt(0, 0, doc, 1)
    alloc.abandon_snapshot(plan)
    alloc.release(0, plan.table)
    assert alloc.available(0) == 6 and alloc.snapshots_taken == 0
    assert alloc.match_snapshot(0, np.concatenate([doc, [0]]))[1] is None
    # a committed one stays where it is
    plan = alloc.admit_prompt(0, 0, doc, 1)
    alloc.commit_snapshot(plan)
    alloc.abandon_snapshot(plan)
    assert alloc.match_snapshot(0, np.concatenate([doc, [0]]))[1] \
        == plan.snapshot_page
    # the engine: a chunk program that raises leaves no page to resume
    # from and none lost
    eng = engine(params)
    calls, real = [], eng._prefill_fn

    def failing(*args):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("a bad chunk")
        return real(*args)
    eng._prefill_fn = failing
    slot = eng.select_slot(doc, 2)
    with pytest.raises(RuntimeError, match="a bad chunk"):
        eng.prefill(doc, slot, max_new_tokens=2)
    eng.release_slot(slot)
    eng._prefill_fn = real
    assert eng.prefix_match_tokens(np.concatenate([doc, [0]])) == 0
    assert eng.allocator.available(0) == eng.num_blocks
    assert eng.allocator.snapshots_taken == 0
    tok, got, info = through(eng, doc)
    assert info["cached_tokens"] == 0
    np.testing.assert_allclose(got, want(params, doc, tok), atol=ATOL)
    assert eng.prefix_match_tokens(np.concatenate([doc, [0]])) == 296
    eng.close()


def test_a_snapshot_is_skipped_when_every_page_is_live():
    alloc = kv_cache.allocator_for([_spec(num_blocks=2)])
    first = alloc.admit_prompt(0, 0, tokens(0, 40), 5)
    plan = alloc.admit_prompt(1, 0, tokens(1, 200), 5)
    assert plan.snapshot_page is None and plan.snapshot_at == 0
    assert first.table != plan.table and alloc.snapshots_taken == 0


def test_engine_refuses_speculation_and_verify_raises(params):
    with pytest.raises(ValueError, match="spec_k"):
        engine(params, spec_k=2)
    from deepspeed_tpu.inference.served import served_model
    served = served_model(tiny())
    assert served.cache_classes == (("", 2, None, True),)
    assert served.token_row_bytes == 256
    with pytest.raises(NotImplementedError):
        served.verify(None, None, None, None, None, num_groups=1,
                      paged_kernel=False)
    # a cost a token that does not depend on the keys in reach
    assert served.cache_cost(64, 8, 4) == served.cache_cost(4096, 8, 4)
    flops, nbytes = served.cache_cost(1, 8, 4)
    assert nbytes == 2 * 2 * 136 * 17 * 4


def test_spans_carry_the_state_counters(params, tmp_path):
    eng = engine(params)
    doc = tokens(0, 300)

    def serve():
        through(eng, doc)
        through(eng, np.concatenate([doc, tokens(1, 13)]))
    from test_program_spans import _session
    found = {name: [args for _, _, args in rows]
             for name, rows in _session(tmp_path, serve).items()}
    first, second = found["prefill"]
    assert first["snapshot_taken"] == 1 and first["resumed_tokens"] == 0
    assert second["resumed_tokens"] == 296 and second["snapshot_taken"] == 0
    assert second["cached_tokens"] == 296
    page = eng.cache_spec.block_nbytes()
    assert (first["state_copy_bytes"], second["state_copy_bytes"]) \
        == (2 * page, 2 * page)
    assert [d["state_pages_live"] for d in found["decode"]] == [1, 1]
    from deepspeed_tpu.monitor.xplane_reader import SCOPES, SPAN_ARGS
    assert set(first) <= set(SPAN_ARGS["prefill"])
    assert set(found["decode"][0]) <= set(SPAN_ARGS["decode"])
    assert {"qkv_proj", "state_update", "retention_chunk", "out_proj",
            "state_copy"} <= set(SCOPES)
    eng.close()


# --------------------------------------------------------------------- #
# 7. Off every other model's start-up
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("model", ["gpt2", "deepseek_v3"])
def test_serving_another_model_imports_none_of_it(model):
    build = {
        "gpt2": """
from deepspeed_tpu.models import GPT2_CONFIGS, gpt2_init
cfg = GPT2_CONFIGS['gpt2-tiny']
params = gpt2_init(jax.random.PRNGKey(0), cfg)
conf = dict(max_slots=2, max_seq_len=64, block_size=16, prefill_chunk=16)
""",
        "deepseek_v3": """
import jax.numpy as jnp
from deepspeed_tpu.models.deepseek_v3 import DeepseekV3Config, deepseek_v3_init
cfg = DeepseekV3Config(vocab_size=64, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=2, first_k_dense_replace=1,
    num_attention_heads=2, n_routed_experts=4, held=(0, 4),
    num_experts_per_tok=2, n_group=2, topk_group=1, q_lora_rank=16,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    max_position_embeddings=64, rope_factor=1.0, dtype=jnp.float32)
params = deepseek_v3_init(jax.random.PRNGKey(0), cfg)
conf = dict(max_slots=2, max_seq_len=64, block_size=16, prefill_chunk=16)
"""}[model]
    code = f"""
import sys, jax
import deepspeed_tpu
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.parallel.topology import build_mesh
{build}
eng = InferenceEngine(cfg, params, config={{'inference': conf}},
                      mesh=build_mesh(devices=jax.devices()[:1]))
assert not eng.cache_spec.per_stream
bad = [m for m in sys.modules if m.startswith('deepspeed_tpu.') and
       m.rsplit('.', 1)[-1] in ('brumby', 'retention', 'power_retention')]
print('LOADED', bad)
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                         capture_output=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu",
                                  PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LOADED []" in out.stdout


def test_the_config_reads_the_published_keys():
    import json
    sizes = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "brumby-14b-base.json")))
    cfg = BrumbyConfig.from_hf(sizes)
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size, cfg.num_hidden_layers) \
        == (5120, 40, 8, 128, 17408, 151936, 4)
    assert cfg.group_size == 5 and cfg.rope_theta == 1e6
    shapes = jax.eval_shape(lambda k: brumby_init(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n - 2.877e9) < 0.002e9
    with pytest.raises(NotImplementedError):
        BrumbyConfig.from_hf(dict(sizes, rope_scaling={"type": "yarn"}))


def test_the_seeded_gate_spreads_half_lives():
    cfg = tiny()
    bg = np.asarray(brumby_init(jax.random.PRNGKey(3), cfg)["layers"]["bg"])
    half = np.log(2) / -np.asarray(jax.nn.log_sigmoid(bg))
    assert half.shape == (2, 2)
    assert (half >= 4 * 0.999).all() and (half <= 256 * 1.001).all()
    # one head of every layer in each half of the (log) range
    assert ((half < 32).sum(1) == 1).all()

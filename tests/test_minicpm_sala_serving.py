"""The ``minicpm_sala`` family (MiniCPM-SALA) through the normal serving path
(PR 59): sparse layers that read only the blocks a weight-free selection over
POOLED keys chooses a token and K/V head (a third pool at another rate beside
the K/V pages, a per-K/V-head table in the paged attend), Lightning
linear-attention layers (an fp32 state a stream, every head a group of its
own in ``ops/ssm_scan.py``).

What is held to what:
1. Served logits and state pages — prefill in chunks ACROSS ``dense_len``,
   decode, a second request through the prefix-hit path — against the plain
   float32 reference the benchmark keeps
   (``perfbench/lib/minicpm_sala_reference.py``), kernels off and on.
2. The selection: the program's chosen sets equal the reference's in fp32;
   sparse == dense where the blocks number no more than ``topk``.
3. Pooled keys under sharing: two streams share block b and differ in b + 1;
   a copy on write of a part-filled block.
4. Lightning: recurrent == chunked == quadratic at one head a group; the
   snapshot a chunk freezes == the state from scratch.
5. The kernels in interpret mode against their plain forms: the per-head
   attend, the state update over several one-head groups a step.
6. The reference's wrong models (the runner's controls) are far from it.
7. A padding row of a vocabulary held as more rows than it has is never
   sampled.
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
from deepspeed_tpu.inference import minicpm_sala as served_sala  # noqa: E402
from deepspeed_tpu.inference.served import served_model         # noqa: E402
from deepspeed_tpu.models import minicpm_sala as sala           # noqa: E402
from deepspeed_tpu.models.minicpm_sala import (                 # noqa: E402
    MinicpmSalaConfig, minicpm_sala_init)
from deepspeed_tpu.ops import paged_attention as paged_attn_ops  # noqa: E402
from deepspeed_tpu.ops import sparse_select, ssm_scan           # noqa: E402
from deepspeed_tpu.parallel.topology import build_mesh          # noqa: E402
from perfbench.lib import minicpm_sala_reference as reference   # noqa: E402

BS, WIDTH, N_OUT = 16, 192, 8
LOGIT_ATOL, PAGE_RTOL = 2e-4, 2e-5
SPARSE_CONFIG = dict(kernel_size=8, kernel_stride=4, block_size=16, topk=4,
                     window_size=32, init_blocks=1, dense_len=64)


def tiny(**kw):
    """4 layers ``minicpm4, lightning, lightning, minicpm4``; 8 query heads
    over 2 K/V heads of 16 (4 a K/V head); 4 Lightning heads of 16; pooled
    keys of 8 tokens every 4, blocks of 16, top 4 (block 0 and the 2 newest
    forced), dense up to 64 tokens."""
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
        mixer_types=(sala.SPARSE, sala.LIGHTNING, sala.LIGHTNING,
                     sala.SPARSE),
        depth_scale_layers=8, dim_model_base=32, scale_emb=12.0,
        max_position_embeddings=512, dtype=jnp.float32,
        **{"sparse_" + k: v for k, v in SPARSE_CONFIG.items()})
    base.update(kw)
    return MinicpmSalaConfig(**base)


def sizes_of(cfg):
    """The configuration file's keys for the reference."""
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    out["assumed"] = {"sparse_config": dict(SPARSE_CONFIG)}
    out["published"] = {"num_hidden_layers": cfg.depth_scale_layers}
    return out


def seeded(cfg, seed=0):
    """The seeded init with the norms' weights moved off their constants,
    so that a norm left out or shared wrongly shows."""
    params = minicpm_sala_init(jax.random.PRNGKey(seed), cfg)
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
    """The file's engines, built once: ``chunked`` (chunks of 16 rows, the
    kernels off), ``kernels`` (the same with the Pallas kernels in interpret
    mode)."""
    if name not in _MADE:
        conf = dict(max_slots=4, max_seq_len=256, block_size=BS,
                    prefill_chunk=16, paged_kernel=name == "kernels",
                    num_blocks={"sparse": 64, "state": 12})
        _MADE[name] = InferenceEngine(
            CFG, params(), config={"inference": conf},
            mesh=build_mesh(devices=jax.devices()[:1]))
    return _MADE[name]


def ref(tokens, positions, state_t=0, fault=None, recurrent=False):
    """(logits, extras) of the reference, one compiled function a variant
    for rows padded to WIDTH."""
    key = ("ref", fault, recurrent)
    if key not in _MADE:
        _MADE[key] = jax.jit(
            lambda p, t, out, at: reference.forward(
                p, t, sizes_of(CFG), out, q_block=32, fault=fault,
                recurrent=recurrent, state_t=at))
    row = np.zeros(WIDTH, np.int32)
    row[:len(tokens)] = tokens
    out = np.zeros(N_OUT, np.int32)
    out[:len(positions)] = positions
    lg, extras = _MADE[key](params(), jnp.asarray(row), jnp.asarray(out),
                            jnp.int32(state_t))
    return np.asarray(lg)[:len(positions)], jax.tree.map(np.asarray, extras)


def page_of(eng, slot):
    """The stream's Lightning states, every layer: [L, nh, d, d]."""
    page = int(eng.block_tables[slot][-1])
    return np.asarray(eng.cache["state.state"])[:, 0, page]


def through(eng, prompt, steps=2):
    """(tokens, logits of the prefill and of ``steps`` decode iterations,
    admission info, the page after prefill and after the last iteration) of
    ``prompt`` served alone."""
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
    eng.release_slot(slot)
    return toks, np.stack(got), info, page0, page1


def prompt_of(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n,
                                                dtype=np.int32)


def rel(got, want):
    return float(np.sqrt(np.square(got - want).sum()
                         / max(np.square(want).sum(), 1e-30)))


def held(prompt, toks, got, page0, page1, steps=2, **variant):
    """(largest logit error, state error after prefill, after the last
    iteration) of a served stream against the reference (a variant)."""
    n = len(prompt)
    seq = np.concatenate([prompt, toks[:-1]])
    at = [n - 1 + i for i in range(steps + 1)]
    want, e0 = ref(seq, at, state_t=n - 1, **variant)
    _, e1 = ref(seq, at, state_t=at[-1], **variant)
    return (float(np.abs(got - want).max()),
            rel(page0, np.stack(e0["states"])),
            rel(page1, np.stack(e1["states"])))


# --------------------------------------------------------------------- #
# 1. Served against the reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("name,n", [("chunked", 40), ("chunked", 100),
                                    ("kernels", 100)])
def test_served_logits_and_states_are_the_references(name, n):
    """40 tokens: dense throughout; 100: the prefill crosses ``dense_len``
    (64) in its fifth chunk and decode reads 4 of 7 blocks."""
    prompt = prompt_of(n, n)
    toks, got, info, page0, page1 = through(engine(name), prompt)
    err, s0, s1 = held(prompt, toks, got, page0, page1)
    assert err <= LOGIT_ATOL and s0 <= PAGE_RTOL and s1 <= PAGE_RTOL, \
        (err, s0, s1)
    assert info["chunks"] == -(-n // 16)


@pytest.mark.parametrize("name,n", [("chunked", 40), ("chunked", 100),
                                    ("kernels", 100)])
def test_the_programs_return_the_sets_their_own_steps_chose(name, n):
    """``prefill_step`` and ``decode_step`` hand back, behind their logits,
    the pool block ids and the count each sparse layer's selection gave its
    attend (``probe_names``; ``engine.last_probes``, fetched with the
    logits): in float32 they are the reference's sets at the prompt's last
    row and at every decoded row, below ``dense_len`` (every block) and past
    it (``topk`` of them)."""
    eng, prompt = engine(name), prompt_of(n + 7, n)
    slot = eng.select_slot(prompt, 3)
    tok, _ = eng.prefill(prompt, slot, return_logits=True, max_new_tokens=3)
    picked = [{k: v[0] for k, v in eng.last_probes.items()}]
    eng.activate_slot(slot, n, tok)
    toks = [tok]
    for _ in range(2):
        sampled, _ = eng.decode_once(return_logits=True)
        toks.append(int(sampled[slot]))
        picked.append({k: v[slot] for k, v in eng.last_probes.items()})
    table = [int(b) for b in eng.block_tables[slot] if b >= 0]
    eng.release_slot(slot)
    assert set(picked[0]) == set(eng.served.probe_names)
    at = [n - 1, n, n + 1]
    _, extras = ref(np.concatenate([prompt, toks[:-1]]), at)
    n_sparse = len(CFG.sparse_layers)
    for r, (pos, got) in enumerate(zip(at, picked)):
        assert got["sparse_chosen"].shape == (
            n_sparse, CFG.num_key_value_heads, CFG.chosen_width)
        for layer in range(n_sparse):
            want = extras["sparse"][layer]["chosen"][r]          # [nKV, nb]
            for h in range(CFG.num_key_value_heads):
                count = int(got["sparse_count"][layer, h])
                ids = got["sparse_chosen"][layer, h]
                assert (ids[count:] < 0).all()
                logical = [table.index(int(b)) for b in ids[:count]]
                assert logical == list(np.flatnonzero(want[h])), (pos, layer)
                assert count == (pos // BS + 1 if pos + 1 <= SZ.dense_len
                                 else SZ.topk)


def test_a_family_without_probes_returns_what_it_returned():
    """``probe_names`` is this family's: the engine's step of any other has
    no output behind its logits, and ``last_probes`` stays None."""
    from deepspeed_tpu.inference.served import ServedModel
    from deepspeed_tpu.inference import InferenceEngine as Engine
    assert ServedModel.probe_names == () and Engine.last_probes is None
    eng = engine("chunked")
    shell = object.__new__(Engine)
    shell._cache_sh = eng._cache_sh
    pools = tuple(range(len(eng._cache_sh)))
    assert shell._outputs(pools + ("fetch", "logits")) == (
        pools, "fetch", "logits", None)
    assert shell._outputs(pools + ("fetch", "logits", "probes"))[3] \
        == "probes"


@pytest.mark.parametrize("name", ["chunked", "kernels"])
def test_a_prefix_hit_resumes_in_both_classes_past_dense_len(name):
    """A document of 100 tokens, then a question behind it: the second
    admission resumes at the document's last block boundary (96) with the
    K/V blocks, THEIR pooled keys and the Lightning snapshot, and selects
    over pooled keys the first stream wrote."""
    eng = engine(name)
    doc = prompt_of(7, 100)
    through(eng, doc, steps=1)
    prompt = np.concatenate([doc[:96], prompt_of(8, 23)])
    toks, got, info, page0, page1 = through(eng, prompt, steps=3)
    assert info["cached_tokens"] == 96, info
    assert set(info["cached_by_class"].values()) == {96}
    err, s0, s1 = held(prompt, toks, got, page0, page1, steps=3)
    assert err <= LOGIT_ATOL and s0 <= PAGE_RTOL and s1 <= PAGE_RTOL, \
        (err, s0, s1)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_runners_controls_are_far_from_the_reference(fault):
    """Each wrong model moves the logits of a 100-token prompt by far more
    than the served path's error (``bf16_state``: the states)."""
    seq, at = prompt_of(3, 120), [99, 107, 119]
    want, e = ref(seq, at, state_t=119)
    low, e_low = ref(seq, at, state_t=119, fault=fault)
    if fault == "bf16_state":
        # the reference's own recurrent form agrees with the quadratic one
        same, _ = ref(seq, at, state_t=119, recurrent=True)
        assert np.abs(same - want).max() <= 1e-4
        assert np.abs(low - want).max() > 1e-3
    else:
        assert np.abs(low - want).max() > 50 * LOGIT_ATOL, fault


# --------------------------------------------------------------------- #
# 2. The selection
# --------------------------------------------------------------------- #
SZ = sparse_select.Sizes.of(CFG)


def _selection_case(seed, T=160):
    """Random normed-like q, k of one stream: (q [T, nH, D], k [T, nKV, D],
    the pooled pool ``ck [1, 1, B, nKV, R, D]`` written chunk by chunk behind
    an identity table, the K pool as held)."""
    kq, kk = jax.random.split(jax.random.PRNGKey(seed))
    nH, nKV, D = 8, 2, 16
    q = 2 * jax.random.normal(kq, (T, nH, D))
    k = 2 * jax.random.normal(kk, (T, nKV, D))
    B = T // BS
    logical = k.reshape(1, 1, B, BS, nKV, D).transpose(0, 1, 2, 4, 3, 5)
    from deepspeed_tpu.inference.kv_cache import paged_folded_view
    pool_k = paged_folded_view(logical)
    ck = jnp.zeros((1, 1, B, nKV, SZ.per_block, D))
    table = jnp.arange(B, dtype=jnp.int32)[None]
    for start in range(0, T, 32):
        ck = sparse_select.write_pooled_chunk(
            ck, pool_k, 0, k[None, start:start + 32], table,
            jnp.asarray([start]), jnp.asarray([31]), jnp.asarray([1]), SZ)
    return q, k, ck, pool_k, table


def test_pooled_keys_lie_where_their_windows_end():
    """Pooled key j (tokens 4 j .. 4 j + 7) is global row j + 1: row (j + 1)
    % 4 of block (j + 1) // 4; row 0 of block 0 stays empty."""
    q, k, ck, pool_k, table = _selection_case(0)
    want = np.asarray(reference.pooled_keys(k, SPARSE_CONFIG))
    got = np.swapaxes(np.asarray(ck[0, 0]), 1, 2).reshape(-1, 2, 16)
    np.testing.assert_allclose(got[1:1 + len(want)], want, atol=1e-6)
    assert not got[0].any()
    # one row a stream (decode) writes the same rows from the K pool
    ck2 = jnp.zeros_like(ck)
    for t in range(160):
        ck2 = sparse_select.write_pooled_rows(
            ck2, pool_k, 0, table[None], jnp.asarray([[t]]),
            jnp.asarray([[True]]), SZ)
    np.testing.assert_allclose(ck2, ck, atol=1e-6)


@pytest.mark.parametrize("K", [1, 32])
def test_the_chosen_sets_are_the_references_in_float32(K):
    """``select_blocks`` (one row a stream, and a chunk's rows) against the
    reference's per-row selection: the same blocks, every row and K/V head,
    dense up to 64 tokens and 4 of up to 10 blocks past it."""
    q, k, ck, pool_k, table = _selection_case(1)
    T = q.shape[0]
    pos = np.arange(T)
    score = reference.block_scores(q, reference.pooled_keys(
        k, SPARSE_CONFIG), jnp.asarray(pos), SPARSE_CONFIG, 4, T // BS)
    want = np.asarray(reference.chosen_mask(score, jnp.asarray(pos),
                                            SPARSE_CONFIG))
    if K == 1:
        ids, n = sparse_select.select_blocks(
            q[:, None], ck, 0, jnp.broadcast_to(table, (T, T // BS)),
            jnp.asarray(pos)[:, None], jnp.ones((T, 1), bool), SZ, 0.25)
        ids, n = np.asarray(ids)[:, 0], np.asarray(n)[:, 0]
    else:
        ids = np.concatenate([np.asarray(sparse_select.select_blocks(
            q[None, s:s + K], ck, 0, table, jnp.asarray(pos)[None, s:s + K],
            jnp.ones((1, K), bool), SZ, 0.25)[0])[0]
            for s in range(0, T, K)])
        n = (ids >= 0).sum(-1)
    got = np.zeros_like(want)
    for t in range(T):
        for h in range(2):
            assert (np.diff(ids[t, h, :n[t, h]]) > 0).all()    # ascending
            assert (ids[t, h, n[t, h]:] == -1).all()
            got[t, h, ids[t, h, :n[t, h]]] = True
    assert (got == want).all()
    assert want[100].sum(-1).tolist() == [4, 4] and want[40].sum() == 6


def test_sparse_is_dense_where_the_blocks_number_no_more_than_topk():
    """With ``topk`` as many blocks as the context has, the sparse layers'
    logits are the dense model's."""
    seq, at = prompt_of(5, 120), [70, 99, 119]
    want, _ = ref(seq, at, fault="dense")
    wide = dict(sizes_of(CFG))
    wide["assumed"] = {"sparse_config": dict(SPARSE_CONFIG, topk=12)}
    row = np.zeros(WIDTH, np.int32)
    row[:120] = seq
    got, _ = jax.jit(lambda p, t: reference.forward(
        p, t, wide, jnp.asarray(at), q_block=32))(params(), jnp.asarray(row))
    np.testing.assert_allclose(got, want, atol=1e-5)
    # ... and the SERVED model at that topk is the dense one as well
    cfg = tiny(sparse_topk=12)
    eng = InferenceEngine(
        cfg, params(), config={"inference": dict(
            max_slots=2, max_seq_len=256, block_size=BS, prefill_chunk=16,
            paged_kernel=False, num_blocks={"sparse": 32, "state": 4})},
        mesh=build_mesh(devices=jax.devices()[:1]))
    slot = eng.select_slot(seq[:100], 1)
    _, pre = eng.prefill(seq[:100], slot, return_logits=True,
                         max_new_tokens=1)
    eng.release_slot(slot)
    np.testing.assert_allclose(np.asarray(pre), want[1], atol=LOGIT_ATOL)


# --------------------------------------------------------------------- #
# 3. Pooled keys under sharing
# --------------------------------------------------------------------- #
def _pooled_of(eng, slot, n_tokens):
    """The stream's pooled rows 1 .. of sparse layer 0 through ITS table."""
    row = np.asarray(eng.block_tables[slot][:-1])
    ck = np.asarray(eng.cache["ck.sparse"])[0, 0][np.maximum(row, 0)]
    ck = np.swapaxes(ck, 1, 2).reshape(-1, 2, 16)
    return ck[1:n_tokens // 4], row


@pytest.mark.parametrize("name", ["chunked", "kernels"])
def test_streams_that_share_a_block_read_their_own_pooled_keys(name):
    """Two streams share blocks 0 .. 5 (96 tokens) and differ from token 96
    on: pooled key 23 (tokens 92 .. 99) spans the shared block 5 and each
    stream's OWN block 6, where it lies; each reads its own.  Then a stream
    that shares a part-filled block copies it on write and its pooled rows
    with it."""
    eng = engine(name)
    doc = prompt_of(11, 100)
    a = np.concatenate([doc[:96], prompt_of(12, 20)])
    b = np.concatenate([doc[:96], prompt_of(13, 20)])
    slots, rows = [], []
    through(eng, doc, steps=1)
    for p in (a, b):
        slot = eng.select_slot(p, 2)
        eng.prefill(p, slot, max_new_tokens=2)
        assert eng.last_admit_info(slot)["cached_tokens"] == 96
        slots.append(slot)
    for slot, p in zip(slots, (a, b)):
        got, row = _pooled_of(eng, slot, len(p))
        rows.append(row)
        _, e = ref(p, [len(p) - 1])
        want = e["sparse"][0]["pooled"][:len(got)]
        np.testing.assert_allclose(got, want, atol=2e-5)
    assert (rows[0][:6] == rows[1][:6]).all() and rows[0][6] != rows[1][6]
    for slot in slots:
        eng.release_slot(slot)


# --------------------------------------------------------------------- #
# 4. Lightning: three forms, and the snapshot a chunk freezes
# --------------------------------------------------------------------- #
def _lightning_case(seed, T=48, nh=4, d=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    lam = jnp.asarray(sala.decay(CFG), jnp.float32)
    return dict(q=jax.random.normal(k[0], (T, nh, d)),
                k=jax.random.normal(k[1], (T, nh, d)),
                v=jax.random.normal(k[2], (T, nh, d)), lam=lam,
                S0=jnp.zeros((nh, d, d)))


@pytest.mark.parametrize("chunk", [8, 16, 48])
def test_lightning_recurrent_chunked_and_quadratic_agree(chunk):
    """Every head a group of its own (G = nh), dt = 1, a constant decay."""
    c = _lightning_case(0)
    T, nh, d = c["q"].shape
    one = jnp.ones((T, nh))
    with jax.default_matmul_precision("highest"):
        y, S, kept = ssm_scan.chunked_scan(
            c["S0"], c["v"], c["k"], c["q"], one, one * jnp.log(c["lam"]),
            chunk=chunk, keep=jnp.int32(0))
    St, ys = c["S0"], []
    for t in range(T):
        yt, St = ssm_scan.recurrent_update(
            St[None], c["v"][t][None], c["k"][t][None], c["q"][t][None],
            one[t][None], c["lam"][None])
        St = St[0]
        ys.append(yt[0])
        if t == chunk - 1:
            np.testing.assert_allclose(kept, St, atol=1e-5)  # the snapshot
    np.testing.assert_allclose(y, jnp.stack(ys), atol=2e-4)
    np.testing.assert_allclose(S, St, atol=1e-5)
    # the quadratic form: o_t = sum_s lam^(t - s) (q_t . k_s) v_s
    gap = jnp.arange(T)[:, None] - jnp.arange(T)[None]
    w = jnp.where(gap >= 0, c["lam"][:, None, None] ** jnp.maximum(gap, 0),
                  0.0)
    with jax.default_matmul_precision("highest"):
        quad = jnp.einsum("hts,shp->thp", jnp.einsum(
            "thn,shn->hts", c["q"], c["k"]) * w, c["v"])
    np.testing.assert_allclose(y, quad, atol=2e-4)
    np.testing.assert_allclose(
        reference.state_at(c["k"], c["v"], c["lam"], T - 1), S, atol=1e-5)


def test_the_snapshot_a_chunk_freezes_is_the_state_from_scratch():
    """A 100-token document leaves its snapshot at 96 from INSIDE its last
    chunk program; the page is the reference's S_95."""
    eng = engine("chunked")
    doc = prompt_of(21, 100)
    through(eng, doc, steps=1)
    tail = np.concatenate([doc[:96], prompt_of(22, 8)])
    slot = eng.select_slot(tail, 1)
    eng.prefill(tail, slot, max_new_tokens=1)
    info = eng.last_admit_info(slot)
    assert info["cached_tokens"] == 96 and info["cow_fork"]
    eng.release_slot(slot)
    snap = eng.allocator.classes[-1]._hash_index[0]
    _, e = ref(doc, [95], state_t=95)
    pages = [np.asarray(eng.cache["state.state"])[:, 0, p]
             for p in set(snap.values())]
    assert min(rel(p, np.stack(e["states"])) for p in pages) <= PAGE_RTOL


# --------------------------------------------------------------------- #
# 5. The kernels in interpret mode against their plain forms
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("pages", [[3, -1, 0, 5, -1], [-1] * 5,
                                   [1, 2, 3, 4, 5]])
def test_the_state_kernel_takes_several_one_head_groups_a_step(pages):
    c = _lightning_case(3, T=5, nh=4, d=128)
    assert ssm_scan.tile_heads(4, 4, 128, 128) == 4
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 1, 6, 4, 128, 128))
    pages = jnp.asarray([pages], jnp.int32)
    one = jnp.ones((1, 5, 4))
    da = one * c["lam"]
    y, new = jax.jit(ssm_scan.state_update)(
        pool, 1, pages, c["v"][None], c["k"][None], c["q"][None], one, da)
    page = jnp.maximum(pages[0], 0)
    want_y, want_S = ssm_scan.recurrent_update(
        pool[1, 0, page], c["v"], c["k"], c["q"], one[0], da[0])
    live = np.asarray(pages[0]) >= 0
    np.testing.assert_allclose(np.asarray(y[0])[live],
                               np.asarray(want_y)[live], atol=1e-4)
    np.testing.assert_allclose(np.asarray(new[1, 0, page])[live],
                               np.asarray(want_S)[live], atol=1e-5)
    assert not np.asarray(y[0])[~live].any()
    assert np.array_equal(new[0], pool[0])
    untouched = [p for p in range(6) if p not in np.asarray(pages[0])[live]]
    assert np.array_equal(new[1, 0, untouched], pool[1, 0, untouched])


@pytest.mark.parametrize("D,bs", [(16, 16), (128, 8)])
def test_the_per_head_attend_kernel_is_the_gather(D, bs):
    """Each K/V head of a stream walks blocks of ITS own, the last one part
    filled; dead rows and dead streams emit zeros."""
    from deepspeed_tpu.inference.kv_cache import paged_folded_view
    G, Q, nKV, grp, J, B = 1, 5, 2, 4, 6, 16
    k = jax.random.split(jax.random.PRNGKey(4), 5)
    pool = lambda key: paged_folded_view(jax.random.normal(   # noqa: E731
        key, (2, G, B, nKV, bs, D)))
    pool_k, pool_v = pool(k[0]), pool(k[1])
    q = jax.random.normal(k[2], (G, Q, 1, nKV * grp, D))
    rng = np.random.default_rng(0)
    chosen = np.stack([[np.sort(rng.choice(B, J, replace=False))
                        for _ in range(nKV)] for _ in range(Q)])[None]
    count = np.asarray([[[6, 3], [1, 6], [4, 4], [0, 0], [2, 5]]])
    chosen = np.where(np.arange(J) < count[..., None], chosen, -1)
    fill = np.asarray([[[bs - 1], [0], [3], [-1], [2]]])
    args = (jnp.asarray(chosen, jnp.int32), jnp.asarray(count, jnp.int32),
            jnp.asarray(fill, jnp.int32))
    want = served_sala.gather_attend_heads(q, pool_k, pool_v, 1, *args,
                                           D ** -0.5)
    plan = paged_attn_ops.attend_plan(args[0], args[2], pool_k, D,
                                      group=grp, count=args[1])
    got = paged_attn_ops.paged_attention(q, pool_k, pool_v, 1, plan=plan,
                                         scale=D ** -0.5)
    assert plan.rows.ndim == 4          # the plan itself says its form
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert not np.asarray(got[0, 3]).any()


def test_what_the_shared_code_answers_about_this_family():
    """Two classes from one ``class_specs`` call: three pools of the sparse
    class (the pooled keys at a quarter of the rows), one float32 state."""
    eng = engine("chunked")
    sparse, state = eng.cache_specs
    assert sparse.pool_names == ("k.sparse", "v.sparse", "ck.sparse")
    assert sparse.pool_shapes["ck.sparse"][3:] == (2, 4, 16)
    assert state.per_stream and state.pool_dtypes["state.state"] == jnp.float32
    assert state.pool_shapes["state.state"][3:] == (4, 16, 16)
    served = served_model(CFG)
    assert served.freezes_in_chunk and not served.rolls_back
    with pytest.raises(ValueError, match="unit"):
        served.class_geometry(served.cache_classes[0], 8)
    assert served.counter_names == ("sparse_blocks_read",
                                    "sparse_blocks_in_reach",
                                    "ck_rows_scored", "ck_blocks_read")


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_a_padding_row_of_the_held_vocabulary_is_never_sampled(temperature):
    """The vocabulary held as more rows than it has (padded to the lanes):
    the head leaves the real rows' logits as they are and gives a padding
    row none, however large its weights — greedy or drawn, no id at or above
    ``vocab_size`` comes out (a chip run of PR 59's cell emitted 73,465 of
    73,448 before the head did this)."""
    from deepspeed_tpu.inference.served import NEG_INF, sample_tokens
    cfg = MinicpmSalaConfig.from_hf(
        dict(sizes_of(tiny(vocab_size=100)),
             assumed={"sparse_config": dict(SPARSE_CONFIG),
                      "vocab_rows_held": 128}), dtype=jnp.float32)
    assert (cfg.vocab_size, cfg.vocab_rows) == (100, 128)
    p = minicpm_sala_init(jax.random.PRNGKey(3), cfg)
    assert p["embed"].shape == p["lm_head"].shape == (128, 64)
    p["lm_head"] = p["lm_head"].at[100:].multiply(50.0)
    h = jax.random.normal(jax.random.PRNGKey(4), (6, 64), jnp.float32)
    logits = served_model(cfg).head(p, h)
    whole = served_model(tiny(vocab_size=128)).head(p, h)
    assert np.asarray(whole).argmax(-1).min() >= 100     # what it would pick
    np.testing.assert_array_equal(logits[:, :100], whole[:, :100])
    assert (np.asarray(logits[:, 100:]) == NEG_INF).all()
    toks = sample_tokens(logits, jax.random.PRNGKey(5),
                         jnp.float32(temperature))
    assert np.asarray(toks).max() < 100

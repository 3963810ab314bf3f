"""The ``deepseek_v3`` family through the normal serving path (PR 32):
latent attention over a paged latent cache, and an expert layer that
holds a share of its experts.

What is held to what:
1. Served logits — prefill, then decode through the paged latent cache,
   then a second request through the prefix-hit path — against the plain
   float32 reference the benchmark keeps (``perfbench/lib/
   deepseek_reference.py``), kernels on and off, whole layer and share.
2. The absorbed attend equals the expanded one; the latent kernel
   (interpret mode) equals the one-hot attend on ragged tables, dead
   streams and rows past the table; the row write lands where the logical
   layout says.
3. YaRN frequencies and ``m`` against hand-computed values.
4. The router: group-limited choice, the bias moves selection and not
   weights, weights sum to the scale; dropless dispatch under a skewed
   router; the grouped kernel equals its jnp form.
5. The share test of ``model-configs`` section 4: the shares' routed parts
   plus the shared expert counted once equal the uncut reference layer.
6. Serving GPT-2 imports none of it.
"""
import dataclasses
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference import InferenceEngine, kv_cache  # noqa: E402
from deepspeed_tpu.inference import latent as latent_mod        # noqa: E402
from deepspeed_tpu.models import deepseek_v3 as dsv3            # noqa: E402
from deepspeed_tpu.models.deepseek_v3 import (                  # noqa: E402
    DeepseekV3Config, deepseek_v3_init)
from deepspeed_tpu.moe import share                             # noqa: E402
from deepspeed_tpu.ops import grouped_gemm                      # noqa: E402
from deepspeed_tpu.ops import latent_attention as la            # noqa: E402
from deepspeed_tpu.parallel.topology import build_mesh          # noqa: E402
from perfbench.lib import deepseek_reference as reference       # noqa: E402


def one_device():
    return build_mesh(devices=jax.devices()[:1])


def tiny(**kw):
    base = dict(
        vocab_size=250, vocab_rows_held=256, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=3, first_k_dense_replace=1,
        num_attention_heads=4, n_routed_experts=16, held=(0, 16),
        num_experts_per_tok=4, n_group=4, topk_group=2, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=24, max_position_embeddings=256,
        rope_original_max_position_embeddings=32, rope_factor=8.0,
        dtype=jnp.float32, initializer_range=0.08)
    base.update(kw)
    return DeepseekV3Config(**base)


def sizes_of(cfg):
    """The configuration file's keys for the reference."""
    return dict(
        rms_norm_eps=cfg.rms_norm_eps,
        num_attention_heads=cfg.num_attention_heads,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
        rope_scaling=dict(
            factor=cfg.rope_factor, beta_fast=cfg.rope_beta_fast,
            beta_slow=cfg.rope_beta_slow, mscale=cfg.rope_mscale,
            mscale_all_dim=cfg.rope_mscale_all_dim,
            original_max_position_embeddings=cfg
            .rope_original_max_position_embeddings),
        n_routed_experts_published=cfg.n_routed_experts,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        num_experts_per_tok=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, held=cfg.held)


# --------------------------------------------------------------------- #
# 1. Served logits against the reference
# --------------------------------------------------------------------- #
def _serve_one(eng, prompt):
    slot = eng.select_slot(prompt, 4)
    tok, pre = eng.prefill(prompt, slot, return_logits=True,
                           max_new_tokens=4)
    info = dict(eng.last_admit_info(slot))
    eng.activate_slot(slot, len(prompt), tok)
    _, dec = eng.decode_once(return_logits=True)
    eng.release_slot(slot)
    return tok, np.stack([pre, dec[slot]]), info


@pytest.mark.parametrize("kernel", [False, True], ids=["onehot", "kernels"])
@pytest.mark.parametrize("held", [(0, 16), (4, 8)], ids=["whole", "share"])
def test_served_logits_match_the_reference(kernel, held):
    cfg = tiny(held=held)
    params = deepseek_v3_init(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, config={"inference": dict(
        max_slots=4, max_seq_len=128, block_size=16, prefill_chunk=32,
        paged_kernel=kernel)}, mesh=one_device())
    assert list(eng.cache) == ["latent"]
    assert eng.cache["latent"].shape == (3, 1, 32, 1, 8, 80)
    rng = np.random.default_rng(0)
    first = rng.integers(0, cfg.vocab_size, size=70, dtype=np.int32)
    second = np.concatenate([first[:64], rng.integers(
        0, cfg.vocab_size, size=9, dtype=np.int32)])
    V = cfg.vocab_size
    for prompt, cached in ((first, 0), (second, 64)):
        tok, got, info = _serve_one(eng, prompt)
        assert info["cached_tokens"] == cached      # the prefix-hit path
        assert tok < V and got[:, V:].max() <= -1e8     # padding rows
        want, _ = reference.forward(
            params, jnp.asarray(np.concatenate([prompt, [tok]])),
            sizes_of(cfg), out_positions=[len(prompt) - 1, len(prompt)],
            q_block=32)
        np.testing.assert_allclose(got[:, :V], np.asarray(want)[:, :V],
                                   atol=2e-5, rtol=2e-5)
    counters = eng.serving.snapshot()["model_counters"]
    share_expected = held[1] / cfg.n_routed_experts
    assert counters["moe_held_pair_share"] == pytest.approx(
        share_expected, abs=0.2 if held[1] < 16 else 1e-9)
    eng.close()


def test_a_batch_through_the_scheduler_matches_one_by_one():
    """``engine.serve`` (the scheduler GPT-2 uses) over several requests
    at once gives each request the tokens it gets alone."""
    from deepspeed_tpu.inference.scheduler import Request
    cfg = tiny()
    params = deepseek_v3_init(jax.random.PRNGKey(1), cfg)
    conf = {"inference": dict(max_slots=4, max_seq_len=128, block_size=16,
                              prefill_chunk=32, paged_kernel=False)}
    rng = np.random.default_rng(1)
    doc = rng.integers(0, cfg.vocab_size, size=48, dtype=np.int32)
    prompts = [np.concatenate([doc, rng.integers(
        0, cfg.vocab_size, size=n, dtype=np.int32)]) for n in (5, 9, 13)]

    def serve(ps):
        eng = InferenceEngine(cfg, params, config=conf, mesh=one_device())
        reqs = [Request(rid=i, prompt=p, max_new_tokens=6, arrival_s=0.0)
                for i, p in enumerate(ps)]
        report = eng.serve(reqs)
        eng.close()
        return [list(r.out_tokens) for r in reqs], report
    together, report = serve(prompts)
    assert report["prefix"]["hit_rate"] > 0.3
    for i, p in enumerate(prompts):
        assert serve([p])[0][0] == together[i]


# --------------------------------------------------------------------- #
# 2. The attend and the write
# --------------------------------------------------------------------- #
def _latent_case(seed, lengths, *, K=1, nH=4, C=32, R=8, B=12, bs=16, J=4,
                 dtype=jnp.float32, past_table=False, live_rows=None):
    """q_abs / q_rope [G, Q, K, nH, .], one layer's logical rows [G, B, bs,
    C + R], tables and positions from per-stream context lengths (<= 0:
    a dead stream; a callable: lengths in BLOCKS from the slot rule's
    group and its narrowest width for this tile and dtype).  ``live_rows``:
    the rows of a chunk from there on are padding (position -1)."""
    rng = np.random.default_rng(seed)
    if callable(lengths):
        P, widths = la.slots_a_step(
            la.row_tokens(K) * nH, J, la.latent_tile(bs, C + R), C,
            jnp.dtype(dtype).itemsize)
        assert J > 2 * P and widths[-1] < P, (P, widths, J)
        lengths = [[n * bs - 3 for n in group]
                   for group in lengths(P, widths[-1])]
    G, Q = len(lengths), len(lengths[0])
    rows = rng.standard_normal((G, B, bs, C + R)).astype(np.float32)
    qa = rng.standard_normal((G, Q, K, nH, C)).astype(np.float32)
    qr = rng.standard_normal((G, Q, K, nH, R)).astype(np.float32)
    bt = np.full((G, Q, J), kv_cache.DEAD_BLOCK, np.int32)
    pos = np.zeros((G, Q, K), np.int32)
    for g in range(G):
        free = list(range(B))
        for s in range(Q):
            ctx = lengths[g][s]
            if ctx <= 0:
                continue
            last = ctx - 1 + K - 1
            nblk = min(last // bs + 1, J)
            bt[g, s, :nblk] = [free.pop() for _ in range(nblk)]
            pos[g, s] = ctx - 1 + np.arange(K)
            if live_rows is not None:
                pos[g, s, live_rows:] = -1
    if past_table:
        assert pos.max() >= J * bs
    return (jnp.asarray(qa), jnp.asarray(qr), jnp.asarray(rows, dtype),
            jnp.asarray(bt), jnp.asarray(pos))


def _onehot(qa, qr, rows, bt, pos, scale):
    C = qa.shape[-1]
    pool = la.fold_rows(rows, C)[None, :, :, None]          # one layer
    J, bs = bt.shape[2], rows.shape[2]
    sel = kv_cache.block_select(bt, rows.shape[1])
    grid = jnp.arange(J * bs, dtype=jnp.int32)[None, None, None, :]
    return latent_mod._onehot_attend(qa, qr, pool, 0, sel,
                                     grid <= pos[..., None], scale, C)


def _kernel(qa, qr, rows, bt, pos, scale, layer=1):
    C = qa.shape[-1]
    tile = la.fold_rows(rows, C)[:, :, None]                # [G, B, 1, h, W]
    pool = jnp.stack([tile if l == layer else jnp.full_like(tile, 7.0)
                      for l in range(3)])
    plan = la.latent_plan(bt, pos, pool)
    return la.latent_attention(qa, qr, pool, layer, plan=plan, scale=scale)


_WIDE = dict(C=512, R=64, bs=64, J=72, B=70, nH=2)
LATENT_CASES = {
    "ragged_and_partial_blocks": ([[16, 17, 13, 1], [33, 0, 8, 5]], {}),
    "dead_streams": ([[0, 0, 9], [0, 20, 0]], {}),
    "verify_rows": ([[7, 15, 21], [3, 12, 0]], {"K": 4}),
    "prefill_chunk_two_row_tiles": ([[17], [40]], {"K": 16}),
    "chunk_not_a_multiple_of_the_row_tile": ([[9], [30]], {"K": 11}),
    "long_table_several_groups": ([[300, 120]], {"J": 20, "B": 40}),
    "rows_past_the_table": ([[60, 10]], {"K": 8, "past_table": True}),
    # cold and warm starts side by side: the first stream of a call and
    # the one after a dead stream start their own copies, the others'
    # are in flight when their step begins
    "live_dead_live_live": ([[9, 0, 20, 5], [0, 7, 3, 0]], {}),
    "verify_rows_dead_stream_between": ([[7, 0, 21], [3, 12, 0]], {"K": 4}),
    # a chunk of three row tiles whose last two are padding: the next
    # stream's first tile has nothing started for it
    "chunk_ends_in_dead_row_tiles": ([[17, 30]], {"K": 24, "live_rows": 8}),
    # at the published tile (blocks of 64 x 576) a decode group is what
    # copies 2 MiB, narrower than the table, and is computed at the
    # narrowest width that holds its live slots
    "exactly_one_group": (lambda P, n: [[P, 1]], _WIDE),
    "one_group_and_a_block": (lambda P, n: [[P + 1, 2]], _WIDE),
    "a_narrow_width_and_a_block": (lambda P, n: [[n + 1, 2 * n + 1]], _WIDE),
    "table_wider_than_two_groups": (lambda P, n: [[2 * P + 3]], _WIDE),
}


@pytest.mark.parametrize("name", sorted(LATENT_CASES))
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 0.06)],
                         ids=["fp32", "bf16-pool"])
def test_latent_kernel_equals_the_onehot_attend(name, dtype, tol):
    lengths, kw = LATENT_CASES[name]
    qa, qr, rows, bt, pos = _latent_case(3, lengths, dtype=dtype, **kw)
    qa, qr = qa.astype(dtype), qr.astype(dtype)
    out = _kernel(qa, qr, rows, bt, pos, 0.11).astype(jnp.float32)
    ref = _onehot(qa, qr, rows, bt, pos, 0.11).astype(jnp.float32)
    live = np.asarray((bt[..., 0] >= 0)[..., None] & (pos >= 0))
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=tol, rtol=2e-5)
    # dead streams and a chunk's padding rows: zeros
    assert not np.asarray(out)[~live].any()


@pytest.mark.parametrize("heads,table", [(32, 96), (64, 272)],
                         ids=["32-heads-table-96", "64-heads-table-272"])
def test_the_slot_rule_at_the_published_widths(heads, table):
    """By hand, blocks of 64 positions x 576 bf16 values (73,728 B a
    tile): decode's rows copy 32 slots a group (the first size that
    reaches 2 MiB) and compute the narrowest of 32 / 16 / 8 / 4 slots
    that holds a group's live ones (4 x 32 columns a half: 128 lanes);
    a prefill chunk's row tile of 8 tokens, and 4 verify tokens of 32
    heads or more, fill the MXU: 512 / 32 = 16 slots, computed whole."""
    rule = lambda K: la.slots_a_step(                       # noqa: E731
        la.row_tokens(K) * heads, table, la.latent_tile(64, 576), 512, 2)
    assert rule(1) == (32, (32, 16, 8, 4))
    assert rule(4) == (16, (16,))
    assert rule(512) == (16, (16,))
    # a float32 pool's tile is twice the bytes: half the slots a group
    assert la.slots_a_step(heads, table, la.latent_tile(64, 576), 512,
                           4) == (16, (16, 8, 4))
    # never wider than the table
    assert la.slots_a_step(heads, 3, la.latent_tile(64, 576), 512, 2) \
        == (3, (3,))


def test_attend_step_counts_equal_a_count_by_hand():
    """A layer's steps at cell 4's shape (64 heads, a table of 272, bf16):
    groups of 32 slots, so 200 / 33 / 32 / 1 live blocks are 7 / 2 / 1 /
    1 live steps and the dead stream one empty step; the first stream and
    the one after the dead one start cold."""
    from types import SimpleNamespace
    from deepspeed_tpu.inference.served import served_model
    served = served_model(DeepseekV3Config(held=(0, 16)))
    spec = SimpleNamespace(max_blocks_per_slot=272, block_size=64,
                           dtype=jnp.bfloat16)
    live = [200, 33, 32, 0, 1]
    count = lambda K: served.attend_step_counts(            # noqa: E731
        live, K=K, spec=spec, mp=1, q_itemsize=2)
    assert count(1) == (12, 11, 2)
    # three verify tokens of 64 heads fill the MXU: groups of 16 slots
    assert count(3) == (13 + 3 + 2 + 1 + 1, 13 + 3 + 2 + 1, 2)
    # as three calls (a dp mesh of three: 200 33 | 32 0 | 1 0) each
    # call's first stream starts cold
    assert served.attend_step_counts(
        live + [0], K=1, spec=spec, mp=1, q_itemsize=2, calls=3)[2] == 3


def test_absorbed_attend_equals_expanded_attend():
    """Scores and values through ``wkv_b`` after the cache (absorbed)
    equal per-head K and V built before it (expanded), in real
    arithmetic."""
    cfg = tiny()
    rng = np.random.default_rng(5)
    S, nH, C = 24, cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope = rng.standard_normal((S, nH, dn)).astype(np.float32)
    q_rope = rng.standard_normal((S, nH, dr)).astype(np.float32)
    ckv = rng.standard_normal((S, C)).astype(np.float32)
    k_rope = rng.standard_normal((S, dr)).astype(np.float32)
    wkv_b = rng.standard_normal((C, nH * (dn + dv))).astype(np.float32) * .2
    wk, wv = dsv3.wkv_b_split({"wkv_b": jnp.asarray(wkv_b)}, cfg)
    causal = np.tril(np.ones((S, S), bool))
    # expanded
    kvb = (ckv @ wkv_b).reshape(S, nH, dn + dv)
    s = np.einsum("snd,tnd->nst", q_nope, kvb[..., :dn]) \
        + np.einsum("snd,td->nst", q_rope, k_rope)
    w = jax.nn.softmax(jnp.where(causal, s * cfg.softmax_scale, -jnp.inf),
                       axis=-1)
    expanded = np.einsum("nst,tnv->snv", np.asarray(w), kvb[..., dn:])
    # absorbed
    q_abs = np.einsum("snd,cnd->snc", q_nope, np.asarray(wk))
    s2 = np.einsum("snc,tc->nst", q_abs, ckv) \
        + np.einsum("snd,td->nst", q_rope, k_rope)
    w2 = jax.nn.softmax(jnp.where(causal, s2 * cfg.softmax_scale, -jnp.inf),
                        axis=-1)
    u = np.einsum("nst,tc->snc", np.asarray(w2), ckv)
    absorbed = np.einsum("snc,cnv->snv", u, np.asarray(wv))
    np.testing.assert_allclose(absorbed, expanded, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("rows_n", [1, 5, 16])
def test_latent_write_lands_where_the_logical_layout_says(rows_n):
    C, R, bs, B, L, G = 32, 8, 16, 6, 2, 2
    rng = np.random.default_rng(rows_n)
    logical = rng.standard_normal((L, G, B, bs, C + R)).astype(np.float32)
    pool = la.fold_rows(jnp.asarray(logical), C)[:, :, :, None]
    new = rng.standard_normal((G, rows_n, C + R)).astype(np.float32)
    # group 0: a run that crosses a block boundary; group 1: dead rows
    # among live ones.
    start = bs - 2
    blk = np.full((G, rows_n), -1, np.int32)
    off = np.zeros((G, rows_n), np.int32)
    for r in range(rows_n):
        p = start + r
        blk[0, r], off[0, r] = (3, p) if p < bs else (1, p - bs)
        if r % 2 == 0:
            blk[1, r], off[1, r] = 4, r
    out = la.latent_write(pool, jnp.asarray(new), 1, jnp.asarray(blk),
                          jnp.asarray(off), kv_lora=C)
    want = logical.copy()
    for g in range(G):
        for r in range(rows_n):
            if blk[g, r] >= 0:
                want[1, g, blk[g, r], off[g, r]] = new[g, r]
    got = la.logical_rows(out[:, :, :, 0], C)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_the_latent_tile_pads_nothing():
    rows, lanes = la.latent_tile(64, 576)
    assert (rows, lanes) == (32, 1152) and lanes % 128 == 0
    served = latent_mod.LatentServed(DeepseekV3Config())
    spec = kv_cache.PagedKVCacheSpec(
        num_layers=5, num_slots=128, num_blocks=7168, block_size=64,
        max_len=17408, num_heads=1, head_dim=576,
        pools=served.cache_pools(64))
    assert spec.block_nbytes() == 5 * 64 * 1152      # 1,152 B a token, layer
    assert spec.pool_shapes == {"latent": (5, 1, 7168, 1, 32, 1152)}
    assert served.attend_dims == (64, 576, 512)


# --------------------------------------------------------------------- #
# 3. YaRN
# --------------------------------------------------------------------- #
def test_yarn_frequencies_and_m_against_hand_computed_values():
    cfg = DeepseekV3Config()                       # the published values
    inv = dsv3.yarn_inv_freq(cfg)
    assert inv.shape == (32,)
    f = 100000.0 ** (-np.arange(32) / 32.0)
    # beta_fast 32 / beta_slow 1 at 4096 positions, theta 1e5, dim 64:
    # 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e5) = 8.38 -> low 8;
    # 64 ln(4096 / (2 pi)) / (2 ln 1e5) = 18.01 -> high 19.
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(1e5))) == 8
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(1e5))) == 19
    np.testing.assert_allclose(inv[:9], f[:9], rtol=1e-12)       # as it is
    np.testing.assert_allclose(inv[19:], f[19:] / 64, rtol=1e-12)  # / factor
    ramp = (13 - 8) / (19 - 8)
    np.testing.assert_allclose(
        inv[13], f[13] / 64 * ramp + f[13] * (1 - ramp), rtol=1e-12)
    np.testing.assert_allclose(inv, reference.yarn_inv_freq(dict(
        qk_rope_head_dim=64, rope_theta=100000, rope_scaling=dict(
            factor=64, beta_fast=32, beta_slow=1,
            original_max_position_embeddings=4096))), rtol=1e-12)
    m = dsv3.yarn_mscale(64.0, 1.0)
    assert m == pytest.approx(0.1 * math.log(64) + 1) \
        and m == pytest.approx(1.41589, abs=1e-5)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    cos, sin = dsv3.rope_cos_sin(cfg, jnp.asarray([0, 7]))
    np.testing.assert_allclose(np.asarray(cos[1]), np.cos(7 * inv),
                               atol=1e-6)                        # ratio 1


def test_rotary_pairs_are_interleaved():
    x = jnp.arange(8, dtype=jnp.float32)[None]
    cos, sin = jnp.full((1, 4), 0.0), jnp.full((1, 4), 1.0)     # 90 degrees
    out = np.asarray(dsv3.rope_interleaved(x, cos, sin))[0]
    np.testing.assert_allclose(out, [-1, 0, -3, 2, -5, 4, -7, 6])
    np.testing.assert_allclose(
        np.asarray(reference._rope(x, cos, sin))[0], out)


# --------------------------------------------------------------------- #
# 4. Router, dispatch, grouped product
# --------------------------------------------------------------------- #
def _router_case(seed=0, T=64, bias_std=0.1, **kw):
    cfg = tiny(**kw)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, cfg.hidden_size)), jnp.float32)
    router = jnp.asarray(rng.standard_normal(
        (cfg.hidden_size, cfg.n_routed_experts)) * 0.2, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(cfg.n_routed_experts) * bias_std,
                       jnp.float32)
    return cfg, x, router, bias


def test_router_is_group_limited_and_weights_sum_to_the_scale():
    cfg, x, router, bias = _router_case()
    idx, w = share.route(x, router, bias, cfg.routing)
    idx, w = np.asarray(idx), np.asarray(w)
    assert idx.shape == (64, 4) and all(len(set(r)) == 4 for r in idx)
    np.testing.assert_allclose(w.sum(-1), cfg.routed_scaling_factor,
                               rtol=1e-5)
    per_group = cfg.n_routed_experts // cfg.n_group
    assert all(len({e // per_group for e in r}) <= cfg.topk_group
               for r in idx)
    # the kept groups are the best by the sum of their two largest c
    c = np.asarray(jax.nn.sigmoid(x @ router) + bias)
    score = np.sort(c.reshape(64, cfg.n_group, per_group), -1)[..., -2:] \
        .sum(-1)
    best = np.argsort(-score, -1)[:, :cfg.topk_group]
    assert all({e // per_group for e in r} <= set(b)
               for r, b in zip(idx, best))
    ref_idx, ref_w, _ = reference.route(x, router, bias, sizes_of(cfg))
    assert (np.sort(idx, -1) == np.sort(np.asarray(ref_idx), -1)).all()
    np.testing.assert_allclose(np.sort(w, -1), np.sort(np.asarray(ref_w), -1),
                               rtol=1e-5)


def test_bias_moves_selection_and_not_weights():
    cfg, x, router, bias = _router_case(bias_std=0.0)
    idx0, w0 = share.route(x, router, bias, cfg.routing)
    push = jnp.zeros_like(bias).at[5].set(10.0)      # expert 5 always wins
    idx1, w1 = share.route(x, router, push, cfg.routing)
    assert (np.asarray(idx1) == 5).any(-1).all()
    assert not (np.asarray(idx0) == 5).any(-1).all()
    # weights are s at the chosen, renormalised: the bias is not in them
    s = np.asarray(jax.nn.sigmoid(x @ router))
    chosen = np.take_along_axis(s, np.asarray(idx1), 1)
    np.testing.assert_allclose(
        np.asarray(w1), chosen / chosen.sum(-1, keepdims=True) * 2.5,
        rtol=1e-5)


@pytest.mark.parametrize("skew", [0.0, 6.0])
@pytest.mark.parametrize("held", [(0, 16), (4, 4)])
def test_dispatch_is_dropless(skew, held):
    """Every routed pair on a held expert gets exactly one buffer row, in
    its expert's group; under a skewed router one expert takes nearly
    every token and still nothing is dropped."""
    cfg, x, router, bias = _router_case(held=held)
    bias = bias.at[held[0]].add(skew)
    idx, _ = share.route(x, router, bias, cfg.routing)
    tm = 16
    d = {k: np.asarray(v)
         for k, v in share.dispatch(idx, cfg.routing, tm).items()}
    idx = np.asarray(idx)
    on = (idx >= held[0]) & (idx < held[0] + held[1])
    assert (d["on"] == on).all()
    assert d["counts"].sum() == on.sum()
    if skew:
        assert d["counts"][0] == 64                  # all tokens, no cap
    rows = d["pos"][on]
    assert len(set(rows.tolist())) == len(rows)      # one row a pair
    tok = np.repeat(np.arange(64)[:, None], 4, 1)[on]
    assert (d["src"][rows] == tok).all()
    assert (d["tile_expert"][rows // tm] == idx[on] - held[0]).all()
    assert d["n_live_tiles"] == sum(-(-c // tm) for c in d["counts"])
    assert rows.max() < d["n_live_tiles"] * tm


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 0.03)])
def test_grouped_swiglu_kernel_equals_its_jnp_form(dtype, atol):
    """The kernel reads token ``src[r]`` into buffer row ``r`` itself, the
    rows each tile holds and no more; the plain form takes the gathered
    copy."""
    rng = np.random.default_rng(2)
    E, F, H, tm, nt, T = 6, 256, 128, 16, 7, 40
    w = {k: jnp.asarray(rng.standard_normal((E, F, H)) * 0.1, dtype)
         for k in ("w_gate", "w_up", "w_down")}
    x = jnp.asarray(rng.standard_normal((T, H)), dtype)
    src = jnp.asarray(rng.integers(0, T, nt * tm), jnp.int32)
    te = jnp.asarray([0, 0, 2, 5, 5, 1, 3], jnp.int32)
    rows = np.asarray([16, 3, 16, 16, 9, 0, 0], np.int32)
    out = grouped_gemm.grouped_swiglu(x, src, w["w_gate"], w["w_up"],
                                      w["w_down"], te, jnp.asarray(rows), 5,
                                      tm=tm)
    ref = share._experts_jnp(x[src], w, te, 5, tm)
    held = (np.arange(nt * tm) % tm) < np.repeat(rows, tm)
    np.testing.assert_allclose(np.asarray(out, np.float32)[held],
                               np.asarray(ref, np.float32)[held], atol=atol)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert not np.asarray(out[5 * tm:], np.float32).any()   # dead tiles


# --------------------------------------------------------------------- #
# 5. The share test
# --------------------------------------------------------------------- #
def _sum_form(p, x, cfg):
    """The uncut layer as a plain sum over all experts (HF's loop)."""
    idx, w = share.route(x, p["router"], p["router_bias"], cfg.routing)
    y = jnp.zeros_like(x)
    for e in range(cfg.n_routed_experts):
        we = jnp.where(idx == e, w, 0.0).sum(-1)
        g = x @ p["w_gate"][e].T
        y = y + we[:, None] * ((jax.nn.silu(g) * (x @ p["w_up"][e].T))
                               @ p["w_down"][e])
    return y


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
def test_the_shares_add_up_to_the_uncut_layer(kernel):
    """model-configs section 4: 16 -> here 4 shares of 4 experts; their
    routed parts plus the shared expert counted ONCE equal what the uncut
    reference layer gives; ``held = (0, E)`` equals the sum form."""
    cfg = tiny()
    params = deepseek_v3_init(jax.random.PRNGKey(3), cfg)
    p = jax.tree_util.tree_map(lambda a: a[0], params["moe"])   # one layer
    x = jnp.asarray(np.random.default_rng(3).standard_normal((40, 64)),
                    jnp.float32)
    whole, counts = share.routed_share(p, x, cfg.routing, kernel=kernel)
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(_sum_form(p, x, cfg)), atol=2e-5)
    assert int(counts.sum()) == 40 * cfg.num_experts_per_tok
    parts = []
    for first in range(0, 16, 4):
        c = dataclasses.replace(cfg, held=(first, 4))
        pp = dict(p, **{k: p[k][first:first + 4]
                        for k in ("w_gate", "w_up", "w_down")})
        parts.append(share.routed_share(pp, x, c.routing, kernel=kernel)[0])
    shared = dsv3.swiglu(x, p["shared_gate"], p["shared_up"],
                         p["shared_down"])
    total = sum(parts) + shared
    np.testing.assert_allclose(
        np.asarray(total), np.asarray(whole + shared), atol=2e-5)
    # ... and the reference's expert layer, given the whole layer
    full, _ = share.expert_layer(p, x, cfg.routing, kernel=kernel)
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               atol=2e-5)


def test_stacked_expert_weights_name_the_layer():
    cfg = tiny()
    params = deepseek_v3_init(jax.random.PRNGKey(4), cfg)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((24, 64)),
                    jnp.float32)
    for l in range(cfg.num_moe_layers):
        one = jax.tree_util.tree_map(lambda a: a[l], params["moe"])
        stacked = dict(one, **{k: params["moe"][k]
                               for k in ("w_gate", "w_up", "w_down")})
        a, _ = share.routed_share(one, x, cfg.routing, kernel=False)
        b, _ = share.routed_share(stacked, x, cfg.routing, kernel=False,
                                 layer=l)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_config_from_the_published_keys():
    import json
    sizes = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "gigachat3.1-702b-a36b.json")))
    cfg = DeepseekV3Config.from_hf(
        sizes, n_routed_experts=sizes["n_routed_experts_published"],
        held=(0, sizes["n_routed_experts"]),
        vocab_rows_held=sizes["assumed"]["vocab_rows_held"])
    assert (cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.v_head_dim, cfg.qk_head_dim) == (7168, 1536, 512, 192, 192)
    assert (cfg.rope_factor, cfg.rope_theta,
            cfg.rope_original_max_position_embeddings) == (64, 100000, 4096)
    assert (cfg.num_dense_layers, cfg.num_moe_layers, cfg.held) == \
        (1, 4, (0, 16))
    shapes = jax.eval_shape(lambda k: deepseek_v3_init(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(shapes))
    assert abs(n - 4.29e9) < 0.01e9                  # the cut's arithmetic
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, held=(250, 16))


# --------------------------------------------------------------------- #
# 6. Off every other model's start-up
# --------------------------------------------------------------------- #
def test_serving_gpt2_imports_none_of_it():
    code = """
import sys, jax
import deepspeed_tpu
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.models import GPT2_CONFIGS, gpt2_init
from deepspeed_tpu.parallel.topology import build_mesh
cfg = GPT2_CONFIGS['gpt2-tiny']
eng = InferenceEngine(cfg, gpt2_init(jax.random.PRNGKey(0), cfg), config={
    'inference': dict(max_slots=2, max_seq_len=64, block_size=16,
                      prefill_chunk=16)},
    mesh=build_mesh(devices=jax.devices()[:1]))
assert type(eng.served).__name__ == 'GPT2Served'
assert list(eng.cache) == ['k', 'v']
bad = [m for m in sys.modules if m.startswith('deepspeed_tpu.') and
       m.rsplit('.', 1)[-1] in ('deepseek_v3', 'share', 'latent',
                                'latent_attention')]
print('LOADED', bad)
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                         capture_output=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu",
                                  PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LOADED []" in out.stdout


def test_a_config_finds_its_served_model_lazily():
    from deepspeed_tpu.inference.served import ServedModel, served_model
    served = served_model(tiny())
    assert isinstance(served, latent_mod.LatentServed)
    assert isinstance(served, ServedModel) and served_model(served) is served
    with pytest.raises(TypeError):
        served_model(object())

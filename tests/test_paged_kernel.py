"""Pallas paged-attention kernel (PR-17 tentpole).

The load-bearing invariants:

1. **Parity** — the table-sliced Pallas kernel (interpret mode on this
   CPU mesh — the same program a TPU compiles) matches the one-hot
   ``kv_cache.paged_attend`` baseline: fp32 logits at tight tolerance,
   bf16 pools at ulp-bounded tolerance (the baseline combines values in
   bf16, the kernel accumulates fp32 — the kernel is the MORE accurate
   side), across ragged contexts, partial final blocks, dead streams,
   CoW-shared block ids, and the K=k+1 verify-row variant.
2. **Bit-identity** — greedy token streams (plain and speculative) are
   identical with the kernel on and off; the PR-12 shared-prefix
   acceptance stream runs kernel-on under ``fail_on_recompile`` with
   zero post-warmup retraces.
3. **Gating** — ``paged_kernel_enabled`` honours True/False force, the
   ``DS_PAGED_KERNEL`` env override, and "auto" = TPU-on/CPU-off.
4. **Cost model** — analytic attend FLOPs / HBM bytes scale with
   ceil(context/bs)*bs on the kernel side and with pool CAPACITY on the
   one-hot side, and the engine feeds both into the serving aggregator.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine, shared_prefix_requests
from deepspeed_tpu.inference import kv_cache
from deepspeed_tpu.models.gpt2 import GPT2_CONFIGS, gpt2_init
from deepspeed_tpu.ops import paged_attention as pa
from deepspeed_tpu.ops.flash_attention import NEG_INF

CFG32 = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"], dtype=jnp.float32)


@pytest.fixture(scope="module")
def params32():
    return gpt2_init(jax.random.PRNGKey(0), CFG32)


# --------------------------------------------------------------------- #
# Direct kernel-vs-one-hot parity
# --------------------------------------------------------------------- #
def _ref_attend(q, pool_k, pool_v, bt, pos, scale):
    """The one-hot baseline exactly as inference/decode.py builds it."""
    J, bs = bt.shape[2], pool_k.shape[3]
    sel = kv_cache.block_select(bt, pool_k.shape[1])
    grid = jnp.arange(J * bs, dtype=jnp.int32)[None, None, None, :]
    pos_mask = grid <= pos[..., None]
    return kv_cache.paged_attend(q, pool_k, pool_v, sel, pos_mask,
                                 scale, NEG_INF)


def _case(seed, lengths, *, K=1, nH=4, D=16, B=12, bs=8, J=4,
          kv_dtype=jnp.float32, shared_prefix_blocks=0):
    """Build a [G, Q, ...] case from per-stream context lengths.

    ``lengths[g][q]`` <= 0 marks a dead stream (DEAD_BLOCK table row).
    ``shared_prefix_blocks`` aliases the first blocks of every live
    stream in a group to the same ids — the post-CoW-fork layout where
    read-only prefix blocks stay shared.
    """
    rng = np.random.default_rng(seed)
    G, Q = len(lengths), len(lengths[0])
    pool_k = rng.standard_normal((G, B, nH, bs, D)).astype(np.float32)
    pool_v = rng.standard_normal((G, B, nH, bs, D)).astype(np.float32)
    q = rng.standard_normal((G, Q, K, nH, D)).astype(np.float32)
    bt = np.full((G, Q, J), kv_cache.DEAD_BLOCK, np.int32)
    pos = np.zeros((G, Q, K), np.int32)
    for g in range(G):
        free = list(range(B))
        shared = [free.pop() for _ in range(shared_prefix_blocks)]
        for s in range(Q):
            ctx = lengths[g][s]
            if ctx <= 0:
                continue                    # dead stream
            # K query rows sit at positions ctx-1 .. ctx-1+K-1 (the
            # verify step's per-row causal offsets).
            nblk = (ctx - 1 + K - 1) // bs + 1
            assert nblk <= J, "case exceeds table width"
            ids = (shared[:nblk] + [free.pop() for _ in
                                    range(max(0, nblk - len(shared)))])
            bt[g, s, :nblk] = ids[:nblk]
            pos[g, s] = ctx - 1 + np.arange(K)
    to_dev = lambda a: jnp.asarray(a, kv_dtype)  # noqa: E731
    return (jnp.asarray(q), to_dev(pool_k), to_dev(pool_v),
            jnp.asarray(bt), jnp.asarray(pos), 1.0 / math.sqrt(D))


def _kernel(q, pool_k, pool_v, bt, pos, layer=1, **kw):
    """The kernel on the pool AS HELD: ``pool_k`` / ``pool_v`` (one
    layer, logical [G, B, nH, bs, D]) become layer ``layer`` of a
    three-layer lane-dense stack whose other layers hold junk."""
    def held(pool):
        junk = jnp.full_like(pool, 7.0)
        stack = jnp.stack([pool if l == layer else junk for l in range(3)])
        return kv_cache.paged_folded_view(stack)
    return pa.paged_attention(q, held(pool_k), held(pool_v), layer, bt,
                              pos, **kw)


class TestKernelParity:
    def test_fp32_ragged_contexts_and_partial_blocks(self):
        # Lengths straddle block boundaries: full final block (16),
        # one-row final block (17), mid-block (13), single token (1),
        # and a dead stream — the shapes the serving batch actually has.
        q, pk, pv, bt, pos, sc = _case(0, [[16, 17, 13, 1], [25, 0, 8, 5]])
        out = _kernel(q, pk, pv, bt, pos, scale=sc)
        ref = _ref_attend(q, pk, pv, bt, pos, sc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_verify_rows_per_row_causal_offsets(self):
        # K=4 (spec_k=3 verify): row k of a stream attends through
        # position ctx-1+k — the final row can spill into a block the
        # earlier rows must not see.
        q, pk, pv, bt, pos, sc = _case(1, [[7, 15, 21], [3, 12, 0]], K=4)
        out = _kernel(q, pk, pv, bt, pos, scale=sc)
        ref = _ref_attend(q, pk, pv, bt, pos, sc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16_pool_dequant_ulp_bounded(self):
        # bf16 pools: the kernel upcasts tiles in-VMEM and accumulates
        # fp32; the baseline's value combine runs in bf16. They agree to
        # bf16 resolution (the kernel side is the more accurate one).
        q, pk, pv, bt, pos, sc = _case(2, [[9, 18, 24, 2]],
                                       kv_dtype=jnp.bfloat16)
        out = _kernel(q, pk, pv, bt, pos, scale=sc)
        ref = _ref_attend(q, pk, pv, bt, pos, sc)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=2e-2, rtol=2e-2)

    def test_cow_shared_prefix_blocks(self):
        # Post-fork layout: every live stream's first two blocks are the
        # SAME pool blocks (refcounted prefix), tails diverge.
        q, pk, pv, bt, pos, sc = _case(
            3, [[17, 20, 25]], shared_prefix_blocks=2)
        assert (np.asarray(bt)[0, :, :2] ==
                np.asarray(bt)[0, 0, :2]).all()
        out = _kernel(q, pk, pv, bt, pos, scale=sc)
        ref = _ref_attend(q, pk, pv, bt, pos, sc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("K", [1, 4])
    @pytest.mark.parametrize("head_dim", [32, 64, 128])
    def test_lane_dense_folds_agree_with_onehot(self, head_dim, K):
        # The pool as the engine holds it: 128 // head_dim positions of
        # a block side by side in the lanes (4 / 2 / none). The kernel
        # reads those tiles as they lie — each query row comes once per
        # folded position and the copies' softmax states are merged at
        # the end — and must still agree with the one-hot baseline on
        # contexts that end on either parity and on a one-token context
        # (whose odd copies never see an attendable position).
        q, pk, pv, bt, pos, sc = _case(
            6, [[33, 1, 20, 0], [2, 47, 16, 7]], K=K, D=head_dim, bs=16,
            B=16)
        assert kv_cache.kv_fold(head_dim, 16) == 128 // head_dim
        out = _kernel(q, pk, pv, bt, pos, scale=sc)
        ref = _ref_attend(q, pk, pv, bt, pos, sc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_dead_streams_emit_exact_zeros(self):
        q, pk, pv, bt, pos, sc = _case(4, [[11, 0, 0, 6]])
        out = np.asarray(_kernel(q, pk, pv, bt, pos, scale=sc))
        assert (out[0, 1] == 0.0).all() and (out[0, 2] == 0.0).all()
        assert np.abs(out[0, 0]).sum() > 0

    @pytest.mark.parametrize("tiles", [
        (1, 1), (2, 1), (4, 1),     # one table slot a step: heads only
        (4, 2), (1, 2),
        (4, 3),                     # divides neither live count nor J
        (2, 4),                     # J itself: one group a stream
        (4, 8),                     # wider than the table
    ])
    def test_tilings_agree(self, tiles):
        # Tilings of the SAME math: (heads a step, table slots a step).
        # Every one reproduces the one-hot baseline at the tolerance of
        # the default tiling; heads a step never changes a bit (a head's
        # fp32 accumulation order is its own), slots a step regroups the
        # online-softmax updates and agrees to rounding.
        q, pk, pv, bt, pos, sc = _case(5, [[14, 22, 5, 0, 32, 9]])
        ref = np.asarray(_ref_attend(q, pk, pv, bt, pos, sc))
        out = np.asarray(_kernel(q, pk, pv, bt, pos, scale=sc,
                                 tiles=tiles))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        bh, P = tiles
        per_head = np.asarray(_kernel(q, pk, pv, bt, pos, scale=sc,
                                      tiles=(1, P)))
        np.testing.assert_array_equal(out, per_head)
        per_slot = np.asarray(_kernel(q, pk, pv, bt, pos, scale=sc,
                                      tiles=(bh, 1)))
        np.testing.assert_allclose(out, per_slot, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("name,lengths,kw,tiles", [
        # a prefill chunk (K = 128 rows) whose last block is ragged and
        # whose last group holds one live slot of four
        ("chunk_k128_ragged_last_block", [[7]],
         dict(K=128, D=64, bs=16, B=16, J=12), (2, 4)),
        # live counts 6 and 2 at P = 4: a half-dead group, and a stream
        # with fewer live blocks than a group holds
        ("group_half_dead", [[44, 12, 0, 31]], dict(J=8, B=24), (4, 4)),
        # two streams name the SAME two prefix tiles inside one group
        ("shared_prefix_inside_one_group", [[17, 20, 25]],
         dict(shared_prefix_blocks=2), (4, 4)),
        # verify rows k = 0..3 at positions 14..17: rows 2 and 3 reach
        # into the second group (P*bs = 16), rows 0 and 1 must not
        ("verify_rows_cross_a_group_boundary", [[15, 16, 29, 3]],
         dict(K=4), (4, 2)),
        # the same under the shape rule's own tiles
        ("verify_rows_rule_tiles", [[15, 16, 29, 3]], dict(K=4), None),
    ])
    def test_groups_of_table_slots(self, name, lengths, kw, tiles):
        q, pk, pv, bt, pos, sc = _case(7, lengths, **kw)
        out = _kernel(q, pk, pv, bt, pos, scale=sc, tiles=tiles)
        ref = _ref_attend(q, pk, pv, bt, pos, sc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_rows_past_the_table_attend_what_there_is(self):
        # A prefill chunk's last rows are padding whose positions lie
        # past the blocks the slot holds (the engine allocates for the
        # prompt, not for the chunk): they attend the live blocks only
        # and stay finite — a NaN there would reach the real rows through
        # the next layer's cache rows — and the real rows are exact.
        q, pk, pv, bt, pos, sc = _case(8, [[33]], K=32, D=32, bs=16,
                                       B=64, J=16)
        bt = jnp.asarray(np.asarray(bt)).at[0, 0, 3:].set(
            kv_cache.DEAD_BLOCK)                  # positions 0..47 held
        out = np.asarray(_kernel(q, pk, pv, bt, pos, scale=sc))
        ref = np.asarray(_ref_attend(q, pk, pv, bt, pos, sc))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out[0, 0, :16], ref[0, 0, :16],
                                   atol=2e-5, rtol=2e-5)

    def test_tile_rule_from_shapes(self):
        # The serve cell's shapes (gpt2-large, bf16 pool, block 16,
        # table 64): every head of sixteen slots a step for decode and
        # verify; a prefill chunk's 256 score rows a head carry 128-lane
        # fp32 state, so fewer heads under the same VMEM budget. Local
        # heads are what the rule sees (gpt2-xl: 25; mp splits them).
        assert pa._tile_rule(1, 20, 64, 16, 64, 2) == (20, 16)
        assert pa._tile_rule(5, 20, 64, 16, 64, 2) == (20, 16)
        bh, P = pa._tile_rule(128, 20, 64, 16, 64, 2)
        assert P == 16 and 20 % bh == 0 and 1 < bh < 20
        assert pa._tile_rule(128, 25, 64, 16, 64, 2)[0] in (1, 5)
        # a table narrower than a group; head_dim 128 does not fold
        assert pa._tile_rule(1, 4, 16, 8, 4, 4) == (4, 4)
        assert pa._tile_rule(1, 16, 128, 16, 64, 4, 4) == (16, 8)
        for K in (1, 5, 32, 128):
            bh, P = pa._tile_rule(K, 20, 64, 16, 64, 2)
            assert pa._step_vmem_bytes(bh, P, K, 64, 16, 2, 2) \
                <= pa._VMEM_BUDGET
        # gpt2-large's tiles are what they were: a decode group of
        # sixteen slots already copies 1.25 MiB and thirty-two would not
        # fit; a chunk's rows fill the MXU and ask for no more bytes.
        assert pa._tile_rule(128, 20, 64, 16, 64, 2) == (5, 16)
        assert pa._step_vmem_bytes(20, 32, 1, 64, 16, 2, 2) \
            > pa._VMEM_BUDGET

    @pytest.mark.parametrize("what,K,J,itemsize,tiles", [
        # 4 K/V heads of 128 under 32 query heads, blocks of 64
        # (Trinity-Mini, `serve.trinity-mini.mixed-docqa-over`): 128
        # lanes are TWO slots = 0.25 MiB a group; a step of few query
        # rows takes slots until a group copies 2 MiB ...
        ("decode_full_table", 8, 528, 2, (4, 16)),
        ("decode_window_ring", 8, 41, 2, (4, 16)),
        ("verify_k5", 40, 528, 2, (4, 16)),
        # ... no more than the table has (a power of two of them) ...
        ("decode_short_ring", 8, 9, 2, (4, 8)),
        ("decode_table_of_three", 8, 3, 2, (4, 2)),
        # ... a float32 pool's slot is twice the bytes ...
        ("decode_f32_pool", 8, 528, 4, (4, 8)),
        # ... and a prefill run of 64 rows x 8 heads = 512 query rows a
        # K/V head takes the chunk body (PR 65): eight slots = 512 keys a
        # group, reckoned by ``_chunk_vmem_bytes``.
        ("prefill_run_512_rows", 512, 528, 2, (4, 8)),
        ("prefill_run_window", 512, 41, 2, (4, 8)),
    ])
    def test_tile_rule_at_wide_grouped_heads(self, what, K, J, itemsize,
                                             tiles):
        got = pa._tile_rule(K, 4, 128, 64, J, itemsize, 2)
        assert got == tiles
        bh, P = got
        if K >= pa._DENSE_ROWS:
            assert pa._chunk_vmem_bytes(bh, P, K, 128, 64, itemsize, 2) \
                <= pa._CHUNK_VMEM_BUDGET
            return
        assert pa._step_vmem_bytes(bh, P, K, 128, 64, itemsize, 2) \
            <= pa._VMEM_BUDGET
        if K < pa._DENSE_ROWS and 2 * P <= J:
            # the rule stopped because the group is large enough, or
            # because twice the slots would not fit
            assert 2 * bh * P * 64 * 128 * itemsize >= pa._GROUP_BYTES \
                or pa._step_vmem_bytes(bh, 2 * P, K, 128, 64, itemsize, 2) \
                > pa._VMEM_BUDGET


# --------------------------------------------------------------------- #
# Wide, grouped heads in long blocks (head_dim 128, blocks of 64, 8 query
# heads a K/V head), with and without a window: the group step by bytes
# --------------------------------------------------------------------- #
def _wide_case(seed, contexts, *, reach=None, J, nKV=2, grp=8, D=128, bs=64,
               dtype=jnp.float32):
    """One group of streams at the given contexts (0: dead) over a
    two-layer pool as held; a window's table is a ring of J slots that
    holds the blocks in reach.  Returns q, pools, the tables, positions."""
    rng = np.random.default_rng(seed)
    Q = len(contexts)
    need = sum((c - 1) // bs + 1 if reach is None
               else (c - 1) // bs - max(0, c - reach) // bs + 1
               for c in contexts if c)
    B = need + 3
    pools = [jnp.asarray(rng.normal(size=(2, 1, B, nKV, bs, D)), dtype)
             for _ in range(2)]
    q = jnp.asarray(rng.normal(size=(1, Q, 1, nKV * grp, D)), dtype)
    bt = np.full((1, Q, J), kv_cache.DEAD_BLOCK, np.int32)
    pos = np.full((1, Q, 1), -1, np.int32)
    free = list(rng.permutation(B))
    for s_, c in enumerate(contexts):
        if not c:
            continue
        pos[0, s_, 0] = c - 1
        first = 0 if reach is None else max(0, c - reach) // bs
        for j in range(first, (c - 1) // bs + 1):
            bt[0, s_, j % J] = free.pop()
    return q, pools, jnp.asarray(bt), jnp.asarray(pos)


def _wide_kernel(q, pools, bt, pos, reach, tiles, grp=8):
    """The kernel's layer 1 at ``tiles`` (None: the shape rule's)."""
    D = q.shape[-1]
    plan = pa.attend_plan(bt, pos, pools[0], D, reach=reach, group=grp)
    return np.asarray(pa.paged_attention(
        q, pools[0], pools[1], 1, plan=plan, scale=D ** -0.5, tiles=tiles),
        np.float32)


def _wide_baseline(q, pools, bt, pos, reach):
    """The served model's own attend without the kernel: blocks gathered,
    a mask from positions."""
    from deepspeed_tpu.inference.kv_pages import gather_attend
    return np.asarray(gather_attend(
        q, pools[0], pools[1], 1, bt, pos, reach, q.shape[-1] ** -0.5),
        np.float32)


class TestWideGroupedHeads:
    # live blocks a stream: 33, 1, 16, dead, 5, 9 — a last group part dead
    # at every P, P = 3 and 5 divide neither a live count nor J = 40
    FULL = [2100, 30, 1024, 0, 300, 520]
    # a window of 200 positions behind a ring of 5: a stream inside its
    # first block, one whose ring has wrapped many times, one that ends
    # on a block's last row, a dead one, one a row into a new block
    WINDOW = [30, 1500, 640, 0, 321]

    @pytest.mark.parametrize("P", [1, 2, 3, 4, 5, 8, 16])
    def test_slots_a_group_over_a_long_table(self, P):
        case = _wide_case(11, self.FULL, J=40)
        got = _wide_kernel(*case, None, (2, P))
        np.testing.assert_allclose(got, _wide_baseline(*case, None),
                                   atol=2e-5, rtol=2e-5)
        assert not got[0, 3].any()
        # regrouping the online softmax moves a float32 sum's order, no more
        np.testing.assert_allclose(got, _wide_kernel(*case, None, (2, 2)),
                                   atol=2e-6, rtol=2e-6)
        # ... and heads a step not a bit
        np.testing.assert_array_equal(got,
                                      _wide_kernel(*case, None, (1, P)))

    @pytest.mark.parametrize("P", [1, 2, 3, 4])
    def test_slots_a_group_over_a_windows_ring(self, P):
        case = _wide_case(12, self.WINDOW, reach=200, J=5)
        got = _wide_kernel(*case, 200, (2, P))
        np.testing.assert_allclose(got, _wide_baseline(*case, 200),
                                   atol=2e-5, rtol=2e-5)
        assert not got[0, 3].any()
        np.testing.assert_allclose(got, _wide_kernel(*case, 200, (2, 2)),
                                   atol=2e-6, rtol=2e-6)

    @pytest.mark.parametrize("reach,J", [(None, 528), (2048, 41)],
                             ids=["full", "window"])
    def test_the_rules_own_tiles(self, reach, J):
        # the cell's tables at the rule's own slots a group (sixteen for two
        # K/V heads of float32): a long stream beside one of one block, a
        # dead one between them (the stream after it starts cold, the
        # others find their first group in flight)
        assert pa._tile_rule(8, 2, 128, 64, J, 4, 4) == (2, 16)
        case = _wide_case(13, [2400, 0, 40, 2049, 700], reach=reach, J=J)
        got = _wide_kernel(*case, reach, None)
        np.testing.assert_allclose(got, _wide_baseline(*case, reach),
                                   atol=2e-5, rtol=2e-5)
        assert not got[0, 1].any()

    def test_one_block_beside_five_hundred(self):
        case = _wide_case(14, [500 * 64 - 7, 9], J=512, nKV=1)
        want = _wide_baseline(*case, None)
        for tiles in ((1, 16), (1, 8)):
            np.testing.assert_allclose(_wide_kernel(*case, None, tiles),
                                       want, atol=2e-5, rtol=2e-5)

    def test_every_order_of_live_and_dead_steps_runs_ahead_alike(self):
        # The copies run ahead across grid steps (a step's last group
        # starts the next live step's first): a stream's output is its
        # own whatever came before it — live, dead, or nothing — and
        # whichever buffer half its first group landed in.
        ctx = [130, 0, 0, 700, 64, 65, 0, 1]
        q, pools, bt, pos = case = _wide_case(15, ctx, J=12)
        got = _wide_kernel(*case, None, (2, 2))
        np.testing.assert_allclose(got, _wide_baseline(*case, None),
                                   atol=2e-5, rtol=2e-5)
        for s_ in (0, 3, 4, 5, 7):
            alone = _wide_kernel(q[:, s_:s_ + 1], pools, bt[:, s_:s_ + 1],
                                 pos[:, s_:s_ + 1], None, (2, 2))
            np.testing.assert_array_equal(got[:, s_], alone[:, 0])
        # head blocks are grid steps too: one head a step
        np.testing.assert_array_equal(got,
                                      _wide_kernel(*case, None, (1, 2)))

    def test_bf16_pool_and_queries_at_the_cells_widths(self):
        # bf16 q and pools as the cell holds them. In interpret mode the
        # kernel widens them and contracts in float32, so against the
        # baseline on the same values it differs by the output's
        # rounding to bf16 alone: half an ulp, 2**-9 relative. (On the
        # chip the MXU takes the operands as bf16 in one pass and rounds
        # the probabilities on the way in, which a contraction written
        # on bf16 operands reproduces bit for bit: PERF.md section 6,
        # PR 40.)
        q, pools, bt, pos = _wide_case(16, self.FULL, J=40,
                                       dtype=jnp.bfloat16)
        want = _wide_baseline(q.astype(jnp.float32),
                              [x.astype(jnp.float32) for x in pools], bt,
                              pos, None)
        np.testing.assert_allclose(_wide_kernel(q, pools, bt, pos, None,
                                                None),
                                   want, rtol=2 ** -8, atol=2 ** -10)


# --------------------------------------------------------------------- #
# A step of many query rows (a run of a prefill chunk: ``_DENSE_ROWS`` or
# more a K/V head) takes the chunk-shaped body, ``_pattn_chunk_kernel``
# --------------------------------------------------------------------- #
def _chunk_case(seed, starts, rows, *, reach=None, J, nKV=2, grp=8, D=128,
                bs=64, runs=2, dead_rows=0, shared_blocks=0,
                dtype=jnp.float32, pool_dtype=None):
    """Prompts mid-prefill as ``kv_pages.paged_classes`` hands them to the
    attend: prompt i has ``starts[i]`` rows cached (-1: a dead slot) and
    its chunk of ``runs * rows`` rows at the positions behind them, cut
    into ``runs`` streams of ``rows`` query rows that share the prompt's
    table (a window's: a ring of J slots holding the blocks in reach).
    ``dead_rows``: the chunk's last rows are padding that attends nothing
    (-1).  ``shared_blocks``: the prompts' first blocks are the same pool
    blocks.  Returns q, pools, the tables, positions."""
    rng = np.random.default_rng(seed)
    chunk = runs * rows
    first = [0 if reach is None else max(0, s_ - reach + 1) // bs
             for s_ in starts]
    B = sum((s_ + chunk - 1) // bs - f0 + 1
            for s_, f0 in zip(starts, first) if s_ >= 0) + 3
    pools = [jnp.asarray(rng.normal(size=(2, 1, B, nKV, bs, D)),
                         pool_dtype or dtype) for _ in range(2)]
    Q = len(starts) * runs
    q = jnp.asarray(rng.normal(size=(1, Q, rows, nKV * grp, D)), dtype)
    bt = np.full((1, Q, J), kv_cache.DEAD_BLOCK, np.int32)
    pos = np.full((1, Q, rows), -1, np.int32)
    free = list(rng.permutation(B))
    shared = [free.pop() for _ in range(shared_blocks)]
    for i, (s_, f0) in enumerate(zip(starts, first)):
        if s_ < 0:
            continue
        for j in range(f0, (s_ + chunk - 1) // bs + 1):
            bt[0, i * runs:(i + 1) * runs, j % J] = \
                shared[j] if j < shared_blocks else free.pop()
        at = s_ + np.arange(chunk)
        at[chunk - dead_rows:] = -1
        pos[0, i * runs:(i + 1) * runs] = at.reshape(runs, rows)
    return q, pools, jnp.asarray(bt), jnp.asarray(pos)


def _kernels_of(fn, *args):
    """Names of the ``pallas_call``s in ``fn(*args)``'s jaxpr."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


class TestChunkBody:
    def _held(self, case, reach, tiles=None, grp=8):
        got = _wide_kernel(*case, reach, tiles, grp=grp)
        np.testing.assert_allclose(got, _wide_baseline(*case, reach),
                                   atol=2e-5, rtol=2e-5)
        return got

    @pytest.mark.parametrize("rows,grp,name", [
        (16, 8, "_pattn_chunk_kernel"),      # 128 rows a K/V head
        (15, 8, "_pattn_kernel"),            # 120
        (4, 8, "_pattn_kernel"),             # a block of 4 positions (SDAR)
        (64, 7, "_pattn_chunk_kernel"), (64, 5, "_pattn_chunk_kernel"),
        (1, 8, "_pattn_kernel"), (5, 8, "_pattn_kernel")])
    def test_the_body_follows_the_rows_a_kv_head(self, rows, grp, name):
        q, pools, bt, pos = _chunk_case(20, [100], rows, J=8, grp=grp, runs=1)
        plan = pa.attend_plan(bt, pos, pools[0], 128, group=grp)
        assert _kernels_of(lambda q: pa.paged_attention(
            q, pools[0], pools[1], 1, plan=plan, scale=1.0), q) == [name]

    def test_a_folded_pool_stays_on_the_decode_body(self):
        # head_dim 64 (GPT-2, lfm2): two positions side by side in the
        # lanes, each query row twice — the chunk body declines it
        q, pk, pv, bt, pos, sc = _case(7, [[7]], K=128, D=64, bs=16, B=16,
                                       J=12)
        assert not pa._dense(128, 64, 16) and pa._dense(128, 128, 16)
        held = lambda p: kv_cache.paged_folded_view(p[None])   # noqa: E731
        assert _kernels_of(lambda q: pa.paged_attention(
            q, held(pk), held(pv), 0, bt, pos, scale=sc), q) \
            == ["_pattn_kernel"]

    @pytest.mark.parametrize("tiles", [None, (2, 1), (1, 2), (2, 3), (1, 16)])
    def test_ragged_contexts_and_partial_last_blocks(self, tiles):
        # chunks that start inside a block, on a block's first row, at the
        # prompt's first token; each row's causal limit is its own (row k
        # of a run reaches its position and no further), the last run's
        # last block is partly filled, and P = 3 divides no live count
        case = _chunk_case(21, [1000, 64, 0, 37], 16, J=20)
        got = self._held(case, None, tiles)
        # regrouping the online softmax moves a float32 sum's order ...
        np.testing.assert_allclose(got, _wide_kernel(*case, None, (2, 2)),
                                   atol=2e-6, rtol=2e-6)
        # ... and heads a step not a bit
        if tiles is not None:
            np.testing.assert_array_equal(
                got, _wide_kernel(*case, None, (1, tiles[1])))

    @pytest.mark.parametrize("start", [0, 130, 1000, 4000])
    @pytest.mark.parametrize("tiles", [None, (2, 1), (2, 2)])
    def test_a_windows_ring(self, start, tiles):
        # a window of 200 positions behind a ring of 8 blocks: a chunk
        # inside its first blocks, one whose first rows still see the
        # prompt's start, ones whose ring has wrapped many times
        self._held(_chunk_case(22, [start], 16, reach=200, J=8), 200, tiles)

    def test_dead_streams_and_dead_rows_emit_exact_zeros(self):
        # a dead slot between two prompts (the stream after it starts
        # cold), and a last chunk whose tail is padding: 21 dead rows end
        # one run (16 rows) and part of the run before
        case = _chunk_case(23, [300, -1, 77], 16, J=10, dead_rows=21)
        got = self._held(case, None)
        assert not got[0, 2:4].any()                # the dead slot's runs
        assert not got[0, 1].any() and not got[0, 0, 11:].any()
        assert got[0, 0, :11].any()
        alone = _wide_kernel(case[0][:, 4:], case[1], case[2][:, 4:],
                             case[3][:, 4:], None, None)
        np.testing.assert_array_equal(got[:, 4:], alone)

    def test_dead_rows_under_a_window(self):
        case = _chunk_case(24, [500, 90], 16, reach=200, J=8, dead_rows=5)
        got = self._held(case, 200)
        assert not got[0, 1, 11:].any() and not got[0, 3, 11:].any()

    def test_shared_prefix_blocks(self):
        # two prompts whose first three blocks are the SAME pool blocks
        # (a cached prefix), in one call
        case = _chunk_case(25, [200, 260], 16, J=8, shared_blocks=3)
        bt = np.asarray(case[2])
        assert (bt[0, 0, :3] == bt[0, 2, :3]).all() \
            and bt[0, 0, 3] != bt[0, 2, 3]
        self._held(case, None)

    @pytest.mark.parametrize("grp,rows", [(8, 16), (7, 32), (5, 32), (1, 128)])
    @pytest.mark.parametrize("reach,J", [(None, 12), (256, 8)],
                             ids=["full", "window"])
    def test_grouped_heads(self, grp, rows, reach, J):
        # 8 (Trinity-Mini, SDAR, Solar-Open2), 7 (SmallThinker: 224 rows a
        # K/V head, two bands of 112) and 5 (Falcon-H1: 160 rows, one
        # band of them) query heads a K/V head; one (rows alone)
        case = _chunk_case(26, [333, 70], rows, reach=reach, J=J, grp=grp,
                           nKV=2 if grp > 1 else 3)
        self._held(case, reach, grp=grp)

    @pytest.mark.parametrize("what,kw,reach", [
        # `serve.solar-open2-250b.agent-sessions-over`: 8 heads a K/V head,
        # blocks of 128, sessions of 18k rows and more
        ("cell14", dict(starts=[18000, 31000], rows=64, J=256, bs=128), None),
        # `serve.smallthinker-21b-a3b.paste-over`: 7 heads a K/V head,
        # blocks of 64, the full class and the window's ring
        ("cell11_full", dict(starts=[2100, 9000], rows=64, J=160, grp=7),
         None),
        ("cell11_window", dict(starts=[2100, 9000], rows=64, J=13, grp=7),
         512)])
    def test_bf16_pool_and_queries_at_the_cells_widths(self, what, kw, reach):
        # bf16 q and pools as the cells hold them, at the cells' runs of
        # rows and under the shape rule's own tiles, against the baseline
        # on the same values in float32; the tolerance is
        # ``TestWideGroupedHeads``' (the output's rounding to bf16).  The
        # probabilities go into the MXU as bf16 here, which is what the
        # chip makes of ``_pattn_kernel``'s float32 product too (PERF.md
        # section 6, PR 65: equal bits at equal tiles); over contexts as
        # long as the cells' that rounding averages out (the next test
        # holds it at a short one).
        grp = kw.get("grp", 8)
        q, pools, bt, pos = _chunk_case(27, runs=2, dtype=jnp.bfloat16, **kw)
        want = _wide_baseline(q.astype(jnp.float32),
                              [x.astype(jnp.float32) for x in pools], bt,
                              pos, reach)
        np.testing.assert_allclose(
            _wide_kernel(q, pools, bt, pos, reach, None, grp=grp), want,
            rtol=2 ** -8, atol=2 ** -10)

    @pytest.mark.parametrize("grp", [8, 7])
    def test_the_probabilities_go_in_as_the_mxu_takes_them(self, grp):
        # A prompt's FIRST chunks (30 rows cached, then 128 of its own: one
        # group of keys): the output is the softmax whose numerators are
        # rounded to bf16 on their way into the value product and whose
        # denominator is not — float32 statistics, bf16 operands — to the
        # output's own rounding.
        nKV, D, bs, rows = 2, 128, 64, 64
        q, pools, bt, pos = _chunk_case(30, [30], rows, J=4, nKV=nKV,
                                        grp=grp, dtype=jnp.bfloat16)
        got = _wide_kernel(q, pools, bt, pos, None, None, grp=grp)
        f32 = lambda x: np.asarray(x.astype(jnp.float32))     # noqa: E731
        blocks = np.asarray(bt)[0, 0, :3]
        k, v = (f32(pool)[1, 0, blocks].transpose(1, 0, 2, 3)
                .reshape(nKV, 3 * bs, D) for pool in pools)
        qs = f32(q)[0].reshape(2 * rows, nKV, grp, D)
        s = np.einsum("rngd,ntd->rngt", qs, k) * np.float32(D ** -0.5)
        seen = np.arange(3 * bs)[None, :] <= np.asarray(pos).reshape(-1, 1)
        s = np.where(seen[:, None, None, :], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("rngt,ntd->rngd", f32(jnp.asarray(p, jnp.bfloat16)),
                         v) / p.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            got[0].reshape(2 * rows, nKV, grp, D), want,
            rtol=2 ** -8, atol=2 ** -10)

    def test_an_fp32_pool_keeps_fp32_operands(self):
        # float32 rows under float32 queries: the tight tolerance of the
        # float32 cases above is this test's too; bf16 queries over a
        # float32 pool go in as float32 (the wider of the two)
        self._held(_chunk_case(28, [700, 3], 16, J=16), None)
        q, pools, bt, pos = _chunk_case(28, [700, 3], 16, J=16,
                                        dtype=jnp.bfloat16,
                                        pool_dtype=jnp.float32)
        want = _wide_baseline(q.astype(jnp.float32), pools, bt, pos, None)
        np.testing.assert_allclose(
            _wide_kernel(q, pools, bt, pos, None, None), want,
            rtol=2 ** -8, atol=2 ** -10)

    def test_a_head_wider_than_the_lanes(self):
        # head_dim 256: the row state is 128 lanes wide, the accumulator
        # 256 — alpha goes in twice side by side
        self._held(_chunk_case(29, [150], 16, J=8, D=256, bs=32), None)

    @pytest.mark.parametrize("what,args,tiles", [
        # cell 14: 512 rows a K/V head, 8 K/V heads, blocks of 128, a
        # table of 1,664 — four slots = 512 keys a group, four heads a
        # step (eight would ask 15.9 MiB)
        ("cell14", (512, 8, 128, 128, 1664, 2, 2), (4, 4)),
        # cell 6 (Trinity-Mini) and cell 13 (SDAR): 512 rows, blocks of 64
        ("cell6_full", (512, 4, 128, 64, 528, 2, 2), (4, 8)),
        # cell 11: 448 rows, 4 K/V heads, blocks of 64, table 256 / ring 73
        ("cell11_full", (448, 4, 128, 64, 256, 2, 2), (4, 8)),
        ("cell11_window", (448, 4, 128, 64, 73, 2, 2), (4, 8)),
        # cell 9 (Falcon-H1): 320 rows
        ("cell9", (320, 4, 128, 64, 48, 2, 2), (4, 8)),
        # a table narrower than a group; a float32 pool's tiles are twice
        # the bytes: fewer heads a step
        ("short_table", (512, 4, 128, 64, 3, 2, 2), (4, 3)),
        ("f32_pool", (512, 8, 128, 128, 1664, 4, 4), (2, 4)),
        # blocks longer than a group's keys: one slot
        ("long_blocks", (128, 2, 128, 1024, 8, 2, 2), (2, 1)),
    ])
    def test_tile_rule_of_a_dense_step(self, what, args, tiles):
        assert pa._dense(*args[:1], args[2], args[3])
        assert pa._tile_rule(*args) == tiles
        K, nH, D, bs, J, itemsize, q_itemsize = args
        assert pa._chunk_vmem_bytes(*tiles, K, D, bs, itemsize, q_itemsize) \
            <= pa._CHUNK_VMEM_BUDGET < pa._VMEM_LIMIT
        bh = tiles[0]
        if bh < nH:
            wider = min(b for b in range(bh + 1, nH + 1) if nH % b == 0)
            assert pa._chunk_vmem_bytes(wider, tiles[1], K, D, bs, itemsize,
                                        q_itemsize) > pa._CHUNK_VMEM_BUDGET

    def test_row_bands(self):
        assert [pa._row_bands(R) for R in (512, 448, 320, 128, 136, 1024)] \
            == [256, 224, 160, 64, 136, 512]


# --------------------------------------------------------------------- #
# Gating contract
# --------------------------------------------------------------------- #
class TestGating:
    def test_forced_flags_win(self, monkeypatch):
        monkeypatch.setenv("DS_PAGED_KERNEL", "1")
        assert pa.paged_kernel_enabled(False) is False
        monkeypatch.setenv("DS_PAGED_KERNEL", "0")
        assert pa.paged_kernel_enabled(True) is True

    def test_env_overrides_auto(self, monkeypatch):
        monkeypatch.setenv("DS_PAGED_KERNEL", "1")
        assert pa.paged_kernel_enabled("auto") is True
        monkeypatch.setenv("DS_PAGED_KERNEL", "0")
        assert pa.paged_kernel_enabled("auto") is False

    def test_auto_is_backend_gated(self, monkeypatch):
        monkeypatch.delenv("DS_PAGED_KERNEL", raising=False)
        expected = jax.default_backend() == "tpu"   # False on this mesh
        assert pa.paged_kernel_enabled("auto") is expected

    def test_config_validation(self, params32):
        from deepspeed_tpu.runtime.config import DeepSpeedConfigError
        with pytest.raises(DeepSpeedConfigError, match="paged_kernel"):
            InferenceEngine(CFG32, params32, config={
                "inference": {"max_slots": 2, "max_seq_len": 32,
                              "block_size": 8, "paged_kernel": "yes"}})


# --------------------------------------------------------------------- #
# Analytic cost model
# --------------------------------------------------------------------- #
class TestAttendCostModel:
    def test_kernel_bytes_scale_with_block_rounded_context(self):
        bs, nH, D = 8, 4, 16
        f = lambda ctx: pa.attend_hbm_bytes_per_token(   # noqa: E731
            nH, D, bs, context=ctx)
        # Within one block the cost is flat; crossing a boundary adds
        # exactly one block's K+V bytes.
        assert f(1) == f(8) == 2 * 8 * nH * D * 4
        assert f(9) == f(16) == 2 * f(8)
        assert f(17) - f(16) == 2 * bs * nH * D * 4
        # ceil(ctx/bs)*bs rows exactly, never pool-sized.
        assert f(25) == 2 * 32 * nH * D * 4

    def test_onehot_bytes_are_pool_capacity_flat(self):
        bs, nH, D, B = 8, 4, 16, 64
        b = pa.attend_hbm_bytes_per_token(nH, D, bs, pool_blocks=B)
        assert b == 2 * B * bs * nH * D * 4
        # Independent of any context — it streams the whole pool.
        assert b > pa.attend_hbm_bytes_per_token(nH, D, bs, context=B * bs
                                                 - bs + 1) - 1

    def test_flops_and_arg_validation(self):
        assert pa.attend_flops_per_token(4, 16, 8, context=8) \
            == 4 * 4 * 16 * 8
        assert pa.attend_flops_per_token(4, 16, 8, pool_blocks=2,
                                         num_layers=3) \
            == 4 * 4 * 16 * 16 * 3
        with pytest.raises(ValueError, match="exactly one"):
            pa.attend_flops_per_token(4, 16, 8)
        with pytest.raises(ValueError, match="exactly one"):
            pa.attend_hbm_bytes_per_token(4, 16, 8, context=4,
                                          pool_blocks=2)


# --------------------------------------------------------------------- #
# Engine-level: kernel on vs off on the dp=8 mesh
# --------------------------------------------------------------------- #
def _engine(params, *, kernel, slots=8, max_len=64, chunk=8,
            block_size=8, spec_k=0, **tel):
    config = {"inference": {"max_slots": slots, "max_seq_len": max_len,
                            "prefill_chunk": chunk,
                            "block_size": block_size,
                            "spec_k": spec_k, "paged_kernel": kernel}}
    config.update(tel)
    return InferenceEngine(CFG32, params, config=config)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG32.vocab_size, size=n).astype(np.int32)


def paged_attn_bytes(sp_):
    """The engine's own live-ctx_max quote, recomputed independently."""
    return pa.attend_hbm_bytes_per_token(
        sp_.num_heads, sp_.head_dim, sp_.block_size, context=sp_.max_len,
        kv_itemsize=jnp.dtype(sp_.dtype).itemsize,
        num_layers=sp_.num_layers)


class TestEngineKernelOn:
    def test_decode_logit_parity_and_greedy_bit_identity(self, params32):
        streams, logits = {}, {}
        for kernel in (False, True):
            e = _engine(params32, kernel=kernel)
            assert e.paged_kernel is kernel
            toks, logs = [], []
            for s, n in ((0, 11), (1, 17)):   # partial + cross-block ctx
                tok, lg = e.prefill(_prompt(n, seed=s), slot=s,
                                    return_logits=True)
                e.activate_slot(s, n, tok)
                toks.append([tok])
                logs.append([np.asarray(lg)])
            for _ in range(6):
                tok, lg = e.decode_once(return_logits=True)
                for i, s in enumerate((0, 1)):
                    toks[i].append(int(np.asarray(tok)[s]))
                    logs[i].append(np.asarray(lg)[s])
            e.close()
            streams[kernel] = toks
            logits[kernel] = logs
        assert streams[True] == streams[False]      # greedy bit-identity
        for a, b in zip(logits[True], logits[False]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_spec_decode_streams_bit_identical(self, params32):
        emitted = {}
        for kernel in (False, True):
            e = _engine(params32, kernel=kernel, spec_k=3)
            n = 13
            tok, _ = e.prefill(_prompt(n, seed=7), slot=0,
                               return_logits=True)
            e.activate_slot(0, n, tok)
            out = [tok]
            for _ in range(4):
                toks, n_new = e.spec_decode_once()
                k = int(np.asarray(n_new)[0])
                out.extend(int(t) for t in np.asarray(toks)[0][:k])
            e.close()
            emitted[kernel] = out
        assert emitted[True] == emitted[False]

    def test_acceptance_stream_kernel_on_zero_recompiles(
            self, params32, tmp_path):
        # The PR-12 acceptance workload, kernel forced ON, retrace =
        # hard failure: proves the static-shape discipline (grid sized
        # by table WIDTH, predication for liveness) holds across chunked
        # prefill, CoW forks, spec verify, and ragged completion.
        e = _engine(params32, kernel=True, spec_k=3,
                    telemetry={"enabled": True,
                               "output_path": str(tmp_path),
                               "job_name": "pk_accept",
                               "report_steps": 10 ** 9,
                               "fail_on_recompile": True})
        report = e.serve(shared_prefix_requests(
            6, prefix_len=16, tail_len=(3, 8), max_new_tokens=4,
            vocab_size=CFG32.vocab_size))
        assert report["recompiles"] == 0
        assert report["completed"] == 6
        # The serving aggregator priced the attend both ways: the
        # structural ratio exists and the kernel side is strictly less
        # work than streaming the pool.
        assert report["attend"]["mode"] == "kernel"
        assert report["attend_work_ratio"] > 1.0
        e.close()

    def test_attend_telemetry_meta_labeled_projection(self, params32):
        e = _engine(params32, kernel=True)
        meta = e.telemetry.meta
        assert meta["paged_kernel"] is True
        for key in ("attend_flops_per_token", "attend_hbm_bytes_per_token"):
            assert meta[key]["projection"] == "analytic"
            assert meta[key]["pool_capacity"] >= meta[key]["live_ctx_max"]
        # live-ctx bound is the block-rounded max context, never pool-
        # sized: blocks_per_group * bs >= ceil(max_len/bs) * bs here.
        sp_ = e.cache_spec
        assert meta["attend_hbm_bytes_per_token"]["live_ctx_max"] == \
            paged_attn_bytes(sp_)
        e.close()
